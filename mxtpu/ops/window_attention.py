"""Sliding-window attention for a prefill: one Pallas TPU kernel a
layer, every score block kept in VMEM.

``ops.attention.window_attention`` (the XLA form) folds each key block
into a float32 online softmax under ``lax.map``; XLA writes each (b, h,
block, block) float32 score block, its mask, maximum, ``exp`` and
weights to HBM and reads them back between its fusions. At the agent
cell's chunk (1024 rows behind a ring of 512, 40 query heads over 10
paired KV heads of 128 lanes) that is 42 MB a block, 48 blocks a chunk,
~17 ms of a 62 ms chunk for what the MXU does in under one.

This kernel computes the same float32 online softmax (running maximum,
denominator and numerator a row; products of the operands' bf16 with
float32 accumulation, the weights rounded to the values' dtype for the
second product, as XLA's default-precision products round them on the
TPU) with each score block in VMEM:

- a grid step is (batch, KV head, query block, key step): the ``rep``
  query heads of a KV head share its key and value blocks, which are
  read as the caller's grouped (b, kvh, keys, d) arrays and never
  repeated;
- queries are the rows that have an output; they sit at key position
  ``q_offset`` (the keys in front, a prefill chunk's ring, have no
  query row);
- a query block reads only the key blocks its window ``(t - window,
  t]`` reaches: ``(bq + window) / bk`` steps, the first ones of a
  sequence's start (block index < 0) or wholly before ``k_start``
  skipped, their DMA too (the index map repeats the first live block);
- ``k_start`` is scalar-prefetched: a traced int, so one compiled
  program hides a chunk's ring at a prompt's start and shows it later.

Written from ``jax.experimental.pallas.ops.tpu.flash_attention``'s
forward kernel (jax 0.9.0): the statistics a row in (bq, 128) float32
tiles, the lanes repeated.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["window_attention_band", "takes_window", "window_block",
           "WINDOW_KERNEL_NAME"]

# the kernel's name as a device trace prints it
WINDOW_KERNEL_NAME = "window_attention_band"

_F32 = jnp.float32
_NEG_INF = -1e30        # ops.attention's finite "minus infinity": m's start
# a masked score: below m's start, so that exp(masked - m) is 0 even in
# a row that has seen no key yet (m still _NEG_INF)
_MASKED = 2 * _NEG_INF
_LANES = 128
# query and key rows a block, the largest that divides the shapes
_BLOCKS = (512, 256, 128)


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def window_block(rows: int, q_offset: int, window: int) -> int:
    """The block (query rows and keys a grid step) for ``rows`` query
    rows at key position ``q_offset`` under ``window``: the largest of
    ``_BLOCKS`` that divides all three (so that every block boundary is
    a window's and the offset's), 0 where none does."""
    return next((b for b in _BLOCKS
                 if rows % b == 0 and q_offset % b == 0 and window % b == 0),
                0)


def takes_window(q_shape, kv_shape, dtype, *, window: int,
                 q_offset: int = 0) -> bool:
    """Whether the compiled kernel takes queries (b, h, rows, d) over
    keys (b, kvh, q_offset + rows, d) of this dtype as they are: bf16
    (a float32 attention keeps XLA's form, whose products the tests
    compare at full precision), heads of whole lane tiles, grouped
    heads (``h % kvh == 0``), and a block that divides the rows, the
    offset and the window."""
    if len(q_shape) != 4 or len(kv_shape) != 4:
        return False
    b, h, rows, d = q_shape
    return (jnp.dtype(dtype) == jnp.bfloat16 and kv_shape[0] == b
            and h % kv_shape[1] == 0 and kv_shape[3] == d
            and d % _LANES == 0 and kv_shape[2] == q_offset + rows
            and window > 0 and window_block(rows, q_offset, window) > 0)


def _kernel(start_ref,                      # scalar prefetch: (k_start,)
            q_ref,                          # (rep, bq, d)
            k_ref, v_ref,                   # (bk, d), (bk, dv)
            o_ref,                          # (rep, bq, dv)
            m_sc, l_sc, acc_sc,             # (rep, bq, 128) x2, (rep, bq, dv)
            *, scale, window, q_offset, bq, bk, base):
    i, j = pl.program_id(2), pl.program_id(3)
    rep, dv = q_ref.shape[0], v_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, _F32)
        l_sc[...] = jnp.zeros(l_sc.shape, _F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, _F32)

    kb = i * _i32(bq // bk) + _i32(base) + j         # this step's key block
    k_start = start_ref[0]

    @pl.when((kb >= 0) & ((kb + 1) * _i32(bk) > k_start))
    def _():
        shape = (bq, bk)
        t = lax.broadcasted_iota(jnp.int32, shape, 0) + (
            i * _i32(bq) + _i32(q_offset))
        key = lax.broadcasted_iota(jnp.int32, shape, 1) + kb * _i32(bk)
        seen = (key <= t) & (key > t - _i32(window)) & (key >= k_start)
        k, v = k_ref[...], v_ref[...]
        for r in range(rep):
            s = lax.dot_general(
                q_ref[r], k, (((1,), (1,)), ((), ())),
                precision=lax.Precision.DEFAULT,
                preferred_element_type=_F32) * _F32(scale)
            s = jnp.where(seen, s, _F32(_MASKED))
            m_prev = m_sc[r]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
            p = jnp.exp(s - jnp.tile(m_next, (1, bk // _LANES)))
            alpha = jnp.exp(m_prev - m_next)
            m_sc[r] = m_next
            l_sc[r] = alpha * l_sc[r] + jnp.sum(p, axis=1)[:, None]
            pv = lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=lax.Precision.DEFAULT,
                preferred_element_type=_F32)
            acc_sc[r] = acc_sc[r] * jnp.tile(alpha, (1, dv // _LANES)) + pv

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for r in range(rep):
            l = l_sc[r]
            # a row that saw no key (none under k_start) comes out zeros
            l = jnp.where(l == 0.0, _F32(1.0), l)
            o_ref[r] = (acc_sc[r] / jnp.tile(l, (1, dv // _LANES))
                        ).astype(o_ref.dtype)


def window_attention_band(q, k, v, *, window: int,
                          scale: Optional[float] = None, q_offset: int = 0,
                          k_start=0, interpret: bool = False):
    """Causal sliding-window attention of query rows (b, h, rows, d) at
    key positions ``q_offset ..`` over keys k (b, kvh, q_offset + rows,
    d) and values v (b, kvh, q_offset + rows, dv): row t (key position
    ``q_offset + t``) sees keys ``(q_offset + t - window, q_offset +
    t]`` from ``k_start`` on (an int, traced or not), query head h the
    KV head ``h // (h / kvh)``. Returns (b, h, rows, dv) in q's dtype;
    a row that sees no key is zeros. A grid step takes
    :func:`window_block`'s rows and keys; ``interpret`` runs the kernel
    in Pallas' TPU interpret mode."""
    b, h, rows, d = q.shape
    kvh, keys, dv = k.shape[1], k.shape[2], v.shape[-1]
    if keys != q_offset + rows or v.shape[2] != keys or h % kvh:
        raise ValueError(f"{h} heads of {rows} rows at {q_offset} over "
                         f"{kvh} heads of {keys} keys")
    bq = bk = window_block(rows, q_offset, window)
    if not bq or dv % _LANES:
        raise ValueError(f"no block of {rows} rows at {q_offset} under a "
                         f"window of {window}")
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # query block i reads key blocks i * bq / bk + base + j, j < steps:
    # from the block of its first row's oldest key to its own last
    base = (q_offset - window) // bk
    steps = (bq + window) // bk
    last = keys // bk - 1

    def key_block(bi, g, i, j, start):
        first = lax.div(jnp.maximum(start[0], _i32(0)), _i32(bk))
        kb = i * _i32(bq // bk) + _i32(base) + j
        # a step that is skipped fetches the first live block, which
        # the next step then finds in place
        return (bi, g, jnp.clip(kb, jnp.minimum(first, _i32(last)),
                                _i32(last)), _i32(0))

    def q_block(bi, g, i, j, start):
        return bi, g, i, _i32(0)

    flops = 2 * b * h * rows * (window + bq) * (d + dv)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), window=window,
                          q_offset=q_offset, bq=bq, bk=bk, base=base),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kvh, rows // bq, steps),
            in_specs=[pl.BlockSpec((None, rep, bq, d), q_block),
                      pl.BlockSpec((None, None, bk, d), key_block),
                      pl.BlockSpec((None, None, bk, dv), key_block)],
            out_specs=pl.BlockSpec((None, rep, bq, dv), q_block),
            scratch_shapes=[pltpu.VMEM((rep, bq, _LANES), _F32),
                            pltpu.VMEM((rep, bq, _LANES), _F32),
                            pltpu.VMEM((rep, bq, dv), _F32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=b * h * rows * (window + bq),
            bytes_accessed=(q.size + b * h * rows * dv) * q.dtype.itemsize
            + b * kvh * (rows // bq) * steps * bk * (d + dv)
            * k.dtype.itemsize),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=WINDOW_KERNEL_NAME,
    )(_i32(k_start).reshape(1), q, k, v)
    return out
