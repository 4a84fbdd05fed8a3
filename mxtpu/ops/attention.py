"""Attention kernels: dense, blockwise (flash-style online softmax),
Pallas flash on TPU, and ring attention over the ``sp`` mesh axis.

NEW components with no reference counterpart (SURVEY.md §5.7: MXNet
predates sequence parallelism; nearest in-tree artifact is the
interleaved MHA contrib op, ``src/operator/contrib/transformer.cc``
[path cite]). Design per the ring-attention recipe: blockwise attention
with running (max, denom, numerator) statistics; the ring variant
rotates KV shards around the sequence axis with ``lax.ppermute`` inside
``shard_map``, overlapping compute with ICI transfers.

All functions take (batch, num_heads, seq, head_dim) arrays. GQA is
supported: kv arrays may have fewer heads (num_heads % kv_heads == 0).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

__all__ = ["dense_attention", "blockwise_attention", "flash_attention",
           "ulysses_attention", "window_attention",
           "ring_attention", "slot_decode_attention",
           "paged_decode_attention", "paged_decode_path",
           "gathered_decode_attention", "latent_decode_path",
           "rows_decode_path", "gathered_rows_decode_attention",
           "block_decode_path", "paged_block_attention",
           "block_causal_rows_attention",
           "paged_latent_decode_attention",
           "gathered_latent_decode_attention", "latent_prefill_attention"]

_NEG_INF = -1e30  # finite "minus infinity": keeps fully-masked rows NaN-free

# the named scopes this file shares with models/llama.py (its comment
# above rms_norm has the whole list): a trace reader follows these names
ATTENTION_SCOPE = "attention"
KV_GATHER_SCOPE = "kv_gather"
# latent (MLA) attention, models/latent_moe.py: its own name, so that a
# trace tells a latent layer's time from a per-head layer's
MLA_SCOPE = "mla_attention"
# a block-diffusion step's attention (models/blockdiff_moe.py): a block
# of query rows a slot over the cache and the block itself
BLOCK_SCOPE = "block_attention"


def _repeat_kv(q, k, v):
    """Broadcast grouped KV heads up to the query head count (GQA)."""
    hq, hk = q.shape[1], k.shape[1]
    if hq != hk:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


def dense_attention(q, k, v, *, causal: bool = False,
                    mask: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    q_offset: int = 0, kv_offset: int = 0):
    """Reference-semantics attention, fully materialized scores.

    ``q_offset``/``kv_offset`` are the global positions of element 0 —
    used by the ring variant where each device holds a sequence shard.
    """
    k, v = _repeat_kv(q, k, v)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    allowed = None
    if causal:
        qpos = jnp.arange(q.shape[2]) + q_offset
        kpos = jnp.arange(k.shape[2]) + kv_offset
        allowed = (qpos[:, None] >= kpos[None, :])[None, None]
    if mask is not None:
        allowed = mask if allowed is None else (allowed & mask)
    if allowed is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        # masked softmax with fully-masked rows → zeros (matches the
        # blockwise/ring _finalize semantics), not uniform attention
        scores = jnp.where(allowed, scores, _NEG_INF)
        e = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        e = jnp.where(allowed, e, 0.0)
        denom = e.sum(axis=-1, keepdims=True)
        probs = e / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _online_block(q, k, v, m, l, o, scale, causal, q_off, kv_off,
                  extra_mask=None):
    """One flash step: fold a KV block into running (m, l, o) stats.

    m: (b,h,q) running row max; l: (b,h,q) running denominator;
    o: (b,h,q,d) running unnormalized numerator. All float32.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    allowed = None
    if causal:
        qpos = jnp.arange(q.shape[2]) + q_off
        kpos = jnp.arange(k.shape[2]) + kv_off
        allowed = (qpos[:, None] >= kpos[None, :])[None, None]
    if extra_mask is not None:
        allowed = extra_mask if allowed is None else (allowed & extra_mask)
    if allowed is not None:
        scores = jnp.where(allowed, scores, _NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    if allowed is not None:
        # fully-masked rows keep m_new == _NEG_INF, where exp(score -
        # m_new) == 1 would silently attend uniformly — zero them so l
        # stays 0 and _finalize emits zeros for such rows
        p = jnp.where(allowed, p, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, o_new


def _finalize(m, l, o, dtype):
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros, not NaN
    return (o / l[..., None]).astype(dtype)


def blockwise_attention(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None,
                        kv_block: int = 512,
                        q_offset: int = 0, kv_offset: int = 0):
    """Flash-style attention as a ``lax.scan`` over KV blocks: O(seq)
    memory, MXU-friendly block matmuls, no materialized score matrix."""
    k, v = _repeat_kv(q, k, v)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, h, sq, d = q.shape
    skv = k.shape[2]
    kv_block = min(kv_block, skv)
    nblk, rem = divmod(skv, kv_block)
    if rem:  # pad KV to a block multiple; padded keys are masked by offset
        pad = kv_block - rem
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        nblk += 1
    else:
        pad = 0

    kb = k.reshape(b, h, nblk, kv_block, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblk, kv_block, d).transpose(2, 0, 1, 3, 4)

    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)

    def body(carry, xs):
        m, l, o = carry
        i, kblk, vblk = xs
        blk_off = kv_offset + i * kv_block
        # padded tail keys: positions >= kv_offset+skv are masked out
        kpos = jnp.arange(kv_block) + blk_off
        valid = kpos < kv_offset + skv
        m2, l2, o2 = _online_block(
            q, kblk, vblk, m, l, o, scale, causal, q_offset,
            blk_off, extra_mask=valid[None, None, None, :])
        return (m2, l2, o2), None

    (m, l, o), _ = lax.scan(body, (m0, l0, o0),
                            (jnp.arange(nblk), kb, vb))
    return _finalize(m, l, o, q.dtype)


def _pallas_block_sizes(sq: int, skv: int):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    # measured v5e sweep at (b4, h16, s2048, d128), fwd+bwd: the
    # kernel's defaults run 24.4 ms; bq=1024/bk=512 runs 9.8 ms (dense
    # is 15.5). Q-blocks want to be wide (amortize the KV stream);
    # K-blocks at 512 keep the VMEM working set resident.
    bq = next(c for c in (1024, 512, 256, 128) if sq % c == 0)
    bk = next(c for c in (512, 256, 128) if skv % c == 0)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
        block_q_dq=bq)


def _tpu_pallas_flash(q, k, v, causal, scale):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _pl_flash)
    return _pl_flash(q, k, v, causal=causal, sm_scale=scale,
                     block_sizes=_pallas_block_sizes(q.shape[2],
                                                     k.shape[2]))


# What the kernel's backward pass needs of its forward pass, under the
# names a ``jax.checkpoint`` policy can keep them by (``models/llama.py``
# ``remat_plan``): the output and the softmax's two statistics a row.
ATTN_OUT_NAME = "attn_out"
ATTN_STATS_NAME = "attn_stats"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas_flash(q, k, v, causal, scale):
    """The Pallas kernel on grouped K and V (``_repeat_kv`` inside), with
    a backward rule of this file's own. The library's rule keeps the
    REPEATED K and V and the forward kernel's ``o``, ``l``, ``m`` where
    no name reaches them, so a checkpointed layer that saved ``o`` still
    ran the forward kernel again for the statistics. This one calls the
    same three kernels (the library's forward-with-residuals and its two
    backward calls: private names of the installed jax, pinned by
    ``tests/test_remat_plan.py`` and compiled for a v5e in
    ``tests/test_tpu_aot_scopes.py``), keeps q and the grouped k, v as
    they came, and names ``o`` and the statistics. Not differentiated,
    it is the library's public call, as before."""
    kr, vr = _repeat_kv(q, k, v)
    return _tpu_pallas_flash(q, kr, vr, causal, scale)


def _pallas_flash_fwd(q, k, v, causal, scale):
    from jax.experimental.pallas.ops.tpu import flash_attention as lib
    kr, vr = _repeat_kv(q, k, v)
    bs = _pallas_block_sizes(q.shape[2], kr.shape[2])
    # under the name the library's own jit gives the kernel in a trace
    # (``flash_attention.N``): the benchmark's readers find it by that
    with jax.named_scope("flash_attention"):
        o, l, m = lib._flash_attention_impl(
            q, kr, vr, None, None, True, causal, scale, bs.block_b,
            bs.block_q, bs.block_k_major, bs.block_k, False)
    o = checkpoint_name(o, ATTN_OUT_NAME)
    l = checkpoint_name(l, ATTN_STATS_NAME)
    m = checkpoint_name(m, ATTN_STATS_NAME)
    return o, (q, k, v, o, l, m)


def _pallas_flash_bwd(causal, scale, res, do):
    from jax.experimental.pallas.ops.tpu import flash_attention as lib
    q, k, v, o, l, m = res
    (kr, vr), fold = jax.vjp(lambda k, v: _repeat_kv(q, k, v), k, v)
    bs = _pallas_block_sizes(q.shape[2], kr.shape[2])
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    dk, dv = lib._flash_attention_bwd_dkv(
        q, kr, vr, None, None, l, m, do, di,
        block_q_major=bs.block_q_major_dkv,
        block_k_major=bs.block_k_major_dkv, block_k=bs.block_k_dkv,
        block_q=bs.block_q_dkv, sm_scale=scale, causal=causal,
        mask_value=lib.DEFAULT_MASK_VALUE, debug=False)
    dq, _ = lib._flash_attention_bwd_dq(
        q, kr, vr, None, None, l, m, do, di,
        block_q_major=bs.block_q_dq, block_k_major=bs.block_k_major_dq,
        block_k=bs.block_k_dq, sm_scale=scale, causal=causal,
        mask_value=lib.DEFAULT_MASK_VALUE, debug=False)
    return (dq, *fold((dk, dv)))


_pallas_flash.defvjp(_pallas_flash_fwd, _pallas_flash_bwd)


def _flash_path(q_shape, kv_len: int) -> str:
    """Which implementation :func:`flash_attention` runs for these
    shapes on this backend: ``"pallas"`` (the Mosaic kernel — TPU
    only, and it wants both sequence lengths and the head dim in
    multiples of 128) or ``"blockwise"`` (the ``lax.scan`` online
    softmax). Decided from backend and shapes alone."""
    if len(q_shape) == 4 and jax.default_backend() == "tpu":
        sq, d = q_shape[2], q_shape[3]
        if sq % 128 == 0 and kv_len % 128 == 0 and d % 128 == 0:
            return "pallas"
    return "blockwise"


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    kv_block: int = 512):
    """Fused attention: Pallas (Mosaic) kernel on TPU, blockwise scan
    elsewhere (:func:`_flash_path` says which). This is the rebuild's
    hot-path attention op — the role cuDNN's fused MHA played in the
    reference. Each path runs under its own named scope, so a lowered
    program or a trace shows which one it holds; a kernel failure
    raises."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if _flash_path(q.shape, k.shape[2]) == "pallas":
        with jax.named_scope("flash_attention_pallas"):
            return _pallas_flash(q, k, v, causal, scale)
    kr, vr = _repeat_kv(q, k, v)
    with jax.named_scope("flash_attention_blockwise"):
        return blockwise_attention(q, kr, vr, causal=causal, scale=scale,
                                   kv_block=kv_block)


def window_attention(q, k, v, *, window: int,
                     scale: Optional[float] = None, block: int = 512,
                     k_start=0):
    """Causal sliding-window attention for a prefill: query ``t`` sees
    keys ``(t - window, t]``, itself and the ``window - 1`` before it.
    Blockwise online softmax (:func:`_online_block`) in which a block of
    queries reads only the key blocks its window can reach — itself and
    ``ceil((window - 1) / block)`` blocks behind it — so the work is
    ``seq x window``, not ``seq x seq``; blocks wholly outside the
    window are never read. q: (b, h, s, d); k: (b, hk, s, d); v:
    (b, hk, s, dv) (GQA: ``h % hk == 0``). Keys before position
    ``k_start`` (an int, traced or not) are not seen: a chunk of a longer
    sequence puts the window's worth of keys before it in front, and
    at the sequence's start there are none."""
    k, v = _repeat_kv(q, k, v)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, h, s, d = q.shape
    dv = v.shape[-1]
    block = min(block, s)
    pad = -s % block
    back = -(-(window - 1) // block)     # key blocks behind a query block
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # keys padded in front by ``back`` blocks, so that query block i
    # reads padded blocks i .. i + back whatever i is; positions < 0
    # (and the tail's, by causality) are masked
    k = jnp.pad(k, ((0, 0), (0, 0), (back * block, pad), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (back * block, pad), (0, 0)))
    at = jnp.arange(block)

    def one(i):
        qi = lax.dynamic_slice_in_dim(q, i * block, block, axis=2)
        qpos = i * block + at
        m = jnp.full((b, h, block), _NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, block), jnp.float32)
        o = jnp.zeros((b, h, block, dv), jnp.float32)
        for j in range(back + 1):
            start = (i + j) * block
            kpos = start - back * block + at
            seen = ((kpos[None, :] <= qpos[:, None])
                    & (kpos[None, :] > qpos[:, None] - window)
                    & (kpos[None, :] >= k_start))
            m, l, o = _online_block(
                qi, lax.dynamic_slice_in_dim(k, start, block, axis=2),
                lax.dynamic_slice_in_dim(v, start, block, axis=2),
                m, l, o, scale, False, 0, 0,
                extra_mask=seen[None, None])
        return _finalize(m, l, o, q.dtype)

    out = lax.map(one, jnp.arange((s + pad) // block))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, s + pad, dv)[:, :, :s]


@jax.named_scope(ATTENTION_SCOPE)
def slot_decode_attention(q, k, v, lengths, *, scale: Optional[float] = None,
                          kv_block: int = 512):
    """Length-masked decode attention over a SLOT KV cache — the
    serving engine's kernel (``mxtpu.serve``): each slot holds an
    independent request whose cache row is valid only up to its own
    ``lengths[i]``, so one fixed-shape program serves a ragged batch.

    q: (slots, n_heads, s, hd) — the new token(s), s is 1 in decode.
    k, v: (slots, n_kv_heads, max_len, hd) — the per-layer slot cache
    (GQA: ``n_heads % n_kv_heads == 0``; queries are grouped per kv
    head, the cache is never repeated).
    lengths: (slots,) int — slot i attends keys ``[0, lengths[i])`` —
    or (slots, s) int for PER-QUERY lengths: query j of slot i attends
    ``[0, lengths[i, j])``. The 2-D form is the speculative verify
    step's causal mask (query j sees the prefix plus the j drafted
    tokens before it) and reduces to the 1-D form at s == 1, so the
    decode fast path is unchanged.

    Blockwise flash-style online softmax over ``kv_block``-wide KV
    slices: the (s, max_len) score matrix is never materialized — only
    one (slots, groups, rep, s, kv_block) block of scores lives at a
    time, with running (max, denom, numerator) carries. Fully-masked
    rows (lengths == 0) come out as zeros, matching ``dense_attention``
    masked-softmax semantics."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hq % hkv:
        raise ValueError(f"{hq} q heads not divisible by {hkv} kv heads")
    rep = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    max_len = k.shape[2]
    lengths = lengths.astype(jnp.int32)
    kv_block = min(kv_block, max_len)
    nblk, remv = divmod(max_len, kv_block)
    if remv:  # pad the cache tail; padded keys are masked by position
        pad = kv_block - remv
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        nblk += 1

    qg = q.reshape(b, hkv, rep, sq, d)
    kb = k.reshape(b, hkv, nblk, kv_block, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nblk, kv_block, d).transpose(2, 0, 1, 3, 4)

    m0 = jnp.full((b, hkv, rep, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, sq), jnp.float32)
    o0 = jnp.zeros((b, hkv, rep, sq, d), jnp.float32)

    def body(carry, xs):
        m, l, o = carry
        i, kblk, vblk = xs
        scores = jnp.einsum("bgrsd,bgkd->bgrsk", qg, kblk,
                            preferred_element_type=jnp.float32) * scale
        kpos = i * kv_block + jnp.arange(kv_block)       # (kv_block,)
        if lengths.ndim == 2:   # per-query: (b, sq, kv_block)
            allowed = kpos[None, None, :] < lengths[:, :, None]
            allowed = allowed[:, None, None, :, :]
        else:
            allowed = kpos[None, :] < lengths[:, None]   # (b, kv_block)
            allowed = allowed[:, None, None, None, :]
        scores = jnp.where(allowed, scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(allowed, p, 0.0)   # length-0 slots stay all-zero
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bgrsk,bgkd->bgrsd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    (m, l, o), _ = lax.scan(body, (m0, l0, o0),
                            (jnp.arange(nblk), kb, vb))
    out = _finalize(m, l, o, q.dtype)
    return out.reshape(b, hq, sq, d)


def paged_decode_path(q_shape, pool_shape, pool_dtype, lengths_ndim: int,
                      *, scales: bool = False, mesh=None) -> str:
    """Which implementation :func:`paged_decode_attention` runs for
    these inputs on this backend: ``"pages"`` (the Pallas kernel of
    ``ops.paged_attention``: it reads the live pages out of the pool
    and nothing else) or ``"gathered"`` (every slot's whole row of
    pages copied out, then :func:`slot_decode_attention`). Decided from
    the backend, shapes and dtypes alone, as :func:`_flash_path` is;
    nobody sets it.

    The kernel runs on a TPU, for the plain decode step (one query a
    slot, (slots,) lengths), over a pool it takes as it is stored
    (``ops.paged_attention.takes``: bfloat16, no scale pools, heads of a
    multiple of 128 lanes in multiples of 8, so that its view of the
    pool is a bitcast), and no mesh. Everything else is gathered: the
    speculative verify step ((slots, W) lengths), int8 pools
    (dequantised on the gathered bytes), a ``tp`` mesh (a kernel under
    ``shard_map`` is a later step), float32 pools (the tests and
    ``chip_smoke.py``'s float32 leg compare tokens with ``generate``
    bit for bit, which another block size would break), and any other
    backend."""
    from .paged_attention import takes
    if (jax.default_backend() == "tpu" and mesh is None and not scales
            and lengths_ndim == 1
            and takes(q_shape, pool_shape, pool_dtype)):
        return "pages"
    return "gathered"


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           layer=None,
                           scale: Optional[float] = None,
                           kv_block: int = 512, mesh=None):
    """Decode attention over a PAGED KV pool (vLLM's PagedAttention,
    Kwon et al. SOSP '23): the cache is a pool of fixed-size pages
    and each slot's logical KV sequence is the concatenation of the
    pool pages its row of ``page_table`` names.

    Two carriers of one algorithm, the float32 online softmax over a
    slot's keys ``[0, lengths[s])``; :func:`paged_decode_path` says
    which runs, from the inputs. **On a TPU, the plain decode step over
    a bfloat16 pool** runs the Pallas kernel ``ops.paged_attention.
    paged_attention_pages`` (``paged_decode_attention_pages`` in a
    trace, under the scope ``attention``): the whole pool stays in HBM
    where it lies, and the kernel DMAs pages ``page_table[s, 0 :
    ceil(lengths[s] / page_size)]`` of ``pool[layer]`` into VMEM and
    stops there; no gathered copy exists and nothing runs under
    ``kv_gather``. **Everything else** (the verify step's (slots, s)
    lengths, float32 and int8 pools, a mesh, a CPU or GPU) gathers +
    runs the blockwise ``slot_decode_attention`` online softmax — the
    tests' reference, bit-exact with the dense slot kernel on the same
    logical KV (the gather materializes the identical (slots, kvh,
    capacity, hd) operand; trailing pages past ``lengths`` are fully
    masked, which the online-softmax scan treats as an exact no-op: m
    unchanged, corr = exp(0) = 1, p zeroed). The two agree up to the
    order of summation.

    q: (slots, n_heads, s, hd) — s is 1 in decode.
    k_pages, v_pages: (n_pages, page_size, n_kv_heads, hd) — the shared
    pool, stored token-major (page, in-page offset lead: the layout the
    decode write wants, ``llama.init_paged_cache``) — or, with
    ``layer`` (a traced scalar), the whole (L, n_pages, page_size,
    n_kv_heads, hd) pool, read at (layer, page) by index so that no
    layer slab is ever sliced out of it. Page 0 is the
    engine's scratch page (never attended: every real table entry
    covering positions < lengths names a live page).
    page_table: (slots, pages_per_slot) int32 — slot i's logical page j
    lives at pool index ``page_table[i, j]``. Every entry must lie in
    ``[0, n_pages)``: the gather promises its indices are in bounds
    (the default out-of-bounds fill is a select over every gathered
    row), and the allocator hands out nothing else (``serve.engine.
    PageAllocator``; zeroed entries name scratch page 0).
    lengths: (slots,) int — slot i attends positions ``[0, lengths[i])``
    of its pages — or (slots, s) for per-query lengths,
    passed straight through to the slot kernel (the speculative
    verify step's mask).
    mesh: the mesh the caller's program is partitioned over, if any
    (the kernel is not partitioned yet).
    """
    if q.shape[0] != page_table.shape[0]:
        raise ValueError(
            f"page_table rows {page_table.shape[0]} != slots {q.shape[0]}")
    if paged_decode_path(q.shape, k_pages.shape, k_pages.dtype,
                         lengths.ndim, mesh=mesh) == "pages":
        from .paged_attention import paged_attention_pages
        with jax.named_scope(ATTENTION_SCOPE):
            return paged_attention_pages(q, k_pages, v_pages, page_table,
                                         lengths, layer=layer, scale=scale)
    return gathered_decode_attention(q, k_pages, v_pages, page_table,
                                     lengths, layer=layer, scale=scale,
                                     kv_block=kv_block)


def gathered_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                              layer=None, scale: Optional[float] = None,
                              kv_block: int = 512):
    """:func:`paged_decode_attention`'s gathered arm, whatever the
    inputs: every slot's whole row of pages copied out of the pool
    (under ``kv_gather``), then :func:`slot_decode_attention`. What the
    kernel is held against, in the tests and on the chip."""
    page_size, hkv, d = k_pages.shape[-3:]
    slots, per_slot = page_table.shape
    idx = page_table if layer is None else (layer, page_table)
    # gather (S, P, ps, kvh, hd) → contiguous (S, kvh, P*ps, hd)
    @jax.named_scope(KV_GATHER_SCOPE)
    def flat(pool):
        g = pool.at[idx].get(mode="promise_in_bounds")
        return (g.reshape(slots, per_slot * page_size, hkv, d)
                 .transpose(0, 2, 1, 3))
    return slot_decode_attention(q, flat(k_pages), flat(v_pages), lengths,
                                 scale=scale, kv_block=kv_block)


def latent_decode_path(q_shape, pool_shape, pool_dtype, *, mesh=None) -> str:
    """Which implementation :func:`paged_latent_decode_attention` runs
    over a latent pool (L, n_pages, page_size, row), decided like
    :func:`paged_decode_path` from backend, shapes and dtypes:
    ``"pages"`` (``ops.paged_attention.paged_latent_pages``: the live
    pages read out of the pool, once, as keys and values) on a TPU over
    a bfloat16 pool it takes as stored (``takes_latent``: rows of whole
    lane tiles) and no mesh; ``"gathered"`` everywhere else. ``q_shape``
    is the query as the pool's rows are wide."""
    from .paged_attention import takes_latent
    if (jax.default_backend() == "tpu" and mesh is None
            and takes_latent(q_shape, pool_shape, pool_dtype)):
        return "pages"
    return "gathered"


def paged_latent_decode_attention(q, pool, page_table, lengths, *, layer,
                                  value_dim: int,
                                  scale: Optional[float] = None):
    """Decode attention in the absorbed form over a PAGED pool of
    latent rows: every head reads the same row a token, which is its
    key and, in its first ``value_dim`` values, its value.

    q: (slots, n_heads, 1, row): a head's query carried into the
    latent space, its rope part behind it. pool: the whole (L, n_pages,
    page_size, >= row) pool, read at (``layer``, page) by index; a
    stored row may end in zeros (padding to whole lane tiles), which
    the query is padded to meet. page_table: (slots, pages_per_slot)
    int32, every entry in [0, n_pages). lengths: (slots,), slot i
    attends ``[0, lengths[i])``. Returns (slots, n_heads, 1, value_dim)
    float32, the softmax-weighted sum of the rows' value parts (a slot
    of length 0: zeros).

    Two carriers (:func:`latent_decode_path`), both under
    ``mla_attention``. **pages**: the Pallas kernel walks each slot's
    live pages (``paged_latent_attention_pages`` in a trace); nothing
    is gathered. **gathered**: :func:`gathered_latent_decode_attention`."""
    row = pool.shape[-1]
    scale = float(scale if scale is not None
                  else 1.0 / math.sqrt(q.shape[-1]))
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, row - q.shape[-1])))
    if latent_decode_path(q.shape, pool.shape, pool.dtype) == "pages":
        from .paged_attention import paged_latent_pages
        with jax.named_scope(MLA_SCOPE):
            return paged_latent_pages(
                q, pool, page_table, lengths, layer=layer, scale=scale
            )[..., :value_dim].astype(jnp.float32)
    return gathered_latent_decode_attention(
        q, pool, page_table, lengths, layer=layer, value_dim=value_dim,
        scale=scale)


def gathered_latent_decode_attention(q, pool, page_table, lengths, *, layer,
                                     value_dim: int, scale: float):
    """:func:`paged_latent_decode_attention`'s gathered arm, whatever
    the inputs (q as wide as the pool's rows): every slot's whole row of
    pages copied out under ``kv_gather``; scores over the whole capacity
    (slots x heads x capacity floats: the row is shared, so there is no
    per-head copy to block over), softmax in float32, the weights
    rounded to the rows' type for the value product. What the kernel is
    held against, in the tests and on the chip."""
    slots, per_slot = page_table.shape
    page_size, row = pool.shape[-2:]
    with jax.named_scope(KV_GATHER_SCOPE):
        rows = pool.at[layer, page_table].get(mode="promise_in_bounds")
        rows = rows.reshape(slots, per_slot * page_size, row)
    with jax.named_scope(MLA_SCOPE):
        s = jnp.einsum("bhsd,bkd->bhsk", q, rows,
                       preferred_element_type=jnp.float32) * scale
        allowed = (jnp.arange(rows.shape[1])[None, :]
                   < lengths.astype(jnp.int32)[:, None])[:, None, None, :]
        s = jnp.where(allowed, s, _NEG_INF)
        e = jnp.where(allowed, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        denom = e.sum(-1, keepdims=True)
        p = e / jnp.where(denom == 0.0, 1.0, denom)
        # over the whole row, the small result cut to its value part:
        # a slice of the gathered rows would be a copy of them
        return jnp.einsum("bhsk,bkv->bhsv", p.astype(rows.dtype), rows,
                          preferred_element_type=jnp.float32
                          )[..., :value_dim]


def rows_decode_path(q_shape, pool_shape, pool_dtype, *, mesh=None) -> str:
    """Which attention a decode program reads pools of TOKEN rows (L,
    n_pages, page_size, kvh * hd) through (``models/sambay.py``'s one
    shared cache: K and V apart, a token's heads end to end), decided
    like :func:`paged_decode_path` from backend, shapes and dtypes:
    ``"pages"`` (``ops.paged_attention.paged_attention_rows``: each
    read walks the slot's live pages where they lie) on a TPU over
    bfloat16 pools it takes as stored (``takes_rows``: heads of whole
    lane tiles, pages of whole (16, 128) tiles) and no mesh;
    ``"gathered"`` everywhere else (every slot's whole row of pages
    copied out and turned head-major, then
    :func:`slot_decode_attention`). ``q_shape`` is (slots, n_heads, 1,
    hd)."""
    from .paged_attention import takes_rows
    if (jax.default_backend() == "tpu" and mesh is None
            and takes_rows(q_shape, pool_shape, pool_dtype)):
        return "pages"
    return "gathered"


def gathered_rows_decode_attention(q, k_pages, v_pages, page_table, lengths,
                                   *, layer, scale: Optional[float] = None,
                                   kv_block: int = 512):
    """Decode attention over pools of token rows (L, n_pages, page_size,
    kvh * hd) by the gathered arm, whatever the inputs: every slot's
    whole row of pages copied out of the pool and turned head-major
    (under ``kv_gather``), then :func:`slot_decode_attention`. What
    ``paged_attention_rows`` is held against, in the tests and on the
    chip."""
    hd = q.shape[-1]
    return slot_decode_attention(
        q, _gather_token_rows(k_pages, layer, page_table, hd),
        _gather_token_rows(v_pages, layer, page_table, hd), lengths,
        scale=scale, kv_block=kv_block)


@jax.named_scope(KV_GATHER_SCOPE)
def _gather_token_rows(pool, layer, page_table, hd: int):
    """Every slot's whole row of pages out of a pool of token rows (L,
    n_pages, page_size, kvh * hd), turned head-major: (slots, kvh,
    capacity, hd). The gathered arms' copy of capacity."""
    slots, per_slot = page_table.shape
    page_size, width = pool.shape[-2:]
    g = pool.at[layer, page_table].get(mode="promise_in_bounds")
    return (g.reshape(slots, per_slot * page_size, width // hd, hd)
             .transpose(0, 2, 1, 3))


def block_decode_path(q_shape, pool_shape, pool_dtype, *, mesh=None) -> str:
    """Which attention a step over BLOCKS of query rows (q (slots,
    n_heads, rows, hd), every row of a slot seeing the same keys) reads
    pools of token rows through, decided as :func:`rows_decode_path`
    decides: ``"pages"`` (``ops.paged_attention.paged_attention_block``:
    the block's rows folded into the query-head group, the walk over
    live pages as it is) on a TPU over pools it takes as stored
    (``takes_block``) and no mesh; ``"gathered"`` everywhere else."""
    from .paged_attention import takes_block
    if (jax.default_backend() == "tpu" and mesh is None
            and takes_block(q_shape, pool_shape, pool_dtype)):
        return "pages"
    return "gathered"


def paged_block_attention(q, k_pages, v_pages, page_table, lengths, *,
                          layer, scale: Optional[float] = None, mesh=None):
    """A block of query rows a slot over pools of token rows, by the
    path :func:`block_decode_path` names. q: (slots, n_heads, rows, hd);
    lengths: (slots,) the keys every row of the slot sees (the caller
    has written the block's own). The gathered arm is
    :func:`gathered_rows_decode_attention`'s: every slot's whole row of
    pages copied out under ``kv_gather``, then
    :func:`slot_decode_attention`, which takes any number of query rows
    under one length."""
    if block_decode_path(q.shape, k_pages.shape, k_pages.dtype,
                         mesh=mesh) == "pages":
        from .paged_attention import paged_attention_block
        with jax.named_scope(BLOCK_SCOPE):
            return paged_attention_block(q, k_pages, v_pages, page_table,
                                         lengths, layer=layer, scale=scale)
    hd = q.shape[-1]
    k = _gather_token_rows(k_pages, layer, page_table, hd)
    v = _gather_token_rows(v_pages, layer, page_table, hd)
    with jax.named_scope(BLOCK_SCOPE):
        return slot_decode_attention(q, k, v, lengths, scale=scale)


def latent_prefill_attention(q_nope, q_rope, rows, wkvb, *, layer,
                             q_offset, scale: float, kv_block: int = 512):
    """Causal attention of a run of queries over latent rows in the
    DECOMPRESSED form: a block of keys at a time, per-head keys and
    values are rebuilt from the rows (``[k_nope, v] = c W_kvb``), ``score
    = q_nope . k_nope + q_rope . k_rope`` with the row's rope part
    shared by every head, online softmax in float32
    (:func:`_online_block`'s arithmetic).

    q_nope: (b, H, s, nope), q_rope: (b, H, s, rope), the queries at
    positions ``q_offset .. q_offset + s`` (``q_offset`` a traced
    scalar). rows: the whole (L, b, capacity, >= rank + rope) row
    store, read at ``layer``; position p's row at index p (zeros may
    follow its rope part); ``capacity`` a multiple of ``kv_block``. wkvb: (rank, H, nope + v). Only the key
    blocks below ``q_offset + s`` are read, so a chunk early in a
    prompt costs less than one late in it. Returns (b, H, s, v)
    float32."""
    b, H, s, nope = q_nope.shape
    scale = float(scale)
    rank = wkvb.shape[0]
    dv = wkvb.shape[-1] - nope
    q_offset = jnp.asarray(q_offset, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    qpos = q_offset + jnp.arange(s, dtype=jnp.int32)
    at = jnp.arange(kv_block, dtype=jnp.int32)
    z = jnp.zeros((), jnp.int32)

    def body(i, carry):
        m, l, o = carry
        i = i.astype(jnp.int32)       # under x64 the loop counts in 64
        blk = lax.dynamic_slice(
            rows, (layer, z, i * kv_block, z),
            (1, b, kv_block, rows.shape[-1]))[0]
        c = blk[..., :rank]
        k_rope = blk[..., rank:rank + q_rope.shape[-1]]
        kv = jnp.einsum("bkr,rhn->bhkn", c, wkvb)
        sc = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, kv[..., :nope],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhqd,bkd->bhqk", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
        allowed = ((i * kv_block + at)[None, :]
                   <= qpos[:, None])[None, None]
        sc = jnp.where(allowed, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.where(allowed, jnp.exp(sc - m_new[..., None]), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkv->bhqv", p.astype(kv.dtype), kv[..., nope:],
            preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    init = (jnp.full((b, H, s), _NEG_INF, jnp.float32),
            jnp.zeros((b, H, s), jnp.float32),
            jnp.zeros((b, H, s, dv), jnp.float32))
    n_blocks = (q_offset + s + kv_block - 1) // kv_block
    m, l, o = lax.fori_loop(z, n_blocks, body, init)
    return _finalize(m, l, o, jnp.float32)


def block_causal_rows_attention(q, k_rows, v_rows, *, layer, q_offset,
                                block: int, scale: Optional[float] = None,
                                kv_block: int = 512):
    """Block-causal attention of a run of queries over stores of token
    rows: position i sees position j iff ``j // block <= i // block``
    (causal across blocks of ``block`` positions, bidirectional inside
    one), which a ``causal`` flag cannot say. XLA's arithmetic
    (:func:`latent_prefill_attention`'s loop): a block of keys at a
    time, online softmax in float32.

    q: (b, H, s, hd), the queries at positions ``q_offset .. q_offset +
    s`` (``q_offset`` a traced scalar). k_rows, v_rows: the whole (L, b,
    capacity, G hd) row stores, a token's ``G`` KV heads end to end,
    read at ``layer``; position p's row at index p; ``capacity`` a
    multiple of ``kv_block``. Only the key blocks below ``q_offset + s``
    rounded up to ``block`` are read. Returns (b, H, s, hd) float32."""
    b, H, s, hd = q.shape
    G = k_rows.shape[-1] // hd
    rep = H // G
    scale = float(scale if scale is not None else 1.0 / math.sqrt(hd))
    q_offset = jnp.asarray(q_offset, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    # the first position a query does NOT see: the end of its own block
    seen = ((q_offset + jnp.arange(s, dtype=jnp.int32)) // block + 1) * block
    at = jnp.arange(kv_block, dtype=jnp.int32)
    z = jnp.zeros((), jnp.int32)
    qg = q.reshape(b, G, rep, s, hd)

    def heads(rows, i):
        blk = lax.dynamic_slice(
            rows, (layer, z, i * kv_block, z),
            (1, b, kv_block, rows.shape[-1]))[0]
        return blk.reshape(b, kv_block, G, hd)

    def body(i, carry):
        m, l, o = carry
        i = i.astype(jnp.int32)       # under x64 the loop counts in 64
        k, v = heads(k_rows, i), heads(v_rows, i)
        sc = jnp.einsum("bgrqd,bkgd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
        allowed = ((i * kv_block + at)[None, :]
                   < seen[:, None])[None, None, None]
        sc = jnp.where(allowed, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.where(allowed, jnp.exp(sc - m_new[..., None]), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    init = (jnp.full((b, G, rep, s), _NEG_INF, jnp.float32),
            jnp.zeros((b, G, rep, s), jnp.float32),
            jnp.zeros((b, G, rep, s, hd), jnp.float32))
    last = (q_offset + s + block - 1) // block * block
    n_blocks = jnp.minimum((last + kv_block - 1) // kv_block,
                           k_rows.shape[2] // kv_block)
    m, l, o = lax.fori_loop(z, n_blocks, body, init)
    return _finalize(m, l, o, jnp.float32).reshape(b, H, s, hd)


def ring_attention(q, k, v, *, axis_name: str = "sp",
                   causal: bool = False,
                   scale: Optional[float] = None,
                   kv_block: int = 512):
    """Ring attention over the ``axis_name`` mesh axis.

    Call INSIDE ``shard_map`` where q/k/v hold this device's sequence
    shard. Each of the ``n`` ring steps computes blockwise attention of
    the local Q against the currently-held KV shard, then rotates KV to
    the next device with ``ppermute`` — total memory O(seq/n), ICI
    traffic fully overlapped by XLA's async collective scheduling.
    """
    k, v = _repeat_kv(q, k, v)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    skv = k.shape[2]

    # derive the running stats from q so they inherit q's varying-
    # manual-axes set (jax>=0.8 types carries by vma; fresh zeros would
    # be unvarying and fail the fori_loop carry check)
    zero = (q[..., 0] * 0).astype(jnp.float32)
    m0 = zero + _NEG_INF
    l0 = zero
    o0 = (q * 0).astype(jnp.float32)

    from ..parallel.collectives import ppermute_ring

    def body(i, carry):
        m, l, o, kc, vc = carry
        # after i rotations (shift=+1) this device holds the shard that
        # started on device (my - i) mod n
        kv_idx = (my - i) % n
        q_off = my * sq
        kv_off = kv_idx * skv
        m, l, o = _online_block(q, kc, vc, m, l, o, scale, causal,
                                q_off, kv_off)
        kc = ppermute_ring(kc, axis_name)
        vc = ppermute_ring(vc, axis_name)
        return m, l, o, kc, vc

    m, l, o, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    return _finalize(m, l, o, q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str = "sp",
                      causal: bool = False,
                      scale: Optional[float] = None,
                      kv_block: int = 512):
    """Ulysses-style sequence parallelism (DeepSpeed-Ulysses; SURVEY
    §5.7(c)): two all-to-alls reshard sequence-sharded QKV into
    head-sharded full-sequence tensors, attention runs locally over the
    FULL sequence for this device's head subset, and a final all-to-all
    restores the sequence sharding.

    Call INSIDE ``shard_map`` with q/k/v holding this device's sequence
    shard, shapes (b, h, s/n, d). Heads must divide by the axis size.
    vs ring attention: 4 all-to-alls (q, k, v, out) instead of n KV
    rotations — wins when heads ≥ devices and seq is very long. KV
    cross the wire UN-repeated (GQA head count) whenever the kv-head
    count divides the axis, so grouped-query models pay kv-sized, not
    q-sized, K/V collectives.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    n = lax.psum(1, axis_name)
    h = q.shape[1]
    h_kv = k.shape[1]
    if h % n:
        raise ValueError(f"{h} heads not divisible over {n} '"
                         f"{axis_name}' devices (Ulysses reshard)")

    def seq_to_heads(x):
        # (b, h, s/n, d) → (b, h/n, s, d)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    if h_kv % n == 0:
        # reshard the GQA-sized KV, repeat locally AFTER the collective
        qh = seq_to_heads(q)
        kh = seq_to_heads(k)
        vh = seq_to_heads(v)
        kh, vh = _repeat_kv(qh, kh, vh)
    else:
        k, v = _repeat_kv(q, k, v)
        qh = seq_to_heads(q)
        kh = seq_to_heads(k)
        vh = seq_to_heads(v)
    # full sequence present locally → plain causal masking works; use
    # the blockwise kernel (O(seq) memory) over the local head subset
    oh = blockwise_attention(qh, kh, vh, causal=causal, scale=scale,
                             kv_block=kv_block)
    return heads_to_seq(oh)
