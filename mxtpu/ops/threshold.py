"""The sampler's two thresholds, found by search and not from an order.

Top-k and top-p (nucleus) sampling mask a row of logits with two
numbers: ``kth``, the k-th largest value of the row, and the nucleus
cut-off, the smallest value ``v`` of the row for which the probability
mass of the values strictly above ``v`` is under ``top_p``. A sort of
the row gives both, and on a TPU a sort of a 200064-wide row is the
costliest operation of a decode step (3.9 ms of 28.6, v5e, PR 33). But
neither number needs an order. Both predicates are monotone in ``v``:

    count(x >= v) >= k                         falls as v rises
    sum(exp(x - max) where x > v) < top_p * Z  rises as v rises

and a monotone predicate over float32 values is decided by bisection on
the value's ORDERED BIT PATTERN (:func:`_key`: the int32 whose order is
the floats' order) in at most 32 probes, each a compare, a select and a
sum over the row. The search is exact for any distribution: every probe
is evaluated by VALUE on the float32 logits (so ``+0.0`` and ``-0.0``
are one value and a tie-class is kept or cut as a whole, as the
``where(lg >= cutoff)`` that follows would), and the sums are float32
sums of the same terms ``cumsum`` would add, in another order. A fixed
order of summation makes each predicate exactly monotone (float
addition is monotone in each operand), so the answer does not depend
on the path of the bisection.

Two forms of that search:

- :func:`kth_value` and :func:`nucleus_cutoff`, in ``jnp``: the
  semantics, and what runs everywhere but a TPU (a ``fori_loop`` of one
  fused compare-and-reduce, the row read from memory each probe);
- one Pallas kernel (:data:`KERNEL_NAME`) that holds eight rows at a
  time in VMEM, a row a sublane, and runs every probe on them there:
  the logits are read from HBM once. It takes blocks of eight rows or
  more (a decode step's bank); a one-row sample keeps the ``jnp`` form.

:func:`thresholds` is the sampler's entry: it runs the kernel or the
``jnp`` form (:func:`thresholds_path` says which, from backend, shape
and dtype; nobody sets it) and skips a search no row asks for
(``top_k`` of the vocabulary's size, ``top_p`` of 1).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kth_value", "nucleus_cutoff", "thresholds", "thresholds_path",
           "KERNEL_NAME", "PROBES"]

KERNEL_NAME = "sampler_threshold_search"
# probes that decide one float32 value between two others: the ordered
# keys are 32 bits wide
PROBES = 32

_F32 = jnp.float32
_NEG_INF = float("-inf")
# the ordered key of -inf (:func:`_key`): every search's lower end. The
# patterns from it up to a row's maximum are all numbers, so no probe is
# a NaN
_KEY_NEG_INF = -0x7F800001
# the kernel's block: 8 rows (a row a sublane) by the whole vocabulary,
# in tiles of 128 lanes, _GROUP tiles an iteration of its inner loop
_ROWS, _LANES, _GROUP = 8, 128, 16
# VMEM the kernel may ask for: the block twice (the pipeline's two
# buffers) and exp(x - max) once; a v5e core has 128 MiB
_VMEM_LIMIT = 64 * 2 ** 20


def _key(x):
    """float32 -> the int32 that orders as the values do (a negative
    float's magnitude bits flipped). Its own inverse on the bits; -0.0
    is key -1 and +0.0 key 0, which is why probes compare VALUES."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _value(key):
    """The float32 of an ordered key."""
    return lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key), _F32)


def _bisect(lo, hi, holds, *, smallest: bool):
    """The value of the smallest (``smallest``) or largest key in [lo,
    hi] at which the monotone ``holds(value)`` is true, in ``PROBES``
    probes. ``holds`` must be true at ``hi`` (smallest) or at ``lo``
    (largest). The answer is the predicate's own step, whatever the
    bounds: the probes only have to bracket it."""
    def probe(_, bounds):
        lo, hi = bounds
        mid = (lo & hi) + ((lo ^ hi) >> 1)      # floor mean, no overflow
        if smallest:
            ok = holds(_value(mid))
            return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)
        mid = mid + ((lo ^ hi) & 1)             # ceiling mean
        ok = holds(_value(mid))
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)
    lo, hi = lax.fori_loop(0, PROBES, probe, (lo, hi))
    return _value(lo if smallest else hi)


# -- the jnp form: the semantics ---------------------------------------------
def kth_value(lg, k):
    """The k-th largest value of each row: the largest ``v`` with
    ``count(x >= v) >= k``. lg: (.., V) float32; k: an int or (.., 1)
    ints >= 1. Returns (.., 1) float32, ``-inf`` ("no threshold") where
    ``k >= V``."""
    V = lg.shape[-1]
    k = jnp.asarray(k, jnp.int32)
    top = _key(jnp.max(lg, axis=-1, keepdims=True))
    kth = _bisect(
        jnp.full_like(top, _KEY_NEG_INF), top,
        lambda v: jnp.sum(lg >= v, axis=-1, keepdims=True,
                          dtype=jnp.int32) >= k,
        smallest=False)
    return jnp.where(k >= V, _NEG_INF, kth)


def nucleus_cutoff(lg, top_p):
    """The smallest logit of each row's top-p nucleus: the smallest row
    value ``v`` with ``sum(exp(x - max) where x > v) < top_p * Z``, so
    that ``lg >= cutoff`` keeps the smallest prefix of the sorted
    distribution whose mass reaches p, a tie-class as a whole. The top
    token always survives; ``top_p >= 1`` keeps every value (``-inf``).
    lg: (.., V) float32; top_p: a number or (.., 1). Returns (.., 1)."""
    top_p = jnp.asarray(top_p, _F32)
    top = jnp.max(lg, axis=-1, keepdims=True)
    e = jnp.exp(lg - top)
    target = top_p * jnp.sum(e, axis=-1, keepdims=True)
    hi = _key(top)
    cut = _bisect(
        jnp.full_like(hi, _KEY_NEG_INF), hi,
        lambda v: jnp.sum(jnp.where(lg > v, e, 0.0), axis=-1,
                          keepdims=True) < target,
        smallest=True)
    return jnp.where(top_p >= 1.0, _NEG_INF, cut)


def _when_any(asks, search, like):
    """``search()`` where some row asks for it, else ``-inf`` a row: a
    branch on the operands, so a step whose rows all leave a threshold
    off pays no probe for it."""
    return lax.cond(jnp.any(asks), search,
                    lambda: jnp.full(like.shape, _NEG_INF, _F32))


def _thresholds_jnp(lg, k, p):
    V = lg.shape[-1]
    kth = _when_any(k < V, lambda: kth_value(lg, k), k)
    # the nucleus is taken over the top-k survivors
    cut = _when_any(
        p < 1.0,
        lambda: nucleus_cutoff(jnp.where(lg < kth, _NEG_INF, lg), p), p)
    return kth, cut


# -- the kernel: eight rows resident, every probe in VMEM --------------------
def _fold(n_tiles, refs, term, combine, init, store=None):
    """``combine`` over ``term(*tiles)`` for every 128-lane tile of the
    block's row, ``tiles`` that tile of each of ``refs``, as one (8,
    128) accumulator: ``_GROUP`` tiles an iteration, combined as a tree
    so that the loop carries one operation; the tiles left over after
    the whole groups follow. A group is ONE read of each ref at a
    dynamic offset, cut into its tiles as a value (a dynamic index costs
    the trace, not the chip: ~100 of them were most of the second this
    kernel added to every program's first call, PERF.md PR 35); with
    ``store`` the terms are also written there, a group at a time."""
    def tree(parts):
        while len(parts) > 1:
            parts = [combine(*parts[i:i + 2]) if i + 1 < len(parts)
                     else parts[i] for i in range(0, len(parts), 2)]
        return parts[0]

    def over(at, tiles):
        blocks = [ref[:, at] for ref in refs]
        parts = [term(*(b[:, j * _LANES:(j + 1) * _LANES] for b in blocks))
                 for j in range(tiles)]
        if store is not None:
            store[:, at] = jnp.concatenate(parts, axis=-1) \
                if tiles > 1 else parts[0]
        return tree(parts)

    def group(g, acc):
        base = pl.multiple_of(g * jnp.int32(_GROUP * _LANES),
                              _GROUP * _LANES)
        return combine(acc, over(pl.ds(base, _GROUP * _LANES), _GROUP))

    whole, rest = divmod(n_tiles, _GROUP)
    acc = jnp.full((_ROWS, _LANES), init, _F32)
    if whole:
        acc = lax.fori_loop(jnp.int32(0), jnp.int32(whole), group, acc)
    if rest:
        acc = combine(acc, over(
            pl.ds(whole * _GROUP * _LANES, rest * _LANES), rest))
    return acc


def _across(acc, reduce):
    """An (8, 128) accumulator reduced over its lanes, broadcast back."""
    return jnp.broadcast_to(reduce(acc, axis=-1, keepdims=True), acc.shape)


def _kernel(asks_ref, x_ref, k_ref, p_ref, out_ref, e_ref, *, n_tiles):
    """Eight rows of the search. asks_ref (SMEM): [2 i] whether a row of
    block i asks for top-k, [2 i + 1] for the nucleus. x_ref: (8, Vp)
    logits, padded with ``-inf``; k_ref, p_ref: (8, 128), a row's k (as
    float32) and top_p along its lanes; out_ref: (8, 128), lane 0 kth,
    the other lanes the cut-off; e_ref: (8, Vp) scratch, the survivors'
    ``exp(x - max)``. Every row-wide quantity lives as an (8, 128)
    vreg, the row's value along the lanes."""
    i = pl.program_id(0)
    ask_k, ask_p = asks_ref[2 * i] != 0, asks_ref[2 * i + 1] != 0
    fold = partial(_fold, n_tiles)
    none = jnp.full((_ROWS, _LANES), _NEG_INF, _F32)
    out_ref[...] = none

    @pl.when(ask_k | ask_p)
    def _():
        top = _across(fold([x_ref], lambda x: x, jnp.maximum, _NEG_INF),
                      jnp.max)
        low = jnp.full((_ROWS, _LANES), _KEY_NEG_INF, jnp.int32)
        zero, add = jnp.zeros_like(top), lambda a, b: a + b

        def search_kth():
            k = k_ref[...]

            def holds(v):
                n = fold([x_ref], lambda x: jnp.where(x >= v, 1.0, zero),
                         add, 0.0)
                return _across(n, jnp.sum) >= k
            kth = _bisect(low, _key(top), holds, smallest=False)
            return jnp.where(k >= n_tiles * _LANES, none, kth)

        kth = lax.cond(ask_k, search_kth, lambda: none)

        def search_cut():
            p = p_ref[...]

            def fill(x):
                return jnp.where(x >= kth, jnp.exp(x - top), zero)
            target = p * _across(fold([x_ref], fill, add, 0.0, store=e_ref),
                                 jnp.sum)

            def holds(v):
                mass = fold([x_ref, e_ref],
                            lambda x, e: jnp.where(x > v, e, zero), add, 0.0)
                return _across(mass, jnp.sum) < target
            cut = _bisect(jnp.maximum(low, _key(kth)), _key(top), holds,
                          smallest=True)
            return jnp.where(p >= 1.0, none, cut)

        cut = lax.cond(ask_p, search_cut, lambda: none)
        lane = lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)
        out_ref[...] = jnp.where(lane == 0, kth, cut)


def _thresholds_pallas(lg, k, p, *, interpret=False):
    """The kernel over lg (rows, V) float32 with k (rows, 1) int32 and p
    (rows, 1) float32. Rows are padded to whole blocks of eight (with
    rows that ask for nothing) and the vocabulary with ``-inf`` to whole
    tiles of 128 lanes."""
    rows, V = lg.shape
    pad_r, pad_v = -rows % _ROWS, -V % _LANES
    # a padded row asks for nothing: k = V, p = 1
    k = jnp.pad(k.astype(jnp.int32), ((0, pad_r), (0, 0)),
                constant_values=V)
    p = jnp.pad(p.astype(_F32), ((0, pad_r), (0, 0)), constant_values=1.0)
    if pad_v:
        lg = jnp.pad(lg, ((0, 0), (0, pad_v)), constant_values=_NEG_INF)
    if pad_r:
        lg = jnp.pad(lg, ((0, pad_r), (0, 0)))
    Vp, blocks = V + pad_v, (rows + pad_r) // _ROWS
    asks = jnp.stack([(k < V).reshape(blocks, _ROWS).any(-1),
                      (p < 1.0).reshape(blocks, _ROWS).any(-1)],
                     axis=-1).astype(jnp.int32).reshape(-1)
    # a count over padding of -inf is the count over the row, and k is
    # compared as the float32 the count is (exact below 2^24)
    k = jnp.where(k >= V, Vp, k).astype(_F32)
    wide = lambda a: jnp.broadcast_to(a, (a.shape[0], _LANES))
    zero = lambda: jnp.int32(0)
    row = pl.BlockSpec((_ROWS, _LANES), lambda i, asks: (i, zero()))
    out = pl.pallas_call(
        partial(_kernel, n_tiles=Vp // _LANES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=[pl.BlockSpec((_ROWS, Vp), lambda i, asks: (i, zero())),
                      row, row],
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((_ROWS, Vp), _F32)]),
        out_shape=jax.ShapeDtypeStruct((rows + pad_r, _LANES), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=KERNEL_NAME,
    )(asks, lg, wide(k), wide(p))
    return out[:rows, 0:1], out[:rows, 1:2]


def thresholds_path(shape, dtype, *, mesh=None) -> str:
    """Which form :func:`thresholds` runs over logits of this shape and
    type on this backend: ``"search_kernel"`` (the Pallas kernel: a TPU,
    a block of eight float32 rows or more that fit its VMEM three times
    over, no mesh, whose partitioner takes no Mosaic kernel) or
    ``"search"`` (the ``jnp`` form). Decided from the backend, shape and
    dtype alone, as ``paged_decode_path`` is; nobody sets it.

    Fewer than eight rows (a prefill program's one-row sample,
    ``generate`` at batch 1) take the ``jnp`` form: the kernel's block
    would be seven-eighths padding, 32 probes over one row re-read
    0.13-0.8 MB each (under 0.2 ms a sample), and the kernel then enters
    only the decode programs, which hold a kernel already, so no prefill
    program pays what comes with one (its lowering, and a cache key that
    holds the checkout's path: PERF.md, PR 35)."""
    Vp = shape[-1] + -shape[-1] % _LANES
    if (jax.default_backend() == "tpu" and mesh is None
            and len(shape) == 2 and shape[0] >= _ROWS
            and jnp.dtype(dtype) == _F32
            and 3 * _ROWS * Vp * 4 + 2 ** 22 <= _VMEM_LIMIT):
        return "search_kernel"
    return "search"


def thresholds(lg, top_k, top_p, *, mesh=None, interpret: bool = False):
    """The sampler's two thresholds for a block of rows: ``(kth,
    cutoff)``, each (rows, 1) float32, such that a row keeps ``x >= kth``
    and ``x >= cutoff`` (``-inf``: keep everything). lg: (rows, V)
    float32 logits, already divided by the temperature; top_k: (rows, 1)
    ints in [1, V], V for "off"; top_p: (rows, 1) float32, 1 for "off".
    The nucleus is taken over the top-k survivors. ``interpret`` runs
    the kernel in Pallas's interpreter (tests, on a CPU)."""
    lg = lg.astype(_F32)        # the keys are float32's bit patterns
    if interpret or thresholds_path(lg.shape, lg.dtype,
                                    mesh=mesh) == "search_kernel":
        return _thresholds_pallas(lg, top_k, top_p, interpret=interpret)
    return _thresholds_jnp(lg, top_k, top_p)
