"""Decode attention straight out of the page pool: one Pallas TPU
kernel that walks each slot's row of the page table and stops at the
slot's length.

The serving engine's KV cache is a pool of fixed-size pages, stored
token-major (``llama.init_paged_cache``: (L, n_pages, page_size, kvh,
hd)). The gathered path (``ops.attention.gathered_decode_attention``)
copies every slot's WHOLE row of pages into a contiguous (slots, kvh,
capacity, hd) operand and masks what lies past the length; at 32 slots
holding ~460 of 2048 tokens that moves fourteen bytes for every live
one. This kernel moves the live ones. The pools stay in HBM
(``memory_space=ANY``) and are never copied, sliced or relaid out;
``layer``, the page table and the lengths are scalar-prefetched. One
invocation loops over the slots: for each it DMAs pages
``page_table[s, 0 : ceil(lengths[s] / page_size)]`` of ``pool[layer]``
into VMEM, ``_BLOCK_BYTES`` of pages to a block, double-buffered (the
next block's pages — the next slot's first block included — fly while
this block's arithmetic runs), and folds the block, half of it at a
time, into the float32 online softmax ``slot_decode_attention`` runs
(running max, denominator, numerator; softmax weights rounded to the
pool's dtype for the value product, as XLA's default-precision product
of the gathered path rounds them on the TPU).

The pool is taken through the view ``(L, n_pages, page_size * kvh,
hd)``, a bitcast of the stored layout on the TPU: a page is one
contiguous run of (token, head) rows of ``hd`` lanes. The kernel never
separates the heads. A chunk's rows, every KV head's interleaved, are
ONE matmul operand: all the query heads against all the rows, and a
score counts where the row's KV head is the query head's own (GQA: the
``rep`` query heads of a KV head share its rows; the cache is never
repeated) and the row's key lies under the length. The products against
other heads' rows ride along for nothing: the MXU's cost here is
latching the keys, not streaming a handful of query rows past them.

Written from ``jax.experimental.pallas.ops.tpu.ragged_paged_attention``
(jax 0.9.0), which wants K and V interleaved on the head axis of ONE
pool; this pool keeps K and V apart, so each is walked by its own DMAs.

A LATENT pool (``models/latent_moe.py``: (L, n_pages, page_size, row),
one row a token and layer, read by every head as its key and, in its
first part, as its value) is the same walk with one pool in both
roles (:func:`paged_latent_pages`): a page is DMAed once, every query
head is of the one "KV head", and the output is the softmax-weighted
sum of whole rows, which the caller cuts to the value part.

Two pools of TOKEN rows (``models/sambay.py``: K and V each (L, n_pages,
page_size, kvh * hd), a token's heads end to end in the lanes) are the
same walk again (:func:`paged_attention_rows`): a page is DMAed as it is
stored, KV head ``g`` is the lane slice ``[g * hd, (g + 1) * hd)`` of a
row, and a query head is placed in its KV head's lanes of an otherwise
zero row-wide query, so that ONE product of a chunk's rows with all the
query heads scores every head against its own keys (the zeros cost
nothing: the MXU latches the same key tiles either way); the value
product is as wide as a row, and each head keeps its own lanes of it.

BLOCKS of query rows a slot that see the cache and each other
(``models/blockdiff_moe.py``: the block being denoised and the one just
finished, whose final keys the same pass makes; their keys and values
written before the read) are that walk once more
(:func:`paged_attention_block`): a block's rows ride as further query
heads of their KV head, ``rows x rep`` to a group, each block's over
its own length (the finished block's a block short of the next's), and
a slot with no finished block to carry runs the next block's rows
alone.
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

__all__ = ["paged_attention_pages", "takes", "KERNEL_NAME",
           "paged_latent_pages", "takes_latent", "LATENT_KERNEL_NAME",
           "paged_attention_rows", "takes_rows", "ROWS_KERNEL_NAME",
           "paged_attention_block", "takes_block"]

# the kernels' names as a device trace prints them
KERNEL_NAME = "paged_decode_attention_pages"
LATENT_KERNEL_NAME = "paged_latent_attention_pages"
ROWS_KERNEL_NAME = "paged_rows_attention_pages"

_NEG_INF = -1e30    # ops.attention's finite "minus infinity"
# bytes of K (and of V) to a DMA block, two of each in VMEM; half a block
# is a compute chunk. At 16-token pages of 8 heads of 128 in bfloat16: 32
# pages = 512 keys a block, 2048 rows a matmul
_BLOCK_BYTES = 1 << 20
# what the whole (slots, hq, hd) queries and outputs may take of VMEM
_QUERY_BYTES = 4 << 20


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


# The kernel's constants are typed: under x64 a Python scalar that meets
# ``jnp.where`` or ``//`` enters the kernel as a 64-bit value to convert,
# and Mosaic lowers no 64-bit type.
def _cdiv(a, n: int):
    """ceil(a / n) of an int32 scalar."""
    return lax.div(a + _i32(n - 1), _i32(n))


def takes(q_shape, pool_shape, pool_dtype) -> bool:
    """Whether the compiled kernel takes these shapes as they are stored:
    one query a slot; a bfloat16 pool whose view ``(.., page_size * kvh,
    hd)`` is a bitcast on the TPU (heads in whole (8, 128) tiles: ``kvh``
    a multiple of 8, ``hd`` of 128 — otherwise XLA relays the whole pool
    out around every call) and whose pages are whole (16, 128) tiles for
    the DMAs; queries that fit VMEM beside the page buffers."""
    page_size, kvh, hd = pool_shape[-3:]
    return (len(q_shape) == 4 and q_shape[2] == 1 and q_shape[3] == hd
            and q_shape[1] % kvh == 0
            and jnp.dtype(pool_dtype) == jnp.bfloat16
            and hd % 128 == 0 and kvh % 8 == 0
            and (page_size * kvh) % 16 == 0
            and page_size * kvh * hd * 2 <= _BLOCK_BYTES
            and 2 * math.prod(q_shape) * 2 <= _QUERY_BYTES)


def _kernel(layer_ref, table_ref, lengths_ref,      # scalar prefetch
            q_ref,                                  # (slots, hq, hd)
            k_hbm, v_hbm,       # (L, n_pages, ps * kvh, hd), HBM; token
                                # rows: (L, n_pages, ps, kvh * hd)
            o_ref,                                  # (slots, hq, hd)
            kbuf, vbuf,                 # (2, ppb, a page as stored), VMEM
            ksem, vsem,                 # DMA semaphores, one a buffer
            **static):
    _walk(layer_ref, table_ref, lengths_ref, q_ref, o_ref,
          (k_hbm, kbuf, ksem), (v_hbm, vbuf, vsem), **static)


def _latent_kernel(layer_ref, table_ref, lengths_ref,
                   q_ref,                           # (slots, hq, row)
                   pool_hbm,                # (L, n_pages, ps, row), HBM
                   o_ref,                           # (slots, hq, row)
                   buf, sem, **static):
    """The walk over ONE pool whose rows are key and value at once."""
    rows = (pool_hbm, buf, sem)
    _walk(layer_ref, table_ref, lengths_ref, q_ref, o_ref, rows, rows,
          kvh=1, **static)


def _walk(layer_ref, table_ref, lengths_ref, q_ref, o_ref, keys, values,
          *, scale: float, page_size: int, kvh: int, cpages: int,
          lane_heads: int = 1, groups: int = 1):
    """The kernels' body. ``keys`` and ``values`` are (pool in HBM,
    (2, ppb, rows a page, lanes a row) VMEM buffer, DMA semaphores): two
    pools, or one pool twice, whose pages are then copied once. ``kvh``
    KV heads take turns in a page's rows; ``lane_heads`` lie end to end
    in a row's lanes (one of the two is 1). ``groups`` = 2 (rows of
    ``lane_heads`` only): a slot's queries are two groups of rows, one
    after the other, and ``lengths_ref`` is (slots, 2), the keys each
    group sees; the walk reaches the second's, which is the larger, and
    a first group whose length is 0 is not computed at all (zeros)."""
    k_hbm, kbuf, ksem = keys
    v_hbm, vbuf, vsem = values
    pools = (keys,) if values is keys else (keys, values)
    n_slots, hq, hd = q_ref.shape
    rep = hq // kvh
    per_slot = table_ref.shape[1]
    ppb, page_rows, width = kbuf.shape[1:]
    crow = cpages * page_rows               # rows of a compute chunk
    layer = layer_ref[0]
    # said here, not left to jax_default_matmul_precision: products of
    # 16-bit operands are exact in one pass (and Mosaic takes no other
    # for them); a float32 pool's keep their bits
    precision = (lax.Precision.DEFAULT if kbuf.dtype.itemsize == 2
                 else lax.Precision.HIGHEST)

    def live_pages(slot):
        reach = (lengths_ref[slot] if groups == 1
                 else lengths_ref[slot, groups - 1])
        return jnp.minimum(_cdiv(reach, page_size), _i32(per_slot))

    def pages_in(slot, b):
        """How many pages of block ``b`` of ``slot`` hold keys: only
        those are read."""
        return jnp.clip(live_pages(slot) - b * _i32(ppb), _i32(0),
                        _i32(ppb))

    def start(slot, b, buf):
        def page(i, _):
            phys = table_ref[slot, b * _i32(ppb) + i]
            for hbm, vm, sem in pools:
                pltpu.make_async_copy(hbm.at[layer, phys], vm.at[buf, i],
                                      sem.at[buf]).start()
        lax.fori_loop(_i32(0), pages_in(slot, b), page, None)

    def wait(slot, b, buf):
        # a DMA semaphore counts bytes: one wait for each power of two
        # in the count of pages that were started, not one a page
        n = pages_in(slot, b)
        size = ppb
        while size:
            @pl.when((n & _i32(size)) != _i32(0))
            def _():
                for hbm, vm, sem in pools:
                    pltpu.make_async_copy(
                        hbm.at[layer, pl.ds(0, size)],
                        vm.at[buf, pl.ds(0, size)], sem.at[buf]).wait()
            size //= 2

    start(_i32(0), _i32(0), _i32(0))
    # row r of a chunk is key r // kvh of KV head r % kvh
    row_head = lax.div(lax.broadcasted_iota(jnp.int32, (hq, crow), 0),
                       _i32(rep))
    col = lax.broadcasted_iota(jnp.int32, (hq, crow), 1)
    own_head = lax.rem(col, _i32(kvh)) == row_head
    vrow = lax.broadcasted_iota(jnp.int32, (crow, width), 0)
    if lane_heads > 1:
        # query head h reads lanes [h // (hq / lane_heads) * hd, + hd)
        own_lanes = lax.div(
            lax.broadcasted_iota(jnp.int32, (hq, width), 1), _i32(hd)
        ) == lax.div(lax.broadcasted_iota(jnp.int32, (hq, width), 0),
                     _i32(hq // lane_heads))

    def fold(s, buf, length, n_blocks, q, lanes, valid):
        """Slot ``s``'s live pages, ``n_blocks`` DMA blocks of them
        under ``length`` keys, folded into queries q (rows, lanes of a
        row); ``valid(left, seen)``: which (rows, crow) scores of a chunk
        are kept, ``left`` the chunk's keys under ``length`` and
        ``seen()`` the keys before the chunk. Returns (the rows' outputs,
        the buffer the next slot's first block is in)."""
        rows = q.shape[0]

        def block(b, carry):
            m, l, acc, buf = carry
            # the next block's pages fly under this block's arithmetic:
            # this slot's, or the next slot's first
            last = b + _i32(1) >= n_blocks
            nxt_slot = lax.select(last, s + _i32(1), s)
            nxt_b = lax.select(last, _i32(0), b + _i32(1))

            @pl.when(nxt_slot < n_slots)
            def _():
                start(nxt_slot, nxt_b, _i32(1) - buf)

            wait(s, b, buf)
            # rows of this block that hold keys under the length
            live_rows = jnp.minimum(
                (length - b * _i32(ppb * page_size)) * _i32(kvh),
                _i32(ppb * page_rows))

            def chunk(c, carry):
                m, l, acc = carry
                at = pl.ds(pl.multiple_of(c * _i32(cpages), cpages), cpages)
                k = kbuf[buf, at].reshape(crow, width)
                v = vbuf[buf, at].reshape(crow, width)
                left = live_rows - c * _i32(crow)
                scores = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32) * _f32(scale)
                scores = jnp.where(
                    valid(left, lambda: b * _i32(ppb * page_size)
                          + c * _i32(crow)), scores, _f32(_NEG_INF))
                # every row has a live key of its own head here (the
                # loop stops at the last live chunk, and a first group
                # that runs sees key 0), so a masked score leaves exp()
                # as 0 with no second mask
                m_new = jnp.maximum(
                    m, jnp.max(scores, axis=1, keepdims=True))
                p = jnp.exp(scores - m_new)
                alpha = jnp.exp(m - m_new)
                l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
                # a page that was not read holds whatever the buffer
                # held: 0 x NaN is NaN, so the values past the length go
                v = jnp.where(vrow < left, v, jnp.zeros_like(v))
                pv = jnp.dot(p.astype(v.dtype), v, precision=precision,
                             preferred_element_type=jnp.float32)
                return m_new, l, alpha * acc + pv

            m, l, acc = lax.fori_loop(_i32(0), _cdiv(live_rows, crow),
                                      chunk, (m, l, acc))
            return m, l, acc, _i32(1) - buf

        m, l, acc, buf = lax.fori_loop(
            _i32(0), n_blocks, block,
            (jnp.full((rows, 1), _NEG_INF, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, width), jnp.float32), buf))
        l = jnp.where(l == _f32(0), _f32(1), l)   # length 0: zeros
        out = acc / l
        if lane_heads > 1:      # each head's own lanes of the row-wide sum
            out = jnp.where(lanes, out, _f32(0))
            out = functools.reduce(jnp.add, (
                out[:, g * hd:(g + 1) * hd] for g in range(lane_heads)))
        return out, buf

    def in_lanes(q, lanes):     # each head in its own lanes of a row
        return jnp.where(lanes, jnp.concatenate([q] * lane_heads, axis=1),
                         jnp.zeros((), q.dtype))

    def slot(s, buf):
        length = lengths_ref[s]
        # a slot of length 0 still takes one (empty) block: nothing is
        # read, nothing computed, and the buffers alternate all the same
        n_blocks = jnp.maximum(_cdiv(live_pages(s), ppb), _i32(1))
        q = q_ref[s]                                        # (hq, hd)
        lanes = None
        if lane_heads > 1:
            q, lanes = in_lanes(q, own_lanes), own_lanes
        out, buf = fold(s, buf, length, n_blocks, q, lanes,
                        lambda left, seen: own_head & (col < left))
        o_ref[s] = out.astype(o_ref.dtype)
        return buf

    if groups == 2:
        # the second group alone: (hq / 2) rows, as a walk of its own
        # heads would lay them out; both: row r is head r % (hq / 2) of
        # group r // (hq / 2), whose KV head is the same in either group
        half = hq // 2
        both_rows = lax.broadcasted_iota(jnp.int32, (hq, 1), 0)

        def lanes_of(rows):
            return lax.div(
                lax.broadcasted_iota(jnp.int32, (rows, width), 1), _i32(hd)
            ) == lax.rem(lax.div(
                lax.broadcasted_iota(jnp.int32, (rows, width), 0),
                _i32(half // lane_heads)), _i32(lane_heads))
        both_lanes, one_lanes = lanes_of(hq), lanes_of(half)
        one_col = lax.broadcasted_iota(jnp.int32, (half, crow), 1)

        def two_groups(s, buf):
            first, length = lengths_ref[s, 0], lengths_ref[s, 1]
            n_blocks = jnp.maximum(_cdiv(live_pages(s), ppb), _i32(1))

            def both(buf):
                # a row's own length: the first group's or the second's
                reach = jnp.where(both_rows < _i32(half), first, length)
                out, buf = fold(s, buf, length, n_blocks,
                                in_lanes(q_ref[s], both_lanes), both_lanes,
                                lambda left, seen: col < reach - seen())
                o_ref[s] = out.astype(o_ref.dtype)
                return buf

            def second(buf):
                out, buf = fold(
                    s, buf, length, n_blocks,
                    in_lanes(q_ref[s, pl.ds(half, half)], one_lanes),
                    one_lanes, lambda left, seen: one_col < left)
                o_ref[s, pl.ds(0, half)] = jnp.zeros((half, hd),
                                                     o_ref.dtype)
                o_ref[s, pl.ds(half, half)] = out.astype(o_ref.dtype)
                return buf

            return lax.cond(first > _i32(0), both, second, buf)

    lax.fori_loop(_i32(0), _i32(n_slots),
                  slot if groups == 1 else two_groups, _i32(0))


def _blocks(page_bytes: int, per_slot: int, block_pages, chunk_pages):
    """(pages a DMA block, pages a compute chunk): ``_BLOCK_BYTES`` of
    pages, a power of two within a slot's row, and half of it, unless
    the caller says."""
    ppb = block_pages or max(1, _BLOCK_BYTES // page_bytes)
    ppb = 1 << (min(ppb, per_slot).bit_length() - 1)
    return ppb, min(chunk_pages or max(1, ppb // 2), ppb)


def _call(kernel, name, q, pools, page, ppb, layer, page_table, lengths,
          interpret):
    """One invocation of a walk. q: (slots, hq, 1, hd), it and the
    output in VMEM; each of ``pools`` (L, n_pages, ...) whole in HBM,
    seen as pages of shape ``page`` (llama's (page_size * kvh, hd) is a
    bitcast of the stored layout; the others' is the stored one), with a
    (2, ppb) + ``page`` buffer and two DMA semaphores of its own;
    ``layer``, the page table and the lengths scalar-prefetched."""
    slots, hq, _, hd = q.shape
    scalars = (_i32(layer).reshape(1), _i32(page_table), _i32(lengths))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),        # the slots are looped inside: each hands its
                            # successor a buffer in flight
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, ppb) + page, pool.dtype)
                            for pool in pools]
            + [pltpu.SemaphoreType.DMA((2,))] * len(pools)),
        out_shape=jax.ShapeDtypeStruct((slots, hq, hd), q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name,
    )(*scalars, q.reshape(slots, hq, hd),
      *(pool.reshape(pool.shape[:2] + page) for pool in pools))
    return out.reshape(q.shape)


def paged_attention_pages(q, k_pages, v_pages, page_table, lengths, *,
                          layer=None, scale: Optional[float] = None,
                          block_pages: Optional[int] = None,
                          chunk_pages: Optional[int] = None,
                          interpret: bool = False):
    """Decode attention over live pages. q: (slots, n_heads, 1, hd);
    k_pages, v_pages: the (L, n_pages, page_size, kvh, hd) pools with
    ``layer`` a (traced) scalar, or one layer's (n_pages, page_size,
    kvh, hd); page_table: (slots, pages_per_slot) int32, every entry in
    ``[0, n_pages)``; lengths: (slots,) int. Returns (slots, n_heads, 1,
    hd) in q's dtype: slot s attends keys ``[0, lengths[s])``, a slot of
    length 0 gives zeros, and no page past ``ceil(lengths[s] /
    page_size)`` is read. ``block_pages`` (a power of two) and
    ``chunk_pages`` size the DMA block and the compute chunk, by default
    from ``_BLOCK_BYTES``: the tests shrink them to their toy pools.
    ``interpret`` runs the kernel in Pallas' TPU interpret mode (the
    tests, on a CPU; any float dtype and head shape there)."""
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    n_layers, n_pages, page_size, kvh, hd = k_pages.shape
    slots, hq, sq, _ = q.shape
    if sq != 1 or lengths.ndim != 1:
        raise ValueError("one query a slot: the verify step's (S, W) "
                         "lengths take the gathered path")
    if hq % kvh:
        raise ValueError(f"{hq} q heads not divisible by {kvh} kv heads")
    page_rows = page_size * kvh
    ppb, cpages = _blocks(page_rows * hd * k_pages.dtype.itemsize,
                          page_table.shape[1], block_pages, chunk_pages)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    return _call(functools.partial(_kernel, scale=scale, page_size=page_size,
                                   kvh=kvh, cpages=cpages),
                 KERNEL_NAME, q, (k_pages, v_pages), (page_rows, hd), ppb,
                 layer, page_table, lengths, interpret)


def takes_latent(q_shape, pool_shape, pool_dtype) -> bool:
    """Whether :func:`paged_latent_pages` takes a latent pool (L,
    n_pages, page_size, row) as it is stored: one query a slot, as wide
    as the row; a bfloat16 pool whose rows are whole lane tiles and
    whose pages are whole (16, 128) tiles for the DMAs; queries that fit
    VMEM beside the page buffers."""
    page_size, row = pool_shape[-2:]
    return (len(q_shape) == 4 and q_shape[2] == 1 and q_shape[3] == row
            and jnp.dtype(pool_dtype) == jnp.bfloat16
            and row % 128 == 0 and page_size % 16 == 0
            and page_size * row * 2 <= _BLOCK_BYTES
            and 2 * math.prod(q_shape) * 2 <= _QUERY_BYTES)


def paged_latent_pages(q, pool, page_table, lengths, *, layer,
                       scale: Optional[float] = None,
                       block_pages: Optional[int] = None,
                       chunk_pages: Optional[int] = None,
                       interpret: bool = False):
    """Decode attention over the live pages of a latent pool. q:
    (slots, n_heads, 1, row); pool: the whole (L, n_pages, page_size,
    row) pool, read at ``layer`` (a traced scalar); page_table: (slots,
    pages_per_slot) int32, every entry in ``[0, n_pages)``; lengths:
    (slots,) int. Returns (slots, n_heads, 1, row) in q's dtype: for
    each head the softmax over ``q . row`` of the slot's rows ``[0,
    lengths[s])`` times the rows themselves; a slot of length 0 gives
    zeros, and no page past ``ceil(lengths[s] / page_size)`` is read.
    :func:`paged_attention_pages`'s walk with one pool as keys and
    values; ``block_pages``, ``chunk_pages`` and ``interpret`` as
    there."""
    n_layers, n_pages, page_size, row = pool.shape
    slots, hq, sq, _ = q.shape
    if sq != 1 or lengths.ndim != 1:
        raise ValueError("one query a slot")
    ppb, cpages = _blocks(page_size * row * pool.dtype.itemsize,
                          page_table.shape[1], block_pages, chunk_pages)
    scale = scale if scale is not None else 1.0 / math.sqrt(row)
    return _call(functools.partial(_latent_kernel, scale=float(scale),
                                   page_size=page_size, cpages=cpages),
                 LATENT_KERNEL_NAME, q, (pool,), (page_size, row), ppb,
                 layer, page_table, lengths, interpret)


def takes_rows(q_shape, pool_shape, pool_dtype) -> bool:
    """Whether :func:`paged_attention_rows` takes pools of token rows
    (L, n_pages, page_size, kvh * hd) as they are stored: one query a
    slot, of a head's ``hd`` lanes, the query heads a multiple of the
    row's ``kvh``; bfloat16 pools whose heads are whole lane tiles (a KV
    head is then a lane slice of the page as it lies) and whose pages
    are whole (16, 128) tiles for the DMAs; queries that fit VMEM beside
    the page buffers."""
    page_size, width = pool_shape[-2:]
    hd = q_shape[-1]
    return (len(q_shape) == 4 and q_shape[2] == 1
            and width % hd == 0 and q_shape[1] % (width // hd) == 0
            and jnp.dtype(pool_dtype) == jnp.bfloat16
            and hd % 128 == 0 and page_size % 16 == 0
            and page_size * width * 2 <= _BLOCK_BYTES
            and 2 * math.prod(q_shape) * 2 <= _QUERY_BYTES)


def paged_attention_rows(q, k_pages, v_pages, page_table, lengths, *, layer,
                         scale: Optional[float] = None,
                         block_pages: Optional[int] = None,
                         chunk_pages: Optional[int] = None,
                         interpret: bool = False):
    """Decode attention over the live pages of two pools of token rows.
    q: (slots, n_heads, 1, hd); k_pages, v_pages: the whole (L, n_pages,
    page_size, kvh * hd) pools, a token's ``kvh`` heads end to end, read
    at ``layer`` (a traced scalar); page_table: (slots, pages_per_slot)
    int32, every entry in ``[0, n_pages)``; lengths: (slots,) int.
    Returns (slots, n_heads, 1, hd) in q's dtype, as
    :func:`paged_attention_pages` does: query head h attends KV head
    ``h // (n_heads / kvh)``'s keys ``[0, lengths[s])``, a slot of
    length 0 gives zeros, and no page past ``ceil(lengths[s] /
    page_size)`` is read. The same walk; ``block_pages``,
    ``chunk_pages`` and ``interpret`` as there."""
    n_layers, n_pages, page_size, width = k_pages.shape
    slots, hq, sq, hd = q.shape
    if sq != 1 or lengths.ndim != 1:
        raise ValueError("one query a slot")
    if width % hd or hq % (width // hd):
        raise ValueError(f"{hq} q heads of {hd} over rows of {width}")
    ppb, cpages = _blocks(page_size * width * k_pages.dtype.itemsize,
                          page_table.shape[1], block_pages, chunk_pages)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    return _call(functools.partial(_kernel, scale=float(scale),
                                   page_size=page_size, kvh=1, cpages=cpages,
                                   lane_heads=width // hd),
                 ROWS_KERNEL_NAME, q, (k_pages, v_pages), (page_size, width),
                 ppb, layer, page_table, lengths, interpret)


def takes_block(q_shape, pool_shape, pool_dtype) -> bool:
    """Whether :func:`paged_attention_block` takes blocks of query rows
    a slot, q (slots, n_heads, rows, hd), over pools of token rows as
    they are stored: :func:`takes_rows` of the rows folded into the
    query heads (the queries, ``rows`` times as many, still fit VMEM)."""
    return len(q_shape) == 4 and takes_rows(
        (q_shape[0], q_shape[1] * q_shape[2], 1, q_shape[3]), pool_shape,
        pool_dtype)


def paged_attention_block(q, k_pages, v_pages, page_table, lengths, *, layer,
                          scale: Optional[float] = None,
                          block_pages: Optional[int] = None,
                          chunk_pages: Optional[int] = None,
                          interpret: bool = False):
    """Attention of TWO BLOCKS of query rows a slot over the live pages
    of two pools of token rows. q: (slots, n_heads, rows, hd), the first
    ``rows / 2`` rows a slot one block and the rest the other; the
    pools, the page table and ``layer`` as :func:`paged_attention_rows`
    takes them; lengths: (slots, 2) int, the keys every row of block g
    sees, ``[0, lengths[s, g])`` (the cache and the blocks, whose keys
    and values the caller has written): inside a block no row is masked
    from another, and the second block sees the more. A block's rows are
    further heads of their KV head (head h, row b of block g is head
    ``h * rows / 2 + b`` of group g), so this is
    :func:`paged_attention_rows`'s walk, ONE walk of the second block's
    pages for both; where a slot's first length is 0 its first block is
    not computed (zeros), and the walk costs what the second's alone
    costs. Returns (slots, n_heads, rows, hd) in q's dtype."""
    slots, hq, rows, hd = q.shape
    if lengths.shape != (slots, 2) or rows % 2:
        raise ValueError("two blocks of rows a slot, one length each")
    n_layers, n_pages, page_size, width = k_pages.shape
    # group-major: group g's heads in order, then the other group's
    grouped = q.reshape(slots, hq, 2, rows // 2, hd).transpose(0, 2, 1, 3, 4)
    ppb, cpages = _blocks(page_size * width * k_pages.dtype.itemsize,
                          page_table.shape[1], block_pages, chunk_pages)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    out = _call(functools.partial(_kernel, scale=float(scale),
                                  page_size=page_size, kvh=1, cpages=cpages,
                                  lane_heads=width // hd, groups=2),
                ROWS_KERNEL_NAME, grouped.reshape(slots, -1, 1, hd),
                (k_pages, v_pages), (page_size, width), ppb, layer,
                page_table, lengths, interpret)
    return out.reshape(slots, 2, hq, rows // 2, hd).transpose(
        0, 2, 1, 3, 4).reshape(q.shape)
