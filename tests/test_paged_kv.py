"""Paged KV cache with copy-on-write prefix sharing (ISSUE 18).

Contracts:
- :class:`PageAllocator` is all-or-nothing with exact refcounts: a
  failed grant leaves the pool untouched (admission backpressure, not
  a crash), shared pages free only on their LAST release, and the
  scratch page 0 can never be allocated, retained, or released;
- :func:`paged_decode_attention` over a scattered page pool is
  BIT-identical to :func:`slot_decode_attention` over the dense bank
  it was paged from — including when two slots alias the same
  physical pages (the sharing read path);
- a paged ``ServeEngine`` streams tokens bit-identical to per-request
  ``llama.generate`` across mixed prompts and sampling configs, and a
  shared system prompt produces prefix-cache hits + a CoW boundary
  fork WITHOUT changing a single token;
- a pool too small for the offered load queues (admission
  backpressure) and still drains every request bit-exactly;
- a journaled page-table restore (``submit_prefilled`` with a resume
  rng mid-stream) continues the stream exactly where the crashed
  engine left off;
- the page gathers promise their indices are in bounds: no table row
  the allocator's grants can build holds an index outside
  ``[0, n_pages)``, and the programs read the edges of that range
  (scratch page 0, page ``n_pages - 1``, a page two slots share)
  exactly as ``generate`` computes.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu.models import llama, sambay
from mxtpu.ops.attention import paged_decode_attention, \
    slot_decode_attention
from mxtpu.serve import Request, ServeEngine
from mxtpu.serve.engine import KVHandoff, PageAllocator, PrefixCache, \
    resume_key

import llama_refs


@pytest.fixture(scope="module")
def cfg(serve_cfg):
    return serve_cfg


@pytest.fixture(scope="module")
def params(serve_params):
    return serve_params


def paged_engine(cfg, params, **kw):
    kw.setdefault("page_size", 8)
    return llama_refs.engine_factory(cfg, params, **kw)()


# ---------------------------------------------------------------------------
# allocator: refcounts, all-or-nothing grants, scratch-page protection
# ---------------------------------------------------------------------------
def test_page_allocator_alloc_release_refcount():
    a = PageAllocator(6)                    # scratch + 5 usable
    assert a.free_pages == 5 and a.used_pages == 0
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and 0 not in got
    assert a.free_pages == 2 and a.used_pages == 3
    assert all(a.refcount(p) == 1 for p in got)
    # share two of them (prefix-cache hold), then release the slot's
    # ownership: shared pages must survive the first release
    a.retain(got[:2])
    assert a.shared_pages == 2
    a.release(got)
    assert a.free_pages == 3                # only the unshared one freed
    assert [a.refcount(p) for p in got] == [1, 1, 0]
    a.release(got[:2])                      # cache lets go -> all free
    assert a.free_pages == 5 and a.shared_pages == 0


def test_page_allocator_exhaustion_is_all_or_nothing():
    a = PageAllocator(4)                    # 3 usable
    assert a.alloc(4) is None               # over-ask: no partial grant
    assert a.free_pages == 3                # pool untouched
    got = a.alloc(3)
    assert a.alloc(1) is None and a.free_pages == 0
    a.release(got[:1])
    assert a.alloc(1) is not None           # freed page is grantable


def test_page_allocator_guards_scratch_and_dead_pages():
    a = PageAllocator(4)
    with pytest.raises(ValueError):
        a.retain([0])                       # scratch page
    with pytest.raises(ValueError):
        a.release([0])
    with pytest.raises(ValueError):
        a.retain([2])                       # never allocated
    got = a.alloc(1)
    a.release(got)
    with pytest.raises(ValueError):
        a.release(got)                      # double free
    with pytest.raises(ValueError):
        a.alloc(-1)
    with pytest.raises(ValueError):
        PageAllocator(0)                    # no pool without its scratch page
    empty = PageAllocator(1)                # scratch alone: a pool of no
    assert empty.free_pages == 0            # pages (tests/test_retention.py)
    assert empty.alloc(1) is None


@pytest.mark.parametrize("seed", range(4))
def test_page_allocator_rows_stay_in_bounds(seed):
    """The invariant the gathers' ``promise_in_bounds`` rests on: walk
    the allocator through admit (fresh pages, sometimes behind another
    row's shared prefix), copy-on-write fork, free and preempt in a
    seeded random order; after every step each table row holds only
    scratch page 0 or a LIVE page in ``[1, n_pages)``, and a page's
    refcount is the number of rows that name it."""
    rng = np.random.default_rng(seed)
    n_pages, slots, per_slot = 12, 4, 4
    a = PageAllocator(n_pages)
    table = np.zeros((slots, per_slot), np.int32)

    def check():
        assert table.min() >= 0 and table.max() < n_pages
        held = table[table > 0]
        assert not set(held.tolist()) & set(a._free)
        for p in range(1, n_pages):
            assert a.refcount(p) == int((held == p).sum())
        assert a.free_pages == n_pages - 1 - len(set(held.tolist()))

    for _ in range(300):
        slot = int(rng.integers(slots))
        row = table[slot]
        live = [int(p) for p in row if p]
        op = rng.choice(["admit", "fork", "free", "preempt"])
        if op == "admit" and not live:
            donor = table[int(rng.integers(slots))]
            shared = [int(p) for p in donor if p][:int(rng.integers(3))]
            fresh = a.alloc(int(rng.integers(1, per_slot + 1 - len(shared))))
            if fresh is None:              # all-or-nothing: row untouched
                check()
                continue
            a.retain(shared)
            row[:len(shared) + len(fresh)] = shared + fresh
        elif op == "fork" and live:
            i = int(rng.integers(len(live)))
            if a.refcount(live[i]) > 1:    # shared: a private copy
                got = a.alloc(1)
                if got is not None:
                    a.release([live[i]])
                    row[i] = got[0]
        elif op in ("free", "preempt") and live:
            # a finished request and a preempted one both hand back
            # every hold of the row and leave it on scratch
            a.release(live)
            row[:] = 0
        check()


def test_prefix_cache_longest_common_prefix_and_eviction():
    a = PageAllocator(10)
    c = PrefixCache(a, max_entries=2)
    pages = a.alloc(2)
    # entry covers 8 tokens of a 10-token registered prompt; the
    # cache retains its OWN hold, so the caller can let go
    c.insert(list(range(10)), 8, pages)
    a.release(pages)
    e, m = c.lookup(list(range(6)) + [99, 98])
    assert e is not None and m == 6         # divergent suffix still hits
    e, m = c.lookup(list(range(10)) + [50])
    assert m == 8                           # capped at covered tokens
    e, m = c.lookup([77, 78, 79])
    assert e is None and m == 0
    # last prompt token never comes from cache (its logits seed the
    # first sample): lookup of the exact prompt is capped at len-1
    e, m = c.lookup(list(range(8)))
    assert m == 7
    # over-capacity insert evicts LRU and releases its page hold:
    # two 1-page allocs out, the evicted entry's 2 pages back
    free0 = a.free_pages
    p1 = a.alloc(1)
    c.insert([201], 1, p1)
    a.release(p1)
    p2 = a.alloc(1)
    c.insert([202], 1, p2)                  # cap 2 -> first entry out
    a.release(p2)
    assert len(c) == 2 and a.free_pages == free0


def test_prefix_cache_pin_and_skip_eviction():
    """pin() freshens LRU order without counting a hit; evict_lru can
    be told to skip one pinned entry (the admission planner's matched
    prefix) and reports nothing-evictable when only that remains."""
    a = PageAllocator(10)
    c = PrefixCache(a)
    p1 = a.alloc(1)
    e1 = c.insert([1], 1, p1)
    a.release(p1)
    p2 = a.alloc(1)
    e2 = c.insert([2], 1, p2)
    a.release(p2)
    c.pin(e1)                               # e2 becomes the LRU
    assert e1.hits == 0                     # pin is not a hit
    assert c.evict_lru() is True
    got, _ = c.lookup([2, 99])
    assert got is None                      # e2 was evicted, e1 kept
    assert c.evict_lru(skip=e1) is False    # only the pinned one left
    got, _ = c.lookup([1, 99])
    assert got is e1
    assert c.evict_lru() is True            # unpinned: evictable again


# ---------------------------------------------------------------------------
# kernel: paged gather == dense slot attention, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,hq,hkv", [(1, 4, 4), (3, 4, 4), (6, 8, 2)])
def test_paged_attention_matches_slot_attention(S, hq, hkv):
    rng = np.random.default_rng(11)
    max_len, hd, ps = 48, 16, 8
    ppr = max_len // ps
    q = jnp.asarray(rng.standard_normal((S, hq, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, hkv, max_len, hd)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, hkv, max_len, hd)),
                    jnp.float32)
    lengths = jnp.asarray(
        [int(x) for x in rng.integers(1, max_len + 1, S)])
    # scatter each slot's dense bank into a shuffled page pool (page 0
    # reserved as scratch), then read it back through the page table
    n_pages = 1 + S * ppr
    perm = rng.permutation(np.arange(1, n_pages))
    table = np.asarray(perm, np.int32).reshape(S, ppr)
    kp = np.zeros((n_pages, ps, hkv, hd), np.float32)   # token-major
    vp = np.zeros((n_pages, ps, hkv, hd), np.float32)
    for s in range(S):
        for j in range(ppr):
            kp[table[s, j]] = np.asarray(
                k[s, :, j * ps:(j + 1) * ps]).transpose(1, 0, 2)
            vp[table[s, j]] = np.asarray(
                v[s, :, j * ps:(j + 1) * ps]).transpose(1, 0, 2)
    ref = slot_decode_attention(q, k, v, lengths, kv_block=16)
    out = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                 jnp.asarray(table), lengths,
                                 kv_block=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_attention_shared_pages_read_path():
    """Two slots whose tables alias the SAME physical prefix pages
    (CoW sharing before any fork) read identical prefixes."""
    rng = np.random.default_rng(12)
    hkv, hq, hd, ps = 2, 4, 16, 8
    kp = jnp.asarray(rng.standard_normal((5, ps, hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((5, ps, hkv, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, hq, 1, hd)), jnp.float32)
    table = jnp.asarray([[1, 2], [1, 3]], jnp.int32)   # page 1 shared
    lengths = jnp.asarray([8, 8])                      # prefix only
    out = paged_decode_attention(jnp.repeat(q[:1], 2, 0), kp, vp,
                                 table, lengths)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))


# ---------------------------------------------------------------------------
# the pages kernel (ops/paged_attention.py), interpreted on the CPU at
# toy widths, against the gathered path on the same pool and table
# ---------------------------------------------------------------------------
_PK = dict(S=4, per_slot=4, ps=4, hkv=2, hd=16, layers=2)
_PK_CAP = _PK["per_slot"] * _PK["ps"]
_PK_NAN = 1 + _PK["S"] * _PK["per_slot"]    # the last page: all NaN
# case -> the four slots' lengths (every case but the last two walks
# its own shuffled pages of layer 1)
_PK_LENGTHS = {
    "length_0": [0, 9, 0, 3],
    "length_1": [1, 1, 14, 1],
    "page_less_1": [3, 7, 15, 11],
    "page": [4, 8, 4, 12],
    "page_plus_1": [5, 9, 13, 5],
    "ragged": [2, 16, 7, 10],
    "capacity": [16, 16, 16, 16],
    # slots 0 and 2 name the same physical pages (a shared prefix)
    "shared_page": [8, 5, 6, 11],
    # idle slots as the engine leaves them: zeroed rows, one key of
    # scratch page 0
    "scratch_rows": [1, 13, 1, 6],
    # every table entry past a slot's length names the NaN page
    "nan_past_length": [0, 5, 8, 15],
    # layer 0 of the two, traced: the other cases read layer 1
    "traced_layer_0": [6, 12, 1, 16],
}


# form -> (KV heads, lanes a head): llama's pool keeps a page's heads in
# rows of ``hd`` lanes; sambay's keeps a token's ten paired heads of 128
# end to end in one row, and the kernel cuts them out as lane slices
_PK_FORMS = {"head_rows": (_PK["hkv"], _PK["hd"]), "token_rows": (10, 128)}


@pytest.fixture(scope="module")
def pages_kernel():
    """form -> (kernel, gathered), each jitted once a shape: two pages
    to a block and one to a chunk, so a slot takes one block, two, or a
    block half read, and a block one chunk or two."""
    from mxtpu.ops.attention import gathered_decode_attention, \
        gathered_rows_decode_attention
    from mxtpu.ops.paged_attention import paged_attention_pages, \
        paged_attention_rows
    sizes = dict(block_pages=2, chunk_pages=1, interpret=True)
    return {"head_rows": (jax.jit(partial(paged_attention_pages, **sizes)),
                          jax.jit(partial(gathered_decode_attention,
                                          kv_block=8))),
            "token_rows": (jax.jit(partial(paged_attention_rows, **sizes)),
                           jax.jit(partial(gathered_rows_decode_attention,
                                           kv_block=8)))}


@pytest.fixture(scope="module")
def pages_kernel_pool():
    """(form, dtype) -> (k pool, v pool) of two layers, random but for
    scratch page 0 (zeros) and the last page (NaN)."""
    rng = np.random.default_rng(30)
    out = {}
    for form, (hkv, hd) in _PK_FORMS.items():
        tail = (hkv, hd) if form == "head_rows" else (hkv * hd,)
        shape = (_PK["layers"], _PK_NAN + 1, _PK["ps"]) + tail

        def pool():
            a = rng.standard_normal(shape).astype(np.float32)
            a[:, 0], a[:, _PK_NAN] = 0.0, np.nan
            return a
        k, v = pool(), pool()
        for dt in (jnp.float32, jnp.bfloat16):
            out[form, dt] = jnp.asarray(k, dt), jnp.asarray(v, dt)
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("case", list(_PK_LENGTHS))
@pytest.mark.parametrize("form", list(_PK_FORMS))
def test_pages_kernel_matches_the_gathered_path(pages_kernel,
                                                pages_kernel_pool, form,
                                                case, rep, dtype):
    """The Pallas kernel walks each slot's pages out of the whole pool
    and stops at the slot's length: the same numbers as gather-then-
    ``slot_decode_attention`` up to the order of summation, zeros for a
    slot of length 0, and nothing of a page past the length (the
    interpreter's buffers start as NaN, so would an unread page that
    leaked). Both layouts of two pools: heads in a page's rows
    (``paged_attention_pages``), and a token's heads end to end in one
    row (``paged_attention_rows``: sambay's ten heads of 128 lanes under
    40 or 10 query heads)."""
    kernel, gathered = pages_kernel[form]
    S, per_slot, ps = (_PK[n] for n in ("S", "per_slot", "ps"))
    hkv, hd = _PK_FORMS[form]
    kp, vp = pages_kernel_pool[form, dtype]
    rng = np.random.default_rng(sorted(_PK_LENGTHS).index(case))
    q = jnp.asarray(rng.standard_normal((S, hkv * rep, 1, hd)), dtype)
    lengths = np.asarray(_PK_LENGTHS[case], np.int32)
    table = (1 + rng.permutation(S * per_slot)).astype(
        np.int32).reshape(S, per_slot)
    if case == "shared_page":
        table[2, :2] = table[0, :2]
    if case == "scratch_rows":
        table[[0, 2]] = 0
    clean = table.copy()
    if case == "nan_past_length":
        live = np.arange(per_slot)[None] * ps < lengths[:, None]
        table, clean = (np.where(live, table, fill).astype(np.int32)
                        for fill in (_PK_NAN, 0))
    layer = 0 if case == "traced_layer_0" else 1
    out = kernel(q, kp, vp, table, lengths, layer=jnp.int32(layer))
    ref = gathered(q, kp, vp, clean, lengths, layer=jnp.int32(layer))
    assert out.shape == ref.shape and out.dtype == ref.dtype
    out, ref = (np.asarray(a, np.float32) for a in (out, ref))
    assert np.isfinite(out).all()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    np.testing.assert_array_equal(out[lengths == 0], 0.0)
    if case == "shared_page":       # the same keys under the same query
        again = kernel(q.at[2].set(q[0]), kp, vp, table,
                       jnp.asarray(lengths).at[2].set(lengths[0]),
                       layer=jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(again[0], np.float32),
                                      np.asarray(again[2], np.float32))


# sambay's window rings through the same walk: the four slots' tokens so
# far (the ring holds the last ``sliding_window`` of them, position p at
# index p % window), one of length 1 in the first case
_RING_TOKENS = {"below_window": [1, 37, 200, 511],
                "at_window": [512, 512, 512, 512],
                "past_window": [513, 700, 1024, 1500]}


@pytest.fixture(scope="module")
def ring_walk():
    """(kernel, arm), jitted: a window layer's read of its ring on the
    pages path (the rows walk over the rings' page view and fixed table,
    at the layer given) and on the gathered one (the layer sliced, relaid
    out head-major, ``slot_decode_attention``)."""
    from mxtpu.ops.paged_attention import paged_attention_rows
    cfg = sambay.SambaYConfig()

    def kernel(q, wk, wv, lengths, layer):
        S, W = wk.shape[1:3]
        view = sambay._ring_pages(wk.shape)
        return paged_attention_rows(
            q, wk.reshape(view), wv.reshape(view), sambay._ring_table(S, W),
            lengths, layer=layer, scale=sambay._scale(cfg), interpret=True)

    def arm(q, wk, wv, lengths, layer):
        return slot_decode_attention(
            q, sambay._kv_heads(cfg, wk[layer]),
            sambay._kv_heads(cfg, wv[layer]), lengths,
            scale=sambay._scale(cfg))
    return cfg, jax.jit(kernel), jax.jit(arm)


@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("case", list(_RING_TOKENS))
def test_ring_walk_matches_the_relaid_ring(ring_walk, case, rep):
    """A window ring read where it lies, as pages of token rows under
    the iota table, gives what its head-major copy gives to
    ``slot_decode_attention`` (bfloat16 at Phi-4-mini-flash's widths: ten
    paired KV heads of 128 under 10 or 40 query heads, a window of 512,
    two layers read at the second), below the window, at it, and past it
    where the ring has wrapped; and both are attention over the slot's
    last ``window`` keys in their own order."""
    cfg, kernel, arm = ring_walk
    W, kvp, hd = cfg.sliding_window, cfg.kv_pairs, 2 * cfg.head_dim
    S = len(_RING_TOKENS[case])
    rng = np.random.default_rng(10 * list(_RING_TOKENS).index(case) + rep)
    tokens = np.asarray(_RING_TOKENS[case])
    seq_k, seq_v = (rng.standard_normal((S, tokens.max(), kvp * hd))
                    .astype(np.float32) for _ in range(2))
    rings = rng.standard_normal((2, 2, S, W, kvp * hd)).astype(np.float32)
    for s, n in enumerate(tokens):
        last = np.arange(max(0, n - W), n)
        rings[0, 1, s, last % W] = seq_k[s, last]
        rings[1, 1, s, last % W] = seq_v[s, last]
    wk, wv = (jnp.asarray(r, jnp.bfloat16) for r in rings)
    q = jnp.asarray(rng.standard_normal((S, kvp * rep, 1, hd)), jnp.bfloat16)
    lengths = jnp.asarray(np.minimum(tokens, W), jnp.int32)
    out = kernel(q, wk, wv, lengths, jnp.int32(1))
    want = arm(q, wk, wv, lengths, jnp.int32(1))
    assert out.shape == want.shape and out.dtype == want.dtype
    out, want = (np.asarray(a, np.float32) for a in (out, want))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)
    # the window in time order, as the sequence had it
    for s, n in enumerate(tokens):
        last = np.arange(max(0, n - W), n)
        k, v = (jnp.asarray(a[s, last], jnp.bfloat16)
                .reshape(1, len(last), kvp, hd).transpose(0, 2, 1, 3)
                for a in (seq_k, seq_v))
        plain = slot_decode_attention(q[s:s + 1], k, v,
                                      jnp.asarray([len(last)]),
                                      scale=sambay._scale(cfg))
        np.testing.assert_allclose(out[s], np.asarray(plain[0], np.float32),
                                   rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# which carrier runs is read off the inputs, and the engine says which
# ---------------------------------------------------------------------------
def _chat_shapes(dtype=jnp.bfloat16, int8=False, n_kv_heads=8):
    """(cfg, kv pools as shapes): the chat cell's widths (32 query / 8
    KV heads of 128, pages of 16 tokens) at two layers."""
    from dataclasses import replace
    cfg = replace(llama.CONFIGS["tiny"], dim=4096, n_heads=32,
                  n_kv_heads=n_kv_heads, n_layers=2, max_seq_len=2048,
                  dtype=dtype, param_dtype=dtype)
    state = jax.eval_shape(lambda: llama.init_paged_cache(
        cfg, 32, 2049, 16, int8=int8))
    return cfg, {n: a for n, a in state.items()
                 if n not in ("lengths", "tokens", "rngs")}


@pytest.mark.parametrize("case,path", [
    ("chat_cell_on_a_tpu", "pages"), ("float32_pool", "gathered"),
    ("int8_pool", "gathered"), ("verify_step", "gathered"),
    ("mesh", "gathered"), ("cpu_backend", "gathered"),
    ("four_kv_heads", "gathered")])
def test_decode_attention_path_is_read_off_the_inputs(monkeypatch, case,
                                                      path):
    """Backend, shapes and dtypes decide, statically: the kernel for
    the chat cell's plain step on a TPU, the gathered path for
    everything the kernel does not do yet (four KV heads are half an
    (8, 128) tile: the kernel's view of such a pool is no bitcast, and
    XLA would copy the pool for it); and the traced program holds the
    kernel exactly when the family says so."""
    if case != "cpu_backend":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, kv = _chat_shapes(
        jnp.float32 if case == "float32_pool" else jnp.bfloat16,
        int8=case == "int8_pool",
        n_kv_heads=4 if case == "four_kv_heads" else 8)
    mesh = None
    if case == "mesh":
        from mxtpu.parallel import create_mesh
        mesh = create_mesh(tp=2, devices=jax.devices()[:2])
    verify = case == "verify_step"
    assert llama.decode_attention_path(cfg, kv, mesh, verify=verify) == path
    if mesh is not None:
        return          # the traced text below needs no second copy
    params = jax.eval_shape(partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    S = 32
    sv = {"lengths": jax.ShapeDtypeStruct((S,), jnp.int32),
          "tokens": jax.ShapeDtypeStruct((S,), jnp.int32),
          "rngs": jax.ShapeDtypeStruct((S, 2), jnp.uint32)}
    args = [params, kv, sv, jax.ShapeDtypeStruct((S,), jnp.bool_),
            jax.ShapeDtypeStruct((S, 128), jnp.int32)]
    if verify:
        args.append(jax.ShapeDtypeStruct((S, 3), jnp.int32))
    args += [jax.ShapeDtypeStruct((S,), jnp.float32),
             jax.ShapeDtypeStruct((S,), jnp.int32),
             jax.ShapeDtypeStruct((S,), jnp.float32)]
    program = llama.decode_slots_spec if verify else llama.decode_slots_paged
    text = str(jax.make_jaxpr(partial(program, cfg))(*args))
    from mxtpu.ops.paged_attention import KERNEL_NAME
    assert (KERNEL_NAME in text) == (path == "pages")


@pytest.mark.parametrize("backend,path", [("cpu", "gathered"),
                                          ("tpu", "pages")])
def test_engine_names_the_attention_its_program_was_built_with(
        monkeypatch, cfg, params, backend, path):
    """``kv_cache_stats()`` and ``serve_decode_steps_total{attention,
    sampler}`` carry the family's word and the threshold search's (the
    Pallas kernel on a TPU for a bank of eight slots or more, the
    ``jnp`` form elsewhere); the counter steps where
    ``serve_steps_total`` does."""
    from mxtpu import telemetry
    if backend == "tpu":
        # construction compiles nothing; a bfloat16 pool of eight
        # 128-lane heads is what the rule wants to see
        from dataclasses import replace
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = replace(cfg, dim=1024, n_heads=8, n_kv_heads=8,
                      dtype=jnp.bfloat16)
    eng = paged_engine(cfg, params)
    assert eng.kv_cache_stats()["decode_attention"] == path
    assert path == llama.decode_attention_path(cfg, eng._kv, None)
    # a bank of two slots is no block of eight rows, on any backend
    assert eng.kv_cache_stats()["sampler"] == "search"
    if backend == "tpu":
        bank = paged_engine(cfg, params, max_slots=8)
        assert bank.kv_cache_stats()["sampler"] == "search_kernel"
        return
    reg = telemetry.registry()
    before = {a: reg.value("serve_decode_steps_total", attention=a,
                           sampler="search")
              for a in ("pages", "gathered")}
    steps = reg.value("serve_steps_total")
    eng.submit(Request(prompt=[5, 6, 7], max_new_tokens=4))
    eng.run()
    ran = reg.value("serve_steps_total") - steps
    assert ran >= 3
    assert reg.value("serve_decode_steps_total", attention="gathered",
                     sampler="search") - before["gathered"] == ran
    assert reg.value("serve_decode_steps_total", attention="pages",
                     sampler="search") == before["pages"]


# ---------------------------------------------------------------------------
# programs: the edges of the page range the gathers promise to stay in
# ---------------------------------------------------------------------------
_EDGE_PAGES = 9                             # scratch + 8
_EDGE_SHARED = [7, 3, 9, 1, 5, 2, 8, 4]     # one whole page of 8
# (table row, prompt, prefix_len) per seated slot; unnamed row entries
# stay 0 and alias the scratch page
_EDGE_CASES = {
    # one live slot whose row tail, and the idle slot's whole row, name
    # page 0; the idle slot's write lands there every step
    "scratch_page_0": [([2, 5], [21, 22, 23], 0)],
    "last_page": [([_EDGE_PAGES - 1, 1], [31, 32, 33, 34, 35], 0),
                  ([4, _EDGE_PAGES - 2], [41, 42], 0)],
    # page 3 holds the shared first page of both prompts; slot 1 is
    # admitted warm behind it
    "shared_page": [([3, 6], _EDGE_SHARED, 0),
                    ([3, 7], _EDGE_SHARED + [13], 8)],
}


@pytest.fixture(scope="module")
def paged_programs(cfg):
    return (jax.jit(partial(llama.prefill_slot_paged, cfg)),
            jax.jit(partial(llama.decode_slots_paged, cfg)))


@pytest.mark.parametrize("case", sorted(_EDGE_CASES))
def test_paged_programs_at_the_page_range_edges(cfg, params,
                                                paged_programs, case):
    """Prefill and decode driven directly, so the table is exactly what
    the case says: every stream equals greedy ``generate``."""
    prefill, decode = paged_programs
    slots, per_slot, ps, mnew = 2, 4, 8, 6
    state = llama.init_paged_cache(cfg, slots, _EDGE_PAGES, ps)
    kv = {n: state[n] for n in ("k", "v")}
    sv = {n: state[n] for n in ("lengths", "tokens", "rngs")}
    table = np.zeros((slots, per_slot), np.int32)
    seated = _EDGE_CASES[case]
    streams = []
    for slot, (row, prompt, prefix_len) in enumerate(seated):
        table[slot, :len(row)] = row
        padded = np.zeros((1, 8), np.int32)
        suffix = prompt[prefix_len:]
        padded[0, :len(suffix)] = suffix
        tok, kv, sv = prefill(
            params, padded, np.int32(len(prompt)), np.int32(prefix_len),
            table[slot].copy(), np.int32(slot), kv, sv,
            jax.random.PRNGKey(0), np.float32(0.0),
            np.int32(cfg.vocab_size), np.float32(1.0))
        streams.append([int(np.asarray(tok)[0])])
    active = np.arange(slots) < len(seated)
    for _ in range(mnew - 1):
        sampled, kv, sv = decode(
            params, kv, sv, active, table,
            np.zeros(slots, np.float32),
            np.full(slots, cfg.vocab_size, np.int32),
            np.ones(slots, np.float32))
        for slot in range(len(seated)):
            streams[slot].append(int(np.asarray(sampled)[slot]))
    for (_, prompt, _), got in zip(seated, streams):
        assert got == llama_refs.reference(cfg, params, prompt, mnew)


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_verify_step_without_drafts_is_the_plain_step(cfg, params,
                                                      paged_programs,
                                                      int8):
    """One block serves both decode programs: ``decode_slots_spec``
    with no drafts (every entry < 0) emits one token a slot, the one
    ``decode_slots_paged`` emits, and leaves lengths, tokens, rng
    chains and every live cache entry bit-for-bit where it leaves them
    (past a slot's length the verify step has written its undrafted
    positions: masked, and overwritten before the length gets there) —
    a sampled and a greedy slot, step after step on its own state, the
    pool in float32 and in int8 with its scales."""
    prefill, decode = paged_programs
    spec = jax.jit(partial(llama.decode_slots_spec, cfg))
    slots, ps = 2, 8
    state = llama.init_paged_cache(cfg, slots, _EDGE_PAGES, ps,
                                   int8=int8)
    sv = {n: state.pop(n) for n in ("lengths", "tokens", "rngs")}
    kv = state
    table = np.asarray([[2, 5, 0, 0], [7, 1, 0, 0]], np.int32)
    temps = np.asarray([1.0, 0.0], np.float32)
    for slot, prompt in enumerate([[21, 22, 23], _EDGE_SHARED + [13]]):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :len(prompt)] = prompt
        _, kv, sv = prefill(
            params, padded, np.int32(len(prompt)), np.int32(0),
            table[slot].copy(), np.int32(slot), kv, sv,
            jax.random.PRNGKey(slot), temps[slot],
            np.int32(cfg.vocab_size), np.float32(1.0))
    sampling = (temps, np.full(slots, cfg.vocab_size, np.int32),
                np.ones(slots, np.float32))
    active = np.ones(slots, bool)
    kv_s, sv_s = kv, sv
    for _ in range(4):
        toks, emits, kv_s, sv_s = spec(
            params, kv_s, sv_s, active, table,
            np.full((slots, 2), -1, np.int32), *sampling)
        sampled, kv, sv = decode(params, kv, sv, active, table,
                                 *sampling)
        assert np.asarray(emits).tolist() == [[True, False, False]] * 2
        np.testing.assert_array_equal(np.asarray(toks)[:, 0],
                                      np.asarray(sampled))
        assert sorted(sv_s) == sorted(sv)
        for n in sv:
            np.testing.assert_array_equal(np.asarray(sv_s[n]),
                                          np.asarray(sv[n]), n)
    assert sorted(kv_s) == sorted(kv) == (
        ["k", "ks", "v", "vs"] if int8 else ["k", "v"])
    for slot, length in enumerate(np.asarray(sv["lengths"])):
        live = np.arange(length)
        page, off = table[slot, live // ps], live % ps
        for n in kv:
            np.testing.assert_array_equal(
                np.asarray(kv_s[n])[:, page, off],
                np.asarray(kv[n])[:, page, off], n)


# ---------------------------------------------------------------------------
# the engine has one bank
# ---------------------------------------------------------------------------
def test_engine_has_no_dense_bank_to_switch_to(cfg, params):
    with pytest.raises(ValueError, match="page pool"):
        llama_refs.engine_factory(cfg, params, paged=False)()


def test_engine_built_with_no_bank_argument_serves_from_pages(cfg,
                                                              params):
    """``ServeEngine(cfg, params)`` IS the page pool (page_size 16,
    every slot's max_len plus the scratch page): it reports pages, and
    its float32 streams equal ``generate`` for a sampled and a greedy
    request."""
    e = llama_refs.engine_factory(cfg, params)()
    st = e.kv_cache_stats()
    assert st["paged"] and st["page_size"] == 16
    assert st["pages_total"] == 2 * (32 // 16)
    assert st["pages_free"] == st["pages_total"]
    reqs = [dict(prompt=[7, 3, 9, 1, 5], max_new_tokens=6,
                 temperature=1.0, seed=3),
            dict(prompt=[21, 22, 23], max_new_tokens=5,
                 temperature=0.0)]
    rids = [e.submit(Request(**r)) for r in reqs]
    out = e.run()
    for rid, r in zip(rids, reqs):
        assert [int(t) for t in out[rid]] == llama_refs.reference(
            cfg, params, r["prompt"], r["max_new_tokens"],
            seed=r.get("seed", 0), temperature=r["temperature"])
    assert e.kv_cache_stats()["pages_used"] == 0


def test_disagg_built_with_no_bank_argument_pages_and_journals(cfg,
                                                               params):
    """``DisaggBackend`` with nothing said about the bank ships the
    handoff as page frames, seats it in the decode pool and journals
    it (32 entries by default)."""
    import threading
    from mxtpu.serve.gateway.disagg import DisaggBackend
    be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1,
                       max_slots=2, max_len=32, min_bucket=4)
    try:
        assert be._journal_cap == 32 and not hasattr(be, "paged")
        frames = int(be._m_page_frames.value)
        prompt = [7, 3, 9, 1, 5, 2, 8, 4, 6, 11, 12, 13, 14, 15, 16,
                  17, 18]                   # 17 tokens: two pages of 16
        toks, done = [], threading.Event()
        be.route(Request(prompt=prompt, max_new_tokens=4,
                         temperature=1.0, seed=2,
                         on_token=lambda rid, t: toks.append(int(t)),
                         on_done=lambda rid, r: done.set()))
        assert done.wait(120)
        assert toks == llama_refs.reference(cfg, params, prompt, 4,
                                            seed=2, temperature=1.0)
        assert int(be._m_page_frames.value) - frames == 2
        assert len(be._journal) == 1
        engine = be.decode.replicas()[0].engine
        assert engine.kv_cache_stats()["page_size"] == 16
    finally:
        be.close()


# ---------------------------------------------------------------------------
# engine: paged streams == generate oracle; sharing changes no tokens
# ---------------------------------------------------------------------------
@pytest.mark.slow   # ~15s; fresh-process home: paged_kv_smoke (ci_fast)
def test_paged_engine_bit_exact_with_prefix_sharing(cfg, params):
    shared = [7, 3, 9, 1, 5, 2, 8, 4, 6]   # 9 toks > page_size 8
    reqs = [
        dict(prompt=shared + [11, 12], max_new_tokens=6,
             temperature=1.0, seed=0),
        dict(prompt=shared + [13], max_new_tokens=6, temperature=1.0,
             seed=1),
        dict(prompt=[21, 22, 23], max_new_tokens=5, temperature=0.0),
        dict(prompt=shared + [14, 15], max_new_tokens=4,
             temperature=1.0, top_k=8, seed=3),
    ]
    e = paged_engine(cfg, params)
    rids = [e.submit(Request(**r)) for r in reqs]
    out = e.run()
    for rid, r in zip(rids, reqs):
        want = llama_refs.reference(
            cfg, params, r["prompt"], r["max_new_tokens"],
            seed=r.get("seed", 0), temperature=r["temperature"],
            top_k=r.get("top_k"))
        assert [int(t) for t in out[rid]] == want
    st = e.kv_cache_stats()
    assert st["prefix_hits"] >= 1, st       # the shared system prompt
    assert st["cow_forks"] >= 1, st         # 9 % 8 -> boundary fork
    assert st["prefix_entries"] >= 1, st
    # churn never retraces: buckets + decode + copy_page
    assert e.compile_count <= e.n_buckets + 2, (e.compile_count,
                                               e.n_buckets)
    # warm wave: hits again, still bit-exact
    p2 = shared + [31]
    rid2 = e.submit(Request(prompt=p2, max_new_tokens=5,
                            temperature=1.0, seed=7))
    got2 = [int(t) for t in e.run()[rid2]]
    assert got2 == llama_refs.reference(cfg, params, p2, 5, seed=7,
                                        temperature=1.0)
    assert e.kv_cache_stats()["prefix_hits"] > st["prefix_hits"]


def test_paged_sharded_tp2_streams_match_generate(cfg, params):
    """The paged pool on a tp mesh: kv heads (axis 3 of the token-major
    pool) sharded, layer, page and offset whole; streams unchanged."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    from mxtpu.parallel import mesh as pmesh
    from mxtpu.parallel.sharding import shard_pytree

    mesh = pmesh.create_mesh(tp=2, devices=jax.devices()[:2])
    e = paged_engine(
        cfg, shard_pytree(params, mesh, llama.sharding_rules(cfg)),
        mesh=mesh)
    k = e._kv["k"]
    assert k.shape[2:4] == (8, cfg.n_kv_heads), k.shape
    assert tuple(k.sharding.spec) == (None, None, None, "tp"), k.sharding
    reqs = [dict(prompt=[7, 3, 9, 1, 5, 2, 8, 4, 6, 11, 12],
                 max_new_tokens=6, temperature=1.0, seed=0),
            dict(prompt=[21, 22, 23], max_new_tokens=5, temperature=0.0)]
    rids = [e.submit(Request(**r)) for r in reqs]
    out = e.run()
    for rid, r in zip(rids, reqs):
        assert [int(t) for t in out[rid]] == llama_refs.reference(
            cfg, params, r["prompt"], r["max_new_tokens"],
            seed=r.get("seed", 0), temperature=r["temperature"])
    assert e.compile_count <= e.n_buckets + 2


@pytest.mark.slow   # ~11s; paged_kv_smoke drives pool-bound admission
def test_paged_pool_exhaustion_backpressures_and_drains(cfg, params):
    # max_len=32, ps=8 -> 4 pages/slot; 5 usable pages < 2 full slots
    e = paged_engine(cfg, params, n_pages=6, prefix_cache=False)
    reqs = [([41, 42, 43], 4, 0), ([44, 45], 4, 1), ([46], 4, 2)]
    rids = [e.submit(Request(prompt=p, max_new_tokens=m,
                             temperature=1.0, seed=s))
            for (p, m, s) in reqs]
    out = e.run()                           # queues, never crashes
    for rid, (p, m, s) in zip(rids, reqs):
        assert [int(t) for t in out[rid]] == llama_refs.reference(
            cfg, params, p, m, seed=s, temperature=1.0)
    assert e.kv_cache_stats()["pages_used"] == 0   # fully drained


@pytest.mark.slow   # ~8s; the warm-hit-under-exhaustion regression
def test_warm_hit_under_pool_exhaustion_stays_safe(cfg, params):
    """Regression: a warm admission planned while the pool is nearly
    dry must NEVER evict its own matched prefix entry mid-plan (that
    freed — or re-handed as 'fresh' — the very pages the plan was
    about to share: dead-page retain killed the loop, a re-handed
    page silently aliased two logical positions). The planner now
    pins the entry's pages first; when even that cannot fit, it falls
    back to a COLD plan where the entry is evictable — backpressure
    or fallback, never a crash, tokens always bit-exact."""
    shared = [7, 3, 9, 1, 5, 2, 8, 4, 6]    # 9 toks: 1 full page + 1
    # 3 usable pages: req A's admission takes all of them (2 row
    # pages + 1 registered boundary copy)
    e = paged_engine(cfg, params, n_pages=4)
    ra = e.submit(Request(prompt=shared, max_new_tokens=4,
                          temperature=1.0, seed=0))
    out = e.run()
    assert [int(t) for t in out[ra]] == llama_refs.reference(
        cfg, params, shared, 4, seed=0, temperature=1.0)
    st = e.kv_cache_stats()
    assert st["prefix_entries"] == 1        # A registered; 2 pages held
    # warm request: matches the entry, but free pages (1) can't cover
    # even the warm plan — the fallback evicts the entry and admits
    # cold instead of corrupting the pool
    p2 = shared + [77, 78]
    rb = e.submit(Request(prompt=p2, max_new_tokens=5,
                          temperature=1.0, seed=1))
    got = [int(t) for t in e.run()[rb]]
    assert got == llama_refs.reference(cfg, params, p2, 5, seed=1,
                                       temperature=1.0)
    assert e.kv_cache_stats()["prefix_entries"] == 1   # B re-registered


@pytest.mark.slow   # ~13s (own bucket shapes); CI home: paged_kv_slow
def test_trimmed_handoff_injects_at_bucket_shape(cfg, params):
    """Regression: the page-granular wire trims handoff blocks to an
    arbitrary page multiple of true_len; the paged inject must pad
    back to the power-of-two bucket — one compiled inject program per
    BUCKET, not per prompt length — and stay bit-exact through the
    zero-padded (length-masked) tail."""
    from mxtpu.serve.gateway.disagg import handoff_to_page_frames, \
        pages_to_handoff

    prompt, mnew, seed, ps = [61, 62, 63, 64, 65], 6, 3, 4
    full = llama_refs.reference(cfg, params, prompt, mnew, seed=seed,
                                temperature=1.0)
    padded = np.zeros((1, 16), np.int32)    # bucket 16 (min_bucket 16)
    padded[0, :len(prompt)] = prompt
    tok, kb, vb, rng = llama.prefill_detached(
        cfg, params, jnp.asarray(padded), np.int32(len(prompt)),
        jax.random.PRNGKey(seed), np.float32(1.0),
        np.int32(cfg.vocab_size), np.float32(1.0))
    h = KVHandoff(k=np.asarray(kb), v=np.asarray(vb),
                  true_len=len(prompt), token=full[0],
                  rng=np.asarray(rng, np.uint32))
    frames = handoff_to_page_frames(0, h, ps)
    _, trimmed = pages_to_handoff(
        frames[-1], {f[2]: (f[3], f[4]) for f in frames[:-1]})
    assert trimmed.k.shape[2] == 8          # ceil(5/4)*4 — wire trim
    e = paged_engine(cfg, params, page_size=ps, min_bucket=16)
    assert e._inject_block_len(trimmed) == 16   # padded to the bucket
    rid = e.submit_prefilled(trimmed, Request(
        prompt=prompt, max_new_tokens=mnew, temperature=1.0,
        seed=seed))
    assert [int(t) for t in e.run()[rid]] == full
    # every trimmed shape the wire can produce maps into the bucket
    # set: the inject compile count is bounded like prefill's
    lens = set()
    for tl in range(1, e.max_len + 1):
        blk = min(-(-tl // ps) * ps, e.max_len)
        fh = KVHandoff(k=np.zeros((1, 1, blk, 1), np.float32),
                       v=np.zeros((1, 1, blk, 1), np.float32),
                       true_len=tl, token=0,
                       rng=np.zeros(2, np.uint32))
        b = e._inject_block_len(fh)
        assert b >= blk and b % ps == 0
        lens.add(b)
    from mxtpu.serve.engine import bucket_for
    possible = {bucket_for(n, e.min_bucket, e.max_len)
                for n in range(1, e.max_len + 1)}
    assert len(lens) <= len(possible)


def test_kv_journal_byte_cap():
    """The seated-handoff journal is bounded in BYTES, not just
    entries: oldest entries fall off past the budget, and a single
    block larger than the whole budget is never journaled."""
    import threading
    from mxtpu.serve.gateway.disagg import DisaggBackend

    be = object.__new__(DisaggBackend)
    be._lock = threading.Lock()
    be._journal_cap = 8
    be._journal = {}
    be._journal_bytes = 0

    def mk(n):
        k = np.zeros((1, 1, n, 1), np.float32)
        return KVHandoff(k=k, v=k.copy(), true_len=n, token=0,
                         rng=np.zeros(2, np.uint32))

    nb = DisaggBackend._handoff_nbytes(mk(4))
    be._journal_max_bytes = 2 * nb          # exactly two blocks fit
    be._journal_put(np.asarray([1], np.int32), mk(4))
    be._journal_put(np.asarray([2], np.int32), mk(4))
    assert len(be._journal) == 2 and be._journal_bytes == 2 * nb
    be._journal_put(np.asarray([3], np.int32), mk(4))
    assert len(be._journal) == 2 and be._journal_bytes == 2 * nb
    assert be._journal_lookup(np.asarray([1, 9], np.int32)) is None
    assert be._journal_lookup(np.asarray([3, 9], np.int32)) is not None
    be._journal_put(np.asarray([4], np.int32), mk(64))  # over budget
    assert be._journal_lookup(np.asarray([4, 9], np.int32)) is None
    assert be._journal_bytes == 2 * nb
    be._journal_cap = 1                     # entry cap still applies
    be._journal_put(np.asarray([5], np.int32), mk(4))
    assert len(be._journal) == 1 and be._journal_bytes == nb


def test_paged_journaled_restore_resumes_stream(cfg, params):
    """Crash re-dispatch: prefill once (detached), emit 2 tokens,
    'crash', then seat the journaled handoff + page table in a FRESH
    engine with the resume rng — the stream continues bit-exactly."""
    prompt, mnew, seed = [51, 52, 53, 54, 55], 6, 9
    full = llama_refs.reference(cfg, params, prompt, mnew, seed=seed,
                                temperature=1.0)
    padded = np.zeros((1, 8), np.int32)     # bucket 8 covers len 5
    padded[0, :len(prompt)] = prompt
    tok, kb, vb, rng = llama.prefill_detached(
        cfg, params, jnp.asarray(padded), np.int32(len(prompt)),
        jax.random.PRNGKey(seed), np.float32(1.0),
        np.int32(cfg.vocab_size), np.float32(1.0))
    assert int(np.asarray(tok)[0]) == full[0]
    h = KVHandoff(k=np.asarray(kb), v=np.asarray(vb),
                  true_len=len(prompt), token=full[0],
                  rng=np.asarray(rng, np.uint32))
    n_em = 2
    e = paged_engine(cfg, params)
    rid = e.submit_prefilled(h, Request(
        prompt=prompt + full[:n_em], max_new_tokens=mnew - n_em,
        temperature=1.0, rng=resume_key(seed, n_em)))
    assert [int(t) for t in e.run()[rid]] == full[n_em:]
    # plain (no-resume) handoff through the paged inject path, too
    e2 = paged_engine(cfg, params)
    rid2 = e2.submit_prefilled(h, Request(
        prompt=prompt, max_new_tokens=mnew, temperature=1.0,
        seed=seed))
    assert [int(t) for t in e2.run()[rid2]] == full


@pytest.mark.slow
def test_paged_int8_pool_deterministic(cfg, params):
    """The int8-per-page pool is self-consistent: two engines, same
    stream (quantized KV is NOT f32-bit-exact, so the contract is
    determinism, matching the dense int8 cache's)."""
    p = [7, 3, 9, 1, 5, 2, 8, 4, 6, 61, 62]
    outs = []
    for _ in range(2):
        e = paged_engine(cfg, params, int8_pages=True)
        rid = e.submit(Request(prompt=p, max_new_tokens=5,
                               temperature=1.0, seed=4))
        outs.append([int(t) for t in e.run()[rid]])
    assert outs[0] == outs[1]


@pytest.mark.slow
def test_disagg_paged_wire_and_journal(cfg, params):
    """Page-granular KV wire + journal-hit crash re-dispatch through
    DisaggBackend: streams bit-exact, kvpage frames flow, a resume
    re-dispatch seats from the journal without a prefill round trip."""
    import threading
    from mxtpu.serve.gateway.disagg import DisaggBackend

    def run_req(be, prompt, mnew, seed=0, rng=None):
        toks, done = [], threading.Event()
        req = Request(prompt=prompt, max_new_tokens=mnew,
                      temperature=1.0, seed=seed, rng=rng,
                      on_token=lambda rid, t: toks.append(int(t)),
                      on_done=lambda rid, r: done.set())
        be.route(req)
        assert done.wait(120)
        return toks

    be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1,
                       max_slots=2, max_len=32, min_bucket=4,
                       page_size=8)
    try:
        p1 = [7, 3, 9, 1, 5, 2, 8, 4, 6, 11, 12]
        full = llama_refs.reference(cfg, params, p1, 6, seed=0,
                                    temperature=1.0)
        assert run_req(be, p1, 6, seed=0) == full
        assert int(be._m_page_frames.value) >= 2   # 11 toks / ps 8
        assert len(be._journal) == 1
        # crash after 2 emitted -> journal hit, decode-side reseat
        got = run_req(be, p1 + full[:2], 4, seed=0,
                      rng=resume_key(0, 2))
        assert got == full[2:]
        assert int(be._m_journal_hits.value) >= 1
        row = be.state()[-1]
        assert row["paged"] and row["kv_journal"] >= 1
    finally:
        be.close()
