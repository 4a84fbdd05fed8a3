"""Continuous-batching serving engine (ISSUE 4 tentpole).

Contracts:
- the length-masked slot attention kernel equals the dense reference
  for ragged lengths, including GQA and the multi-block online path;
- the shared sampler's traced (per-slot) mode is bit-identical to the
  static mode ``generate`` compiles;
- a seeded Poisson arrival stream of mixed prompt/output lengths and
  mixed sampling configs through ``ServeEngine`` yields tokens
  BIT-IDENTICAL to sequential per-request ``generate`` calls;
- compile count stays <= prefill-bucket count + 1 decode program over
  a churny run (requests entering/leaving never retrace);
- the weight-only int8 tree rides the same programs;
- scheduling (overlap mode, slot count) never changes tokens.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu.models import llama
from mxtpu.ops.attention import dense_attention, slot_decode_attention
from mxtpu.serve import Request, ServeEngine, bucket_for


import llama_refs


@pytest.fixture(scope="module")
def cfg(serve_cfg):
    return serve_cfg


@pytest.fixture(scope="module")
def params(serve_params):
    return serve_params


# ---------------------------------------------------------------------------
# kernel: length-masked slot attention == dense reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])  # MHA + GQA
def test_slot_attention_matches_dense_ragged(hq, hkv):
    rng = np.random.default_rng(3)
    S, max_len, hd = 6, 50, 16
    q = jnp.asarray(rng.standard_normal((S, hq, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, hkv, max_len, hd)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, hkv, max_len, hd)),
                    jnp.float32)
    lengths = jnp.asarray([0, 1, 7, 23, 50, 13])
    # kv_block 16 does not divide 50: exercises the padded tail AND
    # the multi-block online-softmax path
    out = slot_decode_attention(q, k, v, lengths, kv_block=16)
    assert out.shape == (S, hq, 1, hd)
    for i, L in enumerate(np.asarray(lengths)):
        if L == 0:     # fully masked -> zeros, not NaN/uniform
            np.testing.assert_array_equal(np.asarray(out[i]), 0.0)
            continue
        ref = dense_attention(q[i:i + 1], k[i:i + 1, :, :L],
                              v[i:i + 1, :, :L])[0]
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"slot {i} len {L}")


def test_slot_attention_rejects_bad_gqa():
    q = jnp.zeros((2, 3, 1, 4))
    k = v = jnp.zeros((2, 2, 8, 4))
    with pytest.raises(ValueError):
        slot_decode_attention(q, k, v, jnp.asarray([1, 2]))


# ---------------------------------------------------------------------------
# shared sampler: traced per-slot mode == static mode, bit for bit
# ---------------------------------------------------------------------------
def _frozen_sample_logits(rng, lg, temperature, top_k, top_p):
    """The traced sampler as it stood before it sorted values (PR 27):
    ``argsort`` and ``take_along_axis``, twice. Kept here only, as the
    oracle. Returns (tokens, the masked logits ``categorical`` saw)."""
    def nucleus_mask(lg, top_p):
        order = jnp.argsort(-lg, axis=-1)
        sorted_lg = jnp.take_along_axis(lg, order, axis=-1)
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (csum - probs) < top_p
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_lg, jnp.inf),
                         axis=-1, keepdims=True)
        return jnp.where(lg >= cutoff, lg, -jnp.inf)

    V = lg.shape[-1]
    t_col = jnp.asarray(temperature, jnp.float32)[:, None]
    k_col = jnp.clip(jnp.asarray(top_k, jnp.int32)[:, None], 1, V)
    p_col = jnp.asarray(top_p, jnp.float32)[:, None]
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    slg = lg / jnp.where(t_col == 0.0, 1.0, t_col)
    srt = jnp.take_along_axis(slg, jnp.argsort(-slg, axis=-1), axis=-1)
    kth = jnp.take_along_axis(srt, jnp.broadcast_to(
        k_col - 1, slg.shape[:-1] + (1,)), axis=-1)
    slg = jnp.where(slg < kth, -jnp.inf, slg)
    slg = nucleus_mask(slg, p_col)
    sampled = jax.random.categorical(rng, slg, axis=-1).astype(jnp.int32)
    return jnp.where(t_col[:, 0] == 0.0, greedy, sampled), slg


def _sampler_logits(kind):
    """(4, V) float32 rows the rewrite could break on."""
    rng = np.random.default_rng(11)
    if kind == "f32_v97":
        return jnp.asarray(rng.standard_normal((4, 97)) * 3, jnp.float32)
    x = rng.standard_normal((4, 1031)) * 2      # 1031: no multiple of 128
    if kind == "bf16_v1031":        # hundreds of exact ties a row
        return jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)
    if kind == "halves_v1031":      # ~20 distinct values: the kth value
        # and the nucleus cut-off both fall inside a tie, the maximum too
        return jnp.asarray(np.round(x * 2) / 2, jnp.float32)
    assert kind == "inf_dups_v1031"
    x[:, 515:1030] = x[:, :515]                 # every value twice
    x[rng.random(x.shape) < 0.3] = -np.inf
    x[3, 7:] = -np.inf                          # seven candidates left
    return jnp.asarray(x, jnp.float32)


_SAMPLER_KINDS = ["f32_v97", "bf16_v1031", "halves_v1031", "inf_dups_v1031"]
# (temperature, top_k, top_p); None = off, "V" = the vocabulary's size
_SAMPLER_CONFIGS = [
    (0.0, None, None), (0.7, None, None), (1.1, 5, None), (0.9, None, 0.6),
    (0.8, 12, 0.9), (1.0, 1, None), (1.0, "V", 1.0), (0.6, None, 0.95),
    (0.7, 40, 0.05), (1.3, 1, 0.05), (0.7, None, 0.05)]


def _traced_args(V, row_cfg):
    """Per-row (temperature, top_k, top_p) as the engine passes them."""
    t, k, p = zip(*row_cfg)
    return dict(
        temperature=jnp.asarray(t, jnp.float32),
        top_k=jnp.asarray([V if x is None else x for x in k], jnp.int32),
        top_p=jnp.asarray([1.0 if x is None else x for x in p],
                          jnp.float32))


def _assert_masks_agree(masked, want_masked, lg, args, record=None):
    """The search's masked logits against the frozen sort's: bit-equal,
    but for a row whose decision lies within float32's rounding of
    ``top_p``. There (the float64 mass before the boundary token within
    1e-6 of ``top_p``) the two kept sets may differ by the tokens at
    that boundary, a sum taken in another order landing on the other
    side; the rows it happened in are named. Returns the (row, token)
    pairs the two sides disagree on."""
    masked, want_masked = np.asarray(masked), np.asarray(want_masked)
    kept, want_kept = masked > -np.inf, want_masked > -np.inf
    both = kept & want_kept
    np.testing.assert_array_equal(masked[both], want_masked[both])
    differ = kept != want_kept
    if not differ.any():
        return set()
    t = np.asarray(args["temperature"], np.float32)[:, None]
    slg = np.asarray(lg, np.float32) / np.where(t == 0, np.float32(1), t)
    V = lg.shape[-1]
    before = llama_refs.mass_before(slg, np.clip(np.asarray(args["top_k"]), 1, V))
    p = np.asarray(args["top_p"], np.float32).astype(np.float64)[:, None]
    off = differ & ~(np.abs(before - p) <= 1e-6)
    assert not off.any(), (
        "kept sets differ away from the boundary", np.argwhere(off)[:8])
    rows = sorted(set(np.argwhere(differ)[:, 0].tolist()))
    print(f"boundary rows (mass within 1e-6 of top_p): {rows}, "
          f"{int(differ.sum())} tokens")
    if record is not None:
        record("boundary_rows", rows)
    return {(int(i), int(j)) for i, j in np.argwhere(differ)}


def _traced_against_oracle(monkeypatch, key, lg, row_cfg, record=None):
    """The traced mode's tokens, after holding them and the masked
    logits that reached ``categorical`` to the frozen formulation's
    (:func:`_assert_masks_agree`)."""
    seen = []
    categorical = jax.random.categorical
    args = _traced_args(lg.shape[-1], row_cfg)
    with monkeypatch.context() as m:
        m.setattr(jax.random, "categorical",
                  lambda rng, logits, **kw: (
                      seen.append(logits),
                      categorical(rng, logits, **kw))[1])
        got = llama.sample_logits(key, lg, **args)
    want, want_masked = _frozen_sample_logits(key, lg, **args)
    (masked,) = seen
    boundary = _assert_masks_agree(masked, want_masked, lg, args, record)
    for i, (g, w) in enumerate(zip(np.asarray(got), np.asarray(want))):
        # a draw may differ only by landing on a boundary token
        assert g == w or {(i, int(g)), (i, int(w))} & boundary, (i, g, w)
    return got


@pytest.mark.parametrize("config", _SAMPLER_CONFIGS, ids=str)
@pytest.mark.parametrize("kind", _SAMPLER_KINDS)
def test_sample_logits_traced_matches_static(monkeypatch, record_property,
                                             kind, config):
    """The serving engine samples through the traced mode (per-slot
    arrays), generate through the static mode — the satellite contract
    is that equal logits give bit-equal tokens either way. And the
    traced mode, which orders values by ONE value sort, hands
    ``categorical`` the very logits the two-``argsort`` formulation
    did."""
    lg = _sampler_logits(kind)
    V = lg.shape[-1]
    t, k, p = config
    k = V if k == "V" else k
    key = jax.random.PRNGKey(5)
    a = llama.sample_logits(key, lg, temperature=t, top_k=k, top_p=p)
    b = _traced_against_oracle(monkeypatch, key, lg, [(t, k, p)] * 4,
                               record_property)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", _SAMPLER_KINDS)
def test_sample_logits_mixed_rows_match_each_rows_static(
        monkeypatch, record_property, kind):
    """Per-row mixed config == each row's static config: a greedy row
    beside sampled rows, top-k alone, top-p alone, both at once."""
    lg = _sampler_logits(kind)
    key = jax.random.PRNGKey(5)
    row_cfg = [(0.0, None, None), (0.7, None, 0.95), (0.9, 5, None),
               (0.8, 12, 0.9)]
    mixed = _traced_against_oracle(monkeypatch, key, lg, row_cfg,
                                   record_property)
    for i, (t, k, p) in enumerate(row_cfg):
        full = llama.sample_logits(key, lg, temperature=t, top_k=k,
                                   top_p=p)
        assert int(mixed[i]) == int(full[i]), (i, row_cfg[i])


# ---------------------------------------------------------------------------
# the threshold search itself, at the serve cells' vocabularies
# ---------------------------------------------------------------------------
_SEARCH_KINDS = ["normal_t0.6", "normal_t0.7", "peaked", "uniform",
                 "signed_zeros", "seven_candidates"]
# (top_k, top_p); "V" = the vocabulary's size, None = 1.0
_SEARCH_CONFIGS = [(1, None), (40, None), ("V", None), (1, 0.95),
                   (40, 0.95), ("V", 0.95)]


def _search_rows(V):
    """(6, V) float32 rows, one of each of ``_SEARCH_KINDS``: near-normal
    logits over two temperatures, a row whose nucleus is one token, a
    row that is one tie-class, a row of whole numbers with ``+0.0`` and
    ``-0.0`` among them, a masked row of seven candidates."""
    rng = np.random.default_rng(V)
    x = rng.standard_normal((6, V)).astype(np.float32)
    x[0] /= np.float32(0.6)
    x[1] /= np.float32(0.7)
    x[2, 17] = 40.0
    x[3] = 0.25
    x[4] = np.round(x[4])
    assert np.signbit(x[4][x[4] == 0]).any() and \
        not np.signbit(x[4][x[4] == 0]).all()
    x[5, 7:] = -np.inf
    return x


def _search_args(x, config):
    V = x.shape[-1]
    k, p = config
    k = V if k == "V" else k
    return k, (1.0 if p is None else p), (
        jnp.asarray(x), jnp.full((len(x), 1), k, jnp.int32),
        jnp.full((len(x), 1), 1.0 if p is None else p, jnp.float32))


@pytest.mark.parametrize("config", _SEARCH_CONFIGS, ids=str)
@pytest.mark.parametrize("V", [32768, 128256, 151936, 200064])
def test_threshold_search_matches_float64_oracle(V, config):
    """``ops.threshold.thresholds`` (the ``jnp`` form here) at the four
    serve cells' vocabularies against ``numpy`` in float64: ``kth`` is
    the k-th largest value bit for bit, and the kept set is the top-k
    survivors with less than ``top_p`` of their mass above them, but for
    tokens whose mass lies within 1e-6 of ``top_p`` (a float32 sum);
    the top token always survives, ``top_p`` off keeps every value."""
    from mxtpu.ops import threshold
    x = _search_rows(V)
    k, p, args = _search_args(x, config)
    kth, cut = (np.asarray(a) for a in jax.jit(threshold.thresholds)(*args))
    want_kth = -np.sort(-x, axis=-1)[:, k - 1:k]
    if k < V:
        np.testing.assert_array_equal(kth, want_kth)
    else:
        assert (kth == -np.inf).all()
    if p >= 1.0:
        assert (cut == -np.inf).all()
    kept = x >= np.maximum(kth, cut)
    before = llama_refs.mass_before(x, [k] * len(x))
    want = (x >= want_kth) & (before < p)
    off = (kept != want) & np.isfinite(x) & (np.abs(before - p) > 1e-6)
    assert not off.any(), np.argwhere(off)[:8]
    assert kept[np.arange(len(x)), x.argmax(-1)].all()
    # the peaked row's nucleus is its one token; the tie-class is whole
    if p < 1.0:
        assert kept[2].sum() == 1
    assert kept[3].all() or (k < V and kept[3].sum() == 0)


@pytest.mark.parametrize("config", _SEARCH_CONFIGS + ["mixed"], ids=str)
@pytest.mark.parametrize("V", [640, 1031, 2688])
def test_threshold_kernel_interpreted_equals_the_jnp_form(V, config):
    """The Pallas kernel, interpreted, against the ``jnp`` form: both
    thresholds of every row bit-equal. 640 lanes are five tiles (no
    whole group of eight), 1031 pads with ``-inf`` to nine, 2688 are two
    groups and five; six rows pad to a block of eight; ``mixed`` asks
    each row for something else, one for nothing."""
    from mxtpu.ops import threshold
    x = _search_rows(V)
    if config == "mixed":
        args = (jnp.asarray(x),
                jnp.asarray([[1], [V], [40], [V], [5], [3]], jnp.int32),
                jnp.asarray([[0.5], [0.95], [1.0], [1.0], [0.05], [0.9]],
                            jnp.float32))
    else:
        *_, args = _search_args(x, config)
    want = jax.jit(threshold.thresholds)(*args)
    got = jax.jit(partial(threshold.thresholds, interpret=True))(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# the engine vs per-request generate (acceptance criterion)
# ---------------------------------------------------------------------------
def _poisson_requests(cfg, n, seed, *, mixed_sampling):
    rng = np.random.default_rng(seed)
    reqs, arrival = [], 0.0
    for i in range(n):
        plen = int(rng.choice([3, 5, 9]))
        mnew = int(rng.choice([1, 2, 4, 6]))
        if mixed_sampling and i % 2:
            samp = dict(temperature=float(rng.choice([0.7, 0.9])),
                        top_k=int(rng.choice([5, 8])) if i % 4 == 1
                        else None,
                        top_p=0.8 if i % 4 == 3 else None)
        else:
            samp = dict(temperature=0.0)
        reqs.append(Request(
            prompt=rng.integers(0, cfg.vocab_size, plen),
            max_new_tokens=mnew, seed=i,
            arrival_step=int(arrival), **samp))
        arrival += rng.exponential(2.0)
    return reqs


def _reference(cfg, params, req):
    return np.asarray(llama_refs.reference(
        cfg, params, req.prompt, req.max_new_tokens, seed=req.seed,
        temperature=req.temperature, top_k=req.top_k,
        top_p=req.top_p))


@pytest.mark.slow   # ~21s; serve_smoke proves the fresh-process
# bit-check and tier-1 keeps test_serve_scheduling_never_changes_tokens
def test_serve_bit_identical_to_generate_poisson_stream(cfg, params):
    """>= 12 requests, seeded Poisson arrivals, mixed prompt/output
    lengths AND mixed per-request sampling configs: the continuous-
    batching engine must emit exactly the tokens each request's own
    batch-1 generate would, and compile at most buckets + 1
    programs."""
    reqs = _poisson_requests(cfg, 14, seed=0, mixed_sampling=True)
    eng = ServeEngine(cfg, params, max_slots=4, max_len=32,
                      min_bucket=4)
    rids = [eng.submit(r) for r in reqs]
    res = eng.run()
    assert eng.compile_count <= eng.n_buckets + 1, \
        (eng.compile_count, eng.n_buckets)
    for rid, req in zip(rids, reqs):
        ref = _reference(cfg, params, req)
        np.testing.assert_array_equal(
            res[rid], ref, err_msg=f"request {rid} "
            f"(plen={len(np.asarray(req.prompt))}, "
            f"new={req.max_new_tokens}, t={req.temperature})")
    lat = eng.latency_stats()
    assert lat["n_gaps"] > 0 and lat["p99_token_ms"] >= \
        lat["p50_token_ms"] >= 0.0


@pytest.mark.slow   # ~18s; bit-identity stays tier-1 via the Poisson
def test_serve_scheduling_never_changes_tokens(cfg, params):  # stream test
    """Tokens are a per-request property: different slot counts and
    overlap modes (different interleavings of the same requests) must
    produce identical output."""
    reqs = _poisson_requests(cfg, 8, seed=4, mixed_sampling=True)
    outs = []
    for slots, overlap in [(2, True), (5, True), (3, False)]:
        eng = ServeEngine(cfg, params, max_slots=slots, max_len=32,
                          min_bucket=4, overlap=overlap)
        rids = [eng.submit(r) for r in reqs]
        outs.append({i: res for i, res in
                     zip(rids, map(eng.run().__getitem__, rids))})
    for other in outs[1:]:
        for rid in outs[0]:
            np.testing.assert_array_equal(outs[0][rid], other[rid])


def test_serve_compile_count_bounded_churn(cfg, params):
    """20 requests churning through 2 slots: the jit-cache counter
    proves ONE decode program total, one prefill per bucket and ONE
    page copy (the 20-token prompts register their partial boundary
    page) — admission/recycling never retraces."""
    rng = np.random.default_rng(9)
    eng = ServeEngine(cfg, params, max_slots=2, max_len=48,
                      min_bucket=4)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.choice([3, 6, 11, 20]))),
                    max_new_tokens=int(rng.choice([1, 3, 5])),
                    arrival_step=i, seed=i) for i in range(20)]
    for r in reqs:
        eng.submit(r)
    res = eng.run()
    assert len(res) == 20
    assert all(len(res[i]) == reqs[i].max_new_tokens
               for i in range(20))
    buckets = {bucket_for(len(np.asarray(r.prompt)), 4, 48)
               for r in reqs}
    assert eng.n_buckets == len(buckets)
    assert eng.compile_count <= len(buckets) + 2, \
        (eng.compile_count, buckets)
    # the decode program specifically: exactly one compilation
    assert eng._decode._cache_size() == 1
    assert eng._copy_fn._cache_size() <= 1


@pytest.mark.slow   # ~14s; ci_all's full tier reruns it every CI
def test_serve_int8_rides_the_same_programs(cfg, params):
    """The weight-only int8 tree serves through the identical engine
    path (same program count) and matches generate over the same
    quantized tree."""
    qparams = llama.quantize_params_int8(cfg, params)
    reqs = _poisson_requests(cfg, 6, seed=2, mixed_sampling=False)
    eng = ServeEngine(cfg, qparams, max_slots=3, max_len=32,
                      min_bucket=4)
    rids = [eng.submit(r) for r in reqs]
    res = eng.run()
    assert eng.compile_count <= eng.n_buckets + 1
    for rid, req in zip(rids, reqs):
        np.testing.assert_array_equal(res[rid],
                                      _reference(cfg, qparams, req))


def test_serve_streaming_and_validation(cfg, params):
    """Per-token callbacks stream in order; slots recycle (more
    requests than slots); submit() rejects what generate rejects."""
    streamed = []
    reqs = [Request(prompt=np.arange(4) + i, max_new_tokens=3, seed=i,
                    on_token=lambda rid, tok: streamed.append(
                        (rid, tok)))
            for i in range(5)]
    eng = ServeEngine(cfg, params, max_slots=2, max_len=16,
                      min_bucket=4)
    rids = [eng.submit(r) for r in reqs]
    res = eng.run()
    for rid in rids:
        got = [tok for r, tok in streamed if r == rid]
        assert got == list(res[rid]), rid
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.arange(4), max_new_tokens=0))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.arange(30), max_new_tokens=5))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.arange(4), max_new_tokens=2,
                           top_p=1.5))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.arange(4), max_new_tokens=2,
                           top_k=0))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.asarray([], np.int32),
                           max_new_tokens=2))


def test_bucket_policy():
    assert bucket_for(1, 4, 64) == 4
    assert bucket_for(4, 4, 64) == 4
    assert bucket_for(5, 4, 64) == 8
    assert bucket_for(33, 4, 64) == 64
    assert bucket_for(50, 4, 60) == 60      # capped at max_len
    with pytest.raises(ValueError):
        bucket_for(65, 4, 64)


@pytest.mark.slow   # ~7s; bench_smoke runs this path fresh-process
def test_bench_serve_smoke(cfg):
    """The serve benchmark's measurement path (the metric the chip run
    emits) runs end to end on a tiny config: record shape, positive
    throughput, ordered percentiles, compile bound."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import bench
    rec = bench.bench_llama_serve(n_requests=4, max_slots=2,
                                  max_len=48, cfg=cfg, seed=1)
    assert rec["metric"] == "llama_500m_serve_tokens_per_s"
    assert rec["value"] > 0 and rec["unit"] == "tok/s"
    assert rec["p99_token_ms"] >= rec["p50_token_ms"] >= 0
    # warmup covered every bucket, so the measured stream added no
    # compilations beyond buckets + 1
    assert rec["compiles"] <= rec["buckets"] + 1
    assert rec["vs_baseline"] is None


def test_gluon_llama_serve(cfg, params):
    """The model-zoo surface: GluonLlama.serve() engines the live
    weights and matches the block's own generate."""
    from mxtpu.gluon.model_zoo import GluonLlama
    net = GluonLlama(cfg)
    net.load_pytree(params)
    eng = net.serve(max_slots=2, max_len=24, min_bucket=4)
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    rid = eng.submit(Request(prompt=prompt, max_new_tokens=4))
    res = eng.run()
    ref = np.asarray(net.generate(jnp.asarray(prompt)[None], 4)
                     ._data)[0, 4:]
    np.testing.assert_array_equal(res[rid], ref)


@pytest.mark.slow   # ~12s; telemetry_smoke + test_telemetry.py keep
# the scrape contract in tier-1; ci_all's full tier reruns this one
def test_serve_telemetry_counters_spans_and_threads(cfg, params):
    """ISSUE 5: the engine feeds the process-wide registry without
    changing tokens, and the counters stay EXACT when two engines run
    concurrently (token-callback threads + decode dispatch threads
    hammering the same counter children)."""
    from mxtpu import telemetry as tm
    reg = tm.registry()
    before_tok = reg.value("serve_tokens_total")
    before_req = reg.value("serve_requests_total")
    reqs = _poisson_requests(cfg, 6, seed=3, mixed_sampling=False)
    results = {}

    def run_one(idx):
        streamed = []
        local = [Request(prompt=r.prompt,
                         max_new_tokens=r.max_new_tokens,
                         temperature=r.temperature, seed=r.seed,
                         arrival_step=r.arrival_step,
                         on_token=lambda rid, tok:
                             streamed.append((rid, tok)))
                 for r in reqs]
        eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                          min_bucket=4)
        rids = [eng.submit(r) for r in local]
        res = eng.run()
        results[idx] = ({rid: res[rid] for rid in rids}, streamed, eng)

    threads = [__import__("threading").Thread(target=run_one,
                                              args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert len(results) == 2
    # scheduling/threading never changes tokens
    for rid in results[0][0]:
        np.testing.assert_array_equal(results[0][0][rid],
                                      results[1][0][rid])
    total_tokens = sum(len(v) for res, _, _ in results.values()
                       for v in res.values())
    assert reg.value("serve_tokens_total") - before_tok == total_tokens
    assert reg.value("serve_requests_total") - before_req == 12
    # per-engine latency stats from the private histogram
    for _, streamed, eng in results.values():
        lat = eng.latency_stats()
        assert lat["n_gaps"] > 0
        assert lat["p99_token_ms"] >= lat["p50_token_ms"] >= 0.0
        eng.reset_stats()
        assert eng.latency_stats()["n_gaps"] == 0
    # admission waits and span histograms were fed
    assert reg.get("serve_admission_wait_steps").count >= 12
    assert reg.get("span_serve_decode_dispatch_ms").count > 0
    assert reg.get("span_serve_prefill_ms").count >= 12
    # churn through 2 slots never recompiled: the watcher agrees with
    # the jit-cache gate (each engine compiles its own programs, so
    # compile events == cache entries, and zero anomalies)
    for _, _, eng in results.values():
        assert len(eng._decode.compiles) == eng._decode._cache_size() \
            == 1
        assert reg.value("recompile_total", fn="serve_decode") == 0


def test_serve_sharded_tp2_matches_single_device(cfg, params):
    """Sharded serving: the slot bank on a tp mesh (kv heads sharded)
    must reproduce the single-device engine's tokens."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    from mxtpu.parallel import mesh as pmesh
    from mxtpu.parallel.sharding import shard_pytree

    reqs = _poisson_requests(cfg, 5, seed=6, mixed_sampling=False)
    ref_eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                          min_bucket=4)
    rids = [ref_eng.submit(r) for r in reqs]
    ref = ref_eng.run()

    mesh = pmesh.create_mesh(tp=2, devices=jax.devices()[:2])
    sparams = shard_pytree(params, mesh, llama.sharding_rules(cfg))
    eng = ServeEngine(cfg, sparams, max_slots=2, max_len=32,
                      min_bucket=4, mesh=mesh)
    state_k = eng._kv["k"]      # (L, pages, page, kv heads, hd)
    assert state_k.sharding.spec[3] == "tp", state_k.sharding
    srids = [eng.submit(r) for r in reqs]
    res = eng.run()
    # the compile bound must hold on the mesh path too (a committed
    # spec that normalizes differently from program outputs would
    # silently double every program)
    assert eng.compile_count <= eng.n_buckets + 1, \
        (eng.compile_count, eng.n_buckets)
    for a, b in zip(rids, srids):
        np.testing.assert_array_equal(ref[a], res[b])
