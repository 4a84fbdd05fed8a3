"""The power-retention family (``mxtpu/ops/retention.py``,
``mxtpu/models/retention.py``: Brumby's gated linear-attention
recurrence with the kernel ``(q . k)^2``) against its plain reference
(``benchmark/grid/reference/retention.py``: float32, the attention form
only: no feature map, no state, no chunks), and through ``ServeEngine``
over a pool of no pages.

Toy widths that keep the published ratios (``CONFIGS["tiny"]``: 10
query heads over 2 KV heads of 16, so a state of 144 rows a head; three
layers; chunks of 16), float32 under conftest's ``highest`` matmul
precision, seeded weights. Every comparison with the reference is of
LOGITS: where the engine hands back tokens only, each greedy token's
reference logit is held against the reference's maximum at that
position (``argmax_gaps``).
"""
import importlib.util
import os
import threading
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import telemetry
from mxtpu.models import retention, serving_family
from mxtpu.ops.retention import (retention_chunk, retention_step,
                                 retention_step_bank, retention_step_path,
                                 sympow2, sympow2_rows)
from mxtpu.serve import Request, ServeEngine
from mxtpu.serve.engine import KVHandoff, PageAllocator
from mxtpu.serve.gateway import Gateway, GatewayClient

CFG = retention.CONFIGS["tiny"]
MODEL = {"num_hidden_layers": CFG.n_layers,
         "num_attention_heads": CFG.n_heads,
         "num_key_value_heads": CFG.n_kv_heads, "head_dim": CFG.head_dim,
         "intermediate_size": CFG.hidden_dim, "rms_norm_eps": CFG.norm_eps,
         "rope_theta": CFG.rope_theta, "tie_word_embeddings": False,
         "vocab_size": CFG.vocab_size}
# float32 against float32 at highest precision: the two differ in the
# order of their sums only (the state's running sum against one sum over
# the keys). Logits spread about 1; the largest difference seen is 7e-6
LOGIT_TOL = 1e-4
GAP_TOL = 2 * LOGIT_TOL
ENGINE = dict(max_slots=3, max_len=96, min_bucket=16, page_size=8)
CHUNK = 16
CHUNKED = dict(ENGINE, prefill_chunk=CHUNK)


def _load(*path):
    file = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "grid", *path)
    spec = importlib.util.spec_from_file_location(
        "grid_" + path[-1].replace(".py", ""), file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference", "retention.py")


def _weights(seed, cfg=CFG):
    """Random weights with the norms' weights moved off their initial
    1, so a layer that dropped one of them would show."""
    params = retention.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 50))

    def move(path, a):
        if path[-1].key.endswith("norm"):
            return a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def params():
    return _weights(1)


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in lengths]


def _gaps(params, prompt, tokens, pad_to=96):
    return np.asarray(ref.argmax_gaps(MODEL, params, list(prompt),
                                      list(tokens), pad_to))


def _bank(slots=3, cfg=CFG):
    kv = retention.init_paged_cache(cfg, slots, 1, 8)
    return kv, {m: kv.pop(m) for m in ("lengths", "tokens", "rngs")}


SAMPLE = (jax.random.PRNGKey(3), np.float32(0.0), np.int32(CFG.vocab_size),
          np.float32(1.0))
NO_PAGES = np.zeros(0, np.int32)
# one compile of each program for the whole file
FORWARD = jax.jit(lambda p, t: retention.forward(CFG, p, t))
PREFILL = jax.jit(partial(retention.prefill_slot_paged, CFG))
PREFILL_CHUNK = jax.jit(partial(retention.prefill_slot_paged_chunk, CFG))
PREFILL_LAST = jax.jit(partial(retention.prefill_slot_paged_last, CFG))
DECODE_LOGITS = jax.jit(partial(retention.decode_logits, CFG))


# -- the feature map and the three forms ------------------------------------------
@pytest.mark.parametrize("d", [2, 16, 128])
def test_feature_map_squares_the_dot_product(d):
    """phi(a) . phi(b) = (a . b)^2, in (d / 2 + 1) d stored rows."""
    a, b = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d))
    pa, pb = sympow2(a), sympow2(b)
    assert pa.shape == (7, sympow2_rows(d)) == (7, (d // 2 + 1) * d)
    assert pa.dtype == jnp.float32
    want = (a * b).sum(-1) ** 2
    np.testing.assert_allclose((pa * pb).sum(-1), want,
                               rtol=1e-5, atol=1e-5 * float(want.max()))
    with pytest.raises(ValueError, match="even width"):
        sympow2_rows(7)
    assert sympow2_rows(128) == 8320          # 8256 distinct products


def _inputs(seed, b=2, H=10, G=2, T=37, d=16, bias=3.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, H, T, d))
    k = jax.random.normal(ks[1], (b, G, T, d))
    v = jax.random.normal(ks[2], (b, G, T, d))
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, G, T)) + bias)
    return q, k, v, log_g


def _attention_form(q, k, v, log_g):
    """The reference's decayed squared-score attention, a sequence of
    the batch at a time: (b, H, T, d)."""
    return jnp.stack([
        ref.attention_form(q[i].transpose(1, 0, 2), k[i].transpose(1, 0, 2),
                           v[i].transpose(1, 0, 2), log_g[i].T,
                           qblock=16).transpose(1, 0, 2)
        for i in range(q.shape[0])])


def _empty(b, G, d):
    F = sympow2_rows(d)
    return jnp.zeros((b, G, d, F)), jnp.zeros((b, G, F))


# one compile of the step, and one of the chunk form a chunk size
STEP = jax.jit(partial(retention_step, scale=0.25))
CHUNK_FORM = jax.jit(partial(retention_chunk, scale=0.25))


def _recurrence(q, k, v, log_g, state=None):
    b, _, T, d = q.shape
    S, z = state or _empty(b, k.shape[1], d)
    ys = []
    for t in range(T):
        y, S, z = STEP(q[:, :, t], k[:, :, t], v[:, :, t], log_g[:, :, t],
                       S, z)
        ys.append(y)
    return jnp.stack(ys, 2), S, z


def _chunked(q, k, v, log_g, sizes):
    b, _, T, d = q.shape
    S, z = _empty(b, k.shape[1], d)
    ys, c0 = [], 0
    for n in sizes:
        cut = lambda a: a[:, :, c0:c0 + n]
        y, S, z = CHUNK_FORM(cut(q), cut(k), cut(v), cut(log_g), S, z)
        ys.append(y)
        c0 += n
    assert c0 == T
    return jnp.concatenate(ys, 2), S, z


@pytest.mark.parametrize("bias", [3.0, -1.0])
def test_recurrence_equals_the_attention_form(bias):
    """The decode step, token by token from an empty state, is the
    attention form's row (the reference's: no phi, no state); an open
    gate (g about 0.95) and a closing one (about 0.27)."""
    q, k, v, log_g = _inputs(0, bias=bias)
    got, _, _ = _recurrence(q, k, v, log_g)
    want = _attention_form(q, k, v, log_g)     # its scale: 1 / sqrt 16
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("sizes", [(1,) * 37, (3,) * 12 + (1,), (16, 16, 5),
                                   (37,), (5, 30, 2)],
                         ids=["ones", "threes", "uneven", "whole", "mixed"])
def test_chunked_form_equals_the_recurrence(sizes):
    """Chunks of 1, of 3, uneven ones and the whole sequence at once:
    the same outputs and the same state handed on."""
    q, k, v, log_g = _inputs(1)
    want, S, z = _recurrence(q, k, v, log_g)
    got, S2, z2 = _chunked(q, k, v, log_g, sizes)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S2, S, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(z2, z, rtol=1e-4, atol=1e-4)


def test_one_state_serves_a_kv_heads_five_query_heads():
    """Five query heads over ONE KV head's state give what five copies
    of the KV head, one a query head, give."""
    q, k, v, log_g = _inputs(2, H=5, G=1)
    shared, S, z = _recurrence(q, k, v, log_g)
    rep = lambda a: jnp.repeat(a, 5, 1)
    own, S5, _ = _recurrence(q, rep(k), rep(v), rep(log_g))
    np.testing.assert_allclose(shared, own, rtol=2e-4, atol=2e-5)
    assert S.shape[1] == 1 and S5.shape[1] == 5
    np.testing.assert_array_equal(S5, jnp.broadcast_to(S, S5.shape))


def test_a_masked_position_moves_no_state():
    """k = 0 and log g = 0 (how a caller masks padding, or a slot that
    is not running): the state stays, in both forms."""
    q, k, v, log_g = _inputs(3, T=8)
    _, S, z = _recurrence(q, k, v, log_g)
    zero = jnp.zeros_like
    _, S1, z1 = _recurrence(q, zero(k), v, zero(log_g), (S, z))
    _, S2, z2 = CHUNK_FORM(q, zero(k), v, zero(log_g), S, z)
    for got in (S1, S2):
        np.testing.assert_array_equal(got, S)
    for got in (z1, z2):
        np.testing.assert_array_equal(got, z)


def test_state_stays_in_the_type_it_is_held_in():
    q, k, v, log_g = _inputs(4, T=4)
    S, z = (a.astype(jnp.bfloat16) for a in _empty(2, 2, 16))
    y, S1, z1 = retention_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                               log_g[:, :, 0], S, z, scale=0.25)
    y2, S2, z2 = retention_chunk(q, k, v, log_g, S, z, scale=0.25)
    assert {a.dtype for a in (S1, z1, S2, z2)} == {jnp.dtype(jnp.bfloat16)}
    assert y.dtype == y2.dtype == jnp.float32


@pytest.mark.parametrize("d,dtype", [(16, jnp.float32), (128, jnp.float32),
                                     (128, jnp.bfloat16)],
                         ids=["one_tile", "five_tiles", "bf16_operands"])
def test_step_kernel_matches_the_jnp_form(d, dtype):
    """The Pallas decode step (interpret mode) on one layer of a bank:
    the state it writes is the ``jnp`` form's, the other layers are not
    touched, the read-out agrees to the rounding of its operands; a
    slot with k = 0 and log g = 0 keeps its state."""
    L, b, H, G, F = 3, 3, 5, 1, sympow2_rows(d)
    ks = jax.random.split(jax.random.PRNGKey(d), 6)
    S = jax.random.normal(ks[0], (L, b, G, d, F))
    z = 30.0 + jax.random.normal(ks[1], (L, b, G, F))
    q = jax.random.normal(ks[2], (b, H, d)).astype(dtype)
    k = jax.random.normal(ks[3], (b, G, d)).astype(dtype).at[2].set(0)
    v = jax.random.normal(ks[4], (b, G, d)).astype(dtype)
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[5], (b, G)) + 3)
    log_g = log_g.at[2].set(0.0)
    kw = dict(scale=d ** -0.5)
    want_y, want_S, want_z = retention_step(q, k, v, log_g, S[1], z[1], **kw)
    y, S2, z2 = jax.jit(lambda *a: retention_step_bank(
        *a, interpret=True, **kw))(q, k, v, log_g, S, z, jnp.int32(1))
    np.testing.assert_allclose(S2[1], want_S, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z2[1], want_z, rtol=1e-6)
    np.testing.assert_array_equal(S2[1, 2], S[1, 2])       # the idle slot
    for other in (0, 2):
        np.testing.assert_array_equal(S2[other], S[other])
        np.testing.assert_array_equal(z2[other], z[other])
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    assert float(jnp.abs(y - want_y).max()) <= tol * max(
        1.0, float(jnp.abs(want_y).max()))
    # off a TPU the bank step is the jnp form, exactly
    assert retention_step_path(S.shape, S.dtype) == "jnp"
    y3, S3, _ = retention_step_bank(q, k, v, log_g, S, z, 1, **kw)
    np.testing.assert_array_equal(y3, want_y)
    np.testing.assert_array_equal(S3[1], want_S)


# -- the model against the reference -----------------------------------------------
@pytest.mark.parametrize("seed,bias", [(1, None), (2, None), (3, 0.0)],
                         ids=["seed1", "seed2", "no_gate_bias"])
def test_forward_matches_reference_logits(seed, bias):
    """Whole sequences (50 tokens: three chunks and a part of one)
    against the reference's attention form; with the gate's bias as
    initialised (uniform 4-8) and zeroed, the bias-free layer."""
    params = _weights(seed)
    if bias is not None:
        params["layers"]["bg"] = jnp.full_like(params["layers"]["bg"], bias)
    else:
        bg = np.asarray(params["layers"]["bg"])
        assert bg.dtype == np.float32 and bg.shape == (3, 2)
        assert 4.0 <= bg.min() and bg.max() <= 8.0
    toks = _prompts(seed, (50,))[0]
    got = np.asarray(FORWARD(params, jnp.asarray(toks)[None]))[0]
    want = np.asarray(ref.logits(MODEL, params, jnp.asarray(toks)))
    assert np.abs(got - want).max() <= LOGIT_TOL
    assert np.abs(want).max() > 1.0


def test_a_zero_gate_bias_and_the_one_degree():
    cfg = replace(CFG, gate_bias=(0.0, 0.0))
    bg = retention.init_params(cfg, jax.random.PRNGKey(0))["layers"]["bg"]
    assert not np.asarray(bg).any()
    with pytest.raises(TypeError):
        replace(CFG, power=3)              # the degree is no field: 2
    with pytest.raises(ValueError, match="multiple of n_kv_heads"):
        replace(CFG, n_heads=9)


def test_each_layer_alone_matches_the_reference_layer(params):
    toks = jnp.asarray(_prompts(7, (41,))[0])
    streams = np.asarray(jax.jit(
        lambda p, t: retention.layer_streams(CFG, p, t))(
            params, toks[None]))[:, 0]
    assert streams.shape == (CFG.n_layers + 1, 41, CFG.dim)
    for i in range(CFG.n_layers):
        want = ref.layer(MODEL, params, i, jnp.asarray(streams[i]),
                         qblock=16, fblock=64)
        assert np.abs(np.asarray(want) - streams[i + 1]).max() <= 1e-4


def test_layer_keys_are_the_reference_layers_projections(params):
    """What every layer's retention was handed (``layer_keys``: a
    check's inputs) against the reference's own projections of the
    stream entering the layer; and one guard on the normaliser for the
    two of them."""
    from mxtpu.ops.retention import EPS
    assert EPS == ref.EPS
    toks = jnp.asarray(_prompts(7, (41,))[0])
    streams = jax.jit(lambda p, t: retention.layer_streams(CFG, p, t))(
        params, toks[None])[:, 0]
    got = jax.jit(lambda p, t: retention.layer_keys(CFG, p, t))(
        params, toks[None])
    assert got[0].shape == (CFG.n_layers, 1, 41, CFG.n_kv_heads,
                            CFG.head_dim)
    names = ref._RETENTION_WEIGHTS
    for i in range(CFG.n_layers):
        w = {n: params["layers"][n][i] for n in names}
        want = ref.projections(w, streams[i], CFG.norm_eps, CFG.rope_theta,
                               CFG.n_heads, CFG.n_kv_heads)[1:]
        for mine, theirs in zip(got, want):
            assert np.abs(np.asarray(mine[i, 0] - theirs)).max() <= 1e-4


def _prefill_whole(params, toks, true_len, slot=1):
    kv, sv = _bank()
    padded = np.zeros((1, 64), np.int32)
    padded[0, :true_len] = toks[:true_len]
    return PREFILL(params, padded, np.int32(true_len), np.int32(0),
                   NO_PAGES, np.int32(slot), kv, sv, *SAMPLE)


def _prefill_chunks(params, toks, true_len, slot=1, stage=None, bank=None):
    kv, sv = bank or _bank()
    stage = stage or retention.init_prefill_stage(CFG, 96, CHUNK)
    done = 0
    while true_len - done > CHUNK:
        stage = PREFILL_CHUNK(params, toks[None, done:done + CHUNK],
                              np.int32(done), stage)
        done += CHUNK
    last = np.zeros((1, CHUNK), np.int32)
    last[0, :true_len - done] = toks[done:true_len]
    tok, kv, sv = PREFILL_LAST(
        params, last, np.int32(done), np.int32(true_len - done), stage,
        NO_PAGES, np.int32(slot), kv, sv, *SAMPLE)
    return tok, kv, sv, stage


@pytest.mark.parametrize("true_len", [53, 48, 33, 16, 5])
def test_prefill_in_chunks_seats_what_the_whole_prefill_seats(params,
                                                              true_len):
    """A prompt through the stage, 16 tokens at a time (a full last
    chunk, a part of one, a single chunk), and through one whole
    prefill padded to a bucket: the same first token, the same state in
    the slot, nothing in any other slot."""
    toks = _prompts(true_len, (64,))[0]
    tok_w, kv_w, sv_w = _prefill_whole(params, toks, true_len)
    tok_c, kv_c, sv_c, _ = _prefill_chunks(params, toks, true_len)
    assert int(tok_w[0]) == int(tok_c[0])
    for n in ("S", "z"):
        np.testing.assert_allclose(kv_c[n], kv_w[n], rtol=1e-4, atol=1e-4)
        assert not np.asarray(kv_c[n][:, 0]).any()
        assert not np.asarray(kv_c[n][:, 2]).any()
        assert np.asarray(kv_c[n][:, 1]).any()
    assert list(np.asarray(sv_c["lengths"])) == [0, true_len, 0]


def test_a_prompts_first_chunk_starts_from_nothing(params):
    """The stage still holds the prompt before: a new prompt's first
    chunk does not read it."""
    old, new = _prompts(8, (40, 37))
    *_, stage = _prefill_chunks(params, old, 40)
    assert np.asarray(stage["S"]).any()
    tok_a, kv_a, _, _ = _prefill_chunks(params, new, 37, stage=stage)
    tok_b, kv_b, _, _ = _prefill_chunks(params, new, 37)
    assert int(tok_a[0]) == int(tok_b[0])
    np.testing.assert_array_equal(kv_a["S"], kv_b["S"])


@pytest.mark.parametrize("true_len", [37, 16])
def test_decode_through_the_state_matches_one_full_forward(params, true_len):
    """Prefill in chunks, then ten decode steps of a bank in which the
    other slots do not run: each step's logits are the reference's at
    that position, and a slot that does not run keeps its state."""
    toks = _prompts(true_len + 1, (64,))[0]
    _, kv, sv, _ = _prefill_chunks(params, toks, true_len, slot=1)
    other = {n: kv[n].at[:, 2].set(1.0) for n in kv}     # someone else's
    want = np.asarray(ref.logits(MODEL, params, jnp.asarray(toks[:true_len
                                                                 + 10])))
    active = np.array([False, True, False])
    kv = other
    for t in range(10):
        sv = dict(sv, tokens=sv["tokens"].at[1].set(int(toks[true_len + t])))
        lg, kv = DECODE_LOGITS(params, kv, sv, active)
        assert np.abs(np.asarray(lg[1]) - want[true_len + t]).max() \
            <= LOGIT_TOL
        sv = dict(sv, lengths=sv["lengths"] + active.astype(np.int32))
    for n in kv:
        np.testing.assert_array_equal(kv[n][:, 2], other[n][:, 2])
        assert not np.asarray(kv[n][:, 0]).any()


# -- through the engine ------------------------------------------------------------
@pytest.fixture(scope="module")
def served(params):
    """Two engines, one prefilling whole prompts and one in chunks of 16
    (with decode steps of the requests already running in between),
    each given the same six greedy requests over three slots (so every
    slot is reseated over another request's state, and holds requests of
    different ages in one step) and three sampled ones. {name: (engine,
    greedy prompts, their tokens, the sampled requests' tokens)}."""
    greedy, sampled = _prompts(0, (30, 41, 17, 33, 64, 9)), \
        _prompts(11, (45, 20, 66))
    out = {}
    for name, kw in (("whole", ENGINE), ("chunked", CHUNKED)):
        eng = ServeEngine(CFG, params, **kw)
        g = [eng.submit(Request(prompt=p, max_new_tokens=12,
                                temperature=0.0)) for p in greedy]
        s = [eng.submit(Request(prompt=p, max_new_tokens=8, seed=i,
                                temperature=0.7, top_p=0.9))
             for i, p in enumerate(sampled)]
        got = eng.run()
        out[name] = (eng, greedy, [got[r] for r in g], [got[r] for r in s])
    return out


def test_family_surface_and_a_pool_of_no_pages(served):
    """The engine's numbers for a family no part of whose state grows
    with tokens: no pages, a fixed block a slot, the gauge by kind."""
    assert serving_family(CFG) is retention
    assert {"prefix_cache", "speculate_k", "int8_pages", "submit_prefilled",
            "mesh"} == set(retention.SERVE_UNSUPPORTED)
    assert set(retention.STATE_KINDS.values()) == {"retention_state"}
    eng = served["chunked"][0]
    kv = eng.kv_cache_stats()
    # a slot's state: 3 layers x 2 KV heads x 144 rows x (16 + 1) float32
    slot = CFG.n_layers * CFG.n_kv_heads * CFG.state_rows \
        * (CFG.head_dim + 1) * 4
    assert CFG.state_rows == 144
    assert retention.RetentionConfig().state_rows == 8320
    assert kv["state_bytes_per_slot"] == slot == 58752
    assert kv["reserved_bytes"] == 3 * slot
    assert (kv["pages_total"], kv["pages_free"], kv["pages_used"],
            kv["pages_shared"]) == (0, 0, 0, 0)
    assert (kv["active"], kv["live_bytes"], kv["occupancy"]) == (0, 0, 0.0)
    assert kv["decode_attention"] == "state"
    assert eng.n_pages == 1 and eng._pt.shape == (3, 0)
    assert eng.prefix_cache_enabled is False     # the default, not asked
    prom = telemetry.prometheus().splitlines()
    line = next(ln for ln in prom if ln.startswith(
        "mxtpu_serve_state_bytes{engine="
        f'"{eng.engine_id}",kind="retention_state"}}'))
    assert float(line.split()[-1]) == 3 * slot
    assert not any(f'engine="{eng.engine_id}",kind="kv_pages"' in ln
                   for ln in prom)
    assert any(ln.startswith('mxtpu_serve_decode_steps_total{attention='
                             '"state",sampler="search"}') for ln in prom)
    # the bank's copy_page has nothing to copy
    bank, _ = _bank()
    assert retention.copy_page(bank, 0, 1) is bank


def test_live_bytes_are_the_running_slots_blocks(params):
    """Admission by free slots: four requests over three slots, the
    fourth waits for a slot (never for a page); while they run the live
    bytes are the running slots' fixed blocks whatever their lengths."""
    eng = ServeEngine(CFG, params, **CHUNKED)
    seen = []
    rids = [eng.submit(Request(
        prompt=p, max_new_tokens=6,
        on_token=lambda rid, tok: seen.append(eng.kv_cache_stats())))
        for p in _prompts(4, (20, 35, 9, 50))]
    got = eng.run()
    assert all(len(got[r]) == 6 for r in rids)
    slot = eng.kv_cache_stats()["state_bytes_per_slot"]
    assert {s["live_bytes"] // slot for s in seen} <= {1, 2, 3}
    assert max(s["active"] for s in seen) == 3
    assert all(s["live_bytes"] == s["active"] * slot for s in seen)
    assert all(s["pages_used"] == 0 for s in seen)


@pytest.mark.parametrize("name", ["whole", "chunked"])
def test_engine_run_matches_reference(params, served, name):
    """Prefill (whole, or in chunks) + 11 decode steps through
    ``ServeEngine.run()``, six requests over three slots: every emitted
    token is the reference's argmax at its position, also of a request
    seated over another request's state."""
    eng, prompts, tokens, _ = served[name]
    for p, toks in zip(prompts, tokens):
        assert len(toks) == 12
        assert _gaps(params, p, toks).max() <= GAP_TOL
    if name == "chunked":
        assert eng.n_buckets == 2 and eng.compile_count == 3
    else:
        assert eng.compile_count == 1 + eng.n_buckets


def test_chunked_and_whole_prefill_sample_the_same_stream(served):
    for whole, chunked in zip(served["whole"][3], served["chunked"][3]):
        assert len(whole) == 8
        np.testing.assert_array_equal(whole, chunked)


def test_gateway_matches_reference(params):
    """The same through ``Gateway.start_http``: streamed tokens of four
    concurrent requests, prompts prefilled in chunks."""
    gw = Gateway(lambda: ServeEngine(CFG, params, **CHUNKED),
                 n_replicas=1, queue_max=16)
    prompts = _prompts(5, (27, 35, 52, 11))
    results = {}
    try:
        port = gw.start_http(port=0)

        def client(i):
            results[i] = GatewayClient("127.0.0.1", port).generate(
                prompts[i], 8, seed=i, temperature=0.0)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        _, prom = GatewayClient("127.0.0.1", port).get_text("/metrics")
    finally:
        gw.close()
    assert 'kind="retention_state"' in prom
    for i, p in enumerate(prompts):
        assert results[i]["status"] == 200, results[i]
        assert len(results[i]["tokens"]) == 8
        assert _gaps(params, p, results[i]["tokens"]).max() <= GAP_TOL


@pytest.mark.parametrize("option,word", [
    ({"prefix_cache": True}, "snapshot of .S, z. at its boundary"),
    ({"speculate_k": 2}, "rolled back"),
    ({"int8_pages": True}, "no pages")])
def test_engine_refuses_what_it_cannot_do(params, option, word):
    with pytest.raises(ValueError, match="retention family.*" + word):
        ServeEngine(CFG, params, **{**ENGINE, **option})


def test_engine_refuses_a_prefilled_handoff_and_a_mesh(params):
    eng = ServeEngine(CFG, params, **ENGINE)
    z = np.zeros((CFG.n_layers, CFG.n_kv_heads, 16, CFG.head_dim),
                 np.float32)
    handoff = KVHandoff(k=z, v=z, true_len=9, token=1,
                        rng=np.zeros(2, np.uint32))
    with pytest.raises(ValueError,
                       match="submit_prefilled.*not a retention state"):
        eng.submit_prefilled(handoff, Request(
            prompt=np.arange(9), max_new_tokens=2))
    from mxtpu.parallel import mesh as pmesh
    with pytest.raises(ValueError, match="mesh.*share of the KV heads"):
        ServeEngine(CFG, params, mesh=pmesh.create_mesh(dp=-1), **ENGINE)
    with pytest.raises(ValueError, match="retention: .*no pages"):
        retention.init_paged_cache(CFG, 2, 1, 8, int8=True)


def test_the_pool_of_no_pages_grants_nothing():
    """What the engine keeps for this family: the scratch page alone."""
    pool = PageAllocator(1)
    assert (pool.free_pages, pool.used_pages, pool.shared_pages) == (0, 0, 0)
    assert pool.alloc(0) == [] and pool.alloc(1) is None
    with pytest.raises(ValueError, match="scratch"):
        PageAllocator(0)


# -- the precision the state is held in ---------------------------------------------
def test_a_state_held_in_bfloat16_fails_the_state_comparison(params):
    """The benchmark's ``state`` check (``drivers/serve_family_state.py``:
    a slot's state after a prompt prefilled in chunks, seated over
    another prompt's state and stepped in a bank of three slots,
    through the family's serving programs, against the reference's sums
    through probe queries) at toy size: float32 passes a limit a bf16
    state misses a hundred times over, though no token moves."""
    driver = _load("drivers", "serve_family_state.py")
    config = {"check": {"prompt_cap": 40, "new_tokens": 12,
                        "state_tol": 1e-4},
              "run": {"engine": dict(ENGINE, n_pages=0,
                                     prefill_chunk=CHUNK)}}
    seqs = [(jnp.asarray(np.random.default_rng(3).integers(
        0, CFG.vocab_size, 52), jnp.int32), [])]
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(driver.__file__))))
    try:
        ok, notes = driver.state_check(config, retention, CFG, ref, params,
                                       seqs, 5, lambda s: None)
        assert ok and notes["check_state_gap"] <= 1e-5
        assert notes["check_state_dtype"] == "float32"
        assert notes["check_state_idle_kept"] is True
        held = replace(CFG, state_dtype=jnp.bfloat16)
        ok, notes = driver.state_check(config, retention, held, ref, params,
                                       seqs, 5, lambda s: None)
    finally:
        sys.path.pop(0)
    assert not ok and notes["check_state_gap"] > 1e-3
    assert notes["check_state_dtype"] == "bfloat16"
    assert notes["check_state_idle_kept"] is True
    # and the engine holds what the config says
    eng = ServeEngine(held, params, **ENGINE)
    assert eng._kv["S"].dtype == jnp.bfloat16
    assert eng.kv_cache_stats()["state_bytes_per_slot"] == 58752 // 2
