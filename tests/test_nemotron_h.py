"""The Nemotron-H family (``mxtpu/models/nemotron_h.py``: Mamba-2 mixers,
relu² experts with a shared expert, attention without position encoding,
by ``hybrid_override_pattern``) against its plain reference
(``benchmark/grid/reference/nemotron_h.py``: float32, the recurrence a
token at a time, every expert on every token), the chunked SSD of
``ops/ssm.py`` against the step recurrence, the non-gated expert bank of
``parallel/moe.py``, and the family through the paged ``ServeEngine``.

Toy widths that keep the published ratios (``CONFIGS["tiny"]``: the
pattern's first period ``MEMEM*EME``, 8 Mamba-2 heads of 8 in 2 groups of
state 16, SSD chunks of 8, 16 experts top-3, 4 query heads over 2 KV
heads of 16), float32 under conftest's ``highest`` matmul precision.
Every comparison is of LOGITS or of states; where the engine hands back
tokens only, each greedy token's reference logit is held against the
reference's maximum at that position (``argmax_gaps``).
"""
import importlib.util
import os
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import telemetry
from mxtpu.models import nemotron_h, serving_family
from mxtpu.ops.ssm import ssd_chunked, ssd_step
from mxtpu.parallel import moe
from mxtpu.serve import Request, ServeEngine
from mxtpu.serve.gateway import Gateway, GatewayClient

CFG = nemotron_h.CONFIGS["tiny"]
MODEL = {"hybrid_override_pattern": CFG.pattern,
         "num_hidden_layers": CFG.n_layers, "norm_eps": CFG.norm_eps,
         "n_groups": CFG.n_groups, "ssm_state_size": CFG.d_state,
         "conv_kernel": CFG.d_conv, "num_attention_heads": CFG.n_heads,
         "num_key_value_heads": CFG.n_kv_heads, "head_dim": CFG.head_dim,
         "routed_scaling_factor": CFG.routed_scaling_factor,
         "num_experts_per_tok": CFG.experts_per_tok,
         "norm_topk_prob": CFG.norm_topk_prob,
         "n_routed_experts": CFG.n_routed_experts,
         "tie_word_embeddings": False, "vocab_size": CFG.vocab_size}
# float32 against float32 at highest precision: the two differ in the
# order of their sums (the chunked SSD, online softmax, sorted rows).
# Logits spread about 1; the largest difference seen is 4e-6
LOGIT_TOL = 1e-4
GAP_TOL = 2 * LOGIT_TOL
ENGINE = dict(max_slots=3, max_len=96, min_bucket=16, page_size=8)
CHUNK = 16
CHUNKED = dict(ENGINE, prefill_chunk=CHUNK)


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "grid", "reference", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("grid_ref_nemotron_h",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def _weights(seed):
    """Random weights with the norms' weights, the convolution's bias and
    the selection bias moved off their initial values, so a layer that
    dropped one of them would show."""
    params = nemotron_h.init_params(CFG, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 50))

    def move(path, a):
        if path[-1].key.endswith("norm") or path[-1].key == "conv_b":
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


def _bank(slots=3, n_pages=40, ps=8):
    kv = nemotron_h.init_paged_cache(CFG, slots, n_pages, ps)
    return kv, {m: kv.pop(m) for m in ("lengths", "tokens", "rngs")}


SAMPLE = (jax.random.PRNGKey(3), np.float32(0.0), np.int32(CFG.vocab_size),
          np.float32(1.0))
# one compile of each program for the whole file
FORWARD = jax.jit(lambda p, t: nemotron_h.forward(CFG, p, t))
PICKS = jax.jit(lambda p, t: nemotron_h.router_picks(CFG, p, t))
PREFILL = jax.jit(partial(nemotron_h.prefill_slot_paged, CFG))
PREFILL_CHUNK = jax.jit(partial(nemotron_h.prefill_slot_paged_chunk, CFG))
PREFILL_LAST = jax.jit(partial(nemotron_h.prefill_slot_paged_last, CFG))
DECODE_LOGITS = jax.jit(partial(nemotron_h.decode_logits, CFG))
DROPLESS = jax.jit(moe.moe_ffn_dropless)
STEP = jax.jit(ssd_step)


# -- the recurrence --------------------------------------------------------
def _ssd_inputs(seed, b=2, s=40, h=4, p=3, g=2, n=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = partial(jax.random.normal, dtype=jnp.float32)
    x = normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(normal(ks[1], (b, s, h)))
    A = -jnp.exp(normal(ks[2], (h,)))
    B = normal(ks[3], (b, s, g, n))
    C = normal(ks[4], (b, s, g, n))
    D = normal(ks[5], (h,))
    S0 = normal(ks[6], (b, h, p, n))
    return x, dt, A, B, C, D, S0


def _steps(x, dt, A, B, C, D, S):
    ys = []
    for t in range(x.shape[1]):
        y, S = STEP(S, x[:, t], dt[:, t], A, B[:, t], C[:, t], D)
        ys.append(y)
    return jnp.stack(ys, 1), S


@pytest.mark.parametrize("chunk", [8, 40, 5])
def test_ssd_chunked_equals_the_step_recurrence(chunk):
    """From a carried state, across chunk boundaries: the chunked form's
    outputs and final state are the step recurrence's."""
    x, dt, A, B, C, D, S0 = _ssd_inputs(chunk)
    want_y, want_S = _steps(x, dt, A, B, C, D, S0)
    got_y, got_S = jax.jit(partial(ssd_chunked, chunk=chunk))(
        x, dt, A, B, C, D, S0)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_S, want_S, atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(want_S - S0).max()) > 0.5


def test_ssd_step_against_the_reference_scan():
    """The step recurrence against the reference's, a group's B and C
    read by each of its heads, from an empty state."""
    x, dt, A, B, C, D, _ = _ssd_inputs(9, b=1)
    y, S = _steps(x, dt, A, B, C, D, jnp.zeros((1, 4, 3, 5)))
    want_S, want_y = ref.ssm_scan(x[0], dt[0], A, B[0], C[0])
    np.testing.assert_allclose(S[0], want_S, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y[0], want_y + D[:, None] * x[0], atol=1e-5,
                               rtol=1e-5)


def test_ssd_padding_with_a_zero_step_leaves_the_state():
    """Positions whose ``dt`` is 0 (a bucket's padded tail, a slot that
    is not running) leave the state as the last real position left it,
    whatever their x, B and C hold."""
    x, dt, A, B, C, D, S0 = _ssd_inputs(4, s=24)
    cut = dt.at[:, 13:].set(0.0)
    _, want = _steps(x[:, :13], dt[:, :13], A, B[:, :13], C[:, :13], D, S0)
    _, got = ssd_chunked(x, cut, A, B, C, D, S0, chunk=8)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    _, kept = ssd_step(S0, x[:, 0], jnp.zeros_like(dt[:, 0]), A, B[:, 0],
                       C[:, 0], D)
    np.testing.assert_array_equal(kept, S0)


# -- the non-gated experts ------------------------------------------------
def _relu2_bank(T, E=16, K=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = partial(jax.random.normal, dtype=jnp.float32)
    x = normal(ks[0], (T, 20))
    bank = {"gate": normal(ks[1], (20, E)) / 4,
            "w_up_t": normal(ks[2], (E, 12, 20)) / 4,
            "w_down": normal(ks[3], (E, 12, 20)) / 4}
    return x, bank


@pytest.mark.parametrize("T", [40, 1])
def test_non_gated_dropless_equals_moe_ffn_dense(T):
    """A bank without ``w_gate`` is relu² up/down in both: the dropless
    dispatch with the dense path's own routing (softmax, top-3,
    renormalised) gives the dense path's output."""
    x, bank = _relu2_bank(T)
    probs = jax.nn.softmax(x @ bank["gate"], -1)
    wts, idx = jax.lax.top_k(probs, 3)
    wts = wts / wts.sum(-1, keepdims=True)
    got, sizes = DROPLESS({n: bank[n] for n in ("w_up_t", "w_down")}, x,
                          idx.astype(jnp.int32), wts)
    want, _ = moe.moe_ffn_dense(bank, x, top_k=3)
    np.testing.assert_allclose(got, want, atol=1e-5)
    manual = jnp.einsum("ted,te->td", jnp.einsum(
        "teh,ehd->ted", jnp.square(jax.nn.relu(
            jnp.einsum("td,ehd->teh", x, bank["w_up_t"]))), bank["w_down"]),
        jnp.zeros((T, 16)).at[jnp.arange(T)[:, None], idx].set(wts))
    np.testing.assert_allclose(want, manual, atol=1e-5)
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(idx).reshape(-1), minlength=16))


def test_non_gated_dropless_on_a_layer_of_a_stacked_bank():
    x, bank = _relu2_bank(24, seed=3)
    idx, wts = moe.route_sigmoid(x, bank["gate"], jnp.zeros(16), top_k=3,
                                 scale=2.5)
    one = {n: bank[n] for n in ("w_up_t", "w_down")}
    stack = {n: jnp.stack([a * 0 + 7.0, a]) for n, a in one.items()}
    valid = jnp.arange(24) % 4 != 0
    got, _ = DROPLESS(stack, x, idx, wts, layer=jnp.int32(1), valid=valid)
    want, _ = DROPLESS(one, x, idx, wts)
    np.testing.assert_allclose(got, jnp.where(valid[:, None], want, 0.0),
                               atol=1e-5)


@pytest.mark.parametrize("dims", [(2688, 1856), (1856, 2688), (2048, 768),
                                  (768, 2048)])
def test_grouped_product_tiles_fit_their_byte_budget(dims):
    """A group's matrix is cut into whole lane tiles that divide it once
    it is past ``_GMM_TILE_BYTES``; the tiles kanana's and SDAR's widths
    had stay what they were."""
    k, n = dims
    tk, tn = moe._fit_tile(min(2048, k), min(2048, n), dims, 2)
    assert tk * tn * 2 <= moe._GMM_TILE_BYTES
    assert (tk == k or k % tk == 0) and (tn == n or n % tn == 0)
    if k * n <= 2048 * 768:
        assert (tk, tn) == (min(2048, k), min(2048, n))


# -- the model against the reference ---------------------------------------
@pytest.mark.parametrize("seed", [1, 2])
def test_forward_matches_reference_logits(seed):
    params = _weights(seed)
    toks = _prompts(seed, [37])[0]
    got = FORWARD(params, toks[None])[0]
    picks = []
    want = ref.logits(MODEL, params, jnp.asarray(toks), picks=picks)
    assert float(jnp.abs(want).max()) > 2.0      # logits spread about 1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)
    mine = PICKS(params, toks[None])
    np.testing.assert_array_equal(np.sort(mine, -1),
                                  np.sort(np.stack(picks), -1))


def test_each_layer_alone_matches_the_reference_layer(params):
    """``layer_streams`` hands out the stream entering every layer and
    leaving the last: the reference's layer applied to each gives the
    next."""
    toks = _prompts(4, [37])[0]
    streams = jax.jit(lambda p, t: nemotron_h.layer_streams(CFG, p, t))(
        params, toks[None])[:, 0]
    assert streams.shape == (CFG.n_layers + 1, 37, CFG.dim)
    for index in range(CFG.n_layers):
        want = ref.layer(MODEL, params, index, streams[index])
        assert float(jnp.abs(want - streams[index]).max()) > 0.05
        np.testing.assert_allclose(streams[index + 1], want, atol=1e-5)


def test_ssm_inputs_are_what_the_first_mamba_layer_scans(params):
    """The recurrence's inputs the program hands a check, a chunk at a
    time from the stage, are what the reference's own first layer
    computes over the whole prompt."""
    toks = _prompts(6, [29])[0]
    inputs = jax.jit(partial(nemotron_h.ssm_inputs, CFG))
    stage = nemotron_h.init_prefill_stage(CFG, 96, CHUNK)
    got = []
    for done in (0, CHUNK):
        part = np.zeros((1, CHUNK), np.int32)
        n = min(CHUNK, 29 - done)
        part[0, :n] = toks[done:done + n]
        chunk, stage = inputs(params, part, np.int32(done), np.int32(n),
                              stage)
        got.append([a[0, :n] for a in chunk])
    w = {n: a[0] for n, a in params["mamba"].items()}
    h = ref._rms(params["tok_embed"][toks], w["norm"], CFG.norm_eps)
    want = ref.mamba_inputs(w, h, CFG.n_groups, CFG.d_state, CFG.d_conv)
    for mine, exp in zip(zip(*got), want):
        np.testing.assert_allclose(jnp.concatenate(mine), exp, atol=1e-5)


def _prefill_whole(params, toks, true_len, row, slot=1, bank=None):
    padded = np.zeros((1, 96), np.int32)
    padded[0, :true_len] = toks[:true_len]
    return PREFILL(params, padded, np.int32(true_len), np.int32(0), row,
                   np.int32(slot), *(bank or _bank()), *SAMPLE)


def _row(true_len, ps=8, cap=96, first=1):
    n = -(-(true_len + 4) // ps)
    row = np.zeros(cap // ps, np.int32)
    row[:n] = np.arange(first, first + n)
    return row


@pytest.mark.parametrize("true_len", [70, 64, 33, 16, 5])
def test_prefill_in_chunks_seats_what_the_whole_prefill_seats(params,
                                                              true_len):
    """A prompt prefilled 16 tokens at a time through the stage (which
    starts out holding another prompt's leavings) seats the keys, values,
    tails and states the one-program prefill seats and samples the same
    first token."""
    toks = _prompts(true_len, [true_len])[0]
    row = _row(true_len)
    tok0, kv0, sv0 = _prefill_whole(params, toks, true_len, row)
    stage = jax.tree_util.tree_map(
        lambda a: a + 3, nemotron_h.init_prefill_stage(CFG, 96, CHUNK))
    done = 0
    while true_len - done > CHUNK:
        stage = PREFILL_CHUNK(params, toks[None, done:done + CHUNK],
                              np.int32(done), stage)
        done += CHUNK
    tail = np.zeros((1, CHUNK), np.int32)
    tail[0, :true_len - done] = toks[done:]
    tok1, kv1, sv1 = PREFILL_LAST(
        params, tail, np.int32(done), np.int32(true_len - done), stage,
        row, np.int32(1), *_bank(), *SAMPLE)
    assert int(tok0[0]) == int(tok1[0])
    assert int(sv1["lengths"][1]) == true_len == int(sv0["lengths"][1])
    live = row[:-(-true_len // 8)]
    for name in ("k", "v"):
        got = np.asarray(kv1[name][:, live]).reshape(1, -1, CFG.kv_width)
        want = np.asarray(kv0[name][:, live]).reshape(1, -1, CFG.kv_width)
        np.testing.assert_allclose(got[:, :true_len], want[:, :true_len],
                                   atol=1e-5)
    for name in ("conv", "ssm"):
        np.testing.assert_allclose(kv1[name][:, 1], kv0[name][:, 1],
                                   atol=1e-5, rtol=1e-5)
        assert float(jnp.abs(kv0[name][:, 1]).max()) > 0.1


@pytest.mark.parametrize("true_len", [34, 16])
def test_decode_through_the_pages_and_state_matches_one_full_forward(
        params, true_len):
    """Prefill then six decode steps through the pages and the Mamba
    state, teacher-forced: every step's logits equal the reference's
    full forward at that position; the slots that do not run keep their
    state bit for bit; the device-side counts are one token's."""
    toks = _prompts(7, [40])[0]
    row = _row(true_len + 6)
    table = np.zeros((3, 12), np.int32)
    table[1] = row
    _, kv, sv = _prefill_whole(params, toks, true_len, row)
    idle = {n: kv[n][:, 0] + 0 for n in ("conv", "ssm")}
    want = ref.logits(MODEL, params, jnp.asarray(toks))
    active = np.asarray([False, True, False])
    for t in range(true_len, true_len + 6):
        sv = dict(sv, tokens=sv["tokens"].at[1].set(int(toks[t])),
                  lengths=sv["lengths"].at[1].set(t))
        logits, kv, counts = DECODE_LOGITS(params, kv, sv, active, table)
        np.testing.assert_allclose(logits[1], want[t], atol=LOGIT_TOL)
        # one token, top-3, four expert layers
        np.testing.assert_array_equal(counts, [12, 12, 4])
    for n in ("conv", "ssm"):
        np.testing.assert_array_equal(kv[n][:, 0], idle[n])


def test_a_slot_seated_over_another_requests_state(params):
    """A prompt seated into a slot that held another request (its state,
    its tails, its pages' keys) decodes as it would from a fresh bank:
    the seating overwrites every kind of state whole."""
    toks, other = _prompts(12, [40, 40])
    table = np.zeros((3, 12), np.int32)
    table[2] = _row(30)
    _, kv, sv = _prefill_whole(params, other, 25, table[2], slot=2)
    for t in range(25, 28):                 # the other request runs
        sv = dict(sv, tokens=sv["tokens"].at[2].set(int(other[t])),
                  lengths=sv["lengths"].at[2].set(t))
        _, kv, _ = DECODE_LOGITS(params, kv, sv, np.asarray(
            [False, False, True]), table)
    _, kv, sv = _prefill_whole(params, toks, 20, table[2], slot=2,
                               bank=(kv, sv))
    _, fresh, fsv = _prefill_whole(params, toks, 20, table[2], slot=2)
    want = ref.logits(MODEL, params, jnp.asarray(toks))
    for t in range(20, 23):
        step = {}
        for name, (k, s) in (("over", (kv, sv)), ("fresh", (fresh, fsv))):
            s = dict(s, tokens=s["tokens"].at[2].set(int(toks[t])),
                     lengths=s["lengths"].at[2].set(t))
            step[name] = DECODE_LOGITS(params, k, s, np.asarray(
                [False, False, True]), table)
        kv, fresh = step["over"][1], step["fresh"][1]
        np.testing.assert_allclose(step["over"][0][2], step["fresh"][0][2],
                                   atol=1e-5)
        np.testing.assert_allclose(step["over"][0][2], want[t],
                                   atol=LOGIT_TOL)


# -- through the engine ----------------------------------------------------
@pytest.fixture(scope="module")
def served(params):
    """Two engines, one prefilling whole prompts and one in chunks of 16
    (with decode steps of the requests already running in between),
    each given the same six greedy requests over three slots (so slots
    are reused) and three sampled ones. {name: (engine, greedy prompts,
    their tokens, the sampled requests' tokens, the step counters'
    change)}."""
    greedy, sampled = _prompts(0, (30, 41, 17, 33, 64, 9)), \
        _prompts(11, (45, 20, 66))
    reg, out = telemetry.registry(), {}
    for name, kw in (("whole", ENGINE), ("chunked", CHUNKED)):
        eng = ServeEngine(CFG, params, **kw)
        before = [reg.value(c["name"]) for c in nemotron_h.STEP_COUNTS[:2]]
        g = [eng.submit(Request(prompt=p, max_new_tokens=12,
                                temperature=0.0)) for p in greedy]
        s = [eng.submit(Request(prompt=p, max_new_tokens=8, seed=i,
                                temperature=0.7, top_p=0.9))
             for i, p in enumerate(sampled)]
        got = eng.run()
        out[name] = (eng, greedy, [got[r] for r in g], [got[r] for r in s],
                     [reg.value(c["name"]) - b for c, b in
                      zip(nemotron_h.STEP_COUNTS[:2], before)])
    return out


def test_family_surface_and_state_bytes(served):
    assert serving_family(CFG) is nemotron_h
    assert {"prefix_cache", "speculate_k", "int8_pages", "submit_prefilled",
            "mesh"} == set(nemotron_h.SERVE_UNSUPPORTED)
    eng = served["whole"][0]
    kv = eng.kv_cache_stats()
    per_slot = 4 * (CFG.count("M") * CFG.mamba_heads * CFG.mamba_head_dim
                    * CFG.d_state + CFG.count("M") * (CFG.d_conv - 1)
                    * CFG.conv_dim)
    assert kv["state_bytes_per_slot"] == per_slot
    assert kv["reserved_bytes"] == eng.n_pages * 8 * 2 * CFG.kv_width * 4 \
        + 3 * per_slot
    assert kv["decode_attention"] == "gathered"


@pytest.mark.parametrize("name", ["whole", "chunked"])
def test_engine_run_matches_reference(params, served, name):
    """Prefill + 12 decode steps through ``ServeEngine.run()``: every
    emitted token is the reference's argmax at its position; the step
    counters moved by four expert layers' top-3 a token."""
    eng, prompts, tokens, _, (assigned, touched) = served[name]
    for p, toks in zip(prompts, tokens):
        assert len(toks) == 12
        assert _gaps(params, p, toks).max() <= GAP_TOL
    assert assigned % 12 == 0 and 12 * (66 + 21) <= assigned
    assert 0 < touched <= assigned


@pytest.mark.parametrize("kw", [ENGINE, CHUNKED])
def test_the_served_slot_holds_the_reference_recurrences_state(params, kw):
    """What ``ServeEngine.device_state`` shows after a greedy request on
    an idle bank: the first slot's first Mamba-2 layer holds the
    reference's recurrence, a token at a time, over the tokens the
    engine's prefill and decode programs folded in (the device's length
    says how many); the slots that never ran hold nothing."""
    prompt = _prompts(21, [41])[0]
    eng = ServeEngine(CFG, params, **kw)
    rid = eng.submit(Request(prompt=prompt, max_new_tokens=6,
                             temperature=0.0))
    toks = np.concatenate([prompt, eng.run()[rid]])
    state = eng.device_state()
    assert {"k", "v", "conv", "ssm", "lengths", "tokens", "rngs"} == \
        set(state)
    n = int(state["lengths"][0])
    assert 41 + 5 <= n <= 41 + 6
    w = {name: a[0] for name, a in params["mamba"].items()}
    h = ref._rms(params["tok_embed"][toks[:n]], w["norm"], CFG.norm_eps)
    x, dt, B, C, _ = ref.mamba_inputs(w, h, CFG.n_groups, CFG.d_state,
                                      CFG.d_conv)
    want, _ = ref.ssm_scan(x, dt, -jnp.exp(w["A_log"]), B, C)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(state["ssm"][0, 0], want, atol=1e-5,
                               rtol=1e-5)
    assert not np.asarray(state["ssm"][:, 1:]).any()


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
    assert 'kind="ssm"' in prom and 'kind="kv_pages"' in prom
    assert "mxtpu_serve_moe_assignments_total" in prom
    for i, p in enumerate(prompts):
        assert results[i]["status"] == 200, results[i]
        assert len(results[i]["tokens"]) == 8
        assert _gaps(params, p, results[i]["tokens"]).max() <= GAP_TOL


@pytest.mark.parametrize("option,word", [
    ({"prefix_cache": True}, "snapshot of the Mamba-2"),
    ({"speculate_k": 2}, "rolled back"),
    ({"int8_pages": True}, "quantised")])
def test_engine_refuses_what_it_cannot_do(params, option, word):
    with pytest.raises(ValueError, match="nemotron_h family.*" + word):
        ServeEngine(CFG, params, **{**ENGINE, **option})


@pytest.mark.parametrize("kw,word", [
    (dict(prefill_chunk=40), "divides the slot's capacity"),
    (dict(max_len=120, prefill_chunk=12), "SSD chunks"),
])
def test_engine_refuses_a_chunk_that_does_not_fit(params, kw, word):
    with pytest.raises(ValueError, match=word):
        ServeEngine(CFG, params, **{**ENGINE, **kw})
