"""The SambaY family (``mxtpu/models/sambay.py``: Mamba, sliding-window,
full, gated-memory and cross-attention layers, differential attention)
against its plain reference (``benchmark/grid/reference/sambay.py``:
float32, no cache, a ``lax.scan`` over time, two softmaxes per pair of
heads), and through the paged ``ServeEngine``.

Toy widths with every kind of layer present (``CONFIGS["tiny"]``: 3
Mamba+window pairs, the Mamba and full layers "6/7", 2 GMU+cross pairs;
window 8), float32 under conftest's ``highest`` matmul precision, and
prompts longer than three windows. Every comparison is of LOGITS: where
the engine hands back tokens only, each greedy token's reference logit
is held against the reference's maximum at that position
(``argmax_gaps``), which is 0 unless the engine's logits part from the
reference's by more than the gap between the two largest.
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
from mxtpu.models import sambay, serving_family
from mxtpu.ops.attention import dense_attention, window_attention
from mxtpu.ops.ssm import selective_scan, selective_scan_step
from mxtpu.serve import Request, ServeEngine
from mxtpu.serve.engine import KVHandoff
from mxtpu.serve.gateway import Gateway, GatewayClient

CFG = sambay.CONFIGS["tiny"]
MODEL = {"num_hidden_layers": CFG.n_layers,
         "num_attention_heads": CFG.n_heads,
         "num_key_value_heads": CFG.n_kv_heads,
         "sliding_window": CFG.sliding_window,
         "layer_norm_eps": CFG.norm_eps, "tie_word_embeddings": True,
         "vocab_size": CFG.vocab_size}
# float32 against float32 at highest precision: the two differ in the
# order of their sums only (chunked scan, online softmax, fused gate/up).
# Logits spread about 1; the largest difference seen is 2e-5
LOGIT_TOL = 2e-4
# an emitted token's reference logit under the reference's maximum: 0
# unless two logits lie within LOGIT_TOL of each other
GAP_TOL = 2 * LOGIT_TOL
ENGINE = dict(max_slots=3, max_len=96, min_bucket=16, page_size=8)


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "grid", "reference", "sambay.py")
    spec = importlib.util.spec_from_file_location("grid_ref_sambay", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def _weights(seed):
    """Random weights with the norms' weights and biases, the
    convolution's bias and ``D`` moved off their initial 1 and 0, so a
    layer that dropped one of them would show."""
    params = sambay.init_params(CFG, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 200))

    def move(path, a):
        name = path[-1].key
        if name.startswith(("norm", "final_norm", "conv_b", "D",
                            "subln")):
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


# -- the operators -----------------------------------------------------------
@pytest.mark.parametrize("chunk", [1, 5, 8, 64])
def test_chunked_scan_equals_stepwise_with_carried_state(chunk):
    """The prefill's chunked scan from a non-empty state equals the
    decode step applied token by token, in outputs and in the state
    handed on; run in two halves with the state carried it equals one
    run."""
    b, s, d, n = 2, 29, 12, 4
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    u = jax.random.normal(k[0], (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, d)))
    A = -jnp.exp(jax.random.normal(k[2], (d, n)))
    B, C = (jax.random.normal(kk, (b, s, n)) for kk in k[3:5])
    D = jax.random.normal(k[5], (d,))
    state0 = jax.random.normal(k[6], (b, d, n))

    state, ys = state0, []
    for t in range(s):
        y, state = selective_scan_step(state, u[:, t], dt[:, t], A,
                                       B[:, t], C[:, t], D)
        ys.append(y)
    y_all, state_all = selective_scan(u, dt, A, B, C, D, state0,
                                      chunk=chunk)
    np.testing.assert_allclose(y_all, jnp.stack(ys, 1), atol=1e-5)
    np.testing.assert_allclose(state_all, state, atol=1e-5)
    h = 13
    y1, mid = selective_scan(u[:, :h], dt[:, :h], A, B[:, :h], C[:, :h],
                             D, state0, chunk=chunk)
    y2, end = selective_scan(u[:, h:], dt[:, h:], A, B[:, h:], C[:, h:],
                             D, mid, chunk=chunk)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_all,
                               atol=1e-5)
    np.testing.assert_allclose(end, state_all, atol=1e-5)


@pytest.mark.parametrize("held,lo,hi", [(jnp.float32, 0.0, 1e-6),
                                        (jnp.bfloat16, 1e-3, 1e-1)])
def test_scan_state_against_the_reference_recurrence(held, lo, hi):
    """The benchmark's second limit (``check.scan_tol``): the program's
    scan hands on the reference recurrence's state when both hold it in
    float32 (the same arithmetic: under 1e-6 of its norm), and a state
    rounded to bfloat16 between steps lies units of 1e-3 away, which no
    emitted token shows. Step sizes as Mamba initialises them, 600
    steps, float32 inputs."""
    s, d, n = 600, 48, 16
    f32 = jnp.float32            # explicit: conftest turns x64 on
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    u = jax.random.normal(k[0], (1, s, d), f32)
    B, C = (jax.random.normal(kk, (1, s, n), f32) for kk in k[1:3])
    step = jnp.exp(jax.random.uniform(k[3], (d,), f32) * np.log(100.0)
                   + np.log(1e-3)).astype(f32)
    dt = jax.nn.softplus(jax.random.normal(k[4], (1, s, d), f32)
                         + step + jnp.log(-jnp.expm1(-step)))
    A = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=f32), (d, n))
    _, got = selective_scan(u, dt, A, B, C, jnp.ones((d,), f32),
                            jnp.zeros((1, d, n), f32), chunk=8)
    want, _ = ref.selective_scan(dt[0], u[0], A, B[0], C[0], held=held)
    exact, _ = ref.selective_scan(dt[0], u[0], A, B[0], C[0])
    gap = float(jnp.linalg.norm(got[0] - want) / jnp.linalg.norm(exact))
    assert lo <= gap <= hi, gap


@pytest.mark.parametrize("window,block,s", [(8, 8, 40), (5, 4, 37),
                                            (16, 4, 32), (64, 8, 24)])
def test_window_attention_matches_masked_dense(window, block, s):
    """Blockwise window attention (which reads only the key blocks a
    query block's window reaches) equals dense attention under the same
    mask, with grouped KV heads and a value width of its own."""
    k = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(k[0], (2, 4, s, 6))
    kk = jax.random.normal(k[1], (2, 2, s, 6))
    v = jax.random.normal(k[2], (2, 2, s, 10))
    at = jnp.arange(s)
    mask = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
    want = dense_attention(q, kk, v, mask=mask[None, None])
    got = window_attention(q, kk, v, window=window, block=block)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("k_start", [0, 8, 13])
def test_window_attention_with_keys_in_front(k_start):
    """A chunk's window attention: a window's worth of keys in front of
    the chunk's own, of which those before ``k_start`` are not seen
    (at a prompt's start: all of them)."""
    W, s = 8, 24
    k = jax.random.split(jax.random.PRNGKey(k_start), 3)
    q = jax.random.normal(k[0], (1, 4, W + s, 6))
    kk = jax.random.normal(k[1], (1, 2, W + s, 6))
    v = jax.random.normal(k[2], (1, 2, W + s, 10))
    at = jnp.arange(W + s)
    mask = ((at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - W)
            & (at[None, :] >= k_start))
    want = dense_attention(q, kk, v, mask=mask[None, None])
    got = window_attention(q, kk, v, window=W, block=8, k_start=k_start)
    np.testing.assert_allclose(got[:, :, W:], want[:, :, W:], atol=1e-5)


# -- the model against the reference -----------------------------------------
@pytest.mark.parametrize("seed", [1, 2])
def test_forward_matches_reference_logits(seed):
    """Every layer on every position, 40 positions (five windows):
    logits within LOGIT_TOL of the plain reference's."""
    params = _weights(seed)
    toks = _prompts(seed, [40])[0]
    got = jax.jit(lambda p, t: sambay.forward(CFG, p, t))(
        params, toks[None])[0]
    want = ref.logits(MODEL, params, jnp.asarray(toks))
    assert float(jnp.abs(want).max()) > 2.0      # logits spread about 1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


@pytest.mark.parametrize("true_len", [40, 29, 3])
def test_prefill_on_last_position_equals_everywhere(params, true_len):
    """The prefill program's logits — cross-decoder on position
    true_len - 1 alone, prompt END-padded to its bucket — equal the
    full forward's at that position (and the reference's)."""
    toks = _prompts(3, [40])[0]
    got, *_ = jax.jit(lambda p, t, n: sambay.prefill_logits(CFG, p, t, n))(
        params, toks[None], true_len)
    everywhere = jax.jit(lambda p, t: sambay.forward(CFG, p, t))(
        params, toks[None, :true_len])[0, -1]
    np.testing.assert_allclose(got[0], everywhere, atol=LOGIT_TOL)
    want = ref.logits(MODEL, params, jnp.asarray(toks[:true_len]),
                      rows=jnp.asarray([true_len - 1]))[0]
    np.testing.assert_allclose(got[0], want, atol=LOGIT_TOL)


CHUNK = 16          # two windows


@pytest.mark.parametrize("true_len", [70, 64, 33, 16, 5])
def test_prefill_in_chunks_seats_what_the_whole_prefill_seats(params,
                                                              true_len):
    """A prompt prefilled 16 tokens at a time through the stage (which
    starts out holding another prompt's leavings) seats the state the
    one-program prefill seats — scan state, convolution tail, rings,
    the full layer's pages — and samples the same first token: the scan
    state is carried from chunk to chunk, a window layer reads the ring
    in front of its chunk, the full layer the chunks before it."""
    cap, ps, n = 96, 8, -(-(true_len + 4) // 8)
    toks = _prompts(true_len, [true_len])[0]
    row = np.zeros(cap // ps, np.int32)
    row[:n] = np.arange(1, n + 1)
    end = (row, np.int32(1))
    sample = (jax.random.PRNGKey(3), np.float32(0.0),
              np.int32(CFG.vocab_size), np.float32(1.0))

    def bank():
        kv = sambay.init_paged_cache(CFG, 3, 40, ps)
        return kv, {m: kv.pop(m) for m in ("lengths", "tokens", "rngs")}

    whole = np.zeros((1, cap), np.int32)
    whole[0, :true_len] = toks
    tok0, kv0, sv0 = jax.jit(partial(sambay.prefill_slot_paged, CFG))(
        params, whole, np.int32(true_len), np.int32(0), *end, *bank(),
        *sample)
    stage = jax.tree_util.tree_map(
        lambda a: a + 3, sambay.init_prefill_stage(CFG, cap, CHUNK))
    chunk = jax.jit(partial(sambay.prefill_slot_paged_chunk, CFG))
    done = 0
    while true_len - done > CHUNK:
        stage = chunk(params, toks[None, done:done + CHUNK], np.int32(done),
                      stage)
        done += CHUNK
    tail = np.zeros((1, CHUNK), np.int32)
    tail[0, :true_len - done] = toks[done:]
    tok1, kv1, sv1 = jax.jit(partial(sambay.prefill_slot_paged_last, CFG))(
        params, tail, np.int32(done), np.int32(true_len - done), stage,
        *end, *bank(), *sample)
    assert int(tok0[0]) == int(tok1[0])
    np.testing.assert_array_equal(sv0["lengths"], sv1["lengths"])
    names = ["conv", "ssm"] + (["wk", "wv"]      # a ring no position
                               if true_len >= CFG.sliding_window else [])
    for name in names:                           # reached is not counted
        np.testing.assert_allclose(kv1[name][:, 1], kv0[name][:, 1],
                                   atol=1e-4, err_msg=name)
    for name in ("k", "v"):
        got, want = (kv[name][0, row[:n]].reshape(n * ps, -1)[:true_len]
                     for kv in (kv1, kv0))
        np.testing.assert_allclose(got, want, atol=1e-4, err_msg=name)


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_programs_gather_once_and_cross_decode_one_position(params):
    """The decode program gathers the shared pool once a step (one
    gather of K, one of V, whatever the number of cross layers), and the
    prefill program's cross-decoder scan carries ONE position."""
    eng = dict(ENGINE)
    state = sambay.init_paged_cache(CFG, eng["max_slots"], 37,
                                    eng["page_size"])
    sv = {n: state.pop(n) for n in ("lengths", "tokens", "rngs")}
    S = eng["max_slots"]
    table = jnp.zeros((S, 12), jnp.int32)
    dec = jax.make_jaxpr(lambda p, kv: sambay.decode_slots_paged(
        CFG, p, kv, sv, jnp.ones(S, bool), table, jnp.zeros(S),
        jnp.full(S, CFG.vocab_size), jnp.ones(S)))(params, state)
    pool = state["k"].shape
    gathers = [e for e in _eqns(dec.jaxpr) if e.primitive.name == "gather"
               and e.invars[0].aval.shape == pool]
    assert len(gathers) == 2
    pre = jax.make_jaxpr(lambda p, t: sambay.prefill_logits(
        CFG, p, t, 30))(params, jnp.zeros((1, 32), jnp.int32))
    scans = [e for e in pre.jaxpr.eqns if e.primitive.name == "scan"]
    carries = [tuple(v.aval.shape for v in e.outvars[:e.params["num_carry"]])
               for e in scans]
    # the pairs' scan carries the whole prompt, the cross pairs' one row
    assert ((1, 32, CFG.dim),) in carries
    assert ((1, 1, CFG.dim),) in carries
    # the same of a chunked prompt's last chunk
    stage = sambay.init_prefill_stage(CFG, 96, 16)
    last = jax.make_jaxpr(lambda p, t, st, kv: sambay.prefill_slot_paged_last(
        CFG, p, t, 32, 9, st, table[0], 1, kv, sv, jax.random.PRNGKey(0),
        0.0, CFG.vocab_size, 1.0))(
            params, jnp.zeros((1, 16), jnp.int32), stage, state)
    carries = [tuple(v.aval.shape for v in e.outvars[:e.params["num_carry"]])
               for e in last.jaxpr.eqns if e.primitive.name == "scan"]
    assert ((1, 16, CFG.dim),) in carries and ((1, 1, CFG.dim),) in carries


def _agent_shapes(dtype=jnp.bfloat16, **widths):
    """(cfg, kv state as shapes): Phi-4-mini-flash's attention widths
    (40 query / 20 KV heads of 64, read as 40 over 10 paired heads of
    128) at eight layers, a pool of 16-token pages under 8 slots (a
    whole block of the sampler's kernel)."""
    from dataclasses import replace
    cfg = replace(sambay.SambaYConfig(n_layers=8, max_seq_len=256,
                                      vocab_size=512, hidden_dim=256),
                  dtype=dtype, param_dtype=dtype, **widths)
    state = jax.eval_shape(lambda: sambay.init_paged_cache(cfg, 8, 65, 16))
    return cfg, {n: a for n, a in state.items()
                 if n not in ("lengths", "tokens", "rngs")}


@pytest.mark.parametrize("case,path", [
    ("agent_cell_on_a_tpu", "pages"), ("float32_pool", "gathered"),
    ("mesh", "gathered"), ("cpu_backend", "gathered"),
    ("paired_heads_of_64_lanes", "gathered")])
def test_decode_attention_path_is_read_off_the_inputs(monkeypatch, case,
                                                      path):
    """Backend, shapes and dtypes decide, statically, as for llama: on a
    TPU over bfloat16 pools whose paired heads are whole lane tiles the
    full layer and the cross layers' scan each hold ONE call of the rows
    kernel and nothing gathers the pool; everywhere else the pool is
    gathered once for K and once for V and no kernel is traced (but the
    sampler's: its threshold search is ONE kernel call on a TPU, whatever
    the pool, and none under a mesh)."""
    from mxtpu.ops.paged_attention import ROWS_KERNEL_NAME
    from mxtpu.ops.threshold import KERNEL_NAME as SEARCH
    if case != "cpu_backend":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, kv = _agent_shapes(
        jnp.float32 if case == "float32_pool" else jnp.bfloat16,
        **(dict(dim=1280) if case == "paired_heads_of_64_lanes" else {}))
    mesh = None
    if case == "mesh":
        from mxtpu.parallel import create_mesh
        mesh = create_mesh(tp=2, devices=jax.devices()[:2])
    assert sambay.decode_attention_path(cfg, kv, mesh) == path
    assert sambay.decode_attention_path(cfg, kv, mesh, verify=True) == path
    S = kv["wk"].shape[1]
    abstract = jax.ShapeDtypeStruct
    sv = {"lengths": abstract((S,), jnp.int32),
          "tokens": abstract((S,), jnp.int32),
          "rngs": abstract((S, 2), jnp.uint32)}
    jaxpr = jax.make_jaxpr(partial(sambay.decode_slots_paged, cfg,
                                   mesh=mesh))(
        jax.eval_shape(partial(sambay.init_params, cfg),
                       jax.random.PRNGKey(0)),
        kv, sv, abstract((S,), jnp.bool_), abstract((S, 16), jnp.int32),
        abstract((S,), jnp.float32), abstract((S,), jnp.int32),
        abstract((S,), jnp.float32))
    eqns = list(_eqns(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    walks = [e for e in kernels if e.params["name"] != SEARCH]
    assert len(kernels) - len(walks) == (
        case != "cpu_backend" and mesh is None)
    gathers = [e for e in eqns if e.primitive.name == "gather"
               and e.invars[0].aval.shape == kv["k"].shape]
    if path == "pages":
        assert len(walks) == 2 and not gathers
        assert all(e.params["name"] == ROWS_KERNEL_NAME
                   for e in walks)
        # the pools as they are stored are the kernel's own operands
        assert all([v.aval.shape for v in e.invars[-2:]]
                   == 2 * [kv["k"].shape] for e in walks)
    else:
        assert not walks and len(gathers) == 2


def test_engine_names_the_rows_kernel_on_a_tpu(monkeypatch):
    """``kv_cache_stats()`` carries the family's word for this family
    too (construction compiles nothing)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, _ = _agent_shapes()
    p = jax.jit(partial(sambay.init_params, cfg))(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, p, max_slots=2, max_len=256, min_bucket=128,
                      page_size=16)
    assert eng.kv_cache_stats()["decode_attention"] == "pages"


# -- through the engine ------------------------------------------------------
def test_engine_picks_the_family_from_the_config(params, serve_cfg):
    from mxtpu.models import llama
    assert serving_family(CFG) is sambay
    assert serving_family(serve_cfg) is llama
    eng = ServeEngine(CFG, params, **ENGINE)
    kv = eng.kv_cache_stats()
    tok = 2 * CFG.n_kv_heads * CFG.head_dim * 4       # one layer, f32
    per_slot = (2 * CFG.n_pairs * CFG.sliding_window * CFG.n_kv_heads
                * CFG.head_dim * 4
                + (CFG.n_pairs + 1) * CFG.d_inner
                * (CFG.d_state + CFG.d_conv - 1) * 4)
    assert kv["state_bytes_per_slot"] == per_slot
    assert kv["reserved_bytes"] == (eng.n_pages * 8 * tok
                                    + ENGINE["max_slots"] * per_slot)
    text = telemetry.prometheus()
    for kind in ("kv_pages", "window_ring", "ssm"):
        assert (f'mxtpu_serve_state_bytes{{engine="{eng.engine_id}",'
                f'kind="{kind}"}}') in text, kind


def test_engine_run_matches_reference(params):
    """Prefill + 12 decode steps through ``ServeEngine.run()``: six
    requests over three slots (so slots are reused and hold requests of
    different ages in one step), prompts up to eight windows long. Every
    emitted token is the reference's argmax at its position."""
    eng = ServeEngine(CFG, params, **ENGINE)
    prompts = _prompts(0, (30, 41, 17, 33, 64, 9))
    rids = [eng.submit(Request(prompt=p, max_new_tokens=12,
                               temperature=0.0)) for p in prompts]
    out = eng.run()
    for p, rid in zip(prompts, rids):
        assert len(out[rid]) == 12
        assert _gaps(params, p, out[rid]).max() <= GAP_TOL
    # one decode program, one prefill program per bucket, copy_page unused
    assert eng.compile_count == 1 + eng.n_buckets


def test_gateway_matches_reference(params):
    """The same through ``Gateway.start_http``: streamed tokens of four
    concurrent requests, each the reference's argmax."""
    gw = Gateway(lambda: ServeEngine(CFG, params, **ENGINE),
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
    assert 'mxtpu_serve_state_bytes{' in prom and 'kind="ssm"' in prom
    for i, p in enumerate(prompts):
        assert results[i]["status"] == 200, results[i]
        assert len(results[i]["tokens"]) == 8
        assert _gaps(params, p, results[i]["tokens"]).max() <= GAP_TOL


def _stream(eng, prompt, n, seed, arrival_step=0, **sampling):
    return eng.submit(Request(prompt=prompt, max_new_tokens=n, seed=seed,
                              arrival_step=arrival_step, **sampling))


def test_reused_slot_gives_what_a_fresh_engine_gives(params):
    """A one-slot engine serves a long request and then a short one in
    the SAME slot: the second request's sampled stream is what a fresh
    engine gives it. The prefill overwrites all of the slot's state —
    rings, convolution tail, scan state — not only the part it fills."""
    long_, short = _prompts(7, (70, 5))
    kw = dict(ENGINE, max_slots=1)
    sampling = dict(temperature=0.8, top_k=20)
    used = ServeEngine(CFG, params, **kw)
    first = _stream(used, long_, 10, seed=1, **sampling)
    second = _stream(used, short, 10, seed=2, **sampling)
    out = used.run()
    fresh = ServeEngine(CFG, params, **kw)
    alone = _stream(fresh, short, 10, seed=2, **sampling)
    np.testing.assert_array_equal(out[second], fresh.run()[alone])
    assert len(out[first]) == 10


def test_slots_of_mixed_ages_in_one_step(params):
    """Requests that arrive at different steps share decode steps with
    slots far ahead of them; each one's stream is what it is alone."""
    prompts = _prompts(9, (40, 12, 25))
    sampling = dict(temperature=0.7, top_p=0.9)
    eng = ServeEngine(CFG, params, **ENGINE)
    rids = [_stream(eng, p, 9, seed=i, arrival_step=4 * i, **sampling)
            for i, p in enumerate(prompts)]
    together = eng.run()
    for i, p in enumerate(prompts):
        alone = ServeEngine(CFG, params, **ENGINE)
        rid = _stream(alone, p, 9, seed=i, **sampling)
        np.testing.assert_array_equal(together[rids[i]], alone.run()[rid])


@pytest.mark.parametrize("option,word", [
    ({"prefix_cache": True}, "snapshot"),
    ({"speculate_k": 2}, "rolled back"),
    ({"int8_pages": True}, "quantised")])
def test_engine_refuses_what_it_cannot_do(params, option, word):
    with pytest.raises(ValueError, match="sambay family.*" + word):
        ServeEngine(CFG, params, **{**ENGINE, **option})


def test_engine_refuses_a_prefilled_handoff(params):
    eng = ServeEngine(CFG, params, **ENGINE)
    assert eng.prefix_cache_enabled is False     # the default, not asked
    z = np.zeros((CFG.n_layers, CFG.n_kv_heads, 16, CFG.head_dim),
                 np.float32)
    handoff = KVHandoff(k=z, v=z, true_len=9, token=1,
                        rng=np.zeros(2, np.uint32))
    with pytest.raises(ValueError, match="submit_prefilled.*hand-off"):
        eng.submit_prefilled(handoff, Request(
            prompt=np.arange(9), max_new_tokens=2))


# -- a prompt prefilled in chunks ---------------------------------------------
CHUNKED = dict(ENGINE, prefill_chunk=CHUNK)


def test_engine_chunked_prefill_matches_reference(params):
    """``test_engine_run_matches_reference`` with every prompt prefilled
    16 tokens at a time (1 to 4 chunks), decode steps of the requests
    already running in between: every emitted token is the reference's
    argmax at its position. Two prefill programs, whatever the lengths."""
    eng = ServeEngine(CFG, params, **CHUNKED)
    prompts = _prompts(0, (30, 41, 17, 33, 64, 9))
    rids = [eng.submit(Request(prompt=p, max_new_tokens=12,
                               temperature=0.0)) for p in prompts]
    out = eng.run()
    for p, rid in zip(prompts, rids):
        assert len(out[rid]) == 12
        assert _gaps(params, p, out[rid]).max() <= GAP_TOL
    assert eng.n_buckets == 2 and eng.compile_count == 3


def test_gateway_chunked_prefill_matches_reference(params):
    gw = Gateway(lambda: ServeEngine(CFG, params, **CHUNKED),
                 n_replicas=1, queue_max=16)
    prompts = _prompts(6, (50, 35, 16, 71))
    try:
        port = gw.start_http(port=0)
        results = [GatewayClient("127.0.0.1", port).generate(
            p, 6, seed=i, temperature=0.0) for i, p in enumerate(prompts)]
    finally:
        gw.close()
    for p, r in zip(prompts, results):
        assert r["status"] == 200 and len(r["tokens"]) == 6, r
        assert _gaps(params, p, r["tokens"]).max() <= GAP_TOL


def _emissions(eng, jobs):
    """Submit ``jobs`` (name, prompt, new tokens, arrival step), run,
    and return (the names in the order their tokens were emitted,
    {name: tokens})."""
    order, rids = [], {}
    for name, prompt, n, step in jobs:
        rids[name] = eng.submit(Request(
            prompt=prompt, max_new_tokens=n, temperature=0.0,
            arrival_step=step,
            on_token=lambda rid, tok, name=name: order.append(name)))
    out = eng.run()
    return "".join(order), {name: out[rid] for name, rid in rids.items()}


def test_a_running_request_stalls_one_chunk_at_a_time(params):
    """A 64-token prompt (four chunks) that arrives while another
    request runs: the running request goes on emitting, one token per
    chunk, where an unchunked prefill holds it for the whole prompt."""
    a, b = _prompts(3, (10, 64))
    jobs = [("a", a, 12, 0), ("b", b, 4, 3)]
    order, out = _emissions(ServeEngine(CFG, params, **CHUNKED), jobs)
    whole, _ = _emissions(ServeEngine(CFG, params, **ENGINE), jobs)
    assert order.index("b") - whole.index("b") == 3, (order, whole)
    assert _gaps(params, a, out["a"]).max() <= GAP_TOL
    assert _gaps(params, b, out["b"]).max() <= GAP_TOL


def test_an_empty_bank_prefills_before_it_decodes(params):
    """Three prompts of three chunks waiting and nothing running:
    chunks run back to back until the running requests are as many as
    the waiting ones (two seated before the first decode step), then
    one to a step: the third prompt's first token comes with the fourth
    step's tokens."""
    prompts = _prompts(4, (40, 40, 40))
    order, out = _emissions(
        ServeEngine(CFG, params, **CHUNKED),
        [(name, p, 6, 0) for name, p in zip("abc", prompts)])
    assert order.startswith("abababab" + "c"), order
    for name, p in zip("abc", prompts):
        assert _gaps(params, p, out[name]).max() <= GAP_TOL


def test_cancel_in_the_middle_of_a_chunked_prefill(params):
    """A request cancelled between two of its chunks gives its slot and
    pages back, and the request seated there next gives what a fresh
    engine gives it (the stage starts over)."""
    a, b, c = _prompts(8, (10, 70, 37))
    eng = ServeEngine(CFG, params, **dict(CHUNKED, max_slots=2))
    seen = []

    def on_a(rid, tok):
        seen.append(tok)
        if len(seen) == 4:           # b has run two of its five chunks
            eng.cancel(rb)
    eng.submit(Request(prompt=a, max_new_tokens=10, temperature=0.0,
                       on_token=on_a))
    rb = eng.submit(Request(prompt=b, max_new_tokens=5, temperature=0.0,
                            arrival_step=2))
    rc = eng.submit(Request(prompt=c, max_new_tokens=5, temperature=0.0,
                            arrival_step=3))
    out = eng.run()
    assert len(out[rb]) == 0 and len(seen) == 10
    assert _gaps(params, c, out[rc]).max() <= GAP_TOL
    assert eng._pages.used_pages == 0 and not eng._prefilling.any()


def test_chunked_and_whole_prefill_sample_the_same_stream(params):
    prompts = _prompts(11, (45, 20, 66))
    sampling = dict(temperature=0.7, top_p=0.9)
    outs = []
    for kw in (ENGINE, CHUNKED):
        eng = ServeEngine(CFG, params, **kw)
        rids = [_stream(eng, p, 8, seed=i, **sampling)
                for i, p in enumerate(prompts)]
        out = eng.run()
        outs.append([out[r] for r in rids])
    for whole, chunked in zip(*outs):
        np.testing.assert_array_equal(whole, chunked)


@pytest.mark.parametrize("kw,word", [
    (dict(prefill_chunk=12), "multiple of the sliding window"),
    (dict(prefill_chunk=64), "divides the slot's capacity"),
])
def test_engine_refuses_a_chunk_that_does_not_fit(params, kw, word):
    with pytest.raises(ValueError, match=word):
        ServeEngine(CFG, params, **{**ENGINE, **kw})


def test_engine_refuses_chunked_prefill_for_llama(serve_cfg):
    from mxtpu.models import llama
    with pytest.raises(ValueError, match="prefill_chunk needs.*sambay"):
        ServeEngine(serve_cfg, llama.init_params(serve_cfg),
                    max_slots=2, max_len=64, prefill_chunk=16)
