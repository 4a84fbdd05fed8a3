"""The block-diffusion, routed-expert family (``mxtpu/models/
blockdiff_moe.py``: Qwen3-MoE layers under a block-causal mask, each
block of the answer denoised from ``[MASK]`` by confidence) against its
plain reference (``benchmark/grid/reference/blockdiff_moe.py``: float32,
no cache, every pass one full forward, every expert on every token), and
through the paged ``ServeEngine``, whose step yields no token or a block
a slot.

Toy widths (``CONFIGS["tiny"]``: 8 query heads over 2 KV heads of 16, 16
experts top-4, three layers, a block of 4 in 4 steps), float32 under
conftest's ``highest`` matmul precision: the engine takes the argmax and
the most confident position itself, so its streams equal the reference
loop's token for token, and where logits are at hand they are compared.
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
from mxtpu.models import blockdiff_moe as bd
from mxtpu.models import llama, serving_family
from mxtpu.ops.attention import (block_causal_rows_attention,
                                 block_decode_path, paged_block_attention)
from mxtpu.parallel import moe
from mxtpu.serve import Request, ServeEngine
from mxtpu.serve.engine import KVHandoff
from mxtpu.serve.gateway import Gateway, GatewayClient

CFG = bd.CONFIGS["tiny"]
B = CFG.block_length


def model_of(cfg):
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "num_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": False, "vocab_size": cfg.vocab_size,
            "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps,
            "remasking": cfg.remasking,
            "confidence_threshold": cfg.confidence_threshold,
            "mask_token_id": cfg.mask_token_id}


MODEL = model_of(CFG)
LOGIT_TOL = 1e-4
ENGINE = dict(max_slots=2, max_len=64, min_bucket=8, page_size=8)
CHUNKED = dict(ENGINE, prefill_chunk=16)


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "grid", "reference", "blockdiff_moe.py")
    spec = importlib.util.spec_from_file_location("grid_ref_blockdiff_moe",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


@pytest.fixture(scope="module")
def params():
    return bd.init_params(CFG, jax.random.PRNGKey(1))


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.mask_token_id, n).tolist() for n in lengths]


def _metric(name):
    """A series' value summed over its labels, from the registry's
    exposition."""
    return sum(float(ln.split()[-1]) for ln in
               telemetry.prometheus().splitlines()
               if ln.startswith(name + "{") or ln.startswith(name + " "))


def _serve(params, prompts, news, cfg=CFG, **engine):
    eng = ServeEngine(cfg, params, **{**ENGINE, **engine})
    rids = [eng.submit(Request(prompt=p, max_new_tokens=n))
            for p, n in zip(prompts, news)]
    out = eng.run()
    return eng, [out[r].tolist() for r in rids]


# -- the router and the forward ------------------------------------------------
@pytest.mark.parametrize("renorm", [True, False])
def test_route_softmax_against_the_reference(renorm):
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(4), (64, 16), jnp.bfloat16) / 8
    idx, wts = moe.route_softmax(x, w, top_k=4, renorm=renorm)
    choice, dense = ref.route(x.astype(jnp.float32), w.astype(jnp.float32),
                              4, renorm)
    assert idx.dtype == jnp.int32 and wts.dtype == jnp.float32
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(choice, -1))
    got = jnp.zeros_like(dense).at[jnp.arange(40)[:, None], idx].set(wts)
    np.testing.assert_allclose(got, dense, atol=1e-6)
    if renorm:
        np.testing.assert_allclose(wts.sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("seed,n", [(1, 22), (2, 37)])
def test_block_causal_forward_against_the_reference(params, seed, n):
    toks = jnp.asarray(_prompts(seed, [n]))
    got = bd.forward(CFG, params, toks)[0]
    want = ref.logits(MODEL, params, toks[0])
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)
    # position i sees position j iff j // B <= i // B: a token changed
    # in block 2 moves every row from block 2 on and none before
    other = bd.forward(CFG, params, toks.at[0, 2 * B + 1].set(3))[0]
    moved = np.abs(np.asarray(other - got)).max(-1)
    assert not moved[:2 * B].any() and (moved[2 * B:] > 0).all()


def test_each_layer_alone_matches_the_reference_layer(params):
    toks = jnp.asarray(_prompts(5, [26]))
    streams = bd.layer_streams(CFG, params, toks)[:, 0]
    picks = []
    for i in range(CFG.n_layers):
        want = ref.layer(MODEL, params, i, streams[i], picks=picks)
        np.testing.assert_allclose(streams[i + 1], want, atol=LOGIT_TOL)
    mine = np.sort(np.asarray(bd.router_picks(CFG, params, toks)), -1)
    np.testing.assert_array_equal(mine, np.sort(np.stack(picks), -1))


def test_prefill_attention_is_block_causal_at_an_offset():
    rng = np.random.default_rng(9)
    H, G, hd, s, start = 4, 2, 16, 8, 8
    k = jnp.asarray(rng.standard_normal((1, 1, 32, G * hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 32, G * hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, H, s, hd)), jnp.float32)
    got = block_causal_rows_attention(q, k, v, layer=0, q_offset=start,
                                      block=B, kv_block=16)
    kh = k[0, 0].reshape(32, G, hd).repeat(H // G, 1)
    vh = v[0, 0].reshape(32, G, hd).repeat(H // G, 1)
    sc = jnp.einsum("hqd,khd->hqk", q[0], kh) / np.sqrt(hd)
    seen = (np.arange(32)[None] // B) <= ((start + np.arange(s))[:, None]
                                          // B)
    p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
    np.testing.assert_allclose(got[0], jnp.einsum("hqk,khd->hqd", p, vh),
                               atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_block_pages_kernel_matches_the_gathered_path(dtype):
    """A block of query rows a slot rides through the rows walk
    (interpreted here) as further query heads of their KV head: the
    gathered path's numbers up to the order of summation; zeros for a
    slot of length 0; nothing of a page past the length (NaN there)."""
    from mxtpu.ops.paged_attention import (paged_attention_block,
                                           takes_block)
    S, H, G, hd, per_slot, ps, L = 4, 8, 2, 128, 6, 16, 2
    rng = np.random.default_rng(33)
    nan_page = 1 + S * per_slot
    shape = (L, nan_page + 1, ps, G * hd)
    pools = []
    for _ in range(2):
        pool = rng.standard_normal(shape).astype(np.float32)
        pool[:, 0], pool[:, nan_page] = 0.0, np.nan
        pools.append(jnp.asarray(pool, dtype))
    q = jnp.asarray(rng.standard_normal((S, H, B, hd)), dtype)
    lengths = np.asarray([0, 4, 36, 96], np.int32)
    table = (1 + rng.permutation(S * per_slot)).astype(
        np.int32).reshape(S, per_slot)
    clean = table.copy()
    for s_, n in enumerate(lengths):
        table[s_, -(-int(n) // ps):] = nan_page
        clean[s_, -(-int(n) // ps):] = 0
    kernel = jax.jit(partial(paged_attention_block, layer=jnp.int32(1),
                             scale=0.1, block_pages=2, chunk_pages=1,
                             interpret=True))
    got = np.asarray(kernel(q, *pools, jnp.asarray(table),
                            jnp.asarray(lengths)), np.float32)
    want = np.asarray(paged_block_attention(
        q, *pools, jnp.asarray(clean), jnp.asarray(lengths), layer=1,
        scale=0.1), np.float32)
    assert got.shape == (S, H, B, hd)
    assert np.isfinite(got).all() and not got[0].any()
    tol = 1e-5 if dtype == jnp.float32 else 4 * 2.0 ** -8 * np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=tol)
    # the published shapes ride the walk as stored; a (.., 4, 128) pool
    # of half tiles would not
    assert takes_block((32, 32, 4, 128), (6, 6401, 16, 512), jnp.bfloat16)
    assert not takes_block((32, 32, 4, 128), (6, 6401, 16, 512), jnp.float32)
    assert block_decode_path((32, 32, 4, 128), (6, 6401, 16, 512),
                             jnp.bfloat16) == "gathered"     # a CPU


# -- the unmasking on made-up logits ---------------------------------------------
def _made_up(conf_rows):
    """Logits (S, B, V) whose row (s, b) gives its argmax ``10 s + b`` the
    probability ``conf_rows[s][b]``."""
    conf = np.asarray(conf_rows, np.float64)
    S, V = conf.shape[0], CFG.vocab_size
    lg = np.zeros((S, B, V), np.float32)
    for s in range(S):
        for b in range(B):
            # softmax: one id at x, V - 2 at 0 (the mask id is cut)
            lg[s, b, 10 * s + b] = np.log(
                conf[s, b] * (V - 2) / (1 - conf[s, b]))
    return jnp.asarray(lg)


def _unmask(cfg, lg, masked):
    S = lg.shape[0]
    return [np.asarray(a) for a in bd.unmask(
        cfg, lg, jnp.asarray(masked), jax.random.split(
            jax.random.PRNGKey(0), S), jnp.zeros((S,)),
        jnp.full((S,), cfg.vocab_size, jnp.int32), jnp.ones((S,)))]


@pytest.mark.parametrize("steps", [4, 2, 1])
def test_static_schedule_takes_the_most_confident(steps):
    cfg = replace(CFG, denoising_steps=steps)
    conf = [[0.3, 0.6, 0.5, 0.4], [0.2, 0.2, 0.7, 0.2], [0.9, 0.8, 0.7, 0.6]]
    masked = np.array([[1, 1, 1, 1], [1, 1, 0, 1], [0, 0, 0, 1]], bool)
    x0, got, take, by = _unmask(cfg, _made_up(conf), masked)
    np.testing.assert_allclose(got, conf, rtol=1e-5)
    np.testing.assert_array_equal(
        x0, [[10 * s + b for b in range(B)] for s in range(3)])
    want = {4: [[0, 1, 0, 0], [1, 0, 0, 0], [0, 0, 0, 1]],
            2: [[0, 1, 1, 0], [1, 1, 0, 0], [0, 0, 0, 1]],
            1: masked}[steps]            # ties go to the lower position
    np.testing.assert_array_equal(take, np.asarray(want, bool))
    assert not by.any()
    model = model_of(cfg)
    for s in range(3):
        _, c = ref.confidence(model, np.asarray(_made_up(conf))[s])
        np.testing.assert_array_equal(ref.transfer(model, c, masked[s])[0],
                                      take[s])


def test_dynamic_threshold_finishes_a_block_in_one_pass():
    cfg = replace(CFG, remasking="low_confidence_dynamic")
    conf = [[0.95, 0.97, 0.92, 0.99],      # all pass: done in one pass
            [0.95, 0.5, 0.92, 0.3],        # two pass: both are taken
            [0.5, 0.6, 0.3, 0.2],          # none passes: the largest
            [0.95, 0.5, 0.99, 0.3]]        # the passing one is filled
    masked = np.array([[1, 1, 1, 1], [1, 1, 1, 1], [1, 1, 1, 1],
                       [1, 1, 0, 1]], bool)
    _, _, take, by = _unmask(cfg, _made_up(conf), masked)
    np.testing.assert_array_equal(take, np.array(
        [[1, 1, 1, 1], [1, 0, 1, 0], [0, 1, 0, 0], [1, 0, 0, 0]], bool))
    np.testing.assert_array_equal(by, [True, True, False, True])
    model = model_of(cfg)
    for s in range(4):
        _, c = ref.confidence(model, np.asarray(_made_up(conf))[s])
        np.testing.assert_array_equal(ref.transfer(model, c, masked[s])[0],
                                      take[s])


def test_a_sampled_candidate_is_worth_its_probability_in_the_nucleus():
    S = 3
    lg = 2.0 * jax.random.normal(jax.random.PRNGKey(5),
                                 (S, B, CFG.vocab_size), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(6), S)
    x0, conf, _, _ = bd.unmask(
        CFG, lg, jnp.ones((S, B), bool), keys, jnp.full((S,), 0.7),
        jnp.full((S,), CFG.vocab_size, jnp.int32), jnp.full((S,), 0.9))
    for s in range(S):
        _, want = ref.confidence(MODEL, np.asarray(lg[s]), x0=np.asarray(
            x0[s]), temperature=0.7, top_p=0.9)
        assert (want > 0).all()
        np.testing.assert_allclose(conf[s], want, rtol=1e-4)
    assert (np.asarray(x0) != CFG.mask_token_id).all()


# -- through the engine's pages ---------------------------------------------------
@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunked"])
def test_every_pass_through_the_pages_matches_one_full_forward(params,
                                                               chunked):
    """Prefill (the prompt's whole blocks), the remainder seated as the
    first block, denoise passes over tentative keys and the commit
    through ``ServeEngine``'s pages: before every step the logits the
    step program is about to read (``decode_logits`` over the engine's
    own state) are the reference's full forward over the prefix and the
    block as the slot holds it."""
    prompt = _prompts(11, [21])[0]           # 5 blocks and a remainder of 1
    eng = ServeEngine(CFG, params, overlap=False,
                      **(CHUNKED if chunked else ENGINE))
    rid = eng.submit(Request(prompt=prompt, max_new_tokens=9))
    peek = jax.jit(partial(bd.decode_logits, CFG))
    passes = 0
    eng._loop_iter(None)                     # the admission and pass 1
    while not eng._done[rid]:
        sv = {n: np.asarray(a) for n, a in eng._sv.items()}
        slot = eng._slot_rid.index(rid)
        length = int(sv["lengths"][slot])
        # the host's mirror counts the prompt's remainder, which waits
        # in the first block, from the admission on
        assert length % B == 0 and int(eng._slot_len[slot]) == max(
            length, len(prompt))
        fed = np.where(sv["masked"][slot], CFG.mask_token_id,
                       sv["tokens"][slot])
        got = peek(params, eng._kv, eng._sv, eng._active.copy(),
                   eng._pt)[0][slot]
        seq = list(prompt) + [int(t) for t in eng._results[rid]]
        want = ref.logits(MODEL, params, seq[:length] + fed.tolist(),
                          rows=jnp.arange(length, length + B))
        np.testing.assert_allclose(got, want, atol=LOGIT_TOL)
        eng._loop_iter(None)
        passes += 1
    assert passes >= 2 * (CFG.denoising_steps + 1)
    assert eng._results[rid] == ref.generate(MODEL, params, prompt, 9)


@pytest.mark.parametrize("remainder", [0, 1, 3])
@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunked"])
def test_a_prompt_with_any_remainder_streams_the_reference_loop(
        params, remainder, chunked):
    prompt = _prompts(12 + remainder, [16 + remainder])[0]
    # 10 new tokens: the request is cut inside its last block
    eng, (got,) = _serve(params, [prompt], [10],
                         **(CHUNKED if chunked else {}))
    assert got == ref.generate(MODEL, params, prompt, 10)
    assert CFG.mask_token_id not in got
    assert eng.kv_cache_stats()["pages_used"] == 0
    if chunked:          # 16 tokens are the last chunk alone; 17 are two
        assert eng.n_buckets == (2 if remainder else 1)


@pytest.mark.parametrize("overlap", [True, False])
def test_slots_out_of_phase_and_reseated_stream_what_they_stream_alone(
        params, overlap):
    """Five requests over two slots: their blocks are out of phase in
    one step (other remainders, other lengths), three are seated over
    another request's block state, and each streams what it streams
    alone, the reference loop's tokens."""
    prompts = _prompts(0, (8, 9, 11, 5, 3))
    news = [10, 7, 9, 13, 6]
    eng, got = _serve(params, prompts, news, overlap=overlap)
    for prompt, n, toks in zip(prompts, news, got):
        assert toks == ref.generate(MODEL, params, prompt, n)
        assert CFG.mask_token_id not in toks
    # one step program, one prefill bucket (8 and 16), the page copy
    assert eng.compile_count == eng.n_buckets + 1
    st = eng.kv_cache_stats()
    assert st["pages_used"] == 0 and st["decode_attention"] == "gathered"


@pytest.mark.parametrize("steps", [2, 1])
def test_fewer_denoising_steps_through_the_engine(params, steps):
    cfg = replace(CFG, denoising_steps=steps)
    prompt = _prompts(21, [10])[0]
    eng, (got,) = _serve(params, [prompt], [11], cfg=cfg)
    assert got == ref.generate(model_of(cfg), params, prompt, 11)
    # a full block costs steps + 1 passes of its slot
    assert eng.steps_run <= 4 * (steps + 1) + 1


def test_sampled_streams_do_not_depend_on_the_company(params):
    prompts = _prompts(31, (9, 14, 6))

    def run(which, **engine):
        eng = ServeEngine(CFG, params, **{**ENGINE, **engine})
        rids = [eng.submit(Request(prompt=prompts[i], max_new_tokens=12,
                                   temperature=0.8, top_p=0.9, seed=40 + i))
                for i in which]
        out = eng.run()
        return [out[r].tolist() for r in rids]
    together = run([0, 1, 2])
    assert together == run([0, 1, 2], overlap=False, max_slots=3)
    for i in range(3):
        assert run([i]) == [together[i]]
        assert CFG.mask_token_id not in together[i]


def test_the_replay_holds_what_the_engine_emitted(params):
    prompts = _prompts(41, (13, 20))
    _, got = _serve(params, prompts, [11, 9])
    for prompt, toks in zip(prompts, got):
        notes = {}
        gaps = ref.argmax_gaps(MODEL, params, prompt, toks, 1e-3,
                               notes=notes, pad_to=40)
        assert gaps.shape == (len(toks),) and gaps.max() <= 1e-3
        assert notes["order_retries"] == 0
        # another stream's tokens do not pass for this prompt's
        wrong = ref.argmax_gaps(MODEL, params, prompt, toks[1:] + toks[:1],
                                1e-3, notes=notes, pad_to=40)
        assert wrong.max() > 1e-3 and notes["order_retries"] > 0


# -- the engine's bookkeeping -------------------------------------------------------
def test_family_surface_and_state(params):
    assert serving_family(CFG) is bd
    eng = ServeEngine(CFG, params, **ENGINE)
    assert set(eng._sv) == set(bd.SLOT_VARS)
    assert eng._sv["tokens"].shape == (2, B)
    assert set(eng._kv) == {"k", "v"}
    assert eng._kv["k"].shape == (CFG.n_layers, eng.n_pages, 8,
                                  CFG.n_kv_heads * CFG.head_dim)
    assert eng.prefix_cache_enabled is False     # the default, not asked
    assert eng.overlap is True                   # nothing waits for the host
    st = eng.kv_cache_stats()
    assert st["reserved_bytes"] == 2 * eng._kv["k"].nbytes
    assert st["state_bytes_per_slot"] == 0
    with pytest.raises(ValueError, match="straddle"):
        ServeEngine(CFG, params, **{**ENGINE, "page_size": 6})


def test_plan_pages_rounds_a_request_up_to_whole_blocks(params):
    eng = ServeEngine(CFG, params, **ENGINE)
    assert bd.positions_written(CFG, 13, 2) == 16
    assert bd.positions_written(CFG, 12, 4) == 16
    assert bd.positions_written(CFG, 13, 4) == 20
    # 15 + 1 = 16 positions are two pages of 8; 15 + 2 round up to 20:
    # three, because the last block is written whole
    for new, pages in ((1, 2), (2, 3)):
        plan = eng._plan_pages(Request(prompt=np.arange(15),
                                       max_new_tokens=new), None)
        assert len(plan["row"]) == pages
        eng._pages.release([int(p) for p in plan["row"]])
    # 61 + 3 tokens fit max_len 64; 62 + 3 would write a 17th block
    eng.submit(Request(prompt=np.arange(61), max_new_tokens=3))
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(Request(prompt=np.arange(62), max_new_tokens=3))


def test_ttft_is_observed_at_the_first_block_and_counters_count(params):
    telemetry.reset()
    prompts = _prompts(51, (9, 12))
    eng, got = _serve(params, prompts, [8, 8])
    value = _metric
    # a prefill yields no token: the first emission is the first
    # block's commit, and every request is observed once there
    assert value("mxtpu_serve_ttft_first_wait_ms_count") == 2
    assert value("mxtpu_serve_ttft_queue_ms_count") == 2
    assert value("mxtpu_serve_tokens_total") == 16
    # 9 = two blocks + 1: the first block has 3 to fill (3 + 1 passes),
    # then 4 + 1 and, cut at 8 tokens, 4 + 1 of which 1 token counts;
    # 12: three full blocks' worth, 2 emitted... counted on the device
    passes = value("mxtpu_serve_block_passes_total")
    blocks = value("mxtpu_serve_blocks_committed_total")
    assert blocks >= 5 and passes >= 4 * blocks
    assert passes <= (CFG.denoising_steps + 1) * blocks + 2 * (
        CFG.denoising_steps + 1)
    assert value("mxtpu_serve_block_unmasked_total") >= 16
    assert value("mxtpu_serve_block_threshold_transfers_total") == 0
    assert value("mxtpu_serve_moe_assignments_total") > 0
    assert value("mxtpu_serve_block_length") == B
    assert value("mxtpu_serve_denoising_steps") == CFG.denoising_steps
    # a step is a pass of the bank: tokens a step is under one a slot
    assert value("mxtpu_serve_steps_total") == eng.steps_run
    assert 16 / eng.steps_run < 2


@pytest.mark.parametrize("option,word", [
    ({"prefix_cache": True}, "block boundary"),
    ({"speculate_k": 2}, "second block"),
    ({"int8_pages": True}, "quantised")])
def test_engine_refuses_what_it_cannot_do(params, option, word):
    with pytest.raises(ValueError, match="blockdiff_moe family.*" + word):
        ServeEngine(CFG, params, **{**ENGINE, **option})


def test_engine_refuses_a_handoff_a_mesh_and_a_resumed_chain(params):
    eng = ServeEngine(CFG, params, **ENGINE)
    z = np.zeros((CFG.n_layers, CFG.n_kv_heads, 16, CFG.head_dim),
                 np.float32)
    handoff = KVHandoff(k=z, v=z, true_len=9, token=1,
                        rng=np.zeros(2, np.uint32))
    with pytest.raises(ValueError, match="submit_prefilled.*no token"):
        eng.submit_prefilled(handoff, Request(
            prompt=np.arange(9), max_new_tokens=2))
    with pytest.raises(ValueError, match="resume_key.*schedule"):
        eng.submit(Request(prompt=np.arange(9), max_new_tokens=2,
                           rng=np.zeros(2, np.uint32)))
    from mxtpu.parallel import mesh as pmesh
    with pytest.raises(ValueError, match="mesh.*expert bank"):
        ServeEngine(CFG, params, mesh=pmesh.create_mesh(dp=-1), **ENGINE)
    with pytest.raises(ValueError, match="whole number of blocks"):
        ServeEngine(CFG, params, **{**ENGINE, "prefill_chunk": 6})
    assert set(bd.SERVE_UNSUPPORTED) == {
        "prefix_cache", "speculate_k", "int8_pages", "submit_prefilled",
        "mesh", "resume_key"}


def test_gateway_streams_blocks(params):
    """Through ``Gateway.start_http``: three concurrent requests, prompts
    prefilled in chunks; each stream is the reference loop's."""
    gw = Gateway(lambda: ServeEngine(CFG, params, **CHUNKED),
                 n_replicas=1, queue_max=16)
    prompts = _prompts(61, (27, 18, 11))
    results = {}
    try:
        port = gw.start_http(port=0)

        def client(i):
            results[i] = GatewayClient("127.0.0.1", port).generate(
                prompts[i], 9, seed=i, temperature=0.0)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        _, prom = GatewayClient("127.0.0.1", port).get_text("/metrics")
    finally:
        gw.close()
    assert 'kind="kv_pages"' in prom
    assert "mxtpu_serve_blocks_committed_total" in prom
    for i, p in enumerate(prompts):
        assert results[i]["status"] == 200, results[i]
        assert results[i]["tokens"] == ref.generate(MODEL, params, p, 9)


# -- one emit path for every kind of step -------------------------------------------
LLAMA = llama.CONFIGS["tiny"]


@pytest.mark.parametrize("kind", ["plain", "speculative", "block"])
@pytest.mark.parametrize("overlap", [True, False])
def test_every_kind_of_step_goes_through_the_one_emit_path(params, kind,
                                                           overlap):
    """A plain step yields one token a slot, a speculative verify 1..k +
    1, a block step none or up to B: all three hand ``_process`` tokens
    and which of them were emitted (a block step's ride behind its
    tokens, so its width reads 2 B), and it alone mirrors the lengths
    and emits. Held on each: every request gets
    exactly its tokens, in the order of its own stream; the mirrored
    lengths equal the device's when the bank drains; TTFT is observed
    once a request at its first emission."""
    if kind == "block":
        cfg, weights, extra = CFG, params, {}
    else:
        cfg = LLAMA
        weights = llama.init_params(cfg, jax.random.PRNGKey(2))
        extra = {"speculate_k": 3} if kind == "speculative" else {}
    rng = np.random.default_rng(7)
    prompts = [np.tile(rng.integers(0, 200, 5), 3)[:n] for n in (13, 9, 11)]
    eng = ServeEngine(cfg, weights, overlap=overlap, **ENGINE, **extra)
    widths, seen = set(), {}
    inner = eng._process

    def watched(disp):
        if disp.slots:
            n = len(eng._step_counts)
            toks = np.asarray(disp.sampled).reshape(-1)
            widths.add((toks.size - n) // eng.max_slots)
        return inner(disp)
    eng._process = watched
    rids = [eng.submit(Request(
        prompt=p, max_new_tokens=10,
        on_token=lambda rid, tok: seen.setdefault(rid, []).append(tok)))
        for p in prompts]
    telemetry.reset()
    out = eng.run()
    for rid in rids:
        assert out[rid].tolist() == seen[rid] and len(seen[rid]) == 10
    assert widths == {"plain": {1}, "speculative": {1, 4},
                      "block": {2 * B}}[kind] or (
        kind == "speculative" and widths == {4})
    # the host's mirror is the device's vector for the last request a
    # slot held, less what the device ran past its end
    device = np.asarray(eng._sv["lengths"])
    assert (eng._slot_len <= device).all() and eng._slot_len.sum() > 0
    assert _metric("mxtpu_serve_ttft_first_wait_ms_count") == 3
    assert _metric("mxtpu_serve_tokens_total") == 30
    if kind == "speculative":
        assert eng.kv_cache_stats()["spec_accepted"] > 0
