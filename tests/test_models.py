"""Model-family tests: flagship Llama + functional ResNet.

Replicates the reference's test strategy (SURVEY.md §4.2): NumPy/dense
ground truth for fused paths, cross-implementation consistency (ring vs
dense == the reference's cpu-vs-gpu check_consistency), and small
convergence tests as integration signal.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from dataclasses import replace

from mxtpu.models import llama, resnet
from mxtpu.parallel import mesh as pmesh, step as pstep
from mxtpu.parallel.sharding import ShardingRules, P


@pytest.fixture(scope="module")
def tiny_cfg():
    return llama.CONFIGS["tiny"]


def test_llama_forward_shape(tiny_cfg):
    params = llama.init_params(tiny_cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    logits = llama.forward(tiny_cfg, params, tokens)
    assert logits.shape == (2, 32, tiny_cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_llama_scan_matches_unrolled(tiny_cfg):
    params = llama.init_params(tiny_cfg, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                tiny_cfg.vocab_size)
    cfg_f32 = replace(tiny_cfg, dtype=jnp.float32)
    a = llama.forward(replace(cfg_f32, scan_layers=True), params, tokens)
    b = llama.forward(replace(cfg_f32, scan_layers=False), params, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow   # ~23s; ci_all's unittest_cpu_mesh runs the full suite
def test_chunked_ce_matches_full(tiny_cfg):
    """VERDICT r2 #5: the streaming chunked cross-entropy must match
    the materialized log_softmax path in value AND gradient, including
    a chunk width that does not divide the vocab."""
    cfg = replace(tiny_cfg, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 24), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens,
             "mask": (jax.random.uniform(jax.random.PRNGKey(8),
                                         (2, 24)) > 0.2)}
    full = replace(cfg, ce_chunk=None)
    for chunk in (64, 100, 256):        # 100 does not divide 256
        ch = replace(cfg, ce_chunk=chunk)
        lf, gf = jax.value_and_grad(llama.loss_fn(full))(params, batch)
        lc, gc = jax.value_and_grad(llama.loss_fn(ch))(params, batch)
        np.testing.assert_allclose(float(lf), float(lc),
                                   rtol=1e-5, atol=1e-6)
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_flatten_with_path(gf)[0],
                jax.tree_util.tree_flatten_with_path(gc)[0]):
            assert pa == pb
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=str(pa))
    # auto rule: big vocab chunks, small vocab doesn't
    assert llama._resolve_ce_chunk(
        replace(cfg, vocab_size=128256)) == 8192
    assert llama._resolve_ce_chunk(cfg) == 0
    assert llama._resolve_ce_chunk(replace(cfg, ce_chunk=512)) == 512
    # False and None are explicit opt-outs even at big vocab
    assert llama._resolve_ce_chunk(
        replace(cfg, vocab_size=128256, ce_chunk=False)) == 0
    assert llama._resolve_ce_chunk(
        replace(cfg, vocab_size=128256, ce_chunk=None)) == 0


def test_llama_kv_cache_decode_matches_forward(tiny_cfg):
    """VERDICT r2 #4: prefill + per-token KV-cache decode must produce
    the same logits as the full forward pass at every position."""
    cfg = replace(tiny_cfg, dtype=jnp.float32, attn_impl="dense")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(11), (2, 12), 0,
                                cfg.vocab_size)
    ref = llama.forward(cfg, params, tokens)          # (b, 12, V)

    s0 = 5
    cache = llama.init_cache(cfg, 2, 12)
    pre_logits, cache = llama.prefill(cfg, params, tokens[:, :s0], cache)
    np.testing.assert_allclose(np.asarray(pre_logits),
                               np.asarray(ref[:, :s0]),
                               rtol=2e-4, atol=2e-4)
    assert int(cache["pos"]) == s0
    for i in range(s0, 12):       # feed the TRUE next token each step
        step_logits, cache = llama.decode_step(
            cfg, params, tokens[:, i:i + 1], cache)
        np.testing.assert_allclose(np.asarray(step_logits),
                                   np.asarray(ref[:, i]),
                                   rtol=2e-4, atol=2e-4, err_msg=f"pos {i}")
    assert int(cache["pos"]) == 12


def test_llama_generate(tiny_cfg):
    """generate() is greedy-deterministic, jittable end to end, and
    its continuation agrees with argmax over full forward logits."""
    cfg = replace(tiny_cfg, dtype=jnp.float32, attn_impl="dense")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(13), (2, 6), 0,
                                cfg.vocab_size)
    gen = jax.jit(lambda p, t: llama.generate(cfg, p, t, 5))
    out = gen(params, prompt)
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(np.asarray(out[:, :6]),
                                  np.asarray(prompt))
    # greedy property: each generated token is the argmax of the full
    # forward logits over the sequence so far
    seq = np.asarray(out)
    for i in range(6, 11):
        lg = llama.forward(cfg, params, jnp.asarray(seq[:, :i]))
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(lg[:, -1], axis=-1)), seq[:, i],
            err_msg=f"pos {i}")
    # temperature sampling is deterministic given the rng
    a = llama.generate(cfg, params, prompt, 4, temperature=0.8,
                       rng=jax.random.PRNGKey(3))
    b = llama.generate(cfg, params, prompt, 4, temperature=0.8,
                       rng=jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow   # ~18s; sampler modes also pinned in test_serve's
def test_llama_generate_topk_topp(tiny_cfg):    # traced==static gate
    """top-k / nucleus sampling (round 4): every sampled token must lie
    inside the allowed set at its position, sampling is deterministic
    given the rng, and bad arguments raise."""
    cfg = replace(tiny_cfg, dtype=jnp.float32, attn_impl="dense")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (3, 5), 0,
                                cfg.vocab_size)

    out = llama.generate(cfg, params, prompt, 6, temperature=0.9,
                         top_k=5, rng=jax.random.PRNGKey(1))
    seq = np.asarray(out)
    for i in range(5, 11):
        lg = llama.forward(cfg, params, jnp.asarray(seq[:, :i]))[:, -1]
        top5 = np.asarray(jax.lax.top_k(lg, 5)[1])
        for b in range(3):
            assert seq[b, i] in top5[b], (b, i)

    outp = llama.generate(cfg, params, prompt, 6, temperature=0.9,
                          top_p=0.6, rng=jax.random.PRNGKey(1))
    seqp = np.asarray(outp)
    for i in range(5, 11):
        lg = np.asarray(
            llama.forward(cfg, params, jnp.asarray(seqp[:, :i]))[:, -1])
        for b in range(3):
            pr = np.exp(lg[b] / 0.9 - np.max(lg[b] / 0.9))
            pr /= pr.sum()
            order = np.argsort(-pr)
            csum = np.cumsum(pr[order])
            nucleus = set(order[:int((csum < 0.6).sum()) + 1])
            assert seqp[b, i] in nucleus, (b, i)

    a = llama.generate(cfg, params, prompt, 4, temperature=0.8,
                       top_k=8, top_p=0.9, rng=jax.random.PRNGKey(3))
    b2 = llama.generate(cfg, params, prompt, 4, temperature=0.8,
                        top_k=8, top_p=0.9, rng=jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b2))
    # top_k=1 at temperature == greedy
    g = llama.generate(cfg, params, prompt, 4)
    k1 = llama.generate(cfg, params, prompt, 4, temperature=1.0,
                        top_k=1, rng=jax.random.PRNGKey(4))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(k1))
    with pytest.raises(ValueError):
        llama.generate(cfg, params, prompt, 4, top_k=0)
    with pytest.raises(ValueError):
        llama.generate(cfg, params, prompt, 4, top_p=1.5)


def test_llama_sharded_decode_matches_single_device(tiny_cfg):
    """VERDICT r3 #1: the flagship's serving half on a mesh. Prefill +
    decode with a tp/fsdp-sharded KV cache must reproduce the
    single-device path bit-for-bit in greedy token space and to
    float tolerance in logits; the cache must actually be sharded
    (kv heads over tp, batch over dp/fsdp)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    from jax.sharding import NamedSharding
    from mxtpu.parallel.sharding import shard_pytree

    cfg = replace(tiny_cfg, dtype=jnp.float32, remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 10), 0,
                                cfg.vocab_size)
    ref_tokens = jax.jit(
        lambda p, t: llama.generate(cfg, p, t, 6))(params, prompt)

    mesh = pmesh.create_mesh(dp=2, fsdp=2, tp=2)
    rules = llama.sharding_rules(cfg)
    sparams = shard_pytree(params, mesh, rules)
    sprompt = jax.device_put(
        prompt, NamedSharding(mesh, P(("dp", "fsdp"))))

    # cache placement: kv heads over tp, batch over the data axes
    kv_sharding = NamedSharding(
        mesh, P(None, ("dp", "fsdp"), "tp", None, None))
    cache = llama.init_cache(cfg, 4, 16, mesh=mesh)
    assert cache["k"].sharding.is_equivalent_to(kv_sharding, 5)

    # prefill + stepwise decode on the mesh == full forward logits
    ref_logits = llama.forward(cfg, params, prompt)
    pre, cache = jax.jit(
        lambda p, t, c: llama.prefill(cfg, p, t, c, mesh=mesh))(
        sparams, sprompt, cache)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    assert cache["k"].sharding.is_equivalent_to(kv_sharding, 5), \
        "prefill lost the cache sharding"
    step_logits, cache = jax.jit(
        lambda p, t, c: llama.decode_step(cfg, p, t, c, mesh=mesh))(
        sparams, sprompt[:, -1:], cache)
    assert step_logits.shape == (4, cfg.vocab_size)
    assert int(cache["pos"]) == 11

    # one-program sharded generate == single-device generate
    out = jax.jit(
        lambda p, t: llama.generate(cfg, p, t, 6, mesh=mesh))(
        sparams, sprompt)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref_tokens))


def test_llama_int8_decode_matches_dequantized_float(tiny_cfg):
    """VERDICT r4 #4: weight-only int8 serving. The in-program dequant
    path must equal running the float path on MANUALLY dequantized
    weights (same math, so tight tolerance), stay CLOSE to the bf16/
    f32 original (bounded quantization error), and generate end to
    end."""
    cfg = replace(tiny_cfg, dtype=jnp.float32, remat=False,
                  attn_impl="dense")
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    qparams = llama.quantize_params_int8(cfg, params)
    assert qparams["layers"]["wq"]["q8"].dtype == jnp.int8
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 12), 0,
                                cfg.vocab_size)

    # manual dequant -> the existing float serving path (any depth)
    fparams = jax.tree.map(
        lambda v: (v["q8"].astype(jnp.float32) * v["s8"]
                   if isinstance(v, dict) and "q8" in v else v),
        qparams,
        is_leaf=lambda v: isinstance(v, dict) and "q8" in v)

    cache_q = llama.init_cache(cfg, 2, 16)
    cache_f = llama.init_cache(cfg, 2, 16)
    lq, _ = llama.prefill(cfg, qparams, prompt, cache_q,
                          last_only=True)
    lf, _ = llama.prefill(cfg, fparams, prompt, cache_f,
                          last_only=True)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lf),
                               rtol=1e-5, atol=1e-5)

    # bounded quantization error vs the unquantized original
    cache_o = llama.init_cache(cfg, 2, 16)
    lo, _ = llama.prefill(cfg, params, prompt, cache_o,
                          last_only=True)
    err = np.abs(np.asarray(lq) - np.asarray(lo))
    scale = np.abs(np.asarray(lo)).max()
    assert err.max() / scale < 0.05, err.max() / scale

    # end-to-end generation off the quantized tree
    out = jax.jit(
        lambda p, t: llama.generate(cfg, p, t, 5))(qparams, prompt)
    assert out.shape == (2, 17)


def test_llama_chunked_prefill_matches_single_shot(tiny_cfg):
    """VERDICT r4 #5: streaming prefill. Chunked must equal one-shot
    prefill(last_only=True) — logits AND the full cache — and feed a
    decode that continues identically."""
    cfg = replace(tiny_cfg, dtype=jnp.float32, remat=False,
                  attn_impl="dense")
    params = llama.init_params(cfg, jax.random.PRNGKey(15))
    prompt = jax.random.randint(jax.random.PRNGKey(16), (2, 24), 0,
                                cfg.vocab_size)

    c_ref = llama.init_cache(cfg, 2, 32)
    lg_ref, c_ref = llama.prefill(cfg, params, prompt, c_ref,
                                  last_only=True)
    for chunk in (24, 12, 8, 4):          # incl. the n==1 fast path
        c = llama.init_cache(cfg, 2, 32)
        lg, c = llama.chunked_prefill(cfg, params, prompt, c, chunk)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"chunk={chunk}")
        np.testing.assert_allclose(np.asarray(c["k"]),
                                   np.asarray(c_ref["k"]),
                                   rtol=2e-5, atol=2e-5)
        assert int(c["pos"]) == 24
    # a decode step off the chunked cache continues the sequence
    tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    d1, _ = llama.decode_step(cfg, params, tok, c)
    d2, _ = llama.decode_step(cfg, params, tok, c_ref)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=2e-5, atol=2e-5)
    # ragged prompts: 24 = 3×7 + 3 runs full chunks + a remainder
    # pass (padding would corrupt the cache/RoPE — never pad)
    cr = llama.init_cache(cfg, 2, 32)
    lg_r, cr = llama.chunked_prefill(cfg, params, prompt, cr, 7)
    np.testing.assert_allclose(np.asarray(lg_r), np.asarray(lg_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cr["k"]),
                               np.asarray(c_ref["k"]),
                               rtol=2e-5, atol=2e-5)
    assert int(cr["pos"]) == 24


def test_llama_chunked_prefill_sharded(tiny_cfg):
    """Chunked prefill on the serving mesh: the scanned cache carry
    must keep its kv-head/batch sharding chunk to chunk."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    from jax.sharding import NamedSharding
    from mxtpu.parallel.sharding import shard_pytree

    cfg = replace(tiny_cfg, dtype=jnp.float32, remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(17))
    prompt = jax.random.randint(jax.random.PRNGKey(18), (4, 16), 0,
                                cfg.vocab_size)
    ref_c = llama.init_cache(cfg, 4, 24)
    ref_lg, ref_c = llama.prefill(cfg, params, prompt, ref_c,
                                  last_only=True)

    mesh = pmesh.create_mesh(dp=2, fsdp=2, tp=2)
    sparams = shard_pytree(params, mesh, llama.sharding_rules(cfg))
    sprompt = jax.device_put(
        prompt, NamedSharding(mesh, P(("dp", "fsdp"))))
    cache = llama.init_cache(cfg, 4, 24, mesh=mesh)
    kv_sharding = cache["k"].sharding
    lg, cache = jax.jit(
        lambda p, t, c: llama.chunked_prefill(cfg, p, t, c, 4,
                                              mesh=mesh))(
        sparams, sprompt, cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(cache["k"]),
                               np.asarray(ref_c["k"]),
                               rtol=2e-4, atol=2e-4)
    assert cache["k"].sharding.is_equivalent_to(kv_sharding, 5), \
        "chunked prefill lost the cache sharding"


def test_llama_int8_sharded_decode_on_tp_mesh(tiny_cfg):
    """int8 serving composes with the tp mesh: quantized q8/s8 leaves
    place by int8_sharding_rules (the int8 bank really shards over
    fsdp x tp) and the sharded quantized generate matches the
    single-device quantized generate token-for-token."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    from jax.sharding import NamedSharding
    from mxtpu.parallel.sharding import shard_pytree

    cfg = replace(tiny_cfg, dtype=jnp.float32, remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(9))
    qparams = llama.quantize_params_int8(cfg, params)
    prompt = jax.random.randint(jax.random.PRNGKey(10), (4, 8), 0,
                                cfg.vocab_size)
    ref = jax.jit(
        lambda p, t: llama.generate(cfg, p, t, 5))(qparams, prompt)

    mesh = pmesh.create_mesh(dp=2, fsdp=2, tp=2)
    rules = llama.int8_sharding_rules(cfg)
    sq = shard_pytree(qparams, mesh, rules)
    # the int8 bank really shards: wq (L, dim, out) over fsdp x tp
    wq = sq["layers"]["wq"]["q8"]
    assert wq.sharding.shard_shape(wq.shape)[1] == wq.shape[1] // 2
    assert wq.sharding.shard_shape(wq.shape)[2] == wq.shape[2] // 2
    sprompt = jax.device_put(
        prompt, NamedSharding(mesh, P(("dp", "fsdp"))))
    out = jax.jit(
        lambda p, t: llama.generate(cfg, p, t, 5, mesh=mesh))(
        sq, sprompt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_llama_causality(tiny_cfg):
    """Changing a future token must not change past logits."""
    cfg = replace(tiny_cfg, dtype=jnp.float32, attn_impl="dense")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    t1 = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0,
                            cfg.vocab_size)
    t2 = t1.at[0, 10].set((t1[0, 10] + 1) % cfg.vocab_size)
    l1 = llama.forward(cfg, params, t1)
    l2 = llama.forward(cfg, params, t2)
    np.testing.assert_allclose(np.asarray(l1[:, :10]),
                               np.asarray(l2[:, :10]), rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(l1[:, 10:]), np.asarray(l2[:, 10:]))


def test_llama_ring_matches_dense(tiny_cfg):
    """ring attention over sp==2 must match dense attention globally
    (the rebuild's check_consistency for the sequence-parallel path)."""
    mesh = pmesh.create_mesh(dp=1, sp=2, tp=2,
                             devices=jax.devices()[:4])
    cfg_d = replace(tiny_cfg, dtype=jnp.float32, attn_impl="dense",
                    remat=False)
    cfg_r = replace(cfg_d, attn_impl="ring")
    params = llama.init_params(cfg_d, jax.random.PRNGKey(4))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0,
                                cfg_d.vocab_size)
    dense = llama.forward(cfg_d, params, tokens)
    ring = jax.jit(lambda p, t: llama.forward(cfg_r, p, t, mesh=mesh))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               rtol=1e-4, atol=1e-4)


def test_llama_train_step_learns(tiny_cfg):
    """Few steps of AdamW on one repeated batch must cut the loss — the
    rebuild's tests/python/train convergence smoke."""
    cfg = replace(tiny_cfg, remat=False)
    mesh = pmesh.create_mesh(dp=-1)
    rules = llama.sharding_rules(cfg)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(1e-2)
    state = pstep.init_state(params, tx, mesh, rules)
    step = pstep.make_train_step(llama.loss_fn(cfg), tx, mesh, rules)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(9), (8, 32),
                                          0, cfg.vocab_size)}
    state, first = step(state, batch)
    for _ in range(20):
        state, loss = step(state, batch)
    assert float(loss) < float(first) * 0.7


def test_resnet_forward_and_train():
    cfg = resnet.CONFIGS["tiny"]
    params = resnet.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
    logits = resnet.forward(cfg, params, x)
    assert logits.shape == (8, cfg.num_classes)

    state0 = resnet.init_state(cfg)
    logits, state1 = resnet.forward(cfg, params, x, state0, train=True)
    # running stats must move away from init
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)), state0, state1)
    assert any(jax.tree.leaves(moved))

    mesh = pmesh.create_mesh(dp=-1)
    rules = ShardingRules([(r".*", P())])
    tx = optax.sgd(0.1, momentum=0.9)
    tstate = pstep.init_state(params, tx, mesh, rules,
                              model_state=state0)
    step = pstep.make_train_step(resnet.loss_fn(cfg), tx, mesh, rules,
                                 has_state=True)
    batch = {"image": x, "label": jnp.arange(8, dtype=jnp.int32)}
    tstate, l0 = step(tstate, batch)
    for _ in range(10):
        tstate, loss = step(tstate, batch)
    assert float(loss) < float(l0)
    # BN running stats accumulated across steps (not stuck at init)
    mm = tstate.model_state["stem_bn"]["mean"]
    assert float(jnp.abs(mm).sum()) > 0


def test_resnet_s2d_stem_matches_std_logits():
    """ISSUE 3 tentpole: the space-to-depth stem is an EXACT rewrite of
    the 7×7/stride-2 SAME stem — same param tree, transformed kernel —
    so logits match the standard stem to float tolerance (f32, CPU;
    the diff is reassociation only)."""
    cfg = replace(resnet.CONFIGS["tiny"], dtype=jnp.float32)
    cfg_s2d = replace(cfg, stem="s2d")
    params = resnet.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3),
                          jnp.float32)
    a = resnet.forward(cfg, params, x)
    b = resnet.forward(cfg_s2d, params, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
    # raw kernel transform is exact in f64 (pure permutation + pad)
    k = jax.random.normal(jax.random.PRNGKey(2), (7, 7, 3, 16),
                          jnp.float64)
    xs = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 3),
                           jnp.float64)
    from jax import lax
    ref = lax.conv_general_dilated(
        xs, k, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    got = lax.conv_general_dilated(
        resnet.space_to_depth(xs), resnet.s2d_stem_kernel(k), (1, 1),
        [(1, 2), (1, 2)], dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-12, atol=1e-12)


def test_resnet_s2d_stem_train_trajectory_matches_std():
    """Because the kernel transform is linear and its zero taps are
    structural (re-created from zeros every step), gradients flow back
    to the shared 7×7 parameter unchanged: a jitted train trajectory
    from identical init must track the standard stem step for step.

    Compared in float64. The two stems sum the same products in
    different orders, and this trajectory (momentum 0.9, lr 0.1, eight
    images, batch norm) grows a difference about a hundredfold a step:
    float32's 4e-6 in the first loss is 0.28 in the fourth, which says
    nothing about the stem. In float64 the gap stays at rounding size,
    and a wrong gradient would show in the second loss."""
    cfg = replace(resnet.CONFIGS["tiny"], dtype=jnp.float64,
                  param_dtype=jnp.float64)
    cfg_s2d = replace(cfg, stem="s2d")
    params = resnet.init_params(cfg, jax.random.PRNGKey(0))
    mesh = pmesh.create_mesh(dp=-1)
    rules = ShardingRules([(r".*", P())])
    batch = {"image": jax.random.normal(jax.random.PRNGKey(1),
                                        (8, 32, 32, 3), jnp.float64),
             "label": jnp.arange(8, dtype=jnp.int32)}

    losses = {}
    final = {}
    for key, c in (("std", cfg), ("s2d", cfg_s2d)):
        tx = optax.sgd(0.1, momentum=0.9)
        tstate = pstep.init_state(params, tx, mesh, rules,
                                  model_state=resnet.init_state(c))
        step = pstep.make_train_step(resnet.loss_fn(c), tx, mesh, rules,
                                     has_state=True)
        ls = []
        for _ in range(4):
            tstate, loss = step(tstate, batch)
            ls.append(float(loss))
        losses[key] = ls
        final[key] = tstate.params
    assert final["s2d"]["stem_conv"].dtype == jnp.float64
    assert losses["std"][0] > losses["std"][-1]      # it trains
    np.testing.assert_allclose(losses["s2d"], losses["std"],
                               rtol=1e-9, atol=1e-10)
    # the stem parameter itself (same tree both sides) stays aligned
    np.testing.assert_allclose(
        np.asarray(final["s2d"]["stem_conv"]),
        np.asarray(final["std"]["stem_conv"]), rtol=1e-8, atol=1e-9)


def test_resnet_s2d_stem_rejects_odd_input():
    cfg = replace(resnet.CONFIGS["tiny"], dtype=jnp.float32, stem="s2d")
    params = resnet.init_params(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((1, 31, 32, 3), jnp.float32)
    with pytest.raises(ValueError, match="even"):
        resnet.forward(cfg, params, x)


@pytest.mark.slow   # ~17s; fresh-process home: multichip_dryrun CI stage
def test_graft_entry():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert bool(jnp.isfinite(out).all())
    g.dryrun_multichip(8)


def test_bert_forward_and_pretrain_step():
    from mxtpu.models import bert
    cfg = bert.CONFIGS["tiny"]
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    B, S, Pm = 8, 32, 5
    rng = jax.random.PRNGKey(1)
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    seq, pooled = bert.forward(cfg, params, tokens)
    assert seq.shape == (B, S, cfg.dim)
    assert pooled.shape == (B, cfg.dim)
    assert bool(jnp.isfinite(seq).all())

    batch = {
        "tokens": tokens,
        "mask": jnp.ones((B, S), jnp.float32),
        "mlm_positions": jnp.tile(jnp.arange(Pm), (B, 1)),
        "mlm_labels": tokens[:, :Pm],
        "mlm_weights": jnp.ones((B, Pm), jnp.float32),
        "nsp_labels": jnp.zeros((B,), jnp.int32),
    }
    mesh = pmesh.create_mesh(dp=-1)
    rules = bert.sharding_rules(cfg)
    tx = optax.adamw(1e-3)
    state = pstep.init_state(params, tx, mesh, rules)
    step = pstep.make_train_step(bert.loss_fn(cfg), tx, mesh, rules)
    state, l0 = step(state, batch)
    for _ in range(15):
        state, loss = step(state, batch)
    assert float(loss) < float(l0)    # memorizes the fixed batch


def test_bert_sharded_multiaxis():
    """bert under dp×fsdp×tp mesh (fsdp=2: sharded params + opt state)
    compiles and runs (CPU mesh)."""
    from dataclasses import replace
    from mxtpu.models import bert
    cfg = replace(bert.CONFIGS["tiny"], remat=True)
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    mesh = pmesh.create_mesh(dp=2, fsdp=2, sp=1, tp=2,
                             devices=jax.devices()[:8])
    rules = bert.sharding_rules(cfg)
    tx = optax.sgd(0.1)
    state = pstep.init_state(params, tx, mesh, rules)
    step = pstep.make_train_step(bert.loss_fn(cfg), tx, mesh, rules)
    B, S, Pm = 4, 16, 3
    batch = {
        "tokens": jnp.ones((B, S), jnp.int32),
        "mask": jnp.ones((B, S), jnp.float32),
        "mlm_positions": jnp.tile(jnp.arange(Pm), (B, 1)),
        "mlm_labels": jnp.ones((B, Pm), jnp.int32),
        "mlm_weights": jnp.ones((B, Pm), jnp.float32),
    }
    state, loss = step(state, batch)
    assert bool(jnp.isfinite(loss))


def test_llama_fsdp_matches_unsharded(tiny_cfg):
    """fsdp=2 (param + optimizer-state sharding, all-gather on use,
    reduce-scatter on grads — all XLA-inserted) must reproduce the
    single-device trajectory, and the state leaves must ACTUALLY carry
    the fsdp sharding (an untested parallelism axis is unimplemented)."""
    cfg = replace(tiny_cfg, dtype=jnp.float32, attn_impl="dense",
                  remat=False)
    rules = llama.sharding_rules(cfg)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    tx = optax.adamw(1e-2)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(8), (4, 32),
                                          0, cfg.vocab_size)}

    def run(mesh, steps=3):
        state = pstep.init_state(params, tx, mesh, rules)
        step = pstep.make_train_step(llama.loss_fn(cfg), tx, mesh, rules)
        losses = []
        for _ in range(steps):
            state, loss = step(state, batch)
            losses.append(float(loss))
        return losses, state

    ref_losses, _ = run(pmesh.create_mesh(dp=1,
                                          devices=jax.devices()[:1]))
    mesh = pmesh.create_mesh(dp=1, fsdp=2, tp=2,
                             devices=jax.devices()[:4])
    fsdp_losses, fstate = run(mesh)
    np.testing.assert_allclose(fsdp_losses, ref_losses,
                               rtol=1e-5, atol=1e-6)

    # params carry the fsdp axis: wq spec is (layer, fsdp, tp) → the
    # live array must be split over devices on dim 1
    wq = fstate.params["layers"]["wq"]
    assert "fsdp" in tuple(wq.sharding.spec), wq.sharding.spec
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[1] == wq.shape[1] // 2, (shard_shape, wq.shape)
    # optimizer moments inherit the parameter's fsdp sharding
    mu_leaves = [l for l in jax.tree_util.tree_leaves(fstate.opt_state)
                 if getattr(l, "shape", None) == wq.shape]
    assert mu_leaves, "adam mu/nu for wq not found in opt_state"
    for m in mu_leaves:
        assert m.sharding.shard_shape(m.shape)[1] == wq.shape[1] // 2


def test_llama_ulysses_matches_dense(tiny_cfg):
    """Ulysses all-to-all sequence parallelism over sp=2 must match
    dense attention globally (same check_consistency pattern as ring)."""
    mesh = pmesh.create_mesh(dp=1, sp=2, tp=2,
                             devices=jax.devices()[:4])
    cfg_d = replace(tiny_cfg, dtype=jnp.float32, attn_impl="dense",
                    remat=False)
    cfg_u = replace(cfg_d, attn_impl="ulysses")
    params = llama.init_params(cfg_d, jax.random.PRNGKey(4))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0,
                                cfg_d.vocab_size)
    dense = llama.forward(cfg_d, params, tokens)
    uly = jax.jit(lambda p, t: llama.forward(cfg_u, p, t, mesh=mesh))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(uly),
                               rtol=1e-4, atol=1e-4)
