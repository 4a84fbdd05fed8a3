"""Mesh / sharding / collectives / sharded-step tests on the 8-device
virtual CPU mesh — the rebuild's analogue of the reference's local-
tracker distributed kvstore tests (SURVEY.md §4.2,
``tests/nightly/dist_sync_kvstore.py`` [path cite])."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from mxtpu import parallel as par
from mxtpu.ops import (blockwise_attention, dense_attention, flash_attention,
                       ring_attention)


def test_mesh_create_resolve():
    mesh = par.create_mesh()  # all 8 in dp
    assert mesh.shape["dp"] == 8 and mesh.shape["tp"] == 1
    mesh = par.create_mesh(dp=2, tp=4)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    mesh = par.create_mesh(tp=4)  # dp absorbs remainder
    assert mesh.shape["dp"] == 2
    with pytest.raises(ValueError):
        par.create_mesh(dp=3, tp=4)  # 12 != 8


def test_use_mesh_ambient():
    mesh = par.create_mesh(dp=8)
    assert par.current_mesh() is None
    with par.use_mesh(mesh) as m:
        assert par.current_mesh() is m
        assert par.axis_size("dp") == 8 and par.axis_size("tp") == 1
    assert par.current_mesh() is None


def test_sharding_rules_first_match_wins():
    rules = par.ShardingRules([
        (r"attn.*wq$", P("fsdp", "tp")),
        (r".*", P()),
    ])
    assert rules.spec("layers/attn0/wq") == P("fsdp", "tp")
    assert rules.spec("layers/mlp/w1") == P()
    tree = {"attn": {"wq": jnp.zeros((4, 4))}, "b": jnp.zeros((2,))}
    specs = rules.tree_specs(tree)
    assert specs["attn"]["wq"] == P("fsdp", "tp")
    assert specs["b"] == P()


def test_shard_pytree_places_leaves():
    mesh = par.create_mesh(dp=2, tp=4)
    rules = par.ShardingRules([(r".*w$", P(None, "tp")), (r".*", P())])
    tree = {"w": jnp.ones((4, 8)), "b": jnp.ones((3,))}
    placed = par.shard_pytree(tree, mesh, rules)
    assert placed["w"].sharding.spec == P(None, "tp")
    assert placed["b"].sharding.spec == P()


def test_collectives_allreduce_ring():
    mesh = par.create_mesh(dp=8)
    x = jnp.arange(8.0)

    f = shard_map(lambda v: par.allreduce(v, "dp"),
                  mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    out = f(x)
    assert np.allclose(np.asarray(out), np.full(8, x.sum()))

    g = shard_map(lambda v: par.ppermute_ring(v, "dp", 1),
                  mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    out = np.asarray(g(x))
    assert np.allclose(out, np.roll(np.arange(8.0), 1))


def test_train_step_dp_matches_single_device():
    """dp-sharded step must produce the same params as an unsharded one
    — the rebuild of 'threaded engine == naive engine' equivalence."""
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(4, 3), jnp.float32)
    xs = jnp.asarray(rng.randn(16, 4), jnp.float32)
    ys = jnp.asarray(rng.randn(16, 3), jnp.float32)

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2)

    tx = optax.sgd(0.1)
    mesh = par.create_mesh(dp=8)
    rules = par.ShardingRules([(r".*", P())])
    state = par.init_state({"w": w}, tx, mesh, rules)
    step = par.make_train_step(loss_fn, tx, mesh, rules)
    state2, loss = step(state, (xs, ys))

    # single-device reference
    grads = jax.grad(loss_fn)({"w": w}, (xs, ys))
    ref_w = w - 0.1 * grads["w"]
    assert np.allclose(np.asarray(state2.params["w"]), np.asarray(ref_w),
                       atol=1e-6)
    assert float(loss) > 0
    assert int(state2.step) == 1


def test_train_step_tp_sharded_params():
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(8, 8), jnp.float32)
    xs = jnp.asarray(rng.randn(16, 8), jnp.float32)
    ys = jnp.asarray(rng.randn(16, 8), jnp.float32)

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    tx = optax.adam(1e-2)
    mesh = par.create_mesh(dp=2, tp=4)
    rules = par.ShardingRules([(r".*w$", P(None, "tp"))])
    state = par.init_state({"w": w}, tx, mesh, rules)
    assert state.params["w"].sharding.spec == P(None, "tp")
    # adam moments inherit the tp sharding via propagation
    mu = state.opt_state[0].mu["w"]
    assert mu.sharding.spec == P(None, "tp")
    step = par.make_train_step(loss_fn, tx, mesh, rules)
    s1, l1 = step(state, (xs, ys))
    s2, l2 = step(s1, (xs, ys))
    assert float(l2) < float(l1)
    assert s2.params["w"].sharding.spec == P(None, "tp")


def test_grad_accum_equals_big_batch():
    rng = np.random.RandomState(2)
    w = jnp.asarray(rng.randn(4, 2), jnp.float32)
    xs = jnp.asarray(rng.randn(16, 4), jnp.float32)
    ys = jnp.asarray(rng.randn(16, 2), jnp.float32)

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    tx = optax.sgd(0.1)
    mesh = par.create_mesh(dp=8)
    rules = par.ShardingRules([(r".*", P())])

    state = par.init_state({"w": w}, tx, mesh, rules)
    step1 = par.make_train_step(loss_fn, tx, mesh, rules)
    s_big, _ = step1(state, (xs, ys))

    state = par.init_state({"w": w}, tx, mesh, rules)
    step2 = par.make_train_step(loss_fn, tx, mesh, rules, grad_accum=2)
    mb = (xs.reshape(2, 8, 4), ys.reshape(2, 8, 2))
    s_acc, _ = step2(state, mb)
    assert np.allclose(np.asarray(s_big.params["w"]),
                       np.asarray(s_acc.params["w"]), atol=1e-6)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    b, h, s, d = 2, 4, 64, 16
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_vs_dense(qkv, causal):
    q, k, v = qkv
    ref = dense_attention(q, k, v, causal=causal)
    blk = blockwise_attention(q, k, v, causal=causal, kv_block=16)
    assert np.allclose(np.asarray(ref), np.asarray(blk), atol=1e-5)


def test_blockwise_gqa_and_ragged_block(qkv):
    q, k, v = qkv
    k2, v2 = k[:, :2], v[:, :2]
    ref = dense_attention(q, k2, v2, causal=True)
    blk = blockwise_attention(q, k2, v2, causal=True, kv_block=48)
    assert np.allclose(np.asarray(ref), np.asarray(blk), atol=1e-5)


def test_flash_attention_dispatches(qkv):
    q, k, v = qkv
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    assert np.allclose(np.asarray(ref), np.asarray(out), atol=1e-5)


def test_flash_attention_kernel_failure_raises(qkv, monkeypatch):
    """Where the shapes pick the Pallas kernel, its failure is the
    caller's: the blockwise scan must not stand in for it (a cell
    would time the stand-in as "flash attention")."""
    from mxtpu.ops import attention
    q, k, v = qkv
    assert attention._flash_path(q.shape, k.shape[2]) == "blockwise"

    def boom(*a):
        raise RuntimeError("mosaic refused the block shapes")

    monkeypatch.setattr(attention, "_flash_path", lambda *a: "pallas")
    monkeypatch.setattr(attention, "_tpu_pallas_flash", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        flash_attention(q, k, v, causal=True)


@pytest.mark.parametrize("axes,batch", [
    ({"dp": 2, "tp": 4}, 4),       # tp > n_kv_heads: heads stay whole
    ({"fsdp": 4, "tp": 2}, 3),     # 4 does not divide 3 rows: they do
    ({"dp": 2, "fsdp": 2, "tp": 2}, 4)])
def test_llama_flash_on_a_mesh_matches_no_mesh(axes, batch):
    """With ``mesh=`` flash attention runs per device under shard_map
    (the Pallas kernel is a custom call); the layout is cut to what
    divides, so every mesh the plain program accepts gives its loss."""
    from dataclasses import replace
    from mxtpu.models import llama
    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                  attn_impl="flash", n_layers=1)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = {"tokens": jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, 32)), jnp.int32)}
    want = jax.jit(llama.loss_fn(cfg))(params, tokens)
    mesh = par.create_mesh(**axes)
    with par.use_mesh(mesh):
        got = jax.jit(llama.loss_fn(cfg, mesh))(
            par.shard_pytree(params, mesh, llama.sharding_rules(cfg)),
            tokens)
    assert np.allclose(float(got), float(want), rtol=1e-5), (got, want)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_vs_dense(qkv, causal):
    q, k, v = qkv
    mesh = par.create_mesh(sp=8)
    spec = P(None, None, "sp", None)
    f = shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, axis_name="sp",
                                        causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = f(q, k, v)
    ref = dense_attention(q, k, v, causal=causal)
    assert np.allclose(np.asarray(ref), np.asarray(out), atol=1e-5)


def test_ring_attention_jitted_under_mesh(qkv):
    q, k, v = qkv
    mesh = par.create_mesh(dp=2, sp=4)
    spec = P("dp", None, "sp", None)
    f = jax.jit(shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, axis_name="sp",
                                        causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    out = f(q, k, v)
    ref = dense_attention(q, k, v, causal=True)
    assert np.allclose(np.asarray(ref), np.asarray(out), atol=1e-5)


def test_sharded_embedding_lookup_matches_dense_and_grads():
    """SURVEY §2.4 sparse row: table row-sharded over the mesh, lookup
    assembles rows via one psum; fwd == dense gather, and the table
    grad is the exact scatter-add (checked vs jax.grad of the dense
    lookup)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.parallel import mesh as pmesh
    from mxtpu.parallel.sparse_embed import (shard_embedding,
                                             sharded_embedding_lookup)

    mesh = pmesh.create_mesh(dp=2, fsdp=4, devices=jax.devices()[:8])
    V, D = 32, 16
    rng = np.random.default_rng(0)
    table_h = rng.standard_normal((V, D)).astype(np.float32)
    ids_h = np.array([[0, 31, 7], [8, 8, 25]], np.int32)

    table = shard_embedding(jnp.asarray(table_h), mesh, axis="fsdp")
    assert "fsdp" in tuple(table.sharding.spec)
    ids = jnp.asarray(ids_h)

    out = jax.jit(lambda t, i: sharded_embedding_lookup(
        t, i, mesh, axis="fsdp"))(table, ids)
    np.testing.assert_allclose(np.asarray(out), table_h[ids_h],
                               rtol=1e-6)

    def loss_sharded(t):
        return (sharded_embedding_lookup(t, ids, mesh, "fsdp") ** 2).sum()

    def loss_dense(t):
        return (t[ids] ** 2).sum()

    g_sharded = jax.jit(jax.grad(loss_sharded))(table)
    g_dense = jax.grad(loss_dense)(jnp.asarray(table_h))
    np.testing.assert_allclose(np.asarray(g_sharded),
                               np.asarray(g_dense), rtol=1e-5)


def test_moe_ffn_reference_semantics():
    """parallel.moe (expert parallelism, round 4): the capacity-based
    einsum dispatch must equal a naive per-token gather reference when
    nothing is dropped, drop tokens (zero contribution) when capacity
    binds, and produce a differentiable load-balance aux."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.parallel import moe

    T, d, h, E, K = 32, 16, 32, 4, 2
    params = moe.init_moe_params(jax.random.PRNGKey(0), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, d))

    out, aux = moe.moe_ffn(params, x, top_k=K, capacity_factor=8.0)
    # naive reference: every token through its top-k experts
    probs = jax.nn.softmax((x @ params["gate"]).astype(jnp.float32), -1)
    gv, idx = jax.lax.top_k(probs, K)
    gv = gv / gv.sum(-1, keepdims=True)
    ref = np.zeros((T, d), np.float32)
    for t in range(T):
        for k in range(K):
            e = int(idx[t, k])
            xe = x[t]
            he = jax.nn.silu(xe @ params["w_gate"][e]) * \
                (xe @ params["w_up"][e])
            ref[t] += float(gv[t, k]) * np.asarray(
                he @ params["w_down"][e])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                               atol=2e-5)
    assert 0.5 < float(aux) < 4.0          # ≈1 at uniform routing

    # the dense dropless path (serving) == routed path when nothing
    # drops, and == the naive reference
    out_d, aux_d = moe.moe_ffn_dense(params, x, top_k=K)
    np.testing.assert_allclose(np.asarray(out_d), ref, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(float(aux_d), float(aux), rtol=1e-6)

    # capacity binds: C=1 drops most tokens; dropped rows are ZERO
    out_c, _ = moe.moe_ffn(params, x, top_k=1, capacity_factor=1e-9)
    kept = np.abs(np.asarray(out_c)).sum(-1) > 0
    assert kept.sum() <= E                  # ≤1 token per expert
    # differentiable end to end (grads flow to gate and experts)
    g = jax.grad(lambda p: moe.moe_ffn(p, x, top_k=K,
                                       capacity_factor=8.0)[0].sum() +
                 moe.moe_ffn(p, x, top_k=K,
                             capacity_factor=8.0)[1])(params)
    assert float(jnp.abs(g["gate"]).sum()) > 0
    assert float(jnp.abs(g["w_down"]).sum()) > 0


def test_moe_expert_parallel_matches_unsharded():
    """Expert parallelism: the SAME moe_ffn on an ep-sharded mesh must
    reproduce the unsharded math exactly, with the expert banks really
    split over ep."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.parallel import moe, mesh as pmesh

    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs 8 (virtual) devices")
    T, d, h, E, K = 64, 16, 32, 4, 2
    params = moe.init_moe_params(jax.random.PRNGKey(2), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(3), (T, d))
    ref, ref_aux = jax.jit(
        lambda p, xx: moe.moe_ffn(p, xx, top_k=K,
                                  capacity_factor=2.0))(params, x)

    mesh = pmesh.create_mesh(dp=2, ep=2, tp=2)
    espec = {"gate": P(), "w_gate": P("ep", None, None),
             "w_up": P("ep", None, None), "w_down": P("ep", None, None)}
    sp = jax.tree.map(
        lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
        params, espec)
    sx = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp"))))
    out, aux = jax.jit(
        lambda p, xx: moe.moe_ffn(p, xx, top_k=K, capacity_factor=2.0,
                                  mesh=mesh))(sp, sx)
    assert len(sp["w_gate"].sharding.device_set) == 8
    assert sp["w_gate"].sharding.shard_shape(
        sp["w_gate"].shape)[0] == E // 2     # experts really split
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-6)


def test_moe_llama_trains_and_serves():
    """MoE llama end to end: cfg.moe_experts swaps every FFN for the
    expert bank; the sharded train step runs on a dp×ep×tp mesh with
    the aux loss in, and greedy decode matches the full forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from dataclasses import replace
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep

    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs 8 (virtual) devices")
    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                  attn_impl="dense", remat=False, moe_experts=4,
                  moe_top_k=2, moe_capacity=4.0)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["w_gate"].shape[1] == 4   # expert bank
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 24)), jnp.int32)

    mesh = pmesh.create_mesh(dp=2, ep=2, tp=2)
    rules = llama.sharding_rules(cfg)
    tx = optax.adam(1e-2)
    state = pstep.init_state(params, tx, mesh, rules)
    step = pstep.make_train_step(llama.loss_fn(cfg, mesh), tx, mesh,
                                 rules)
    losses = []
    for _ in range(6):
        state, loss = step(state, {"tokens": tokens})
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses   # it trains
    # expert banks really ep-sharded through the step
    wg = state.params["layers"]["w_gate"]
    assert wg.sharding.shard_shape(wg.shape)[1] == 2  # E=4 over ep=2

    # decode == forward (greedy), single device
    p2 = llama.init_params(cfg, jax.random.PRNGKey(5))
    prompt = tokens[:2, :6]
    gen = jax.jit(lambda p, t: llama.generate(cfg, p, t, 4))(p2, prompt)
    seq = np.asarray(gen)
    for i in range(6, 10):
        lg = llama.forward(cfg, p2, jnp.asarray(seq[:, :i]))
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(lg[:, -1], -1)), seq[:, i],
            err_msg=f"pos {i}")


def test_gpipe_matches_sequential_llama_layers():
    """VERDICT r1 #9: pp=2 GPipe schedule over llama-tiny's layer stack
    matches the 1-stage sequential numerics, forward AND backward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dataclasses import replace
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh
    from mxtpu.parallel.pipeline import gpipe

    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                  attn_impl="dense", remat=False, n_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    layers = params["layers"]
    B, Ssq, D = 4, 16, cfg.dim
    x = jax.random.normal(jax.random.PRNGKey(1), (B, Ssq, D),
                          jnp.float32)
    cos, sin = llama.rope_tables(cfg, Ssq)

    def layer_fn(lp, xx):
        # _layer returns (x, moe_aux); the dense stack only pipelines x
        return llama._layer(cfg, None, cos, sin, xx, lp)[0]

    def seq_apply(layers_p, xx):
        def body(c, lp):
            return layer_fn(lp, c), None
        return jax.lax.scan(body, xx, layers_p)[0]

    ref = seq_apply(layers, x)

    mesh = pmesh.create_mesh(dp=1, pp=2, devices=jax.devices()[:2])
    out = jax.jit(lambda lp, xx: gpipe(
        layer_fn, lp, xx, mesh=mesh, n_microbatches=2, axis="pp"))(
            layers, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # backward through the pipeline == backward through the stack
    g_ref = jax.grad(lambda lp: (seq_apply(lp, x) ** 2).sum())(layers)
    g_pp = jax.jit(jax.grad(lambda lp: (gpipe(
        layer_fn, lp, x, mesh=mesh, n_microbatches=2,
        axis="pp") ** 2).sum()))(layers)
    for kk in g_ref:
        np.testing.assert_allclose(
            np.asarray(g_pp[kk]), np.asarray(g_ref[kk]),
            rtol=5e-4, atol=5e-5, err_msg=kk)


def test_gpipe_four_stages_and_s1_fallback():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.parallel import mesh as pmesh
    from mxtpu.parallel.pipeline import gpipe

    # simple affine layers: y = x @ w + b
    L, D = 8, 6
    k = jax.random.PRNGKey(0)
    ws = jax.random.normal(k, (L, D, D)) * 0.1
    bs = jax.random.normal(jax.random.PRNGKey(1), (L, D)) * 0.1
    params = {"w": ws, "b": bs}
    x = jax.random.normal(jax.random.PRNGKey(2), (8, D))

    def layer_fn(lp, xx):
        return jnp.tanh(xx @ lp["w"] + lp["b"])

    def seq(xx):
        for i in range(L):
            xx = layer_fn({"w": ws[i], "b": bs[i]}, xx)
        return xx
    ref = seq(x)

    mesh4 = pmesh.create_mesh(dp=1, pp=4, devices=jax.devices()[:4])
    out4 = gpipe(layer_fn, params, x, mesh=mesh4, n_microbatches=4,
                 axis="pp")
    np.testing.assert_allclose(np.asarray(out4), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # S=1 mesh: plain scan fallback
    mesh1 = pmesh.create_mesh(dp=1, devices=jax.devices()[:1])
    out1 = gpipe(layer_fn, params, x, mesh=mesh1, n_microbatches=2,
                 axis="pp")
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
