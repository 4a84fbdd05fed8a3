"""Tools tests: parse_log, launch.py local tracker + dist kvstore
invariants (the reference's tests/nightly/dist_sync_kvstore.py pattern:
the local tracker forks workers on one host, SURVEY.md §4.2)."""
import glob
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
LAUNCH = os.path.join(REPO, "tools", "launch.py")


def test_parse_log():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import parse_log
    rows = parse_log.parse([
        "INFO:root:Epoch[0] Train-accuracy=0.5",
        "INFO:root:Epoch[0] Time cost=1.25",
        "INFO:root:Epoch[1] Train-accuracy=0.75",
        "INFO:root:Epoch[1] Validation-accuracy=0.7",
    ])
    assert rows[0]["train-accuracy"] == 0.5
    assert rows[0]["time"] == 1.25
    assert rows[1]["validation-accuracy"] == 0.7


def test_launch_local_env_wiring(tmp_path):
    worker = tmp_path / "worker.py"
    # write to per-rank files: concurrent stdout interleaves
    worker.write_text(textwrap.dedent(f"""
        import os
        rank = os.environ["DMLC_WORKER_ID"]
        with open({str(tmp_path)!r} + "/rank" + rank, "w") as f:
            f.write(os.environ["DMLC_NUM_WORKER"] + " " +
                    os.environ["DMLC_PS_ROOT_URI"])
    """))
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "3", "--launcher", "local", "--",
         sys.executable, str(worker)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for rank in range(3):
        content = (tmp_path / f"rank{rank}").read_text().split()
        assert content[0] == "3"
        assert content[1] == "127.0.0.1"


def test_launch_local_refuses_to_share_chips(monkeypatch):
    """A chip belongs to one process: N local workers that would all
    open this host's chips are refused with the reason, workers pinned
    to the CPU are not, and the launcher asks a child instead of
    importing jax itself."""
    import types
    from tools import launch
    assert "jax" not in vars(launch)
    probes = []

    def fake_run(cmd, env=None, **kw):
        probes.append(cmd)
        return types.SimpleNamespace(returncode=0, stdout="tpu\n",
                                     stderr="")

    monkeypatch.setattr(launch.subprocess, "run", fake_run)
    launch._refuse_shared_chips({"JAX_PLATFORMS": "cpu"}, 4)
    launch._refuse_shared_chips({}, 1)
    assert probes == []
    with pytest.raises(SystemExit, match="one process at a time"):
        launch._refuse_shared_chips({}, 4)
    assert "jax.devices()" in probes[0][-1]


@pytest.mark.slow
def test_dist_sync_kvstore_invariants(tmp_path):
    """After a synchronized push from W workers, the pulled value is
    W * grad (reference dist_sync_kvstore.py assertion)."""
    worker = tmp_path / "kv_worker.py"
    worker.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxtpu as mx
        from mxtpu.parallel import dist
        dist.initialize()
        import numpy as np
        kv = mx.kv.create("dist_sync")
        rank, W = kv.rank, kv.num_workers
        assert W == 2, W
        kv.init("w", mx.nd.zeros((4,)))
        kv.push("w", mx.nd.ones((4,)) * (rank + 1))   # 1 + 2 = 3
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)
        expected = 3.0
        assert np.allclose(out.asnumpy(), expected), out.asnumpy()
        kv.barrier()
        print("KVOK", rank, flush=True)
    """))
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--env", "JAX_PLATFORMS=cpu", "--",
         sys.executable, str(worker)],
        capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert out.stdout.count("KVOK") == 2


def test_opperf_runs():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "opperf",
                                      "opperf.py"),
         "--ops", "relu,sum", "--iters", "3"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-1000:]
    assert "relu" in out.stdout


@pytest.mark.slow   # ~7s; dist_tests runs test_tools.py in full
def test_im2rec_exists_and_diagnose():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-500:]
    assert "mxtpu version" in out.stdout


@pytest.mark.slow
def test_dist_allreduce_fast_path_matches_veneer(tmp_path):
    """VERDICT r1 #3: Trainer's dist grad reduction must ride ONE jitted
    collective program (no per-param host hops) and agree bitwise with
    the KVStore veneer."""
    worker = tmp_path / "fast_worker.py"
    worker.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import mxtpu as mx
        from mxtpu.parallel import dist
        dist.initialize()
        kv = mx.kv.create("dist_sync")
        rank, W = kv.rank, kv.num_workers
        assert W == 2, W

        rng = np.random.default_rng(rank)
        grads = [mx.nd.array(rng.standard_normal((5, 3))
                             .astype(np.float32)),
                 mx.nd.array(rng.standard_normal((7,))
                             .astype(np.float32))]
        expected = [kv._allreduce(g).asnumpy() for g in grads]

        for step in range(3):   # same signature → one compile total
            fast = kv._allreduce_tree([g._data for g in grads])
            for f, e in zip(fast, expected):
                assert (np.asarray(f) == e).all(), (step, f, e)
        assert kv.num_collective_compiles == 1, \\
            kv.num_collective_compiles

        # end-to-end Gluon Trainer drive: both ranks end bit-identical
        from mxtpu import gluon, autograd
        from mxtpu.gluon import nn
        net = nn.Dense(2, in_units=3)
        net.initialize()  # deterministic seed → same init on all ranks
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {{"learning_rate": 0.1}}, kvstore=kv)
        x = mx.nd.array(rng.standard_normal((4, 3)).astype(np.float32))
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        tr.step(4)
        w = net.weight.data().asnumpy()
        got = kv._allreduce(mx.nd.array(w)).asnumpy()
        assert np.allclose(got, W * w, rtol=1e-6), "ranks diverged"
        kv.barrier()
        print("FASTOK", rank, flush=True)
    """))
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--env", "JAX_PLATFORMS=cpu", "--",
         sys.executable, str(worker)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert out.stdout.count("FASTOK") == 2


@pytest.mark.slow
def test_dist_async_kvstore_invariants(tmp_path):
    """Reference tests/nightly/dist_async_kvstore.py invariants:
    per-push server-side updates with NO barrier (one worker's push is
    visible without the other pushing), server-side optimizer via
    set_optimizer, and row_sparse_pull fetching only requested rows."""
    worker = tmp_path / "async_worker.py"
    worker.write_text(textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import mxtpu as mx
        from mxtpu.parallel import dist
        dist.initialize()
        kv = mx.kv.create("dist_async")
        rank, W = kv.rank, kv.num_workers
        assert W == 2, W
        kv.init("w", mx.nd.zeros((4,)))
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0,
                                          rescale_grad=1.0))

        if rank == 0:
            # ONLY rank 0 pushes: async semantics means the update must
            # be visible to BOTH ranks without rank 1 pushing anything
            kv.push("w", mx.nd.ones((4,)))
        kv.barrier()          # order the test, not the update path
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)
        # sgd with lr 1.0: w = 0 - 1.0 * grad = -1
        assert np.allclose(out.asnumpy(), -1.0), (rank, out.asnumpy())

        # no-barrier interleaving: both ranks push; total applied
        # updates = 2 regardless of order
        kv.push("w", mx.nd.ones((4,)) * 0.5)
        kv.barrier()
        kv.pull("w", out=out)
        assert np.allclose(out.asnumpy(), -2.0), (rank, out.asnumpy())

        # sparse: pull only requested rows of a (8, 3) table
        kv.init("emb", mx.nd.array(
            np.arange(24, dtype=np.float32).reshape(8, 3)))
        from mxtpu.ndarray.sparse import RowSparseNDArray
        rs = mx.nd.sparse.row_sparse_array(
            (np.zeros((1, 3), np.float32), [0]), shape=(8, 3))
        kv.row_sparse_pull("emb", out=rs, row_ids=[5, 2, 5])
        assert rs.indices.asnumpy().tolist() == [2, 5]
        assert np.allclose(rs.data.asnumpy(),
                           [[6, 7, 8], [15, 16, 17]])
        kv.barrier()
        print("ASYNCOK", rank, flush=True)
    """))
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--env", "JAX_PLATFORMS=cpu", "--",
         sys.executable, str(worker)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert out.stdout.count("ASYNCOK") == 2


def test_dist_async_single_process():
    """dist_async on one process still provides PS semantics (server
    thread + loopback client)."""
    import numpy as np
    import mxtpu as mx
    kv = mx.kv.create("dist_async")
    kv.init(9, mx.nd.ones((3,)))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5,
                                      rescale_grad=1.0))
    kv.push(9, mx.nd.ones((3,)))
    out = mx.nd.zeros((3,))
    kv.pull(9, out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.5 * np.ones(3))
    with pytest.raises(Exception):
        kv.set_updater(lambda k, g, w: None)
    # duplicate init keeps the base-class contract
    with pytest.raises(Exception):
        kv.init(9, mx.nd.ones((3,)))
    # row_sparse_pull without row_ids fills ALL rows on a sparse out
    kv.init("tbl", mx.nd.array(np.arange(6, dtype=np.float32)
                               .reshape(3, 2)))
    rs = mx.nd.sparse.row_sparse_array(
        (np.zeros((1, 2), np.float32), [0]), shape=(3, 2))
    kv.row_sparse_pull("tbl", out=rs)
    assert rs.indices.asnumpy().tolist() == [0, 1, 2]
    np.testing.assert_allclose(rs.data.asnumpy(),
                               np.arange(6).reshape(3, 2))


def test_trainer_update_on_kvstore_async():
    """Trainer with dist_async routes updates THROUGH the server
    (push grad -> server-side SGD -> pull weight); no local update."""
    import numpy as np
    import mxtpu as mx
    from mxtpu import gluon, autograd
    from mxtpu.gluon import nn
    net = nn.Dense(1, in_units=2, use_bias=False)
    net.initialize()
    kv = mx.kv.create("dist_async")
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=kv)
    x = mx.nd.array(np.ones((4, 2), np.float32))
    w0 = net.weight.data().asnumpy()
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(4)
    w1 = net.weight.data().asnumpy()
    # dL/dW = sum_b x = 4 per element, rescaled by 1/4 -> grad 1;
    # server SGD: w - 0.1 * 1
    np.testing.assert_allclose(w1, w0 - 0.1, rtol=1e-5)
    # second step: server state persists, same delta again
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(4)
    np.testing.assert_allclose(net.weight.data().asnumpy(), w0 - 0.2,
                               rtol=1e-5)


def test_two_async_stores_coexist():
    """Session namespacing: a second dist_async store must not clobber
    a live first store's keys or optimizer."""
    import numpy as np
    import mxtpu as mx
    kv1 = mx.kv.create("dist_async")
    kv1.init("shared_name", mx.nd.ones((2,)))
    kv2 = mx.kv.create("dist_async")
    kv2.init("shared_name", mx.nd.zeros((2,)))   # same name, own ns
    kv1.push("shared_name", mx.nd.ones((2,)))    # accumulate: 1+1
    o1, o2 = mx.nd.zeros((2,)), mx.nd.zeros((2,))
    kv1.pull("shared_name", out=o1)
    kv2.pull("shared_name", out=o2)
    np.testing.assert_allclose(o1.asnumpy(), [2, 2])
    np.testing.assert_allclose(o2.asnumpy(), [0, 0])


def test_ps_wire_codec_roundtrip():
    """The PS wire format is a SAFE tag codec (no pickle for data):
    every message shape the protocol uses must round-trip, and foreign
    bytes must be rejected rather than interpreted (ADVICE r2)."""
    import numpy as np
    from mxtpu.kvstore import server as psrv
    cases = [
        ("ping",),
        ("init", (0, "w"), np.arange(6, dtype=np.float32).reshape(2, 3)),
        ("push_many", [((0, "a"), np.ones((1,), np.float16)),
                       ((0, "b"), np.zeros((2, 2), np.int64))]),
        ("row_pull", (1, "tbl"), [0, 2, 5]),
        ("set_optimizer", 0, b"\x80\x04opaque-blob"),
        ("ok", None, True, False, 3.5, -7, "err msg",
         np.array(2.5, np.float64)),          # 0-d array
    ]
    def same(a, b):
        if isinstance(b, np.ndarray):
            assert isinstance(a, np.ndarray)
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)
        elif isinstance(b, (tuple, list)):
            assert type(a) is type(b) and len(a) == len(b)
            for x, y in zip(a, b):
                same(x, y)
        else:
            assert a == b and type(a) is type(b)

    for msg in cases:
        out = bytearray()
        psrv._enc(msg, out)
        dec, pos = psrv._dec(memoryview(bytes(out)), 0)
        assert pos == len(out)
        same(dec, msg)
    # a pickle frame (or any foreign bytes) must raise, never execute
    import pickle
    evil = pickle.dumps(("push", 0, "x"))
    with pytest.raises(Exception):
        psrv._dec(memoryview(evil), 0)
    # unpicklable-on-purpose: arbitrary objects are not wire-safe
    with pytest.raises(TypeError):
        psrv._enc(("cmd", object()), bytearray())


def test_ps_hmac_and_set_optimizer_gating(monkeypatch):
    """With MXTPU_PS_SECRET set, frames are HMAC-authenticated end to
    end; without it, set_optimizer is refused on non-loopback binds
    (the one pickled payload must never come from an untrusted peer)."""
    import pickle
    import numpy as np
    import mxtpu as mx
    from mxtpu.kvstore import server as psrv
    monkeypatch.setenv("MXTPU_PS_SECRET", "test-secret-r3")
    monkeypatch.setenv("MXTPU_PS_PORT_OFFSET", "311")
    srv = psrv.KVStoreServer("127.0.0.1", 9402)
    try:
        cl = psrv.ServerClient("127.0.0.1", 9402)
        assert cl.request("ping")[1] == "mxtpu-ps"
        cl.request("init", "k", np.ones((2,), np.float32))
        blob = pickle.dumps(mx.optimizer.SGD(learning_rate=1.0))
        cl.request("set_optimizer", None, blob)   # authed → accepted
        cl.request("push", "k", np.ones((2,), np.float32))
        _, val = cl.request("pull", "k")
        np.testing.assert_allclose(val, [0.0, 0.0])  # 1 - 1.0*1
        # a client with the WRONG secret must be rejected
        monkeypatch.setenv("MXTPU_PS_SECRET", "wrong")
        bad = psrv.ServerClient("127.0.0.1", 9402)
        with pytest.raises(Exception):
            bad.request("ping")
        bad.close()
        cl.close()
    finally:
        srv.stop()
    # unauthenticated peer on a non-loopback bind: refuse the pickle op
    monkeypatch.delenv("MXTPU_PS_SECRET")
    srv2 = psrv.KVStoreServer("127.0.0.1", 9403)
    try:
        srv2._loopback = False    # simulate an external-interface bind
        reply = srv2._handle(("set_optimizer", None, blob), authed=False)
        assert reply[0] == "err" and "refused" in reply[1]
        assert srv2._handle(("ping",), authed=False)[0] == "ok"
    finally:
        srv2.stop()


def test_trainer_async_propagates_all_hyperparams():
    """Mutating a non-lr hyperparameter (wd) on the live optimizer must
    reach the server-side copy on the next step (ADVICE r2: the change
    signature covers ALL hyperparameters, not just lr/rescale)."""
    import numpy as np
    import mxtpu as mx
    from mxtpu import gluon, autograd
    from mxtpu.gluon import nn
    net = nn.Dense(1, in_units=2, use_bias=False)
    net.initialize()
    kv = mx.kv.create("dist_async")
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "wd": 0.0}, kvstore=kv)
    x = mx.nd.array(np.ones((4, 2), np.float32))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(4)
    w1 = net.weight.data().asnumpy()
    tr._optimizer.wd = 0.5              # NOT lr, NOT rescale_grad
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(4)
    w2 = net.weight.data().asnumpy()
    # server SGD with wd: w - lr*(grad + wd*w) = w*(1-lr*wd) - lr*grad
    np.testing.assert_allclose(w2, w1 * (1 - 0.1 * 0.5) - 0.1,
                               rtol=1e-5)
    # the fingerprint must be STABLE across steps when nothing changed
    # (param weights mutate every step and live in param_dict — they
    # must not be part of the signature, or every step re-ships the
    # optimizer)
    fp = tr._opt_fingerprint()
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(4)
    assert tr._opt_fingerprint() == fp


@pytest.mark.slow
def test_global_mesh_across_processes(tmp_path):
    """VERDICT r2 #6: a real pod is multi-process AND multi-device at
    once (ICI within a slice + DCN across). Two processes with 4 CPU
    devices each form ONE global dp2xfsdp2xtp2 mesh; the sharded llama
    train step over it must reproduce the single-process 8-device
    trajectory."""
    import json
    import numpy as np

    # single-process 8-device reference (this pytest process has the
    # virtual 8-device mesh from conftest)
    import jax
    import jax.numpy as jnp
    import optax
    from dataclasses import replace
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep

    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                  attn_impl="dense", remat=False)
    rules = llama.sharding_rules(cfg)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(4), (8, 32), 0,
                           cfg.vocab_size))
    mesh = pmesh.create_mesh(dp=2, fsdp=2, tp=2)
    state = pstep.init_state(params, optax.sgd(0.1), mesh, rules)
    step = pstep.make_train_step(llama.loss_fn(cfg), optax.sgd(0.1),
                                 mesh, rules)
    ref_eager = float(jnp.mean(llama.forward(
        cfg, params, jnp.asarray(tokens)).astype(jnp.float32)))
    ref = []
    for _ in range(3):
        state, loss = step(state, {"tokens": jnp.asarray(tokens)})
        ref.append(float(loss))

    np.save(tmp_path / "tokens.npy", tokens)
    worker = tmp_path / "gmesh_worker.py"
    worker.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        from mxtpu.parallel import dist
        dist.initialize()
        assert jax.process_count() == 2
        assert len(jax.local_devices()) == 4, jax.local_devices()
        assert len(jax.devices()) == 8, "global mesh must see 8 devices"
        import json
        import numpy as np
        import jax.numpy as jnp
        import optax
        from dataclasses import replace
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mxtpu.models import llama
        from mxtpu.parallel import mesh as pmesh, step as pstep

        cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                      attn_impl="dense", remat=False)
        rules = llama.sharding_rules(cfg)
        mesh = pmesh.create_mesh(dp=2, fsdp=2, tp=2)   # global: 2x4 devs
        # every process holds the same host values; device_put onto the
        # GLOBAL sharding hands each process its addressable shards
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jax.device_put(
                leaf, NamedSharding(
                    mesh, rules.spec("/".join(
                        str(getattr(k, "key", k)) for k in path)))),
            jax.tree.map(np.asarray,
                         llama.init_params(cfg, jax.random.PRNGKey(3))))
        tokens = np.load({str(tmp_path / "tokens.npy")!r})
        batch = {{"tokens": jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"))))}}
        state = pstep.init_state(params, optax.sgd(0.1), mesh, rules)
        step = pstep.make_train_step(llama.loss_fn(cfg),
                                     optax.sgd(0.1), mesh, rules)
        losses = []
        for _ in range(3):
            state, loss = step(state, batch)
            losses.append(float(jax.device_get(loss)))
        # params really span both processes: a wq shard lives on 4
        # local devices here and 4 remote ones
        wq = state.params["layers"]["wq"]
        assert len(wq.sharding.device_set) == 8
        assert len([d for d in wq.sharding.device_set
                    if d.process_index == jax.process_index()]) == 4
        out = {{"GMESH": losses}}

        # the GLUON surface on the same global mesh (VERDICT r2 weak
        # #7: the KVStore veneer assumed one device per process; the
        # fused step has no such assumption)
        import mxtpu as mx
        from mxtpu import gluon
        from mxtpu.gluon.model_zoo import GluonLlama
        net = GluonLlama(cfg)
        net.load_pytree(jax.tree.map(
            np.asarray, llama.init_params(cfg, jax.random.PRNGKey(3))))
        net.hybridize()
        net.shard(mesh, rules)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {{"learning_rate": 0.1, "wd": 0.0}})
        fused = tr.make_fused_step(net)
        tok_nd = mx.nd.array(tokens)
        # EAGER inference through the globally-sharded net (advisor r3
        # #2): the input is a committed process-local device array, so
        # placement must take the global_device_put host-hop — plain
        # device_put onto the non-addressable mesh raises.
        y = net(tok_nd)
        out["GEAGER"] = float(y.astype("float32").mean().asscalar())
        g_losses = [float(fused(tok_nd, tok_nd).asscalar())
                    for _ in range(3)]
        out["GGLUON"] = g_losses
        # per-rank result FILES: gloo's C++ stdout writes splice into
        # python lines, so stdout parsing is unreliable
        with open({str(tmp_path)!r} +
                  f"/gmesh{{jax.process_index()}}.json", "w") as f:
            json.dump(out, f)
        dist.shutdown()
    """))
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--env", "JAX_PLATFORMS=cpu",
         "--env", "XLA_FLAGS=--xla_force_host_platform_device_count=4",
         "--", sys.executable, str(worker)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    for rank in range(2):
        with open(tmp_path / f"gmesh{rank}.json") as f:
            res = json.load(f)
        for tag in ("GMESH", "GGLUON"):
            np.testing.assert_allclose(res[tag], ref, rtol=2e-5,
                                       atol=1e-6,
                                       err_msg=f"rank{rank} {tag}")
        np.testing.assert_allclose(res["GEAGER"], ref_eager, rtol=2e-5,
                                   atol=1e-6,
                                   err_msg=f"rank{rank} GEAGER")


@pytest.mark.slow
def test_dist_compressed_allreduce_packed_wire(tmp_path):
    """allreduce_grads with 2-bit compression crosses processes as
    PACKED bytes and both ranks see the summed ternary grads."""
    worker = tmp_path / "comp_worker.py"
    worker.write_text(textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import mxtpu as mx
        from mxtpu.parallel import dist
        dist.initialize()
        kv = mx.kv.create("dist_sync")
        rank, W = kv.rank, kv.num_workers
        kv.set_gradient_compression({{"type": "2bit",
                                      "threshold": 0.5}})
        from mxtpu.gluon import nn
        from mxtpu import gluon, autograd
        net = nn.Dense(1, in_units=3, use_bias=False)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {{"learning_rate": 0.0}}, kvstore=kv)
        # grads: rank0 pushes +0.9 (-> +0.5 ternary), rank1 -0.7
        # (-> -0.5): sum = 0 on every element
        g = np.full((1, 3), 0.9 if rank == 0 else -0.7, np.float32)
        x = mx.nd.array(g)
        with autograd.record():
            loss = net(x).sum()   # dW = x
        loss.backward()
        tr.allreduce_grads()
        got = net.weight.grad().asnumpy()
        assert np.allclose(got, 0.0), (rank, got)
        kv.barrier()
        print("COMPOK", rank, flush=True)
    """))
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--env", "JAX_PLATFORMS=cpu", "--",
         sys.executable, str(worker)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert out.stdout.count("COMPOK") == 2


# -- the example gate: EVERY script under example/ runs (VERDICT r4
# #2: 8 of 18 suites were never executed and could rot invisibly).
# The walker globs example/**/*.py so new suites AUTO-ENROLL; per-
# script argv here only shrinks shapes for CI (scripts must pass with
# plain defaults on real hardware). MXTPU_SMOKE=1 is the walker-wide
# convention for scripts whose smallness knob isn't an argv flag.
_EXAMPLE_ARGV = {
    "example/bert/pretrain.py": ["--steps", "4", "--batch-size", "8",
                                 "--seq-len", "64"],
    "example/gluon/mnist.py": ["--epochs", "1", "--batch-size", "64"],
    "example/image-classification/benchmark_score.py":
        ["--models", "squeezenet1.1", "--batch", "2", "--size", "64"],
    "example/sparse/linear_classification.py":
        ["--epochs", "2", "--dim", "200"],
}
# scripts that are multi-process entry points: run under launch.py -n 2
_EXAMPLE_LAUNCHED = {"example/distributed_training/train_dist.py"}


def _example_scripts():
    repo = os.path.abspath(REPO)
    pats = os.path.join(repo, "example", "**", "*.py")
    return sorted(
        os.path.relpath(p, repo).replace(os.sep, "/")
        for p in glob.glob(pats, recursive=True)
        if "__pycache__" not in p)


def test_example_walker_sees_known_suites():
    """If the glob rots, fail loudly instead of silently gating
    nothing."""
    scripts = _example_scripts()
    assert len(scripts) >= 25, scripts
    assert "example/moe/train_moe.py" in scripts
    assert "example/nmt/train_transformer_nmt.py" in scripts
    assert "example/neural-style/neural_style.py" in scripts
    assert "example/recommenders/matrix_fact.py" in scripts
    for k in list(_EXAMPLE_ARGV) + list(_EXAMPLE_LAUNCHED):
        assert k in scripts, f"stale config entry {k}"


@pytest.mark.slow
@pytest.mark.parametrize("script", _example_scripts())
def test_example_scripts_smoke(script):
    """Every example suite runs end-to-end on the CPU mesh."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "MXTPU_PS_PORT_OFFSET": "31", "MXTPU_SMOKE": "1",
           "PYTHONPATH": REPO + os.pathsep +
           os.environ.get("PYTHONPATH", "")}
    if script in _EXAMPLE_LAUNCHED:
        cmd = [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
               "--env", "JAX_PLATFORMS=cpu", "--",
               sys.executable, os.path.join(REPO, script)]
    else:
        cmd = [sys.executable, os.path.join(REPO, script)] + \
            _EXAMPLE_ARGV.get(script, [])
    out = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=600, env=env)
    assert out.returncode == 0, (script, out.stdout[-600:],
                                 out.stderr[-1200:])


@pytest.mark.slow
def test_bandwidth_probe_runs_on_virtual_mesh():
    """VERDICT r4 weak #6: the psum-sweep measurement path must
    EXECUTE on the virtual 8-device mesh (harness correctness — the
    GB/s number is meaningless on CPU, but the shard_map/fori_loop/
    fence machinery is rehearsed here; PR 21 ran it on four chips)."""
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "bandwidth", "measure.py"),
         "--sizes", "0.25,1", "--iters", "3"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PYTHONPATH": REPO + os.pathsep +
             os.environ.get("PYTHONPATH", "")})
    assert out.returncode == 0, out.stderr[-1200:]
    assert out.stdout.count("busbw") == 2, out.stdout
    assert "CpuDevice" in out.stdout         # really on the CPU mesh


def test_launch_sge_emits_script(tmp_path):
    """The SGE tracker writes a qsub array-job script with the DMLC
    env protocol (reference dmlc_tracker/sge.py)."""
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "4", "--launcher", "sge",
         "--env", "FOO=1", "--", "python", "train.py"],
        capture_output=True, text=True, timeout=60, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr
    script = (tmp_path / "mxtpu_sge_job.sh").read_text()
    assert "#$ -t 1-4" in script
    assert "DMLC_NUM_WORKER=4" in script
    assert "DMLC_WORKER_ID=$((SGE_TASK_ID - 1))" in script
    assert "export FOO=1" in script
    assert "python train.py" in script


def test_launch_mpi_rank_wrapper():
    """The SHIPPED mpi wrapper (tools.launch._dmlc_wrapper) derives
    DMLC_WORKER_ID from the MPI rank env and quotes env values."""
    import argparse
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import launch as launch_mod
    args = argparse.Namespace(num_workers=2,
                              env=["EXTRA_ARGS=--foo bar"])
    wrapper = launch_mod._dmlc_wrapper(
        "${OMPI_COMM_WORLD_RANK:-${PMI_RANK:-0}}", args, "10.0.0.1",
        9091)
    out = subprocess.run(
        ["bash", "-c", wrapper, "--", "bash", "-c",
         'echo "$DMLC_WORKER_ID $EXTRA_ARGS"'],
        capture_output=True, text=True, timeout=30,
        env={**os.environ, "OMPI_COMM_WORLD_RANK": "3"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "3 --foo bar"


def test_launch_ssh_secret_via_stdin(tmp_path):
    """Advisor r3 #1: MXTPU_PS_SECRET must never appear on a command
    line (ps / /proc/<pid>/cmdline are world-readable). The ssh
    launcher pipes it via ssh's stdin; the remote prologue reads and
    exports it. Verified with a fake `ssh` that logs its argv and runs
    the remote command locally."""
    fake = tmp_path / "ssh"
    fake.write_text("#!/bin/bash\n"
                    f"echo \"$@\" >> {tmp_path}/argv.log\n"
                    "exec bash -c \"$2\"\n")
    fake.chmod(0o755)
    worker = tmp_path / "sec_worker.py"
    worker.write_text(
        "import os\n"
        f"open(os.path.join({str(tmp_path)!r},"
        " 'sec' + os.environ['DMLC_WORKER_ID']), 'w')"
        ".write(os.environ.get('MXTPU_PS_SECRET', 'MISSING'))\n")
    hostfile = tmp_path / "hosts"
    hostfile.write_text("h0\nh1\n")
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "ssh",
         "-H", str(hostfile), "--", sys.executable, str(worker)],
        env={**os.environ, "PATH": f"{tmp_path}:{os.environ['PATH']}",
             "MXTPU_PS_SECRET": "s3cr3t-r4"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    for rank in range(2):
        assert (tmp_path / f"sec{rank}").read_text() == "s3cr3t-r4"
    argv = (tmp_path / "argv.log").read_text()
    assert "s3cr3t-r4" not in argv, "secret leaked into ssh argv"
    assert "MXTPU_PS_SECRET=$(cat)" in argv  # stdin prologue in place


@pytest.mark.slow
def test_sparse_linear_classification_dist_async(tmp_path):
    """BASELINE config 4's distributed leg end-to-end: the sparse
    linear-classification example converges on 2 workers over the
    dist_async parameter server, with row-sparse pulls."""
    out = subprocess.run(
        [sys.executable, LAUNCH, "-n", "2", "--launcher", "local",
         "--env", "JAX_PLATFORMS=cpu", "MXTPU_PS_PORT_OFFSET=43", "--",
         sys.executable,
         os.path.join(REPO, "example", "sparse",
                      "linear_classification.py"),
         "--kvstore", "dist_async", "--epochs", "6", "--dim", "400"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert out.stdout.count("done") == 2
    assert "row_sparse_pull fetched" in out.stdout
