"""Fault-tolerance suite (ISSUE 2 / docs/robustness.md): seeded chaos
against the distributed stack — dropped/duplicated PS messages, server
kill+restart mid-epoch, dead DataLoader workers, NaN-poisoned ranks,
simulated preemption. Every scenario asserts the RECOVERED run is
indistinguishable from a fault-free one (exact parameter equality,
resumed trajectories), not merely that nothing crashed.

Everything here is deterministic (fixed seeds, scheduled faults) —
ci/runtime_functions.sh reruns the file under tools/flakiness_checker.py
to prove it."""
import os
import signal
import socket
import threading
import time

import numpy as onp
import pytest

import mxtpu as mx
from mxtpu import autograd, gluon
from mxtpu.base import MXNetError, atomic_write
from mxtpu.contrib import chaos
from mxtpu.gluon import nn
from mxtpu.kvstore import server as psrv

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# PS wire resilience: seq dedup, retry, reconnect, hung-server detection
# ---------------------------------------------------------------------------

def test_ps_retry_is_exactly_once():
    """Both halves of the retry ambiguity: a request dropped BEFORE the
    server saw it must be re-applied; a request dropped AFTER the
    server applied it (lost ack) must be deduped on retry. Either way
    the store advances exactly once per logical push."""
    port = chaos.free_port()
    srv = psrv.KVStoreServer("127.0.0.1", port)
    try:
        cl = psrv.ServerClient("127.0.0.1", port)
        cl.request("init", "k", onp.zeros(3, onp.float32))
        plan = chaos.attach(cl, chaos.ChaosPlan(schedule={
            0: "drop_before_send",      # push 1: lost request
            1: "drop_after_send",       # push 2: lost ack -> dup delivery
            3: "drop_after_send",       # pull: dup delivery of a read
        }))
        cl.request("push", "k", onp.ones(3, onp.float32))
        cl.request("push", "k", onp.ones(3, onp.float32))
        _, v = cl.request("pull", "k")
        onp.testing.assert_array_equal(v, 2.0 * onp.ones(3))
        _, v = cl.request("pull", "k")          # the scheduled dup read
        onp.testing.assert_array_equal(v, 2.0 * onp.ones(3))
        assert plan.total_injected == 3, plan.injected
        cl.close()
    finally:
        srv.stop()


def test_ps_hung_server_detected(monkeypatch):
    """A server that accepts but never replies must surface as an
    error within the retry deadline — never an indefinite hang (the
    heartbeat/timeout half of the wire-resilience story)."""
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lst.bind(("127.0.0.1", 0))
    lst.listen(4)
    port = lst.getsockname()[1]
    eaten = []

    def _eat():    # accept and read, never answer: a wedged peer
        while True:
            try:
                conn, _ = lst.accept()
            except OSError:
                return
            eaten.append(conn)

    threading.Thread(target=_eat, daemon=True).start()
    monkeypatch.setenv("MXTPU_PS_REQUEST_TIMEOUT", "0.3")
    monkeypatch.setenv("MXTPU_PS_RETRY_DEADLINE", "1.2")
    cl = psrv.ServerClient("127.0.0.1", port, timeout=5.0)
    t0 = time.monotonic()
    with pytest.raises(MXNetError):
        cl.request("ping")
    assert time.monotonic() - t0 < 10.0     # bounded, not blocked
    cl.close()
    lst.close()
    for c in eaten:
        c.close()


def test_ps_snapshot_roundtrip_and_corrupt_snapshot(tmp_path):
    """The server's crash-recovery snapshot: store + updater + dedup
    state reload on restart (same path), and an unreadable snapshot
    degrades to an empty store with a warning instead of bricking the
    server."""
    snap = str(tmp_path / "ps.snap")
    port = chaos.free_port()
    srv = psrv.KVStoreServer("127.0.0.1", port, snapshot_path=snap,
                             snapshot_every=1)
    cl = psrv.ServerClient("127.0.0.1", port)
    cl.request("init", "k", onp.zeros(2, onp.float32))
    cl.request("push", "k", onp.ones(2, onp.float32))
    cl.close()
    srv.stop()
    assert os.path.exists(snap)

    port2 = chaos.free_port()
    srv2 = psrv.KVStoreServer("127.0.0.1", port2, snapshot_path=snap,
                              snapshot_every=1)
    cl2 = psrv.ServerClient("127.0.0.1", port2)
    _, v = cl2.request("pull", "k")
    onp.testing.assert_array_equal(v, onp.ones(2))
    cl2.close()
    srv2.stop()

    with open(snap, "wb") as f:     # torn-by-hand snapshot
        f.write(b"not a pickle")
    port3 = chaos.free_port()
    with pytest.warns(RuntimeWarning, match="unreadable"):
        srv3 = psrv.KVStoreServer("127.0.0.1", port3, snapshot_path=snap,
                                  snapshot_every=1)
    cl3 = psrv.ServerClient("127.0.0.1", port3)
    with pytest.raises(MXNetError, match="not initialized"):
        cl3.request("pull", "k")
    cl3.close()
    srv3.stop()


# ---------------------------------------------------------------------------
# THE acceptance scenario: dist_async training through chaos
# ---------------------------------------------------------------------------

def _async_training_run(steps, kill_restart_at=None, server=None,
                        plan=None):
    """One dist_async Trainer run against the CURRENT server_address()
    env; returns final weights. Deterministic: fixed init + data."""
    mx.random.seed(123)
    net = nn.Dense(2, in_units=3, use_bias=False)
    net.initialize()
    kv = mx.kv.create("dist_async")
    if plan is not None:
        chaos.attach(kv, plan)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=kv)
    rng = onp.random.default_rng(0)
    xs = rng.standard_normal((steps, 4, 3)).astype(onp.float32)
    for i in range(steps):
        x = mx.nd.array(xs[i])
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        tr.step(4)
        if kill_restart_at is not None and i == kill_restart_at:
            # mid-epoch SIGKILL + restart: the store must come back
            # from its snapshot and the next step's requests must ride
            # the reconnect/backoff path transparently
            server.kill()
            server.start()
    w = net.weight.data().asnumpy().copy()
    kv.close()
    return w


def test_dist_async_training_survives_chaos(tmp_path, monkeypatch):
    """Acceptance: a dist_async run that suffers (a) a mid-epoch
    server kill+restart and (b) >=5 injected connection drops /
    duplicate deliveries finishes with parameters EQUAL to a
    fault-free run's."""
    steps = 12

    # fault-free reference against its own pristine server
    with chaos.ServerProcess(
            snapshot_path=str(tmp_path / "ref.snap")) as ref_srv:
        monkeypatch.setenv("MXTPU_PS_PORT_OFFSET",
                           str(ref_srv.port - 9091))
        w_ref = _async_training_run(steps)

    # chaos run: kill+restart mid-epoch, plus scheduled drops/dups
    # request indices: 0 ping, 1 init, 2 set_optimizer, 3 pull_many,
    # then (push_many, pull_many) per step — 12 steps end at index 27
    plan = chaos.ChaosPlan(seed=11, schedule={
        4: "drop_before_send", 9: "drop_after_send",
        15: "drop_before_send", 21: "drop_after_send",
        24: "drop_before_send", 27: "drop_after_send",
    })
    with chaos.ServerProcess(
            snapshot_path=str(tmp_path / "chaos.snap")) as srv:
        monkeypatch.setenv("MXTPU_PS_PORT_OFFSET", str(srv.port - 9091))
        monkeypatch.setenv("MXTPU_PS_RETRY_DEADLINE", "90")
        w_chaos = _async_training_run(steps, kill_restart_at=steps // 2,
                                      server=srv, plan=plan)

    assert plan.total_injected >= 5, plan.injected
    assert plan.injected["drop_before_send"] >= 1     # lost requests
    assert plan.injected["drop_after_send"] >= 1      # dup deliveries
    onp.testing.assert_array_equal(w_chaos, w_ref)


# ---------------------------------------------------------------------------
# Preemption-safe checkpointing
# ---------------------------------------------------------------------------

def _toy_state():
    import jax.numpy as jnp
    import optax
    from mxtpu.parallel import mesh as pmesh, step as pstep
    from mxtpu.parallel.sharding import P, ShardingRules
    rng = onp.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    xs = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    ys = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    mesh = pmesh.create_mesh(dp=-1)
    rules = ShardingRules([(r".*", P())])
    tx = optax.adam(1e-2)
    state = pstep.init_state({"w": w}, tx, mesh, rules)
    step = pstep.make_train_step(loss_fn, tx, mesh, rules)
    return state, step, (xs, ys)


def test_preemption_guard_sigterm_saves_and_resumes(tmp_path):
    """Simulated preemption: SIGTERM mid-run is absorbed by
    PreemptionGuard (the process does NOT die), the loop breaks at the
    step boundary, save_now() lands a synchronous forced checkpoint,
    and a relaunch resumes onto the uninterrupted trajectory."""
    from mxtpu import checkpoint as ckpt
    total = 8

    # uninterrupted reference
    state, step, batch = _toy_state()
    for _ in range(total):
        state, ref_loss = step(state, batch)

    # preempted run: async saves every other step, SIGTERM at step 5 —
    # a step the save interval would SKIP, so only the forced final
    # save can preserve it
    ckdir = str(tmp_path / "ck")
    state, step, batch = _toy_state()
    mgr = ckpt.CheckpointManager(ckdir, max_to_keep=2,
                                 save_interval_steps=2, async_save=True)
    stopped_at = None
    with ckpt.PreemptionGuard(mgr) as guard:
        for i in range(total):
            state, loss = step(state, batch)
            mgr.save(i, state)
            if i == 5:
                chaos.simulate_preemption(signal.SIGTERM)
            if guard.preempted:
                guard.save_now(i, state)     # forced + synchronous
                stopped_at = i
                break
    assert guard.preempted and guard.signum == signal.SIGTERM
    assert stopped_at == 5
    mgr.close()

    # relaunch: resume from the forced save, finish, land on the
    # reference trajectory
    fresh, step2, batch2 = _toy_state()
    mgr2 = ckpt.CheckpointManager(ckdir, max_to_keep=2,
                                  save_interval_steps=2, async_save=True)
    assert mgr2.latest_step() == 5     # save_now ignored the interval
    state2 = mgr2.restore(abstract_state=fresh)
    for _ in range(stopped_at + 1, total):
        state2, loss2 = step2(state2, batch2)
    onp.testing.assert_allclose(float(loss2), float(ref_loss), rtol=1e-6)
    mgr2.close()


def test_restore_falls_back_on_torn_latest_step(tmp_path):
    """A kill mid-write can tear the newest step directory; restore()
    must fall back to the previous retained step (with a warning)
    instead of failing the relaunch. An explicitly requested step must
    NOT fall back."""
    import pathlib
    from mxtpu import checkpoint as ckpt
    state, step, batch = _toy_state()
    ckdir = str(tmp_path / "ck")
    mgr = ckpt.CheckpointManager(ckdir, max_to_keep=3, async_save=False)
    saved = {}
    for i in range(3):
        state, _ = step(state, batch)
        mgr.save(i, state)
        saved[i] = onp.asarray(state.params["w"]).copy()
    mgr.wait_until_finished()
    mgr.close()

    for p in pathlib.Path(ckdir, "2").rglob("*"):   # tear the newest
        if p.is_file():
            p.write_bytes(b"x")

    fresh, _, _ = _toy_state()
    mgr2 = ckpt.CheckpointManager(ckdir, max_to_keep=3, async_save=False)
    with pytest.warns(RuntimeWarning, match="partial/corrupt"):
        restored = mgr2.restore(abstract_state=fresh)
    onp.testing.assert_array_equal(
        onp.asarray(restored.params["w"]), saved[1])
    with pytest.raises(Exception):
        mgr2.restore(step=2, abstract_state=fresh)   # explicit: no fallback
    mgr2.close()


def test_trainer_save_states_atomic(tmp_path):
    """Trainer.save_states rides the shared atomic_write helper: the
    target is REPLACED, never truncated-then-rewritten, and no temp
    droppings survive."""
    mx.random.seed(5)
    net = nn.Dense(2, in_units=3)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.01})
    x = mx.nd.array(onp.ones((2, 3), onp.float32))
    with autograd.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    tr.step(2)
    fname = str(tmp_path / "opt.states")
    tr.save_states(fname)
    first = open(fname, "rb").read()
    tr.load_states(fname)               # still a valid pickle
    with autograd.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    tr.step(2)
    tr.save_states(fname)               # overwrite goes through replace
    assert open(fname, "rb").read() != first
    tr.load_states(fname)
    assert [f for f in os.listdir(tmp_path)
            if f.startswith("opt.states.tmp")] == []

    # the helper itself: a failed write must leave the old content
    atomic_write(fname, b"new-blob")
    assert open(fname, "rb").read() == b"new-blob"
    with pytest.raises(TypeError):
        atomic_write(fname, None)       # write fails mid-flight
    assert open(fname, "rb").read() == b"new-blob"   # old file intact


# ---------------------------------------------------------------------------
# DataLoader dead-worker handling
# ---------------------------------------------------------------------------

class _CrashingDataset:
    """Worker suicide via os._exit at one index; with a marker file the
    crash happens once (first pool) and the restarted pool succeeds."""

    def __init__(self, n, crash_idx, marker=None):
        self.n, self.crash_idx, self.marker = n, crash_idx, marker

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.crash_idx:
            if self.marker is None:
                os._exit(3)
            if not os.path.exists(self.marker):
                with open(self.marker, "w"):
                    pass
                os._exit(3)
        return onp.full((2,), i, onp.float32)


def test_dataloader_dead_worker_retries_with_fresh_pool(tmp_path):
    """A worker killed mid-task (os._exit) surfaces as a timeout; the
    loader restarts the pool ONCE, resubmits pending batches, and the
    epoch completes with every batch intact and ordered."""
    from mxtpu.gluon.data import DataLoader
    ds = _CrashingDataset(16, crash_idx=5,
                          marker=str(tmp_path / "crashed"))
    loader = DataLoader(ds, batch_size=4, num_workers=2,
                        thread_pool=False, timeout=6)
    batches = [b.asnumpy() for b in loader]
    assert os.path.exists(str(tmp_path / "crashed"))   # it really died
    assert len(batches) == 4
    onp.testing.assert_array_equal(
        onp.concatenate([b[:, 0] for b in batches]), onp.arange(16))


def test_dataloader_dead_worker_reports_exit_codes():
    """A worker that dies EVERY time exhausts the single retry; the
    error must carry the dead workers' exit codes (the debugging
    breadcrumb the bare TimeoutError lacked)."""
    from mxtpu.gluon.data import DataLoader
    ds = _CrashingDataset(8, crash_idx=1, marker=None)   # always dies
    # the timeout has to outlast a worker's start (it imports mxtpu:
    # 3.3-4.0 s here), or both rounds end before any worker has died
    loader = DataLoader(ds, batch_size=4, num_workers=2,
                        thread_pool=False, timeout=8)
    with pytest.raises(RuntimeError, match=r"exit code"):
        list(loader)


# ---------------------------------------------------------------------------
# AMP global overflow skip on the 8-rank virtual mesh
# ---------------------------------------------------------------------------

def test_amp_global_overflow_skip_across_8_virtual_ranks():
    """NaN-poison ONE rank's grads out of 8: EVERY rank must skip the
    update (weights bit-unchanged) and shrink its loss scale
    identically — the cross-rank agreement Trainer._all_workers_finite
    exists for. A rank-local check would let 7 ranks apply a poisoned
    global batch while one skips, diverging the replicas forever."""
    from mxtpu import amp
    N, poisoned = 8, 3
    kv = chaos.VirtualAllreduceKV(N)
    amp.init("float16")                  # dynamic loss scaling path
    nets, trainers = [], []
    for _ in range(N):
        mx.random.seed(1)                # identical replicas
        net = nn.Dense(1, in_units=3, use_bias=False)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore=kv)
        amp.init_trainer(tr)
        nets.append(net)
        trainers.append(tr)
    x = mx.nd.array(onp.ones((4, 3), onp.float32))

    def backward_all():
        for net in nets:
            net.weight.zero_grad()
            with autograd.record():
                loss = (net(x) ** 2).mean()
            loss.backward()

    # step 1: rank `poisoned` overflows -> GLOBAL skip
    backward_all()
    chaos.poison_nan(nets[poisoned].weight)
    w_before = [n.weight.data().asnumpy().copy() for n in nets]
    scale0 = float(trainers[0]._amp_loss_scaler.loss_scale)
    kv.run(lambda r: trainers[r].step(4))
    for r in range(N):
        onp.testing.assert_array_equal(
            nets[r].weight.data().asnumpy(), w_before[r])
        assert float(trainers[r]._amp_loss_scaler.loss_scale) == \
            scale0 / 2.0, r

    # step 2: clean grads everywhere -> every rank applies, replicas
    # stay bit-identical
    backward_all()
    kv.run(lambda r: trainers[r].step(4))
    w_after = [n.weight.data().asnumpy() for n in nets]
    for r in range(1, N):
        onp.testing.assert_array_equal(w_after[r], w_after[0])
    assert not onp.array_equal(w_after[0], w_before[0])
