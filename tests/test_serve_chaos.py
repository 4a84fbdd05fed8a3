"""Serving-tier fault tolerance (ISSUE 7): replica supervision,
deterministic re-dispatch, self-healing disagg, chaos harness.

Contracts (all provoked by seeded ``ServeChaosPlan`` faults — never
trusted):

- a request that survives a replica crash emits the EXACT same tokens
  it would have without the crash: the gateway journals (prompt,
  params, seed, streamed prefix) and resumes on a healthy replica via
  re-prefill with the rng chain fast-forwarded (``serve.resume_key``);
- the supervisor detects dead/stalled replicas by step-progress
  heartbeat, restarts within a bounded budget, and counts every event
  in ``gateway_replica_restarts_total{reason}``;
- zero healthy replicas is a DISTINCT failure: 503 + Retry-After at
  the front door, parked work failed loudly once the budget is spent;
- Retry-After values carry seeded jitter (no thundering re-herd);
- the KV-handoff channel severed mid-handoff reconnects with backoff,
  re-authenticates via HMAC, and the resent handoff seats the
  bit-identical block; a wrong secret fails FAST (no retry loop);
- a killed prefill worker is respawned with a single resubmit; a
  persistently failing prefill path trips the circuit breaker into
  bit-identical colocated fallback, surfaced as ``degraded`` in
  /healthz.

Everything is deterministic: the ``chaos_serve`` CI stage reruns this
file under tools/flakiness_checker.py to prove it.

ISSUE 8 adds the distributed-tracing contracts on top: a request that
survives a replica kill keeps its ONE trace_id across the crash, the
``gateway.redispatch`` span links the old and new replica, the KV
handoff frames carry a versioned context header old decoders still
accept, and ``tools/diagnose.py timeline`` stitches the per-process
trace streams into valid chrome-trace JSON.
"""
import gc
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import rpc, telemetry
from mxtpu.contrib.chaos import ServeChaosPlan, attach_serve
from mxtpu.models import llama
from mxtpu.serve import Request, ServeEngine, resume_key
from mxtpu.serve.gateway import (CircuitBreaker, DisaggBackend,
                                 Gateway, GatewayClient,
                                 GatewayUnavailable, KVChannel,
                                 NoHealthyReplicas, ReplicaSet)

# fast supervision for tests: tight heartbeat, tiny restart backoff
SUP = dict(heartbeat_s=0.05, stall_s=30.0, backoff_base_s=0.01,
           backoff_max_s=0.05)


import llama_refs


@pytest.fixture(scope="module")
def cfg(serve_cfg):
    return serve_cfg


@pytest.fixture(scope="module")
def params(serve_params):
    return serve_params


def _reference(cfg, params, prompt, mnew, seed=0, temperature=0.0,
               top_k=None, top_p=None):
    return llama_refs.reference(cfg, params, prompt, mnew, seed=seed,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p)


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("min_bucket", 4)
    return ServeEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# the resume primitive: re-prefill past a streamed prefix, bit-exactly
# ---------------------------------------------------------------------------
def test_resume_key_replays_sampling_chain(cfg, params):
    """The crux of deterministic re-dispatch: a SAMPLED request
    resumed after n streamed tokens — prompt+prefix re-prefilled with
    resume_key(seed, n) — continues the exact token sequence of an
    uninterrupted run. (Greedy would hide a broken chain; temperature
    + top_k makes every split position observable.)"""
    prompt = (np.arange(6) * 5 + 1) % cfg.vocab_size
    total = 8
    ref = _reference(cfg, params, prompt, total, seed=7,
                     temperature=0.9, top_k=7)
    for n in (0, 1, 3):
        resumed = np.concatenate(
            [prompt, np.asarray(ref[:n], np.int32)])
        eng = _engine(cfg, params)
        rid = eng.submit(Request(
            prompt=resumed, max_new_tokens=total - n,
            temperature=0.9, top_k=7, seed=7,
            rng=resume_key(7, n) if n else None))
        res = eng.run()
        assert list(res[rid]) == ref[n:], n


# ---------------------------------------------------------------------------
# tentpole (a)+(b): supervision + deterministic re-dispatch
# ---------------------------------------------------------------------------
@pytest.mark.slow   # ~27s; runs in chaos_serve (+x3 flakiness) and
# by node id in lockcheck_smoke — tier-1 keeps the single-kill and
# resume_key re-dispatch gates
def test_replica_kill_poisson_stream_bit_identical(cfg, params):
    """THE acceptance gate: a seeded multi-client Poisson stream
    through a 2-replica HTTP gateway with a chaos-killed replica —
    every accepted request completes, every token list is
    bit-identical to a fault-free per-request generate, and the
    restart counter proves the kill actually fired."""
    reg = telemetry.registry()
    r0 = reg.value("gateway_replica_restarts_total", reason="died")
    gw = Gateway(lambda: _engine(cfg, params), n_replicas=2,
                 queue_max=256, supervisor_opts=SUP)
    plan = attach_serve(gw, ServeChaosPlan(
        seed=3, kill_replica={0: 2}))   # replica r0 dies at step 2
    try:
        port = gw.start_http(port=0)
        rng = np.random.default_rng(17)
        jobs, results = [], {}
        for i in range(10):
            plen = int(rng.choice([3, 5, 9]))
            samp = (dict(temperature=float(rng.choice([0.7, 0.9])),
                         top_k=int(rng.choice([5, 8])))
                    if i % 2 else dict(temperature=0.0))
            jobs.append(dict(
                prompt=rng.integers(0, cfg.vocab_size, plen),
                mnew=int(rng.choice([4, 6])), seed=i,
                delay=float(rng.exponential(0.01)), **samp))

        def client(i, job):
            time.sleep(job["delay"])
            cli = GatewayClient("127.0.0.1", port)
            results[i] = cli.generate(
                job["prompt"], job["mnew"], seed=job["seed"],
                temperature=job.get("temperature", 0.0),
                **({"top_k": job["top_k"]} if "top_k" in job else {}))

        threads = [threading.Thread(target=client, args=(i, j))
                   for i, j in enumerate(jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert plan.injected["replica_kill"] >= 1, plan.injected
        assert len(results) == 10
        for i, job in enumerate(jobs):
            assert results[i]["status"] == 200, (i, results[i])
            assert results[i]["reason"] == "complete", (i, results[i])
            assert results[i]["tokens"] == _reference(
                cfg, params, job["prompt"], job["mnew"],
                seed=job["seed"],
                temperature=job.get("temperature", 0.0),
                top_k=job.get("top_k")), (i, job)
        # the fault was detected, counted, and repaired
        assert reg.value("gateway_replica_restarts_total",
                         reason="died") - r0 >= 1
        sup = gw.supervisor.describe()
        assert sup["restarts"] >= 1
        assert any(h["reason"] == "died" for h in sup["history"])
    finally:
        gw.close()


def test_decode_raise_restart_history_and_state(cfg, params):
    """A raise INSIDE decode dispatch on the only replica: the
    supervisor restarts it, the stranded request resumes bit-identical
    mid-stream, and /state carries the restart history + health."""
    reg = telemetry.registry()
    rd0 = reg.value("gateway_redispatch_total")
    gw = Gateway(lambda: _engine(cfg, params, max_slots=1),
                 n_replicas=1, queue_max=16, supervisor_opts=SUP)
    plan = attach_serve(gw, ServeChaosPlan(
        seed=1, raise_in_decode={0: 3}))
    try:
        prompt = np.arange(5) % cfg.vocab_size
        h = gw.submit(prompt, 8, seed=4, temperature=0.8)
        toks = h.result(timeout=120)
        assert h.reason == "complete"
        assert list(toks) == _reference(cfg, params, prompt, 8,
                                        seed=4, temperature=0.8)
        assert plan.injected["decode_raise"] == 1
        assert reg.value("gateway_redispatch_total") - rd0 >= 1
        st = gw.state()
        sup = st["supervisor"]
        assert sup["restarts"] >= 1
        assert any(h_["reason"] == "died" for h_ in sup["history"])
        assert any("ServeChaosFault" in (h_["error"] or "")
                   for h_ in sup["history"])
        # the replacement replica is healthy and serving
        assert any(r["healthy"] for r in st["replicas"])
    finally:
        gw.close()


@pytest.mark.slow   # ~20s (spec engines recompile on the respawned
# replica); CI home: chaos_serve — tier-1 keeps the rng-advance gate
# in tests/test_spec_decode.py and the fresh-process spec_smoke stage
def test_replica_kill_mid_speculative_run_bit_identical(cfg, params):
    """ISSUE 19: a replica dies MID-ACCEPTED-RUN — the journaled
    emitted prefix was produced by multi-token speculative steps, so
    the re-dispatch must fast-forward the rng chain by the EMITTED
    count (one split per valid token), not by decode steps. The
    plateau prompt keeps speculation firing (multi-token advance before
    the kill); the sampled request observes every split position."""
    reg = telemetry.registry()
    rd0 = reg.value("gateway_redispatch_total")
    gw = Gateway(lambda: _engine(cfg, params, page_size=8,
                                 speculate_k=3),
                 n_replicas=1, queue_max=16, supervisor_opts=SUP)
    plan = attach_serve(gw, ServeChaosPlan(
        seed=5, raise_in_decode={0: 3}))    # dies on its 3rd step
    try:
        jobs = [dict(prompt=[140, 141, 140], mnew=12,
                     temperature=0.0, seed=0),
                dict(prompt=[9, 4, 7, 1, 6], mnew=8,
                     temperature=0.9, top_k=7, seed=6)]
        hs = [gw.submit(j["prompt"], j["mnew"], seed=j["seed"],
                        temperature=j["temperature"],
                        **({"top_k": j["top_k"]} if "top_k" in j
                           else {}))
              for j in jobs]
        for h, j in zip(hs, jobs):
            toks = h.result(timeout=180)
            assert h.reason == "complete", j
            assert list(toks) == _reference(
                cfg, params, j["prompt"], j["mnew"], seed=j["seed"],
                temperature=j["temperature"],
                top_k=j.get("top_k")), j
        assert plan.injected["decode_raise"] == 1
        assert reg.value("gateway_redispatch_total") - rd0 >= 1
        # the replica was speculating when it died AND after respawn
        st = gw.state()
        assert any(r["healthy"] for r in st["replicas"])
    finally:
        gw.close()


def test_zero_healthy_replicas_503_and_parked_failure(cfg, params):
    """Restart budget 0 + a dead only-replica: new submissions get the
    DISTINCT unavailable error (HTTP 503 + Retry-After), the stranded
    request fails loudly with reason 'error' instead of hanging, and
    /healthz reports degraded."""
    gw = Gateway(lambda: _engine(cfg, params, max_slots=1),
                 n_replicas=1, queue_max=16,
                 supervisor_opts=dict(SUP, max_restarts=0))
    attach_serve(gw, ServeChaosPlan(seed=2, kill_replica={0: 1}))
    try:
        port = gw.start_http(port=0)
        h = gw.submit(np.arange(4) % cfg.vocab_size, 8, seed=0)
        toks = h.result(timeout=60)      # killed, never replaced
        assert h.reason == "error" and len(toks) <= 8
        with pytest.raises(GatewayUnavailable):
            gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=1)
        cli = GatewayClient("127.0.0.1", port)
        rec = cli.generate(np.arange(4) % cfg.vocab_size, 2, seed=1)
        assert rec["status"] == 503
        assert rec["retry_after_s"] >= 1
        status, hz = cli.get_json("/healthz")
        assert status == 200
        assert hz["status"] == "degraded"
        assert hz["healthy_replicas"] == 0
    finally:
        gw.close()


def test_retry_after_jitter_spreads(cfg, params):
    """Shed responses must not synchronize their victims: consecutive
    Retry-After values from one overloaded gateway are jittered
    (seeded — the SEQUENCE is reproducible, the VALUES spread)."""
    gw = Gateway(lambda: _engine(cfg, params, max_slots=1),
                 n_replicas=1, queue_max=2, started=False,
                 supervise=False, retry_jitter=4.0)
    try:
        for i in range(2):
            gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=i)
        values = []
        for i in range(8):
            try:
                gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=9)
            except Exception as e:
                values.append(e.retry_after)
        assert len(values) == 8
        assert len(set(values)) >= 2, values   # jitter spreads them
        assert all(v >= 1 for v in values)
        gw.backend.start()                     # drain for clean close
    finally:
        gw.close()


def test_supervisor_stall_detection(cfg, params):
    """A replica whose loop stops making step progress while holding
    work is STALLED: the supervisor pulls it from routing (reason
    'stalled'), restarts, and the wedged request resumes elsewhere —
    without waiting for the stuck thread."""
    reg = telemetry.registry()
    s0 = reg.value("gateway_replica_restarts_total", reason="stalled")
    gw = Gateway(lambda: _engine(cfg, params, max_slots=1),
                 n_replicas=1, queue_max=16,
                 supervisor_opts=dict(SUP, stall_s=0.3))
    try:
        replica = gw.backend.replicas()[0]
        eng = replica.engine
        orig = eng._dispatch
        fired = {"n": 0}

        def wedge(firsts):
            if fired["n"] == 2:
                fired["n"] += 1
                time.sleep(2.5)      # wedged well past stall_s
            else:
                fired["n"] += 1
            return orig(firsts)

        eng._dispatch = wedge
        prompt = np.arange(4) % cfg.vocab_size
        h = gw.submit(prompt, 6, seed=3, temperature=0.7)
        toks = h.result(timeout=120)
        assert h.reason == "complete"
        assert list(toks) == _reference(cfg, params, prompt, 6,
                                        seed=3, temperature=0.7)
        assert reg.value("gateway_replica_restarts_total",
                         reason="stalled") - s0 >= 1
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# tentpole (c): self-healing disagg
# ---------------------------------------------------------------------------
def _tcp_channel_pair(secret):
    """connect+accept a re-healable TCP channel pair (the cross-host
    deployment shape: tx redials, rx re-accepts)."""
    listener, port = KVChannel.listen("127.0.0.1", 0)
    out = {}

    def rx_side():
        out["rx"] = KVChannel.accept(listener, secret=secret,
                                     reaccept=True)

    t = threading.Thread(target=rx_side)
    t.start()
    tx = KVChannel.connect("127.0.0.1", port, secret=secret)
    t.join(30)
    return tx, out["rx"]


def test_kv_channel_sever_reconnect_reauth_bit_identical():
    """Satellite: a TCP handoff channel severed mid-handoff reconnects
    with backoff, re-authenticates via the HMAC hello, and the RESENT
    frame's arrays are bit-identical; counters prove the reconnect
    happened. A wrong-secret dial fails FAST with an auth error —
    no retry loop."""
    reg = telemetry.registry()
    rc0 = reg.value("gateway_kv_reconnects_total")
    rs0 = reg.value("gateway_kv_resends_total")
    tx, rx = _tcp_channel_pair(b"kv-chaos")
    got = []
    done = threading.Event()

    def feeder():
        for _ in range(2):
            got.append(rx.recv_handoff())
        done.set()

    t = threading.Thread(target=feeder, daemon=True)
    t.start()
    block = np.arange(48, dtype=np.float32).reshape(2, 2, 6, 2)
    frame = ("kvpage", 11, 0, block, block * 2)
    tx.send_handoff(frame)
    # sever mid-stream: the next handoff must ride a fresh,
    # re-authenticated connection
    tx._sock.close()
    frame2 = ("kvpage", 12, 0, block + 1, block * 3)
    tx.send_handoff(frame2)
    assert done.wait(60)
    assert [m[1] for m in got] == [11, 12]
    np.testing.assert_array_equal(got[1][3], block + 1)   # bit-exact
    np.testing.assert_array_equal(got[1][4], block * 3)
    assert got[1][3].dtype == np.float32
    assert reg.value("gateway_kv_reconnects_total") - rc0 >= 1
    assert reg.value("gateway_kv_resends_total") - rs0 >= 1
    tx.close()
    rx.close()

    # auth failure fails FAST: a wrong-secret dialer gets an auth
    # error from the handshake, not a silent retry loop
    listener, port = KVChannel.listen("127.0.0.1", 0)
    srv_err = {}

    def rx_auth():
        try:
            KVChannel.accept(listener, secret=b"right")
        except rpc.RPCAuthError as e:
            srv_err["e"] = e

    t2 = threading.Thread(target=rx_auth, daemon=True)
    t2.start()
    t0 = time.monotonic()
    with pytest.raises((rpc.RPCAuthError, rpc.RPCProtocolError)):
        KVChannel.connect("127.0.0.1", port, secret=b"wrong")
    assert time.monotonic() - t0 < 5.0    # fast, not a backoff loop
    t2.join(30)
    assert isinstance(srv_err.get("e"), rpc.RPCAuthError)
    listener.close()


def test_prefill_worker_kill_respawn_single_resubmit(cfg, params):
    """The DataLoader dead-worker pattern, serving edition: a chaos-
    killed prefill worker is respawned, its in-flight job resubmitted
    ONCE, and the request completes bit-identically."""
    reg = telemetry.registry()
    w0 = reg.value("gateway_prefill_restarts_total")
    be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1,
                       max_slots=2, max_len=32, min_bucket=4)
    gw = Gateway(backend=be, queue_max=16, supervisor_opts=SUP)
    plan = attach_serve(gw, ServeChaosPlan(
        seed=5, kill_prefill={0: 0}))   # dies on its first job
    try:
        prompt = np.arange(5) % cfg.vocab_size
        h = gw.submit(prompt, 4, seed=6, temperature=0.9)
        toks = h.result(timeout=120)
        assert h.reason == "complete"
        assert list(toks) == _reference(cfg, params, prompt, 4,
                                        seed=6, temperature=0.9)
        assert plan.injected["prefill_kill"] == 1
        assert reg.value("gateway_prefill_restarts_total") - w0 == 1
        # the pool is at size with a live replacement
        assert len(be.prefill) == 1 and be.prefill[0].alive
    finally:
        gw.close()


def test_breaker_trips_to_bit_identical_colocated_fallback(cfg,
                                                           params):
    """Sustained prefill failure trips the circuit breaker: requests
    fall back to COLOCATED prefill (same graph/sampler/rng chain →
    bit-identical), /healthz degrades, and a half-open probe after
    cooldown closes the breaker once the pool heals."""
    reg = telemetry.registry()
    fb0 = reg.value("gateway_breaker_fallback_total")
    now = {"t": 0.0}
    breaker = CircuitBreaker(threshold=2, cooldown_s=10.0,
                             clock=lambda: now["t"])
    be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1,
                       max_slots=2, max_len=32, min_bucket=4,
                       breaker=breaker)
    gw = Gateway(backend=be, queue_max=16, supervisor_opts=SUP)
    try:
        port = gw.start_http(port=0)
        worker = be.prefill[0]
        orig_fn = worker._fn

        def poisoned(bucket):
            def f(*a, **k):
                raise RuntimeError("injected prefill failure")
            return f

        worker._fn = poisoned
        for i in range(2):               # 2 failures trip threshold 2
            h = gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=i)
            h.result(timeout=60)
            assert h.reason == "error"
        assert breaker.describe()["state"] == "open"
        # open breaker: requests served colocated, bit-identically
        prompt = np.arange(6) % cfg.vocab_size
        h = gw.submit(prompt, 3, seed=9, temperature=0.8)
        assert list(h.result(timeout=120)) == _reference(
            cfg, params, prompt, 3, seed=9, temperature=0.8)
        assert h.reason == "complete"
        assert reg.value("gateway_breaker_fallback_total") - fb0 >= 1
        status, hz = GatewayClient("127.0.0.1", port) \
            .get_json("/healthz")
        assert status == 200 and hz["status"] == "degraded"
        assert hz["breaker"]["state"] == "open"
        # pool heals; after cooldown ONE half-open probe closes it
        worker._fn = orig_fn
        now["t"] = 11.0
        h = gw.submit(prompt, 2, seed=10)
        assert list(h.result(timeout=120)) == _reference(
            cfg, params, prompt, 2, seed=10)
        assert breaker.describe()["state"] == "closed"
        _, hz = GatewayClient("127.0.0.1", port).get_json("/healthz")
        assert hz["status"] == "ok" and hz["breaker"]["state"] == \
            "closed"
    finally:
        gw.close()


@pytest.mark.slow   # ~31s; runs in chaos_serve (+x3 flakiness)
def test_disagg_chaos_stream_bit_identical_over_tcp(cfg, params):
    """THE disagg acceptance gate: a seeded client stream through
    disaggregated prefill/decode over an HMAC TCP channel, with an
    injected prefill-worker kill AND severed/corrupted KV frames —
    every request completes bit-identically; the retry counters prove
    the faults fired."""
    reg = telemetry.registry()
    rc0 = reg.value("gateway_kv_reconnects_total")
    w0 = reg.value("gateway_prefill_restarts_total")
    tx, rx = _tcp_channel_pair(b"kv-e2e")
    be = DisaggBackend(cfg, params, n_prefill=2, n_decode=2,
                       max_slots=2, max_len=32, min_bucket=4,
                       channel=(tx, rx))
    gw = Gateway(backend=be, queue_max=64, supervisor_opts=SUP)
    plan = attach_serve(gw, ServeChaosPlan(
        seed=9, kill_prefill={1: 0},
        kv_frames={1: "sever", 3: "corrupt", 4: "delay"},
        delay_s=0.01))
    try:
        port = gw.start_http(port=0)
        rng = np.random.default_rng(23)
        jobs, results = [], {}
        for i in range(8):
            plen = int(rng.choice([3, 5, 9]))
            jobs.append(dict(
                prompt=rng.integers(0, cfg.vocab_size, plen),
                mnew=int(rng.choice([2, 4])), seed=i,
                temperature=float(rng.choice([0.0, 0.8]))))

        def client(i, job):
            cli = GatewayClient("127.0.0.1", port)
            results[i] = cli.generate(job["prompt"], job["mnew"],
                                      seed=job["seed"],
                                      temperature=job["temperature"])

        threads = [threading.Thread(target=client, args=(i, j))
                   for i, j in enumerate(jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert len(results) == 8
        for i, job in enumerate(jobs):
            assert results[i]["status"] == 200, (i, results[i])
            assert results[i]["reason"] == "complete", (i, results[i])
            assert results[i]["tokens"] == _reference(
                cfg, params, job["prompt"], job["mnew"],
                seed=job["seed"], temperature=job["temperature"]), i
        # the faults actually fired and were healed
        assert plan.injected["prefill_kill"] == 1
        assert plan.injected["kv_sever"] == 1
        assert plan.injected["kv_corrupt"] == 1
        assert reg.value("gateway_kv_reconnects_total") - rc0 >= 1
        assert reg.value("gateway_prefill_restarts_total") - w0 >= 1
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# ISSUE 8: distributed request tracing through a crash
# ---------------------------------------------------------------------------
def _trace_events_for(trace_dir, trace_id):
    evts = []
    for f in sorted(os.listdir(trace_dir)):
        if not f.endswith(".jsonl"):
            continue
        for line in open(os.path.join(trace_dir, f)):
            try:
                e = json.loads(line)
            except ValueError:
                continue
            if (e.get("args") or {}).get("trace_id") == trace_id:
                evts.append(e)
    return evts


def test_replica_kill_keeps_trace_id_and_redispatch_span(
        cfg, params, tmp_path, monkeypatch):
    """THE tracing acceptance (satellite + tentpole): a request whose
    replica is chaos-killed mid-decode resumes on another replica
    under the SAME trace_id; the seam is an explicit
    ``gateway.redispatch`` span naming the old and new replica; both
    replicas' per-request events carry the trace; and ``diagnose
    timeline`` stitches it all into valid chrome-trace JSON."""
    monkeypatch.setenv("MXTPU_TELEMETRY_TRACE_DIR", str(tmp_path))
    reg = telemetry.registry()
    rd0 = reg.value("gateway_redispatch_total")
    gw = Gateway(lambda: _engine(cfg, params, max_slots=1),
                 n_replicas=2, queue_max=16, supervisor_opts=SUP)
    plan = attach_serve(gw, ServeChaosPlan(
        seed=11, kill_replica={0: 2}))
    try:
        port = gw.start_http(port=0)
        prompt = np.arange(6) % cfg.vocab_size
        cli = GatewayClient("127.0.0.1", port)
        rec = cli.generate(prompt, 8, seed=5, temperature=0.8)
        assert rec["status"] == 200 and rec["reason"] == "complete"
        assert rec["tokens"] == _reference(cfg, params, prompt, 8,
                                           seed=5, temperature=0.8)
        assert plan.injected["replica_kill"] >= 1
        assert reg.value("gateway_redispatch_total") - rd0 >= 1
        # the HTTP trailer names the trace; every event carries it
        trace_id = rec["trace_id"]
        assert isinstance(trace_id, str) and len(trace_id) >= 8
        evts = _trace_events_for(str(tmp_path), trace_id)
        names = {e["name"] for e in evts}
        assert "gateway.submit" in names
        assert "serve.done" in names
        # the crash seam: one redispatch span, old AND new replica
        rd = [e for e in evts if e["name"] == "gateway.redispatch"]
        assert rd and rd[0]["ph"] == "X"
        assert rd[0]["args"]["old_replica"] == "r0"
        assert rd[0]["args"]["new_replica"] not in (None, "r0")
        # per-request engine events on BOTH banks, one trace
        roles = {e["args"].get("role") for e in evts
                 if e["name"] == "serve.seat"}
        assert len(roles) >= 2, roles
        # stitched timeline is a valid chrome-trace JSON array
        from tools.diagnose import timeline
        out = str(tmp_path / "timeline.json")
        path, mine = timeline(trace_id, trace_dir=str(tmp_path),
                              out=out)
        assert path == out
        loaded = json.load(open(path))
        assert loaded and all(
            "name" in e and "ph" in e and "pid" in e for e in loaded)
        assert all("ts" in e and "tid" in e for e in loaded
                   if e["ph"] != "M")
        assert any(e["name"] == "gateway.redispatch"
                   for e in loaded)
        tids = {e["args"]["trace_id"] for e in loaded
                if e["ph"] != "M"}
        assert tids == {trace_id}
        # the rid baggage resolves the same timeline without the id
        rid = rd[0]["args"]["rid"]
        path2, mine2 = timeline(rid, trace_dir=str(tmp_path),
                                out=str(tmp_path / "t2.json"))
        assert path2 and len(mine2) == len(mine)
    finally:
        gw.close()


def test_disagg_trace_spans_every_hop(cfg, params, tmp_path,
                                      monkeypatch):
    """Disagg topology: ONE trace covers front door, the prefill
    worker's compute span, the KV handoff receive, and the decode
    seat — and the handoff frame on the wire carries the versioned
    context header."""
    monkeypatch.setenv("MXTPU_TELEMETRY_TRACE_DIR", str(tmp_path))
    be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1,
                       max_slots=2, max_len=32, min_bucket=4)
    gw = Gateway(backend=be, queue_max=16, supervisor_opts=SUP)
    try:
        prompt = np.arange(5) % cfg.vocab_size
        h = gw.submit(prompt, 4, seed=6, temperature=0.9)
        toks = h.result(timeout=120)
        assert h.reason == "complete"
        assert list(toks) == _reference(cfg, params, prompt, 4,
                                        seed=6, temperature=0.9)
        evts = _trace_events_for(str(tmp_path), h.trace_id)
        names = {e["name"] for e in evts}
        assert {"gateway.submit", "gateway.prefill",
                "gateway.handoff_recv", "serve.seat",
                "serve.done"} <= names, names
        pre = [e for e in evts if e["name"] == "gateway.prefill"]
        assert pre[0]["args"]["worker"].startswith("p")
    finally:
        gw.close()


def test_disagg_replica_kill_one_timeline_acceptance(
        cfg, params, tmp_path, monkeypatch):
    """THE ISSUE-8 acceptance scenario verbatim: disagg mode, a
    decode replica killed mid-decode — ONE trace_id spanning the
    front door, the prefill worker, BOTH decode replicas and the
    re-dispatch, stitched into one valid chrome-trace timeline, with
    tokens bit-identical to the fault-free run."""
    monkeypatch.setenv("MXTPU_TELEMETRY_TRACE_DIR", str(tmp_path))
    be = DisaggBackend(cfg, params, n_prefill=1, n_decode=2,
                       max_slots=1, max_len=32, min_bucket=4)
    gw = Gateway(backend=be, queue_max=32, supervisor_opts=SUP)
    plan = attach_serve(gw, ServeChaosPlan(
        seed=13, kill_replica={0: 2}))   # decode r0 dies mid-decode
    try:
        port = gw.start_http(port=0)
        prompt = np.arange(6) % cfg.vocab_size
        cli = GatewayClient("127.0.0.1", port)
        rec = cli.generate(prompt, 8, seed=4, temperature=0.8)
        assert rec["status"] == 200 and rec["reason"] == "complete"
        assert rec["tokens"] == _reference(cfg, params, prompt, 8,
                                           seed=4, temperature=0.8)
        assert plan.injected["replica_kill"] >= 1
        trace_id = rec["trace_id"]
        evts = _trace_events_for(str(tmp_path), trace_id)
        names = {e["name"] for e in evts}
        # every hop of the request's life, one trace
        assert {"gateway.submit", "gateway.prefill",
                "gateway.handoff_recv", "serve.seat",
                "gateway.redispatch", "serve.done"} <= names, names
        roles = {e["args"].get("role") for e in evts
                 if e["name"] == "serve.seat"}
        assert {"r0", "r1"} <= roles, roles    # both decode banks
        rd = [e for e in evts if e["name"] == "gateway.redispatch"]
        assert rd and rd[0]["args"]["trace_id"] == trace_id
        from tools.diagnose import timeline
        path, mine = timeline(trace_id, trace_dir=str(tmp_path),
                              out=str(tmp_path / "acc.json"))
        loaded = json.load(open(path))
        assert {e["name"] for e in loaded} >= names
        assert all("ts" in e and "tid" in e for e in loaded
                   if e["ph"] != "M")
    finally:
        gw.close()


def test_kv_frame_context_header_is_versioned():
    """The wire-compat satellite: a pre-ISSUE-8 frame (no header)
    splits to itself and still decodes as a handoff; a wrapped frame
    round-trips its context through the rpc codec; an UNKNOWN header
    version keeps the payload usable and only drops the context. The
    frame is the handoff's closing ``kvdone``, the one the context
    rides."""
    from mxtpu.serve.gateway.disagg import (handoff_to_page_frames,
                                            pages_to_handoff)
    from mxtpu.serve.engine import KVHandoff
    block = np.arange(24, dtype=np.float32).reshape(1, 2, 6, 2)
    h = KVHandoff(k=block, v=block * 2, true_len=5, token=42,
                  rng=np.asarray([1, 2], np.uint32))
    *pages, old_frame = handoff_to_page_frames(3, h, 4)
    parts = {f[2]: (f[3], f[4]) for f in pages}
    assert old_frame[0] == "kvdone" and sorted(parts) == [0, 1]
    # old frame: pass-through, no context
    payload, ctx = rpc.split_context(old_frame)
    assert payload is old_frame and ctx is None
    rid, h2 = pages_to_handoff(payload, parts)
    assert rid == 3 and h2.token == 42
    # new frame: context survives the full encode/decode round trip
    tctx = telemetry.distributed.mint(rid=3, seed=7,
                                      deadline_abs=12.5)
    wrapped = rpc.attach_context(old_frame, tctx.to_wire())
    wire = rpc.decode(bytes(rpc.encode(wrapped)))
    payload, ctx = rpc.split_context(wire)
    got = telemetry.TraceContext.from_wire(ctx)
    assert got.trace_id == tctx.trace_id and got.rid == 3
    assert got.seed == 7 and got.deadline_abs == 12.5
    rid, h3 = pages_to_handoff(payload, parts)
    assert rid == 3
    np.testing.assert_array_equal(h3.k, block)
    # future version: payload usable, context dropped — never an error
    future = (rpc.CTX_TAG, rpc.CTX_VERSION + 1,
              tctx.to_wire() + ("new-field",), old_frame)
    payload, ctx = rpc.split_context(
        rpc.decode(bytes(rpc.encode(future))))
    assert ctx is None
    assert pages_to_handoff(payload, parts)[0] == 3


def test_slo_burn_rate_degrades_healthz(cfg, params, monkeypatch):
    """The derived-SLO satellite: with a (deliberately impossible)
    TTFT target configured, one served request pushes the burn rate
    over threshold and /healthz flips to degraded with the slo block
    populated; the SLO gauges land in the registry."""
    monkeypatch.setenv("MXTPU_GATEWAY_SLO_TTFT_MS", "0.0001")
    # wide window: the explicit force-ticks below advance it, while
    # the /healthz and /metrics paths inside the window REUSE the
    # last computed burn instead of consuming a fresh (empty) window
    monkeypatch.setenv("MXTPU_GATEWAY_SLO_WINDOW_S", "600")
    gw = Gateway(lambda: _engine(cfg, params), n_replicas=1,
                 queue_max=16, supervise=False)
    try:
        assert gw.slo is not None
        gw.slo.tick(force=True)              # baseline window
        h = gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=0)
        h.result(timeout=60)
        snap = gw.slo.tick(force=True)
        assert snap["ttft"]["burn"] is not None
        assert snap["ttft"]["burn"] > 1.0
        hz = gw.health()
        assert hz["status"] == "degraded"
        assert hz["slo"]["breached"] is True
        assert hz["slo"]["slos"]["ttft"]["target_ms"] == \
            pytest.approx(0.0001)
        reg = telemetry.registry()
        assert reg.value("gateway_slo_burn_rate", slo="ttft") > 1.0
        assert reg.value("gateway_slo_target_ms", slo="ttft") == \
            pytest.approx(0.0001)
        # scrape path ticks + renders without error
        assert "gateway_slo_burn_rate" in gw.metrics_text()
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# graceful degradation: deadline-aware shedding tiers
# ---------------------------------------------------------------------------
def test_tier1_deadline_aware_shed_and_healthz(cfg, params):
    """Past the soft bound the door sheds requests whose own deadline
    cannot survive the backlog (tier 1) while still admitting patient
    ones; /healthz surfaces the tier as degraded. At the hard bound
    everything sheds (tier 2)."""
    gw = Gateway(lambda: _engine(cfg, params, max_slots=1),
                 n_replicas=1, queue_max=4, started=False,
                 supervise=False)
    try:
        assert gw.health()["status"] == "ok"
        handles = [gw.submit(np.arange(4) % cfg.vocab_size, 2,
                             seed=i) for i in range(2)]
        # depth 2 >= soft bound (0.5 * 4): estimated drain ~2 gens —
        # a 0.5 s budget can't survive it -> tier-1 shed
        with pytest.raises(Exception) as ei:
            gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=8,
                      deadline_s=0.5)
        assert getattr(ei.value, "tier", None) == 1
        hz = gw.health()
        assert hz["tier"] == 1 and hz["status"] == "degraded"
        # a patient request (no deadline) is still admitted at tier 1
        handles.append(gw.submit(np.arange(4) % cfg.vocab_size, 2,
                                 seed=2))
        handles.append(gw.submit(np.arange(4) % cfg.vocab_size, 2,
                                 seed=3))
        # hard bound: everything sheds, deadline or not
        with pytest.raises(Exception) as ei:
            gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=9)
        assert getattr(ei.value, "tier", None) == 2
        assert gw.health()["tier"] == 2
        gw.backend.start()
        for i, h in enumerate(handles):
            assert list(h.result(timeout=120)) == _reference(
                cfg, params, np.arange(4) % cfg.vocab_size, 2, seed=i)
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# ISSUE 15: replica kill during a fleet hot-swap
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_fleet_replica_kill_mid_swap_bit_identical(cfg, params):
    """The fleet swap under fire: a chaos-killed old-build replica
    DURING a live checkpoint hot-swap. Contract: zero accepted
    requests dropped; every request that was accepted on the old
    build finishes on the old build (version-aware re-dispatch lands
    on the still-draining old replica, never the new weights), so
    every token list is bit-identical to a fault-free generate with
    the weights its version label names."""
    from mxtpu.serve.fleet import FleetGateway, ModelSpec

    reg = telemetry.registry()
    rd0 = reg.value("gateway_redispatch_total", model="m")
    p1 = llama.init_params(cfg, jax.random.PRNGKey(1))
    a_prompt = [3, 1, 4, 1, 5, 9]
    b_prompt = [2, 7, 1, 8]
    # every fault-free reference BEFORE the fleet exists: reference
    # compiles must not race the live engine threads' own compiles
    ref_anchor = _reference(cfg, params, a_prompt, 16, seed=99,
                            temperature=0.9)
    ref_anchor2 = _reference(cfg, params, a_prompt, 12, seed=98,
                             temperature=0.9)
    ref_burst = [_reference(cfg, params, b_prompt, 8, seed=i,
                            temperature=0.8) for i in range(6)]
    ref_post = [_reference(cfg, p1, b_prompt, 6, seed=200 + i,
                           temperature=0.8) for i in range(4)]
    fleet = FleetGateway(
        [ModelSpec("m", lambda params=params: _engine(cfg, params),
                   replicas=2, max_replicas=2)],
        supervisor_opts=SUP)
    try:
        reps = fleet.pool("m").replicas()
        gw = fleet.gateway("m")
        # pre-warm BOTH engines (prefill bucket-4 + decode compiles)
        # so the kill's step timing is milliseconds, not compile-bound
        for r in reps:
            gw.submit(b_prompt, 2, seed=50,
                      prefer_replica=r.name).result(timeout=180)
        # anchors: sampled requests PINNED to r1 — its first prefill
        # hits the cold bucket-8 program, so r1 is busy (a multi-
        # second compile, then decode) far past the kill detection
        # window, and stays a live old-build target for the whole
        # drain: redispatched v0 work always has a same-build home,
        # never the new weights
        anchor = gw.submit(a_prompt, 16, temperature=0.9, seed=99,
                           prefer_replica=reps[1].name)
        anchor2 = gw.submit(a_prompt, 12, temperature=0.9, seed=98,
                            prefer_replica=reps[1].name)
        burst = [fleet.submit_dict(
            {"prompt": b_prompt, "max_new_tokens": 8,
             "temperature": 0.8, "seed": i}) for i in range(6)]
        # kill r0 a few engine steps from NOW (it holds most of the
        # burst: >= 8 dispatches pending, so the kill always fires —
        # within milliseconds, during the swap's surge spawn)
        plan = attach_serve(fleet.pool("m"), ServeChaosPlan(
            seed=5,
            kill_replica={0: reps[0].engine.steps_run + 6}))
        out = fleet.hot_swap("m", params=p1)
        assert out["version"] == "v1" and out["swapped"] >= 1
        assert out["still_draining"] == []
        assert plan.injected["replica_kill"] == 1, plan.injected

        # zero dropped: everything accepted pre-swap completes, on
        # the OLD build, bit-identical to a fault-free v0 run
        for h, want in ((anchor, ref_anchor), (anchor2, ref_anchor2)):
            toks = list(h.result(timeout=180))
            assert h.reason == "complete"
            assert h.version == "v0"
            assert toks == want
        for i, h in enumerate(burst):
            toks = list(h.result(timeout=180))
            assert h.reason == "complete", (i, h.reason)
            assert h.version == "v0", (i, h.version)
            assert toks == ref_burst[i], i
        # the kill really forced a mid-swap re-dispatch
        assert reg.value("gateway_redispatch_total",
                         model="m") - rd0 >= 1

        # a supervisor respawn racing the swap can leave one old-build
        # replica in routing; retire it so the post-swap pool is
        # uniformly the new build
        for r in fleet.pool("m").replicas():
            if r.version != "v1":
                fleet.pool("m").drain_replica(r)
        for i in range(4):
            h = fleet.submit_dict(
                {"prompt": b_prompt, "max_new_tokens": 6,
                 "temperature": 0.8, "seed": 200 + i})
            toks = list(h.result(timeout=180))
            assert h.version == "v1", (i, h.version)
            assert toks == ref_post[i], i
    finally:
        fleet.close()
        gc.collect()   # release the engines' compiled executables
