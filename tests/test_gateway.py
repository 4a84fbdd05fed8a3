"""Multi-host serving tier (ISSUE 6 tentpole): gateway front door,
engine replicas, disaggregated prefill/decode, autoscaling.

Contracts:
- the shared framed-RPC layer (``mxtpu.rpc``) round-trips the kvstore
  codec and enforces the ``MXTPU_RPC_MAX_FRAME`` ceiling;
- ``ServeEngine.cancel`` / per-request deadlines free the slot at the
  next step boundary and count in ``serve_cancelled_total{reason}``;
- a seeded multi-client Poisson stream through the HTTP gateway across
  2 engine replicas is BIT-IDENTICAL to per-request ``generate``;
- admission past the queue bound is shed with 429 + Retry-After;
- the prefill→KV-handoff→decode path (disaggregated mode) is
  bit-identical, both as raw programs and end to end over the
  framed-RPC channel;
- the autoscaler makes one up and one down decision deterministically
  under a fake clock + injected load, logged through telemetry.
"""
import json
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import rpc, telemetry
from mxtpu.models import llama
from mxtpu.serve import KVHandoff, Request, ServeEngine, bucket_for
from mxtpu.serve.gateway import (AutoscalePolicy, Autoscaler,
                                 DisaggBackend, Gateway, GatewayClient,
                                 KVChannel, ReplicaSet)


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


# ---------------------------------------------------------------------------
# mxtpu.rpc: the factored wire layer
# ---------------------------------------------------------------------------
def test_rpc_roundtrip_and_frame_limit(monkeypatch):
    """The kvstore codec lives in mxtpu.rpc now (kvstore/server.py
    aliases it); frames round-trip over a real socket with and without
    HMAC, and the max-frame ceiling is an env knob."""
    from mxtpu.kvstore import server as psrv
    assert psrv.PSAuthError is rpc.RPCAuthError
    assert psrv.PSProtocolError is rpc.RPCProtocolError
    a, b = socket.socketpair()
    msg = ("push", ("ns", "w"), np.arange(12, dtype=np.float32)
           .reshape(3, 4), None, True, 2.5, [b"raw", "s"])

    def same(x, y):
        if isinstance(y, np.ndarray):
            np.testing.assert_array_equal(x, y)
            assert x.dtype == y.dtype
        elif isinstance(y, (tuple, list)):
            assert type(x) is type(y) and len(x) == len(y)
            for i, j in zip(x, y):
                same(i, j)
        else:
            assert x == y and type(x) is type(y)

    rpc.send_msg(a, msg)
    got, authed = rpc.recv_msg(b)
    same(got, msg)
    assert not authed
    rpc.send_msg(a, msg, b"sekrit")
    got, authed = rpc.recv_msg(b, b"sekrit")
    same(got, msg)
    assert authed
    # secret mismatch -> auth error, not garbage
    rpc.send_msg(a, msg, b"sekrit")
    with pytest.raises(rpc.RPCAuthError):
        rpc.recv_msg(b, b"other")
    # extension dtypes survive the wire: bf16 is the DEFAULT KV dtype
    # (LlamaConfig.dtype), so the handoff codec must round-trip it
    # bit-exactly, not decode it as raw void
    import ml_dtypes
    bf = np.arange(8, dtype=np.float32).astype(ml_dtypes.bfloat16)
    rpc.send_msg(a, bf)
    got, _ = rpc.recv_msg(b)
    assert got.dtype == bf.dtype, got.dtype
    np.testing.assert_array_equal(got.view(np.uint16),
                                  bf.view(np.uint16))
    with pytest.raises(TypeError):      # structured stays refused
        rpc.encode(np.zeros(2, dtype=[("a", "<f4")]))
    # the frame ceiling is the env knob now, not a constant
    monkeypatch.setenv("MXTPU_RPC_MAX_FRAME", "16")
    sizes = []
    rpc.send_msg(a, np.zeros(64, np.float32))
    with pytest.raises(rpc.RPCProtocolError):
        rpc.recv_msg(b, observe=sizes.append)
    assert sizes and sizes[0] > 16      # observed before rejection
    a.close()
    b.close()


# ---------------------------------------------------------------------------
# engine: cancel + deadline (the gateway's slow-client defense)
# ---------------------------------------------------------------------------
def test_engine_cancel_frees_slot_and_counts(cfg, params):
    """cancel(rid) mid-run: the slot frees at a step boundary, the
    other request still matches generate bit-for-bit, partial tokens
    are kept, serve_cancelled_total{cancel} counts, and on_done fires
    with the reason. A queued rid cancels without ever taking a
    slot."""
    reg = telemetry.registry()
    before = reg.value("serve_cancelled_total", reason="cancel")
    eng = ServeEngine(cfg, params, max_slots=1, max_len=32,
                      min_bucket=4)
    done = {}
    long_req = Request(prompt=np.arange(4) % cfg.vocab_size,
                       max_new_tokens=20, seed=1,
                       on_done=lambda rid, r: done.setdefault(rid, r))
    # cancel the long request from a token callback after 3 tokens —
    # deterministic: no wall clock involved
    long_rid = {}

    def on_tok(rid, tok):
        if len(eng._results[rid]) >= 3:
            eng.cancel(long_rid["rid"])
    long_req.on_token = on_tok
    long_rid["rid"] = eng.submit(long_req)
    queued = Request(prompt=np.arange(5) % cfg.vocab_size,
                     max_new_tokens=2, seed=2,
                     on_done=lambda rid, r: done.setdefault(rid, r))
    qrid = eng.submit(queued)         # waits behind the 1-slot bank
    cancel_queued = Request(prompt=np.arange(3) % cfg.vocab_size,
                            max_new_tokens=2, seed=3, arrival_step=10**6,
                            on_done=lambda rid, r:
                            done.setdefault(rid, r))
    crid = eng.submit(cancel_queued)
    assert eng.cancel(crid, "cancel")
    res = eng.run()
    # the cancelled-active request stopped early with partial tokens
    assert 3 <= len(res[long_rid["rid"]]) < 20
    assert done[long_rid["rid"]] == "cancel"
    # its partial tokens are a prefix of its own generate chain
    ref = _reference(cfg, params, np.arange(4) % cfg.vocab_size, 20,
                     seed=1)
    n = len(res[long_rid["rid"]])
    assert list(res[long_rid["rid"]]) == ref[:n]
    # the queued request got the freed slot and matches generate
    assert list(res[qrid]) == _reference(
        cfg, params, np.arange(5) % cfg.vocab_size, 2, seed=2)
    assert done[qrid] == "complete"
    # the queued-cancelled request produced nothing and finalized
    assert len(res[crid]) == 0 and done[crid] == "cancel"
    assert reg.value("serve_cancelled_total",
                     reason="cancel") - before == 2
    # cancel of a finished rid is a no-op
    assert not eng.cancel(qrid)
    # every slot was reclaimed
    assert eng.load()["active"] == 0


def test_engine_deadline_fake_clock(cfg, params):
    """Deadlines run on the engine's injectable clock: a request whose
    budget expires mid-decode frees its slot at the next step boundary
    (reason 'deadline'); one whose budget never expires is untouched
    and bit-identical."""
    reg = telemetry.registry()
    before = reg.value("serve_cancelled_total", reason="deadline")
    now = {"t": 100.0}
    eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                      min_bucket=4, clock=lambda: now["t"])
    done = {}
    ticking = Request(
        prompt=np.arange(4) % cfg.vocab_size, max_new_tokens=16,
        seed=5, deadline_s=50.0,
        on_done=lambda rid, r: done.setdefault(rid, r))
    # advance the fake clock past the deadline after the 4th token
    rid_box = {}

    def tick(rid, tok):
        if len(eng._results[rid]) >= 4:
            now["t"] = 200.0
    ticking.on_token = tick
    r1 = eng.submit(ticking)
    rid_box["rid"] = r1
    r2 = eng.submit(Request(
        prompt=np.arange(6) % cfg.vocab_size, max_new_tokens=5,
        seed=6, deadline_s=10**6,
        on_done=lambda rid, r: done.setdefault(rid, r)))
    res = eng.run()
    assert done[r1] == "deadline"
    assert 4 <= len(res[r1]) < 16
    assert done[r2] == "complete"
    assert list(res[r2]) == _reference(
        cfg, params, np.arange(6) % cfg.vocab_size, 5, seed=6)
    assert reg.value("serve_cancelled_total",
                     reason="deadline") - before == 1
    assert eng.load()["active"] == 0


# ---------------------------------------------------------------------------
# the gateway: Poisson multi-client stream, 2 replicas, bit-identity
# ---------------------------------------------------------------------------
@pytest.mark.slow   # ~26s; fresh-process contract home: gateway_smoke
def test_gateway_two_replicas_poisson_bit_identical(cfg, params):
    """12 seeded clients with Poisson-spaced arrivals hammer the HTTP
    front door over 2 engine replicas (mixed lengths + sampling
    configs): every streamed token sequence must equal the request's
    own per-request generate — routing, replication and streaming are
    transport, never math. The Prometheus scrape must carry the
    gateway metric families."""
    gw = Gateway(lambda: ServeEngine(cfg, params, max_slots=2,
                                     max_len=32, min_bucket=4),
                 n_replicas=2, queue_max=256)
    try:
        port = gw.start_http(port=0)
        rng = np.random.default_rng(11)
        plan = []
        for i in range(12):
            plen = int(rng.choice([3, 5, 9]))
            samp = (dict(temperature=float(rng.choice([0.7, 0.9])),
                         top_k=int(rng.choice([5, 8])))
                    if i % 2 else dict(temperature=0.0))
            plan.append(dict(
                prompt=rng.integers(0, cfg.vocab_size, plen),
                mnew=int(rng.choice([1, 2, 4])), seed=i,
                delay=float(rng.exponential(0.01)), **samp))
        results = {}

        def client(i, job):
            time.sleep(job["delay"])
            cli = GatewayClient("127.0.0.1", port)
            results[i] = cli.generate(
                job["prompt"], job["mnew"], seed=job["seed"],
                temperature=job.get("temperature", 0.0),
                **({"top_k": job["top_k"]} if "top_k" in job else {}))

        threads = [threading.Thread(target=client, args=(i, job))
                   for i, job in enumerate(plan)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert len(results) == 12
        for i, job in enumerate(plan):
            assert results[i]["status"] == 200, results[i]
            assert results[i]["reason"] == "complete"
            ref = _reference(cfg, params, job["prompt"], job["mnew"],
                             seed=job["seed"],
                             temperature=job.get("temperature", 0.0),
                             top_k=job.get("top_k"))
            assert results[i]["tokens"] == ref, (i, job)
        # both replicas exist and the scrape is well-formed
        st = gw.state()
        assert st["n_replicas"] == 2 and len(st["replicas"]) == 2
        # ISSUE 13: per-replica + aggregate KV-cache occupancy ride
        # /state (reserved is the static page pool; the engines are
        # drained here so live is back to 0)
        kv = st["kv_cache"]
        assert kv["reserved_bytes"] == sum(
            r["kv_cache"]["reserved_bytes"] for r in st["replicas"])
        assert kv["reserved_bytes"] > 0 and kv["slots"] > 0
        assert 0.0 <= kv["occupancy"] <= 1.0
        status, prom = GatewayClient("127.0.0.1", port) \
            .get_text("/metrics")
        assert status == 200
        for fam in ("mxtpu_gateway_replicas",
                    "mxtpu_gateway_requests_total",
                    "mxtpu_gateway_ttft_ms",
                    "mxtpu_serve_tokens_total"):
            assert fam in prom, fam
        for line in prom.splitlines():
            assert line.startswith("#") or " " in line, line
    finally:
        gw.close()


def test_gateway_backpressure_429(cfg, params):
    """Past the queue bound the front door sheds with 429 +
    Retry-After (admission control), and the shed request is COUNTED;
    once the engines start, the accepted backlog still completes
    bit-identically — load shedding never corrupts accepted work."""
    reg = telemetry.registry()
    before = reg.value("gateway_requests_total", code="429")
    gw = Gateway(lambda: ServeEngine(cfg, params, max_slots=1,
                                     max_len=32, min_bucket=4),
                 n_replicas=1, queue_max=2, started=False)
    try:
        port = gw.start_http(port=0)
        cli = GatewayClient("127.0.0.1", port)
        handles = [gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=i)
                   for i in range(2)]          # fill the bound
        rec = cli.generate(np.arange(4) % cfg.vocab_size, 2, seed=9)
        assert rec["status"] == 429
        assert rec["retry_after_s"] >= 1
        assert "queue full" in rec["error"]
        assert reg.value("gateway_requests_total",
                         code="429") - before == 1
        gw.backend.start()                    # engines come up
        for i, h in enumerate(handles):
            toks = h.result(timeout=120)
            assert h.reason == "complete"
            assert list(toks) == _reference(
                cfg, params, np.arange(4) % cfg.vocab_size, 2, seed=i)
        # and the door is open again
        rec = cli.generate(np.arange(4) % cfg.vocab_size, 2, seed=9)
        assert rec["status"] == 200
        assert rec["tokens"] == _reference(
            cfg, params, np.arange(4) % cfg.vocab_size, 2, seed=9)
    finally:
        gw.close()


def test_state_and_diagnose_kv_show_the_page_pool(cfg, params, capsys):
    """An engine built with nothing said about its bank reports its
    page pool through ``/state``, and ``tools/diagnose.py kv`` renders
    it, fleet line and replica row, from that one scrape."""
    from tools.diagnose import kv_state
    gw = Gateway(llama_refs.engine_factory(cfg, params), n_replicas=1,
                 queue_max=8)
    try:
        port = gw.start_http(port=0)
        assert gw.submit(np.arange(1, 6), 3).result(120) is not None
        kv = gw.state()["kv_cache"]
        assert kv["paged"] and kv["pages_total"] == 2 * (32 // 16)
        assert kv["pages_used"] == 0 and kv["prefix_misses"] == 1
        assert kv_state(f"127.0.0.1:{port}")
    finally:
        gw.close()
    out = capsys.readouterr().out
    assert "pages: 0/4 used (4 free, 0 shared)" in out, out
    assert "prefix cache: hits=0 misses=1" in out, out
    assert "pages=0/4" in out and "paged: off" not in out, out


def test_gateway_deadline_reclaims_slot_end_to_end(cfg, params):
    """The gateway's default deadline plumbs down into the engine: a
    request with a tiny budget ends with reason 'deadline' while a
    parallel one completes — the serving tier never lets one slow
    consumer pin a slot."""
    gw = Gateway(lambda: ServeEngine(cfg, params, max_slots=1,
                                     max_len=64, min_bucket=4),
                 n_replicas=1, queue_max=64,
                 default_deadline_s=0.25)
    try:
        h1 = gw.submit(np.arange(4) % cfg.vocab_size, 60, seed=1)
        toks = h1.result(timeout=120)
        assert h1.reason == "deadline"
        assert len(toks) < 60
        # the freed slot serves the next request to completion
        h2 = gw.submit(np.arange(5) % cfg.vocab_size, 3, seed=2,
                       deadline_s=10**6)
        assert list(h2.result(timeout=120)) == _reference(
            cfg, params, np.arange(5) % cfg.vocab_size, 3, seed=2)
        assert h2.reason == "complete"
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# disaggregated prefill/decode with KV handoff
# ---------------------------------------------------------------------------
def test_prefill_detached_inject_bit_identical(cfg, params):
    """The program pair itself: prefill_detached's (token, KV block,
    rng) injected into a fresh engine's bank continues to EXACTLY the
    colocated engine's tokens (same forward graph, same chain), for
    greedy and sampled configs."""
    for seed, temp in [(3, 0.0), (4, 0.9)]:
        prompt = (np.arange(5) * 7 + seed) % cfg.vocab_size
        bucket = bucket_for(5, 4, 32)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :5] = prompt
        tok, kb, vb, rng = llama.prefill_detached(
            cfg, params, jnp.asarray(padded), np.int32(5),
            jax.random.PRNGKey(seed), np.float32(temp),
            np.int32(cfg.vocab_size), np.float32(1.0))
        h = KVHandoff(k=np.asarray(kb), v=np.asarray(vb), true_len=5,
                      token=int(np.asarray(tok)[0]),
                      rng=np.asarray(rng, np.uint32))
        eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                          min_bucket=4)
        rid = eng.submit_prefilled(h, Request(
            prompt=prompt, max_new_tokens=6, temperature=temp,
            seed=seed))
        res = eng.run()
        assert list(res[rid]) == _reference(
            cfg, params, prompt, 6, seed=seed, temperature=temp)
        # admission compiled ONE inject program, zero prefills
        assert eng.n_buckets == 1 and len(eng._prefills) == 0
        assert eng.compile_count <= eng.n_buckets + 1


@pytest.mark.slow   # ~19s; gateway_smoke covers the fresh-process
# path and tier-1 keeps test_gateway_two_replicas_poisson_bit_identical
def test_disagg_gateway_bit_identical_over_rpc_channel(cfg, params):
    """End to end: prompts routed to prefill workers, KV blocks framed
    over the mxtpu.rpc channel (HMAC on), seated in decode replicas —
    tokens bit-identical to generate; handoff counters moved."""
    reg = telemetry.registry()
    before = reg.value("gateway_kv_handoffs_total")
    be = DisaggBackend(cfg, params, n_prefill=2, n_decode=2,
                       max_slots=2, max_len=32, min_bucket=4,
                       channel=KVChannel.pair(secret=b"kv-test"))
    gw = Gateway(backend=be, queue_max=64)
    try:
        port = gw.start_http(port=0)
        rng = np.random.default_rng(21)
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
            assert results[i]["status"] == 200
            assert results[i]["tokens"] == _reference(
                cfg, params, job["prompt"], job["mnew"],
                seed=job["seed"], temperature=job["temperature"]), i
        assert reg.value("gateway_kv_handoffs_total") - before == 8
        hist = reg.get("gateway_kv_handoff_bytes")
        assert hist is not None and hist.count >= 8
    finally:
        gw.close()


def test_disagg_prefill_error_and_pending_deadline(cfg, params):
    """Pool resilience: a failing prefill job finalizes ITS request
    (reason 'error') without killing the worker — the next request
    still serves bit-identically. And the deadline budget starts at
    SUBMIT: a request whose budget is gone by seating time expires at
    the handoff instead of getting a fresh budget."""
    reg = telemetry.registry()
    e0 = reg.value("gateway_prefill_errors_total")
    be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1,
                       max_slots=2, max_len=32, min_bucket=4)
    gw = Gateway(backend=be, queue_max=16)
    try:
        worker = be.prefill[0]
        orig_fn = worker._fn

        def poisoned(bucket):
            def f(*a, **k):
                raise RuntimeError("injected prefill failure")
            return f

        worker._fn = poisoned
        h = gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=0)
        toks = h.result(timeout=60)
        assert h.reason == "error" and len(toks) == 0
        assert reg.value("gateway_prefill_errors_total") - e0 == 1
        # the worker thread survived the failure and serves again
        worker._fn = orig_fn
        h2 = gw.submit(np.arange(4) % cfg.vocab_size, 2, seed=1)
        assert list(h2.result(timeout=120)) == _reference(
            cfg, params, np.arange(4) % cfg.vocab_size, 2, seed=1)
        assert h2.reason == "complete"
        # zero budget: expired before seating -> 'deadline' at the
        # handoff, no decode slot ever taken
        d0 = reg.value("serve_cancelled_total", reason="deadline")
        h3 = gw.submit(np.arange(4) % cfg.vocab_size, 8, seed=2,
                       deadline_s=0.0)
        toks = h3.result(timeout=60)
        assert h3.reason == "deadline" and len(toks) == 0
        assert reg.value("serve_cancelled_total",
                         reason="deadline") - d0 == 1
    finally:
        gw.close()


def test_kv_channel_tcp_listen_connect():
    """The cross-host deployment path: the handoff channel over TCP
    loopback with HMAC, same framed codec."""
    listener, port = KVChannel.listen("127.0.0.1", 0)
    got = {}

    def rx_side():
        ch = KVChannel.accept(listener, secret=b"s")
        got["msg"] = ch.recv()
        ch.close()

    t = threading.Thread(target=rx_side)
    t.start()
    tx = KVChannel.connect("127.0.0.1", port, secret=b"s")
    payload = ("kv", 7, 3, 42, np.ones((2, 2, 4, 2), np.float32),
               np.zeros((2, 2, 4, 2), np.float32),
               np.asarray([1, 2], np.uint32))
    tx.send(payload)
    t.join(30)
    tx.close()
    listener.close()
    assert got["msg"][0] == "kv" and got["msg"][1] == 7
    np.testing.assert_array_equal(got["msg"][4], payload[4])


# ---------------------------------------------------------------------------
# autoscaler: one up and one down decision, fully deterministic
# ---------------------------------------------------------------------------
class _FakePool:
    def __init__(self, n=1, slots_per=4):
        self.n = n
        self.slots_per = slots_per
        self.queued = 0
        self.active = 0
        self.calls = []

    @property
    def size(self):
        return self.n

    def load_total(self):
        return {"queued": self.queued, "active": self.active,
                "slots": self.n * self.slots_per}

    def scale_to(self, n):
        self.calls.append(n)
        self.n = n
        return n


def test_autoscaler_up_down_deterministic():
    """Fake clock + injected load: a queue spike scales up exactly
    once (cooldown absorbs the repeat), sustained idleness past the
    cooldown scales down exactly once, telemetry counts both, and the
    decision log carries the driving signals."""
    reg = telemetry.registry()
    up0 = reg.value("gateway_scale_events_total", direction="up")
    dn0 = reg.value("gateway_scale_events_total", direction="down")
    now = {"t": 0.0}
    pool = _FakePool(n=1, slots_per=4)
    pol = AutoscalePolicy(min_replicas=1, max_replicas=3,
                          target_p99_ms=50.0, queue_high=2.0,
                          occupancy_low=0.25, cooldown_s=10.0,
                          interval_s=1.0)
    lat = {"p99": None}
    sc = Autoscaler(pool, pol, clock=lambda: now["t"],
                    latency_p99=lambda: lat["p99"])
    # quiet start: no decision
    assert sc.tick() is None
    # queue spike -> one up, then cooldown holds even though still hot
    pool.queued = 9
    now["t"] = 1.0
    assert sc.tick() == "up"
    assert pool.n == 2
    now["t"] = 2.0
    assert sc.tick() is None          # in cooldown
    # hot via the latency signal once cooldown passes
    pool.queued = 0
    pool.active = 8
    lat["p99"] = 80.0                 # > target 50
    now["t"] = 12.0
    assert sc.tick() == "up"
    assert pool.n == 3
    # idle must be SUSTAINED for cooldown_s before a down
    pool.active = 0
    lat["p99"] = None
    now["t"] = 23.0
    assert sc.tick() is None          # idle timer starts
    now["t"] = 28.0
    assert sc.tick() is None          # not sustained yet
    now["t"] = 33.5
    assert sc.tick() == "down"
    assert pool.n == 2
    assert pool.calls == [2, 3, 2]
    assert reg.value("gateway_scale_events_total",
                     direction="up") - up0 == 2
    assert reg.value("gateway_scale_events_total",
                     direction="down") - dn0 == 1
    dirs = [d["direction"] for d in sc.decisions]
    assert dirs == ["up", "up", "down"]
    assert sc.decisions[0]["pressure"] == 9.0
    assert sc.decisions[1]["p99_ms"] == 80.0
    # floor: never below min_replicas
    now["t"] = 100.0
    sc.tick()
    now["t"] = 200.0
    sc.tick()
    now["t"] = 300.0
    sc.tick()
    assert pool.n >= pol.min_replicas


def test_autoscaler_scales_real_replica_set(cfg, params):
    """The lever is real: scale_to on a live ReplicaSet adds a serving
    replica that takes traffic, and scaling down drains without
    dropping accepted work."""
    rs = ReplicaSet(lambda: ServeEngine(cfg, params, max_slots=2,
                                        max_len=32, min_bucket=4), 1)
    try:
        assert rs.size == 1
        rs.scale_to(2)
        assert rs.size == 2
        assert telemetry.registry().value("gateway_replicas") == 2
        # submit through the router, then shrink while running;
        # replicas prune engine bookkeeping, so collect via callbacks
        got = {i: [] for i in range(4)}
        finished = {}
        tickets = []
        for i in range(4):
            req = Request(prompt=np.arange(4) % cfg.vocab_size,
                          max_new_tokens=2, seed=i,
                          on_token=(lambda i: lambda rid, tok:
                                    got[i].append(tok))(i),
                          on_done=(lambda i: lambda rid, r:
                                   finished.setdefault(i, r))(i))
            tickets.append(rs.route(req))
        rs.scale_to(1)
        assert rs.size == 1
        # drained replica finishes its accepted requests
        deadline = time.time() + 120
        while time.time() < deadline and len(finished) < 4:
            time.sleep(0.02)
        assert len(finished) == 4 and set(finished.values()) == \
            {"complete"}
        for i in range(4):
            assert got[i] == _reference(
                cfg, params, np.arange(4) % cfg.vocab_size, 2, seed=i)
        # the replica engines pruned their per-request bookkeeping
        # (the forever-serving memory contract)
        for t in tickets:
            eng = t.replica.engine
            assert t.rid not in eng._results
            assert t.rid not in eng._requests
    finally:
        rs.close()


def test_interval_p99_windows():
    """The latency signal is per-window: observations from a previous
    window must not drag the current p99."""
    from mxtpu.serve.gateway.autoscale import interval_p99
    bounds = (1.0, 2.0, 4.0, 8.0)
    assert interval_p99(bounds, None, [0, 0, 0, 0, 0]) is None
    prev = [10, 0, 0, 0, 0]            # old fast window
    cur = [10, 0, 0, 5, 0]             # new slow observations only
    p = interval_p99(bounds, prev, cur)
    assert 4.0 < p <= 8.0
    assert interval_p99(bounds, cur, cur) is None   # empty window


# ---------------------------------------------------------------------------
# bench path
# ---------------------------------------------------------------------------
@pytest.mark.slow   # ~10s; bench_smoke runs this path fresh-process
def test_bench_gateway_smoke(cfg):
    """The gateway benchmark's measurement path on a tiny config:
    record shape, positive throughput, ordered percentiles, and a TTFT
    block (the metric the chip run emits into BENCH_*.json)."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import bench
    rec = bench.bench_gateway(n_requests=4, n_replicas=2, max_slots=2,
                              max_len=48, cfg=cfg, seed=1,
                              mean_interarrival_s=0.005)
    assert rec["metric"] == "llama_500m_gateway_tokens_per_s"
    assert rec["value"] > 0 and rec["unit"] == "tok/s"
    assert rec["p99_token_ms"] >= rec["p50_token_ms"] >= 0
    assert rec["ttft_p99_ms"] >= rec["ttft_p50_ms"] > 0
    assert rec["n_replicas"] == 2
    assert rec["vs_baseline"] is None
