"""Unified runtime telemetry (ISSUE 5 tentpole).

Contracts:
- counters/gauges/histograms are exact under concurrent writers (the
  serve callback thread, kvstore server threads, the prefetcher);
- fixed-bucket percentiles are monotone and bounded by bucket edges;
- the Prometheus dump is grammatical and cumulative;
- spans nest (depth + timestamp containment) and dump as a valid
  chrome-trace JSON array / stream as parseable JSONL;
- the recompile watcher attributes a deliberately cache-key-busting
  call to its offending key and increments ``recompile_total`` —
  including the sharding-spec-only bust (the PR 4 bug class);
- ``simulate_preemption`` through ``PreemptionGuard`` leaves a
  readable flight-recorder dump on disk (the chaos-harness path);
- the kvstore client/server fault counters count real injected faults.
"""
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import telemetry as tm


@pytest.fixture(autouse=True)
def _telemetry_on():
    """Every test in this file assumes the default-enabled state and
    leaves it that way."""
    tm.enable(True)
    yield
    tm.enable(True)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_counter_gauge_basics():
    c = tm.counter("t_basic_total", "help", op="x")
    base = c.value
    c.inc()
    c.inc(2.5)
    assert c.value == base + 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # same (name, labels) -> the SAME child; different labels -> new
    assert tm.counter("t_basic_total", op="x") is c
    assert tm.counter("t_basic_total", op="y") is not c
    g = tm.gauge("t_basic_gauge")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    # kind conflicts are an error, not a silent shadow
    with pytest.raises(ValueError):
        tm.registry().gauge("t_basic_total")


def test_histogram_percentiles_monotone_and_bounded():
    h = tm.Histogram(buckets=(1, 2, 4, 8, 16))
    for v in (0.5, 1.5, 3, 3, 7, 12, 40):
        h.observe(v)
    assert h.count == 7
    assert h.sum == pytest.approx(67.0)
    qs = [h.percentile(q) for q in (0, 10, 50, 90, 99, 100)]
    assert qs == sorted(qs)
    assert qs[0] >= 0.5 * 0.99            # clamped near observed min
    assert h.percentile(50) <= 8          # p50 of 7 values sits <= 4's bucket
    with pytest.raises(ValueError):
        h.percentile(101)
    h.reset()
    assert h.count == 0 and h.percentile(50) == 0.0


def test_counters_exact_under_threads():
    c = tm.counter("t_threads_total")
    h = tm.histogram("t_threads_ms")
    base_c, base_h = c.value, h.count
    N, PER = 8, 5000

    def worker(i):
        for k in range(PER):
            c.inc()
            h.observe(k % 97)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value - base_c == N * PER
    assert h.count - base_h == N * PER


def test_prometheus_grammar_and_cumulative_buckets():
    tm.counter("t_prom_total", "a counter", kind="k").inc(2)
    h = tm.histogram("t_prom_ms", "a histogram", buckets=(1, 10))
    h.observe(0.5)
    h.observe(5)
    h.observe(500)
    text = tm.prometheus()
    lines = text.splitlines()
    assert "# TYPE mxtpu_t_prom_total counter" in lines
    assert '# TYPE mxtpu_t_prom_ms histogram' in lines
    sample = {l.rsplit(" ", 1)[0]: l.rsplit(" ", 1)[1]
              for l in lines if not l.startswith("#")}
    assert sample['mxtpu_t_prom_total{kind="k"}'] == "2"
    # cumulative: le=1 <= le=10 <= +Inf == _count
    b1 = int(sample['mxtpu_t_prom_ms_bucket{le="1.0"}'])
    b10 = int(sample['mxtpu_t_prom_ms_bucket{le="10.0"}'])
    binf = int(sample['mxtpu_t_prom_ms_bucket{le="+Inf"}'])
    cnt = int(sample["mxtpu_t_prom_ms_count"])
    assert b1 <= b10 <= binf == cnt >= 3
    # every non-comment line is "name{labels} value"
    for l in lines:
        if l and not l.startswith("#"):
            assert " " in l and not l.rsplit(" ", 1)[1].isspace()


def test_summary_table_and_reset_keeps_handles():
    c = tm.counter("t_reset_total")
    c.inc(7)
    assert "t_reset_total" in tm.summary()
    tm.registry().reset()
    assert tm.registry().value("t_reset_total") == 0
    c.inc()                               # old handle still live
    assert tm.registry().value("t_reset_total") == 1


def test_disabled_telemetry_is_noop():
    tm.enable(False)
    try:
        c = tm.counter("t_disabled_total")
        c.inc(100)
        assert tm.registry().value("t_disabled_total") == 0
        n_events = len(tm.trace_events())
        with tm.span("t_disabled_span"):
            pass
        assert len(tm.trace_events()) == n_events
        # the flight SINGLETON honors the kill switch too (a direct
        # FlightRecorder instance never does — private use)
        n_flight = len(tm.flight())
        tm.flight().record("note", "t_disabled")
        assert len(tm.flight()) == n_flight
    finally:
        tm.enable(True)


# ---------------------------------------------------------------------------
# spans + trace
# ---------------------------------------------------------------------------
def test_span_nesting_and_trace_dump(tmp_path):
    tm.clear_trace()
    with tm.span("t_outer", stage="unit") as outer:
        assert tm.current_depth() == 1
        with tm.span("t_inner", bucket=64) as inner:
            assert tm.current_depth() == 2
            time.sleep(0.002)
    assert tm.current_depth() == 0
    assert outer.duration_ms >= inner.duration_ms >= 2.0
    events = {e["name"]: e for e in tm.trace_events()
              if e["name"] in ("t_outer", "t_inner")}
    o, i = events["t_outer"], events["t_inner"]
    assert o["ph"] == i["ph"] == "X"
    assert o["tid"] == i["tid"]
    # child contained within parent on the same timeline
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert i["args"] == {"bucket": 64, "depth": 1, "parent": "t_outer"}
    assert "parent" not in o["args"]
    # spans also feed their duration histograms
    assert tm.registry().get("span_t_outer_ms").count >= 1
    path = tm.dump_trace(str(tmp_path / "trace.json"))
    loaded = json.load(open(path))
    assert any(e["name"] == "t_inner" for e in loaded)


def test_a_span_says_which_span_caused_it():
    """``args.parent`` is the enclosing span on the thread, per thread,
    also for a span recorded after it ended; at depth 0 it is absent."""
    tm.clear_trace()
    seen = {}

    def other_thread():
        with tm.span("t_alone"):
            seen["inside"] = tm.tracing.current_span()
    with tm.span("t_a"):
        with tm.span("t_b"):
            with tm.span("t_c"):
                pass
            tm.tracing.record_span("t_late", 10, 30, bucket=1)
            t = threading.Thread(target=other_thread)
            t.start()
            t.join(10)
        with tm.span("t_d"):
            pass
    assert tm.tracing.current_span() is None
    args = {e["name"]: e["args"] for e in tm.trace_events()}
    assert [args[n].get("parent") for n in
            ("t_a", "t_b", "t_c", "t_d", "t_late", "t_alone")] == [
        None, "t_a", "t_b", "t_a", "t_b", None]
    assert args["t_late"] == {"bucket": 1, "parent": "t_b"}
    assert seen == {"inside": "t_alone"}


def test_trace_streaming_jsonl(tmp_path, monkeypatch):
    stream = tmp_path / "stream.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY_TRACE_PATH", str(stream))
    with tm.span("t_streamed"):
        pass
    tm.instant("t_instant", note=1)
    monkeypatch.delenv("MXTPU_TELEMETRY_TRACE_PATH")
    events = [json.loads(l) for l in open(stream)]
    names = [e["name"] for e in events]
    assert "t_streamed" in names and "t_instant" in names


# ---------------------------------------------------------------------------
# recompile watcher (acceptance criterion)
# ---------------------------------------------------------------------------
def test_recompile_watcher_attributes_cache_key_bust():
    """A deliberately cache-key-busting program change must increment
    recompile_total WITH the offending key recorded."""
    f = tm.watch(jax.jit(lambda x: x * 2), "t_bust", expected=1)
    f(jnp.ones((4,), jnp.float32))
    f(jnp.ones((4,), jnp.float32))            # cached: no new event
    assert len(f.compiles) == 1
    assert tm.registry().value("recompile_total", fn="t_bust") == 0
    f(jnp.ones((8,), jnp.float32))            # the bust
    assert len(f.compiles) == 2
    assert tm.registry().value("recompile_total", fn="t_bust") == 1
    assert tm.registry().value("compile_events_total", fn="t_bust") == 2
    assert "float32[8]" in f.compiles[-1]     # offending key, readable
    assert "float32[4]" in f.compiles[0]
    # and the flight recorder holds the anomaly with its key
    recomp = [e for e in tm.flight().tail(100)
              if e["kind"] == "recompile" and e["name"] == "t_bust"]
    assert recomp and "float32[8]" in recomp[-1]["key"]


def test_recompile_watcher_sees_sharding_spec_bust():
    """The PR 4 bug class: SAME shape/dtype, different PartitionSpec →
    a second cache entry. The recorded keys must differ exactly in
    their spec strings, so the anomaly names the bug."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    x = jnp.ones((8, 4), jnp.float32)
    a = jax.device_put(x, NamedSharding(mesh, P()))
    b = jax.device_put(x, NamedSharding(mesh, P("dp")))
    f = tm.watch(jax.jit(lambda t: t + 1), "t_spec_bust", expected=1)
    f(a)
    f(b)
    assert len(f.compiles) == 2
    assert tm.registry().value("recompile_total", fn="t_spec_bust") == 1
    k0, k1 = f.compiles
    assert k0 != k1 and "float32[8, 4]" in k0 and "float32[8, 4]" in k1
    assert "dp" in k1 and "dp" not in k0      # the spec IS the diff


def test_watch_refuses_uninstrumentable_callable():
    with pytest.raises(TypeError):
        tm.watch(lambda x: x, "t_plain")


def test_global_compile_listener_counts():
    assert tm.install_compile_listener()
    before = tm.registry().value("jax_compile_total")
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((3,), jnp.float32))
    assert tm.registry().value("jax_compile_total") > before


# ---------------------------------------------------------------------------
# flight recorder + preemption (acceptance criterion)
# ---------------------------------------------------------------------------
def test_flight_recorder_ring_is_bounded(tmp_path):
    fr = tm.FlightRecorder(maxlen=5)
    for i in range(12):
        fr.record("note", f"e{i}", i=i)
    assert len(fr) == 5
    assert [e["name"] for e in fr.tail(10)] == [f"e{i}" for i in
                                                range(7, 12)]
    path = fr.dump(str(tmp_path / "ring.jsonl"))
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 5 and lines[-1]["i"] == 11
    assert "e11" in fr.format_tail(2)


def test_preemption_leaves_flight_dump_on_disk(tmp_path, monkeypatch):
    """The chaos-harness preemption (simulate_preemption → SIGTERM →
    PreemptionGuard) must leave a readable flight-recorder dump."""
    from mxtpu.checkpoint import PreemptionGuard
    from mxtpu.contrib import chaos
    dump = tmp_path / "flight_preempt.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY_FLIGHT_PATH", str(dump))
    tm.flight().record("note", "step", step=41)
    with PreemptionGuard() as guard:
        chaos.simulate_preemption()
        for _ in range(100):                  # delivery is async-ish
            if guard.preempted:
                break
            time.sleep(0.01)
    assert guard.preempted
    assert guard.flight_dump_path == str(dump)
    events = [json.loads(l) for l in open(dump)]
    assert any(e["kind"] == "preemption" for e in events)
    assert any(e["name"] == "step" and e.get("step") == 41
               for e in events)               # the job's last moments


# ---------------------------------------------------------------------------
# ISSUE 8: metrics federation + exposition grammar
# ---------------------------------------------------------------------------
def test_federated_merge_counter_exact_and_grammar():
    """The federation acceptance: per-process series carry `process`
    labels, the aggregate counter equals the SUM of every process's
    value exactly, histogram buckets merge element-wise, gauges are
    last-write — and the whole multi-process scrape parses under
    strict Prometheus text grammar."""
    from mxtpu.telemetry import distributed as dt
    local = tm.MetricsRegistry()
    r_worker = tm.MetricsRegistry()
    r_kv = tm.MetricsRegistry()
    for reg, n in ((local, 2.0), (r_worker, 3.5), (r_kv, 7.0)):
        reg.counter("fed_requests_total", "requests",
                    code="ok").inc(n)
        reg.gauge("fed_depth", "queue depth").set(n)
        h = reg.histogram("fed_ms", "latency", buckets=(1, 10, 100))
        h.observe(0.5)
        h.observe(n * 10)
    srv1 = tm.RegistryServer(port=0, registry=r_worker,
                             process="worker0")
    srv2 = tm.RegistryServer(port=0, registry=r_kv, process="kvstore")
    try:
        text = dt.federate_text(
            local, [("127.0.0.1", srv1.port),
                    ("127.0.0.1", srv2.port)], process="gateway")
        parsed = tm.parse_prometheus(text)       # strict: raises on
        #                                          any malformed line
        s = parsed["samples"]
        lab = (("code", "ok"),)
        per_proc = [s[("mxtpu_fed_requests_total",
                       tuple(sorted(lab + (("process", p),))))]
                    for p in ("gateway", "worker0", "kvstore")]
        assert per_proc == [2.0, 3.5, 7.0]
        # counter exactness: aggregate == sum of per-process
        assert s[("mxtpu_fed_requests_total", lab)] == sum(per_proc)
        # histogram: merged count == total observations everywhere
        assert s[("mxtpu_fed_ms_count", ())] == 6.0
        assert s[("mxtpu_fed_ms_bucket", (("le", "1.0"),))] == 3.0
        # gauge: last write in scrape order (local, worker0, kvstore)
        assert s[("mxtpu_fed_depth", ())] == 7.0
        assert parsed["types"]["mxtpu_fed_requests_total"] == \
            "counter"
        assert parsed["types"]["mxtpu_fed_ms"] == "histogram"
        # ≥ 3 distinct process labels federated in one scrape
        procs = {dict(labels).get("process")
                 for (_, labels) in s if dict(labels).get("process")}
        assert {"gateway", "worker0", "kvstore"} <= procs
    finally:
        srv1.close()
        srv2.close()


def test_federation_skips_dead_peer_and_counts():
    """A peer that is down mid-restart must cost its series, not the
    scrape: the merged text still renders + parses, and the failure
    is counted per peer."""
    from mxtpu.telemetry import distributed as dt
    local = tm.MetricsRegistry()
    local.counter("fed_alone_total").inc(4)
    before = tm.registry().value("federation_errors_total",
                                 peer="127.0.0.1:1")
    text = dt.federate_text(local, [("127.0.0.1", 1)],
                            process="gateway", timeout=0.5)
    parsed = tm.parse_prometheus(text)
    assert parsed["samples"][("mxtpu_fed_alone_total", ())] == 4.0
    assert tm.registry().value("federation_errors_total",
                               peer="127.0.0.1:1") - before == 1


def test_federation_dedups_colliding_process_roles():
    """Two peers that claim the same role must not produce duplicate
    series (a real Prometheus server rejects the whole scrape on
    one): the second gets a deterministic positional suffix, and the
    strict parser — which now raises on duplicates — stays happy."""
    from mxtpu.telemetry import distributed as dt
    local = tm.MetricsRegistry()
    r1, r2 = tm.MetricsRegistry(), tm.MetricsRegistry()
    local.counter("fed_dup_total").inc(1)
    r1.counter("fed_dup_total").inc(2)
    r2.counter("fed_dup_total").inc(4)
    s1 = tm.RegistryServer(port=0, registry=r1, process="prefill")
    s2 = tm.RegistryServer(port=0, registry=r2, process="prefill")
    try:
        text = dt.federate_text(
            local, [("127.0.0.1", s1.port), ("127.0.0.1", s2.port)],
            process="gateway")
        parsed = tm.parse_prometheus(text)   # raises on duplicates
        s = parsed["samples"]
        assert s[("mxtpu_fed_dup_total", ())] == 7.0
        assert s[("mxtpu_fed_dup_total",
                  (("process", "prefill"),))] == 2.0
        assert s[("mxtpu_fed_dup_total",
                  (("process", "prefill~1"),))] == 4.0
    finally:
        s1.close()
        s2.close()


def test_prometheus_label_escaping_round_trips():
    """Exposition polish satellite: label values with quotes,
    backslashes and newlines must render escaped — the strict parser
    recovers the original bytes."""
    nasty = 'a"b\\c\nd'
    tm.counter("t_escape_total", "counts", err=nasty).inc(3)
    text = tm.prometheus()
    parsed = tm.parse_prometheus(text)
    assert parsed["samples"][("mxtpu_t_escape_total",
                              (("err", nasty),))] == 3.0
    assert parsed["types"]["mxtpu_t_escape_total"] == "counter"


def test_histogram_interval_percentile_shared_helper():
    """The bucket-diff math is one shared helper: the Histogram
    method, the autoscaler alias and the module function agree."""
    from mxtpu.serve.gateway.autoscale import interval_p99
    h = tm.Histogram(buckets=(1, 2, 4, 8))
    prev, _, _ = h.snapshot()
    for v in (3, 3, 3, 7):
        h.observe(v)
    cur, _, _ = h.snapshot()
    via_method = h.interval_percentile(list(prev), q=99.0)
    via_fn = tm.interval_percentile(h.bounds, list(prev), list(cur),
                                    99.0)
    via_alias = interval_p99(h.bounds, list(prev), list(cur))
    assert via_method == via_fn == via_alias
    assert 4 < via_method <= 8          # p99 sits in the (4, 8] bucket
    assert h.interval_percentile(list(cur)) is None   # empty window
    # the burn-rate ingredient: fraction of the window over threshold
    from mxtpu.telemetry.registry import interval_over_fraction
    d_prev, d_cur = list(prev), list(cur)
    frac = interval_over_fraction(h.bounds, d_prev, d_cur, 4.0)
    assert frac == pytest.approx(0.25)  # 1 of 4 observations past 4
    assert interval_over_fraction(h.bounds, None, d_cur, 4.0) is None


def test_flight_fork_path_and_process_tag(tmp_path, monkeypatch):
    """Forked-worker satellite: a process forked after import must not
    clobber the parent's flight dump — the env path gains a .<pid>
    suffix in the child — and every record is tagged with the process
    role."""
    import importlib
    fl = importlib.import_module("mxtpu.telemetry.flight")
    dump = tmp_path / "flight.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY_FLIGHT_PATH", str(dump))
    # parent (the importing pid): exact env path, back-compat
    assert fl.default_flight_path() == str(dump)
    # simulated fork: same module state, different pid
    monkeypatch.setattr(fl, "_IMPORT_PID", os.getpid() + 1)
    child_path = fl.default_flight_path()
    assert child_path == f"{dump}.{os.getpid()}"
    monkeypatch.setattr(fl, "_IMPORT_PID", os.getpid())
    # records carry the role; role honors the env override per call
    fr = tm.FlightRecorder(maxlen=4)
    fr.record("note", "before")
    monkeypatch.setenv("MXTPU_TELEMETRY_PROCESS", "prefill0")
    fr.record("note", "after")
    tail = fr.tail(2)
    assert tail[0]["process"] == f"pid{os.getpid()}"
    assert tail[1]["process"] == "prefill0"


# ---------------------------------------------------------------------------
# kvstore fault counters count real injected faults
# ---------------------------------------------------------------------------
def test_ps_fault_counters_under_chaos():
    from mxtpu.contrib.chaos import ChaosPlan, attach, free_port
    from mxtpu.kvstore.server import KVStoreServer, ServerClient
    reg = tm.registry()
    before = {n: reg.value(n) for n in
              ("ps_retries_total", "ps_reconnects_total",
               "ps_dedup_hits_total")}
    port = free_port()
    srv = KVStoreServer("127.0.0.1", port)
    try:
        cl = ServerClient("127.0.0.1", port)
        cl.request("init", "w", np.zeros(3))
        # drop AFTER send: the push is applied, the ack lost — the
        # retry is a duplicate the server must dedup (index 0: the
        # plan indexes logical requests from attach time)
        plan = attach(cl, ChaosPlan(schedule={0: "drop_after_send"}))
        cl.request("push", "w", np.ones(3))
        assert plan.injected["drop_after_send"] == 1
        _, val = cl.request("pull", "w")
        np.testing.assert_array_equal(val, np.ones(3))   # exactly-once
        assert reg.value("ps_retries_total") - \
            before["ps_retries_total"] >= 1
        assert reg.value("ps_reconnects_total") - \
            before["ps_reconnects_total"] >= 1
        assert reg.value("ps_dedup_hits_total") - \
            before["ps_dedup_hits_total"] >= 1
        assert reg.value("ps_requests_total", op="push") >= 1
        # frame sizes landed in the histogram
        assert reg.get("ps_request_bytes").count >= 3
        cl.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# training-path instrumentation
# ---------------------------------------------------------------------------
def test_prefetcher_records_data_wait():
    from mxtpu.gluon.data.prefetcher import DevicePrefetcher
    h = tm.registry().get("train_data_wait_ms")
    before = h.count if h is not None else 0
    batches = [{"x": np.full((2, 2), i, np.float32)} for i in range(4)]
    with DevicePrefetcher(iter(batches)) as pf:
        got = list(pf)
    assert len(got) == 4
    h = tm.registry().get("train_data_wait_ms")
    assert h is not None and h.count - before == 4


def test_speedometer_routes_registry_and_writer():
    import mxtpu as mx

    class _Param:
        def __init__(self, nbatch):
            self.nbatch = nbatch
            self.epoch = 0
            self.eval_metric = mx.metric.MSE()

    class _Writer:
        def __init__(self):
            self.scalars = []

        def add_scalar(self, tag, value, step=None):
            self.scalars.append((tag, float(value), step))

    w = _Writer()
    sp = mx.callback.Speedometer(batch_size=4, frequent=2,
                                 auto_reset=False, summary_writer=w)
    m = mx.metric.MSE()
    m.update([mx.nd.zeros((2, 1))], [mx.nd.ones((2, 1))])
    for nb in (1, 2, 3, 4):
        p = _Param(nb)
        p.eval_metric = m
        sp(p)                                 # fires at nb=4
    assert tm.registry().value("train_samples_per_s") > 0
    assert tm.registry().value("train_batches_total") >= 2
    assert tm.registry().value("train_metric", metric="mse") == \
        pytest.approx(1.0)
    assert any(t == "train/samples_per_s" for t, _, _ in w.scalars)
    assert any(t == "train/mse" and v == pytest.approx(1.0)
               for t, v, _ in w.scalars)


def test_train_step_dispatch_span():
    import optax
    from mxtpu.parallel import mesh as pmesh, step as pstep
    from mxtpu.parallel.sharding import ShardingRules, P
    h = tm.registry().get("span_train_dispatch_ms")
    before = h.count if h is not None else 0
    mesh = pmesh.create_mesh(dp=-1)
    rules = ShardingRules([(r".*", P())])
    params = {"w": jnp.ones((3,), jnp.float32)}
    tx = optax.sgd(0.1)
    state = pstep.init_state(params, tx, mesh, rules)
    step = pstep.make_train_step(
        lambda p, b: jnp.sum((p["w"] - b["x"]) ** 2), tx, mesh, rules)
    state, loss = step(state, {"x": jnp.zeros((8, 3), jnp.float32)})
    assert float(loss) > 0
    h = tm.registry().get("span_train_dispatch_ms")
    assert h is not None and h.count - before == 1


# ---------------------------------------------------------------------------
# ISSUE 15: fleet series ride the existing registry without breaking
# any grandfathered series name
# ---------------------------------------------------------------------------
def test_goodput_ratio_has_fleet_loop_member():
    """``mxtpu_goodput_ratio{loop=...}`` is the ONE goodput family;
    the fleet admission ratio joins it as ``loop="fleet"`` alongside
    the train/serve members — same name, same gauge type, one more
    label value."""
    from mxtpu.telemetry.perfscope import goodput_gauge
    goodput_gauge("train").set(0.5)
    goodput_gauge("serve").set(0.75)
    goodput_gauge("fleet").set(0.9)
    s = tm.parse_prometheus(tm.prometheus())["samples"]
    vals = {dict(lab)["loop"]: v for (name, lab), v in s.items()
            if name == "mxtpu_goodput_ratio"}
    assert vals["fleet"] == 0.9
    assert {"train", "serve", "fleet"} <= set(vals)
    assert tm.parse_prometheus(tm.prometheus())["types"][
        "mxtpu_goodput_ratio"] == "gauge"


def test_gateway_requests_model_label_grandfathers_unlabeled():
    """A fleet deployment adds ``model=`` to the gateway request
    counters; a single-model gateway keeps emitting the EXACT
    pre-fleet series (``{code}`` only). Both label shapes coexist in
    one scrape under one family header, and the strict-grammar parser
    accepts it — existing dashboards keyed on the unlabeled series
    never notice the fleet exists."""
    reg = tm.registry()
    plain0 = reg.value("gateway_requests_total", code="accepted")
    mod0 = reg.value("gateway_requests_total", code="accepted",
                     model="grandfather-m")
    reg.counter("gateway_requests_total", "by outcome code",
                code="accepted").inc(3)
    reg.counter("gateway_requests_total", "by outcome code",
                code="accepted", model="grandfather-m").inc(2)
    s = tm.parse_prometheus(tm.prometheus())["samples"]
    assert s[("mxtpu_gateway_requests_total",
              (("code", "accepted"),))] == plain0 + 3
    assert s[("mxtpu_gateway_requests_total",
              (("code", "accepted"),
               ("model", "grandfather-m")))] == mod0 + 2
    # the two shapes are distinct series: incrementing one never
    # moves the other
    assert reg.value("gateway_requests_total",
                     code="accepted") == plain0 + 3
    assert reg.value("gateway_requests_total", code="accepted",
                     model="grandfather-m") == mod0 + 2
