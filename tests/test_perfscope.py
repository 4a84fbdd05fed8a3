"""Perfscope: live roofline attribution + HBM ledger (ISSUE 13).

Contracts:
- the shared MFU/MBU/roofline helpers are exact arithmetic, and the
  LIVE mfu gauge agrees with ``perfscope.mfu`` on the same inputs —
  bench.py and the gauges read the SAME function, so offline and
  live MFU can never disagree;
- every watched jitted program enters the cost catalog on compile
  with flops > 0 and a deterministic compute- vs memory-bound class
  at the device knee;
- KV-cache occupancy is exact byte math, both as pure helpers and as
  a running ServeEngine's reserved-vs-live accounting;
- an injected slow step trips the median+k·MAD anomaly detector:
  counter + flight record naming the program;
- the HBM ledger's headroom knob leaves ONE edge-triggered
  OOM-adjacent flight record with the per-category breakdown;
- the new gauges ride the PR 8 federation with process labels and the
  whole scrape stays strict-Prometheus parseable;
- ``tools/diagnose.py perf`` renders the roofline table from the
  same samples.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import telemetry as tm
from mxtpu.telemetry import perfscope as ps


@pytest.fixture(autouse=True)
def _telemetry_on():
    tm.enable(True)
    yield
    tm.enable(True)


# ---------------------------------------------------------------------------
# shared helpers: exact arithmetic
# ---------------------------------------------------------------------------
def test_mfu_mbu_helpers_exact():
    # 1e12 flops in 0.01 s on a 1e15-peak part = 10% MFU, exactly
    assert ps.mfu(1e12, 0.01, peak_flops=1e15) == pytest.approx(0.1)
    assert ps.hbm_bw_util(8e9, 0.01, peak_bw=8e12) == pytest.approx(0.1)
    # degenerate inputs are 0, never a crash or inf
    assert ps.mfu(1e12, 0.0, peak_flops=1e15) == 0.0
    assert ps.mfu(1e12, 0.01, peak_flops=0.0) == 0.0


def test_roofline_class_at_the_knee():
    spec = ps.DeviceSpec(kind="x", peak_flops=100.0, peak_bw=10.0,
                         hbm_bytes=1)
    assert spec.knee == pytest.approx(10.0)
    assert ps.roofline_class(1000, 10, spec) == "compute_bound"   # 100
    assert ps.roofline_class(10, 1000, spec) == "memory_bound"    # .01
    assert ps.roofline_class(100, 10, spec) == "compute_bound"    # ==knee
    # zero traffic can only be compute bound
    assert ps.roofline_class(5, 0, spec) == "compute_bound"


def test_spec_for_and_overrides(monkeypatch):
    assert ps.spec_for("TPU v5e").kind == "v5e"
    assert ps.spec_for("TPU v5p something").kind == "v5p"
    assert ps.spec_for("cpu").kind == "cpu"
    with pytest.raises(KeyError, match="no-such-chip"):
        ps.spec_for("no-such-chip")     # not someone else's peaks
    # the MXTPU_TELEMETRY_PERF_PEAK_FLOPS knob (read at import)
    # overrides the table's peak; everything else stays
    monkeypatch.setattr(ps, "_PEAK_FLOPS", 123e12)
    sp = ps.device_spec()
    assert sp.peak_flops == pytest.approx(123e12)
    assert sp.peak_bw == ps.spec_for(sp.kind).peak_bw


# ---------------------------------------------------------------------------
# cost catalog via watch()
# ---------------------------------------------------------------------------
def test_watched_program_enters_catalog_compute_bound():
    """A 512^3 matmul (intensity ~85 flops/byte in f32) is compute
    bound even at the CPU knee; flops must be the exact 2·n^3."""
    n = 512
    f = tm.watch(jax.jit(lambda a, b: a @ b), "ps_matmul")
    x = jnp.ones((n, n), jnp.float32)
    f(x, x).block_until_ready()
    cost = ps.catalog()["ps_matmul"]
    assert cost.flops == pytest.approx(2 * n ** 3)
    assert cost.bytes_accessed > 0
    assert cost.klass == "compute_bound"
    # the labelled gauges are live in the same scrape
    reg = tm.registry()
    assert reg.value("program_flops", program="ps_matmul") == \
        pytest.approx(2 * n ** 3)
    assert reg.value("program_roofline", program="ps_matmul",
                     **{"class": "compute_bound"}) == 1.0


def test_watched_elementwise_is_memory_bound():
    """1 flop per 12 bytes moved — far below any knee in the table."""
    f = tm.watch(jax.jit(lambda a, b: a + b), "ps_add")
    x = jnp.ones((256, 256), jnp.float32)
    f(x, x).block_until_ready()
    cost = ps.catalog()["ps_add"]
    assert cost.flops > 0
    assert cost.klass == "memory_bound"


def test_cataloging_reads_the_executable_the_call_built():
    """The watcher catalogs from the executable (libtpu has no costs
    for less), memory analysis included, and that must not be a second
    compile: the lowering jax cached for the call holds it."""
    tm.install_compile_listener()
    f = tm.watch(jax.jit(lambda a, b: jnp.tanh(a @ b)), "ps_executable")
    x = jnp.ones((64, 64), jnp.float32)
    jax.block_until_ready(x)
    before = tm.registry().value("jax_compile_total")
    f(x, x).block_until_ready()
    assert tm.registry().value("jax_compile_total") == before + 1
    cost = ps.catalog()["ps_executable"]
    assert cost.flops > 0 and cost.peak_hbm_bytes >= 3 * 64 * 64 * 4
    # a bare Lowered has no costs on libtpu: program_costs says what to
    # pass instead of cataloging nothing
    class NoCosts:
        def cost_analysis(self):
            return None
    with pytest.raises(ValueError, match="compiled"):
        ps.program_costs(NoCosts())


def test_program_costs_on_aot_compiled():
    """The bench path: an explicitly lowered+compiled program through
    the SAME helper, memory fields included (AOT has them for free),
    spec pinned so the class can't drift with the CI host."""
    comp = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((128, 128)), jnp.ones((128, 128))).compile()
    costs = ps.program_costs(comp, name="ps_aot",
                             spec=ps.spec_for("v5e"))
    assert costs["flops"] == pytest.approx(2 * 128 ** 3)
    assert costs["roofline"] in ("compute_bound", "memory_bound")
    # at least the two f32 operands; backends may count more (padding,
    # aliasing) so this is a floor, not an equality
    assert costs["argument_bytes"] >= 2 * 128 * 128 * 4
    assert costs["peak_hbm_bytes"] > 0
    assert "ps_aot" in ps.catalog()


# ---------------------------------------------------------------------------
# live MFU gauge == the bench helper (the can't-disagree acceptance)
# ---------------------------------------------------------------------------
def test_live_mfu_gauge_agrees_with_bench_helper():
    scope = ps.scope()
    name = "ps_mfu_agree"
    scope.register_cost(ps.ProgramCost(name=name, flops=1e9,
                                       bytes_accessed=1e6))
    # steady 10 ms dispatch gaps
    for i in range(6):
        scope.on_call(name, i * 0.010, i * 0.010 + 0.001)
    w = scope._windows[name]
    mean_gap = sum(w.gaps) / len(w.gaps)
    # catalog costs are one device's partition, and so is the peak
    expect = ps.mfu(1e9, mean_gap, peak_flops=scope.spec().peak_flops)
    assert tm.registry().value("mfu", program=name) == \
        pytest.approx(expect)
    assert expect > 0


# ---------------------------------------------------------------------------
# KV-cache occupancy
# ---------------------------------------------------------------------------
def test_serve_engine_kv_occupancy_accounting():
    from mxtpu.models import llama
    from mxtpu.serve import ServeEngine, Request
    cfg = llama.LlamaConfig(
        vocab_size=64, dim=16, n_layers=2, n_heads=2, n_kv_heads=2,
        hidden_dim=32, max_seq_len=32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                      min_bucket=4)
    stats = eng.kv_cache_stats()
    # the pool the engine builds by default: 2 slots x 2 pages of 16
    # and the scratch page, K and V, in the config's bf16
    pool = llama.init_paged_cache(cfg, 2, 5, 16)
    expect_reserved = ps.tree_bytes([pool["k"], pool["v"]])
    assert expect_reserved == (2 * cfg.n_layers * 5 * 16 * cfg.n_kv_heads
                               * cfg.head_dim
                               * np.dtype(jnp.bfloat16).itemsize)
    assert stats["reserved_bytes"] == expect_reserved
    assert stats["live_bytes"] == 0 and stats["occupancy"] == 0.0
    eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
    eng.run()
    # drained engine: slots released, occupancy back to 0; the
    # reserved pool is a static allocation and never changes
    stats = eng.kv_cache_stats()
    assert stats["reserved_bytes"] == expect_reserved
    assert stats["active"] == 0
    # the gauges carried the same numbers
    eid = eng.engine_id
    reg = tm.registry()
    assert reg.value("serve_kv_reserved_bytes", engine=eid) == \
        expect_reserved
    # while the request was live, occupancy rose above 0 then fell;
    # at drain the live gauge is back to 0
    assert reg.value("serve_kv_live_bytes", engine=eid) == 0
    # the ledger recorded the pool under kv_page_pool
    assert ps.ledger().breakdown().get("kv_page_pool", 0) >= \
        expect_reserved


# ---------------------------------------------------------------------------
# step-anomaly detection
# ---------------------------------------------------------------------------
def test_injected_slow_step_trips_anomaly():
    scope = ps.PerfScope(window=16, anomaly_k=4.0, min_samples=4,
                         idle_s=10.0)
    name = "ps_anomaly_prog"
    reg = tm.registry()
    base = reg.value("step_anomalies_total", program=name)
    t = 0.0
    for _ in range(8):                       # steady 10 ms cadence
        scope.on_call(name, t, t + 0.001)
        t += 0.010
    assert reg.value("step_anomalies_total", program=name) == base
    scope.on_call(name, t + 0.490, t + 0.491)   # one 0.5 s stall
    assert reg.value("step_anomalies_total", program=name) == base + 1
    recs = [r for r in tm.flight().tail(50)
            if r.get("name") == "step_anomaly"
            and r.get("program") == name]
    assert recs, "anomaly must leave a flight record naming the program"
    assert recs[-1]["gap_ms"] == pytest.approx(500.0, rel=0.05)


def test_idle_gap_resets_window_instead_of_flagging():
    scope = ps.PerfScope(window=16, anomaly_k=4.0, min_samples=4,
                         idle_s=0.2)
    name = "ps_idle_prog"
    reg = tm.registry()
    base = reg.value("step_anomalies_total", program=name)
    t = 0.0
    for _ in range(8):
        scope.on_call(name, t, t + 0.001)
        t += 0.010
    # a parked loop (gap > idle_s) clears the window, no anomaly
    scope.on_call(name, t + 5.0, t + 5.001)
    assert reg.value("step_anomalies_total", program=name) == base
    assert len(scope._windows[name].gaps) == 0


# ---------------------------------------------------------------------------
# HBM ledger + headroom flight record
# ---------------------------------------------------------------------------
def test_hbm_ledger_breakdown_and_last_write_wins():
    led = ps.HBMLedger()
    led.account("params", 1000, name="train")
    led.account("optimizer", 2000, name="train")
    led.account("params", 500, name="train")     # replaces, not adds
    led.account("params", 300, name="engine0")
    assert led.breakdown() == {"params": 800, "optimizer": 2000}
    assert led.total() == 2800
    led.release("optimizer", name="train")
    assert led.total() == 800
    assert led.headroom() == led.capacity() - 800


def test_headroom_knob_leaves_one_flight_record():
    cap = ps.HBMLedger().capacity()
    led = ps.HBMLedger(headroom_bytes=cap - 100)
    n0 = len([r for r in tm.flight().tail(100)
              if r.get("name") == "hbm_headroom_low"])
    led.account("workspace", 200, name="ps_headroom_test")
    led.account("workspace", 300, name="ps_headroom_test")  # still low
    recs = [r for r in tm.flight().tail(100)
            if r.get("name") == "hbm_headroom_low"]
    assert len(recs) == n0 + 1, "edge-triggered: exactly one record"
    assert recs[-1]["bytes_workspace"] == 200
    assert recs[-1]["threshold_bytes"] == int(cap - 100)


# ---------------------------------------------------------------------------
# goodput family
# ---------------------------------------------------------------------------
def test_goodput_gauge_one_family_by_loop():
    tm.goodput_gauge("train").set(0.5)
    tm.goodput_gauge("serve").set(0.25)
    reg = tm.registry()
    assert reg.value("goodput_ratio", loop="train") == 0.5
    assert reg.value("goodput_ratio", loop="serve") == 0.25
    fams = [f for f in reg.families() if f.name == "goodput_ratio"]
    assert len(fams) == 1


# ---------------------------------------------------------------------------
# train-step integration: the watcher profiles on compile
# ---------------------------------------------------------------------------
def test_train_step_is_cataloged_on_compile():
    import optax
    from mxtpu.models import llama
    from mxtpu.parallel import mesh as pmesh, step as pstep
    cfg = llama.LlamaConfig(
        vocab_size=64, dim=16, n_layers=2, n_heads=2, n_kv_heads=2,
        hidden_dim=32, max_seq_len=16)
    mesh = pmesh.create_mesh(dp=-1)
    rules = llama.sharding_rules(cfg)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    state = pstep.init_state(params, tx, mesh, rules)
    step = pstep.make_train_step(llama.loss_fn(cfg), tx, mesh, rules)
    batch = {"tokens": jnp.zeros(
        (jax.device_count(), 16), jnp.int32)}
    state, loss = step(state, batch)
    jax.block_until_ready(loss)
    cost = ps.catalog().get("train_step")
    assert cost is not None and cost.flops > 0
    assert cost.bytes_accessed > 0 and cost.peak_hbm_bytes > 0
    # init_state accounted params + optimizer into the ledger
    bd = ps.ledger().breakdown()
    assert bd.get("params", 0) > 0
    assert bd.get("optimizer", 0) > 0


# ---------------------------------------------------------------------------
# scrape grammar + federation
# ---------------------------------------------------------------------------
def test_new_gauges_parse_and_federate_with_process_labels():
    from mxtpu.telemetry import distributed as dt
    # grammar: the whole live scrape (catalog gauges included from the
    # other tests in this file) stays strict-parseable
    parsed = tm.parse_prometheus(tm.prometheus())
    # federation: a peer's perfscope gauges arrive with its process
    # label and survive the strict parse
    peer = tm.MetricsRegistry()
    peer.gauge("program_flops", "f", program="peer_step").set(3e9)
    peer.gauge("mfu", "m", program="peer_step").set(0.42)
    srv = tm.RegistryServer(port=0, registry=peer, process="worker0")
    try:
        text = dt.federate_text(
            tm.MetricsRegistry(), [("127.0.0.1", srv.port)],
            process="gateway")
    finally:
        srv.close()
    s = tm.parse_prometheus(text)["samples"]
    key = ("mxtpu_program_flops",
           (("process", "worker0"), ("program", "peer_step")))
    assert s[key] == pytest.approx(3e9)
    assert s[("mxtpu_mfu",
              (("process", "worker0"),
               ("program", "peer_step")))] == pytest.approx(0.42)


# ---------------------------------------------------------------------------
# diagnose.py perf renders the same samples
# ---------------------------------------------------------------------------
def test_diagnose_perf_rows_join():
    from tools.diagnose import perf_rows
    samples = {
        ("mxtpu_program_flops", (("program", "stepA"),)): 4e9,
        ("mxtpu_program_bytes_accessed",
         (("program", "stepA"),)): 1e9,
        ("mxtpu_program_roofline",
         (("class", "compute_bound"), ("program", "stepA"))): 1.0,
        ("mxtpu_program_roofline",
         (("class", "memory_bound"), ("program", "stepA"))): 0.0,
        ("mxtpu_mfu", (("program", "stepA"),)): 0.31,
        ("mxtpu_program_wall_ms_total", (("program", "stepA"),)): 75.0,
        ("mxtpu_program_flops", (("program", "stepB"),)): 1e6,
        ("mxtpu_program_wall_ms_total", (("program", "stepB"),)): 25.0,
        ("mxtpu_other_gauge", ()): 1.0,          # no program label
    }
    rows = perf_rows(samples)
    assert [r["program"] for r in rows] == ["stepA", "stepB"]
    a, b = rows
    assert a["roofline"] == "compute_bound"      # the value==1 class
    assert a["mfu"] == pytest.approx(0.31)
    assert a["wall_share"] == pytest.approx(0.75)
    assert b["wall_share"] == pytest.approx(0.25)


def test_diagnose_perf_cli_on_saved_scrape(tmp_path, capsys):
    from tools.diagnose import perf
    f = tm.watch(jax.jit(lambda a: a * 2.0), "ps_cli_prog")
    f(jnp.ones((64, 64))).block_until_ready()
    path = tmp_path / "scrape.txt"
    path.write_text(tm.prometheus())
    assert perf(str(path)) is True
    out = capsys.readouterr().out
    assert "ps_cli_prog" in out
    assert "Roofline attribution" in out
