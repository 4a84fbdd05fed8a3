"""Set-up told from the inside (ISSUE 36): the compile listener gives
every phase of a build (trace, lowering, backend) and the persistent
cache's answer to the watched program that was being called, or to
``others``; the catalog keeps the build beside the scope map; and the
places that build run under ``setup.*`` spans that say which span
caused them.

Engines use the standard tier-1 shape (``llama_refs.engine_factory``).
"""
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import llama_refs
from mxtpu import runtime, telemetry
from mxtpu.models import llama
from mxtpu.parallel import mesh as pmesh, step as pstep
from mxtpu.serve.gateway import Gateway
from mxtpu.telemetry import scopes as tscopes, watcher

BUILD_SERIES = ("program_trace_seconds_total", "program_lower_seconds_total",
                "program_backend_seconds_total", "program_compiled_total",
                "program_nested_traces_total")


@pytest.fixture(autouse=True)
def listener():
    telemetry.install_compile_listener()


def toy(x):
    y = jnp.sin(x) @ x
    return jnp.where(y > 0, y, jnp.tanh(y)).sum()


def spans(name, **args):
    """The ring's ``name`` spans whose args hold ``args``."""
    return [e for e in telemetry.trace_events() if e["name"] == name
            and all(e["args"].get(k) == v for k, v in args.items())]


def series(name, program):
    return telemetry.registry().value(name, program=program)


def check_build(prog):
    """A catalogued program's record of its build is filled in, and its
    spans hang under the call that built it."""
    assert prog.trace_s > 0 and prog.lower_s > 0 and prog.backend_s > 0
    assert prog.first_call_s >= prog.trace_s + prog.lower_s + prog.backend_s
    assert prog.compiled + prog.fetched >= 1
    assert prog.cache in ("hit", "miss", "unwritten", "off")
    assert prog.module and prog.temp_bytes is not None
    (call,) = spans("setup.first_call", program=prog.name)[-1:]
    for phase in ("trace", "lower", "backend"):
        (inner,) = spans(f"setup.{phase}", program=prog.name,
                         parent="setup.first_call")[-1:]
        assert call["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= call["ts"] + call["dur"]
    assert spans("setup.catalog", program=prog.name)
    for name in BUILD_SERIES[:3]:
        assert series(name, prog.name) > 0


def test_phases_go_to_the_watched_program_by_name():
    w = telemetry.watch_jit(toy, "setup_toy", "setup_toy_program")
    assert float(w(jnp.ones((8, 8)))) == pytest.approx(64 * np.sin(1) * 8)
    prog = telemetry.programs()["setup_toy"]
    check_build(prog)
    # sin, matmul, greater, tanh, where (and what where traces inside
    # itself), sum: counted, and their seconds lie inside the program's
    # own trace, which is jax's one event for it and nothing added
    assert prog.nested_traces >= 6
    (own,) = spans("setup.trace", program="setup_toy")
    assert own["args"]["fun_name"] == "setup_toy_program"
    assert prog.trace_s == pytest.approx(own["dur"] / 1e6, abs=2e-6)
    assert 0 < prog.nested_trace_s < prog.trace_s
    assert series("program_nested_traces_total",
                  "setup_toy") == prog.nested_traces
    # a second call builds nothing
    before = (prog.trace_s, prog.nested_traces, prog.backend_s)
    w(jnp.ones((8, 8)))
    prog = telemetry.programs()["setup_toy"]
    assert (prog.trace_s, prog.nested_traces, prog.backend_s) == before


def test_a_build_outside_any_watched_call_lands_in_others():
    before = telemetry.programs().get(tscopes.OTHERS, tscopes.Program(""))
    lowered = before.lower_s
    counted = before.compiled + before.fetched
    assert float(jax.jit(lambda x: toy(x) + 1)(jnp.ones((4, 4)))) > 0
    others = telemetry.programs()[tscopes.OTHERS]
    assert others.lower_s > lowered
    assert others.compiled + others.fetched > counted
    assert others.module == "" and not others.scopes
    # no call of a program caused it: the span has no such parent
    assert spans("setup.backend", program="others")[-1]["args"].get(
        "parent") is None


@pytest.fixture
def cache_dir(tmp_path):
    """jax's persistent cache in a directory of the test's own, every
    executable written whatever it took to compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path), 0.0, 0)):
        jax.config.update(n, v)
    cc.reset_cache()
    try:
        yield str(tmp_path)
    finally:
        for n, v in old.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_a_second_build_reads_hit_and_a_small_program_unwritten(cache_dir):
    def build(name):
        w = telemetry.watch_jit(toy, name, name + "_program")
        w(jnp.ones((8, 8)))
        return telemetry.programs()[name]
    first = build("setup_cached")
    assert (first.cache, first.compiled, first.fetched) == ("miss", 1, 0)
    jax.clear_caches()
    second = build("setup_cached")
    assert (second.cache, second.compiled, second.fetched) == ("hit", 0, 1)
    assert series("program_fetched_total", "setup_cached") == 1
    assert series("program_compiled_total", "setup_cached") == 1
    # jax's own rule: what compiled in under a second is not written,
    # and is compiled again by every process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    small = build("setup_small")
    assert (small.cache, small.compiled) == ("unwritten", 1)
    jax.clear_caches()
    assert build("setup_small").cache == "unwritten"


def test_use_compile_cache_starts_the_record(cache_dir, monkeypatch):
    entry = os.path.join(cache_dir, "entry")
    with open(entry, "wb") as f:
        f.write(b"x" * 100)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    telemetry.reset()                   # a process that has not started
    assert runtime.use_compile_cache() == cache_dir
    assert runtime.use_compile_cache() == cache_dir     # once a process
    reg = telemetry.registry()
    assert reg.value("compile_cache_entries") == 1
    assert reg.value("compile_cache_bytes") == 100
    (start,), (imported,) = spans("setup.backend_init"), spans("setup.import")
    # `import mxtpu` loads no telemetry to say so: two stamps, read here
    assert imported["dur"] / 1e6 == reg.value("import_seconds") > 0
    assert imported["ts"] + imported["dur"] <= start["ts"]
    assert reg.value("setup_spanned_seconds_total") == pytest.approx(
        (imported["dur"] + start["dur"]) / 1e6, abs=1e-4)


def test_engine_programs_spans_and_series(serve_cfg, serve_params):
    telemetry.clear_trace()
    gw = Gateway(llama_refs.engine_factory(serve_cfg, serve_params,
                                           page_size=4),
                 n_replicas=1, queue_max=8)
    try:
        gw.start_http(port=0)
        # one request a bucket (4 and 8); the first runs decode too
        for n in (3, 7):
            assert gw.submit(np.arange(1, 1 + n), 3,
                             seed=n).result(120) is not None
    finally:
        gw.close()
    mine = {n: p for n, p in telemetry.programs().items()
            if n in ("serve_decode", "serve_prefill_b4",
                     "serve_prefill_b8")}
    assert len(mine) == 3, sorted(telemetry.programs())
    for prog in mine.values():
        check_build(prog)
    (engine,) = spans("setup.engine_build")
    assert "parent" not in engine["args"]
    (alloc,) = spans("setup.state_alloc")
    assert alloc["args"]["parent"] == "setup.engine_build"
    # the allocation's own little programs belong to no watched call,
    # and were built inside the allocation's span
    assert spans("setup.backend", program="others",
                 parent="setup.state_alloc")
    assert spans("setup.gateway_start")
    # the engine's loop called the programs: its span caused the build
    assert spans("setup.first_call", program="serve_decode")[0]["args"][
        "parent"] == "serve.decode_step"
    text = telemetry.prometheus()
    for name in BUILD_SERIES + (
            "setup_spanned_seconds_total", "span_setup_engine_build_ms_sum",
            "span_setup_state_alloc_ms_sum", "span_setup_first_call_ms_sum",
            "span_setup_catalog_ms_sum", "span_setup_gateway_start_ms_sum"):
        assert f"mxtpu_{name}" in text, name


def test_train_step_build_spans_and_series():
    telemetry.clear_trace()
    named = telemetry.registry().value("setup_spanned_seconds_total")
    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                  attn_impl="dense")
    mesh = pmesh.create_mesh(fsdp=1, devices=jax.devices()[:1])
    rules = llama.sharding_rules(cfg)
    tx = optax.sgd(1e-3)
    state = pstep.init_state(
        llama.init_params(cfg, jax.random.PRNGKey(0)), tx, mesh, rules)
    step = pstep.make_train_step(llama.loss_fn(cfg, mesh=mesh), tx, mesh,
                                 rules)
    tokens = jnp.zeros((2, 16), jnp.int32)
    state, loss = step(state, {"tokens": tokens})
    assert np.isfinite(float(loss))
    prog = telemetry.programs()["train_step"]
    check_build(prog)
    (alloc,), (build,) = spans("setup.state_alloc"), spans("setup.step_build")
    assert "parent" not in alloc["args"] and "parent" not in build["args"]
    (call,) = spans("setup.first_call", program="train_step")
    assert call["args"]["parent"] == "train.step_dispatch"
    # what no other setup span encloses is the set-up the program names
    top = [e for e in telemetry.trace_events()
           if e["name"].startswith("setup.")
           and not e["args"].get("parent", "").startswith("setup.")]
    named = telemetry.registry().value("setup_spanned_seconds_total") - named
    assert named == pytest.approx(sum(e["dur"] for e in top) / 1e6,
                                  abs=1e-4)
    assert named >= prog.first_call_s


def test_the_listener_installed_twice_counts_once():
    assert telemetry.install_compile_listener()
    assert telemetry.install_compile_listener()
    before = telemetry.registry().value("jax_compile_total")
    w = telemetry.watch_jit(toy, "setup_once", "setup_once_program")
    w(jnp.ones((8, 8)))
    assert telemetry.registry().value("jax_compile_total") - before == 1
    assert telemetry.programs()["setup_once"].compiled == 1
    assert series("program_compiled_total", "setup_once") == 1


def test_a_listener_that_raises_does_not_break_jit(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("listener fault")
    monkeypatch.setattr(watcher, "_phase_closed", boom)
    w = telemetry.watch_jit(toy, "setup_boom", "setup_boom_program")
    assert float(w(jnp.ones((8, 8)))) > 0
    # the build happened and is in the catalog, without its seconds
    assert telemetry.programs()["setup_boom"].trace_s == 0.0
