"""The readers of the program's record of its set-up (PR 36) on an
observation written by hand: the arithmetic, ``None`` wherever the
program does not hold the series, the training process's form (the
registry, no scrape), and the new entries of ``BENCHMARK.json`` against
the rules the accepted ones are held to."""
import json
import math
import os

import pytest

import run as grid_run
import setup_reads

GRID = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(GRID))
METRICS = {"setup_trace_s": ("s", "program_counter"),
           "setup_lower_s": ("s", "program_counter"),
           "setup_backend_s": ("s", "program_counter"),
           "setup_compiled_count": ("count", "program_counter"),
           "setup_state_alloc_s": ("s", "program_span"),
           "setup_unspanned_s": ("s", "program_span")}


def read(name, obs):
    return grid_run.load_module("readers", name).read(obs)


def serve_obs():
    """30 s of set-up of which 1 is the ramp: 2 s of import, 4 s of
    backend start, an engine built in 1.5 s (0.5 of it the state) and
    three programs' first calls, 9 s, catalogued in 0.3; the window
    then compiles nothing, but its spans move the second scrape."""
    s0 = {"program_trace_seconds_total": 3.5,
          "program_lower_seconds_total": 2.25,
          "program_backend_seconds_total": 4.0,
          "program_compiled_total": 2.0, "program_fetched_total": 5.0,
          "program_nested_traces_total": 1900.0,
          "setup_spanned_seconds_total": 16.8,
          "import_seconds": 2.0, "compile_cache_entries": 40.0,
          "compile_cache_bytes": 1.5e8}
    for phase, ms, n in (("import", 2000.0, 1), ("backend_init", 4000.0, 1),
                         ("engine_build", 1500.0, 1),
                         ("state_alloc", 500.0, 1),
                         ("first_call", 9000.0, 3), ("catalog", 300.0, 3),
                         ("trace", 3500.0, 9), ("lower", 2250.0, 9),
                         ("backend", 4000.0, 9)):
        s0[f"span_setup_{phase}_ms_sum"] = ms
        s0[f"span_setup_{phase}_ms_count"] = float(n)
    s1 = dict(s0, span_serve_emit_ms_sum=215.0)
    # one program fetched inside the window: the catalog, read after
    # it, is then no longer the set-up's alone, and the note says so
    s1["program_fetched_total"] = 6.0
    row = {"module": "jit_decode_slots_paged", "scopes": {"f": ("", False)},
           "parse_s": 0.01, "trace_s": 0.91234567, "nested_traces": 620,
           "nested_trace_s": 0.7, "lower_s": 0.68, "backend_s": 0.4,
           "compiled": 0, "fetched": 1, "cache": "hit",
           "first_call_s": 2.1, "custom_calls": 2, "fast_mem_buffers": 91,
           "fast_mem_bytes": 103988744, "temp_bytes": 4096}
    return {"scrape0": s0, "scrape1": s1,
            "end_to_end": {"setup_s": 30.0}, "traffic": {"ramp_s": 1.0},
            "programs": {"serve_decode": row,
                         # the parent's catalog: no build to tell
                         "old": {"module": "jit_old", "scopes": {}}}}


def test_the_six_readers_on_a_hand_made_observation():
    obs = serve_obs()
    got = {name: read(name, obs) for name in METRICS}
    assert got == {"setup_trace_s": 3.5, "setup_lower_s": 2.25,
                   "setup_backend_s": 4.0, "setup_compiled_count": 2.0,
                   "setup_state_alloc_s": 0.5,
                   "setup_unspanned_s": pytest.approx(30.0 - 1.0 - 16.8)}
    notes = obs["notes"]
    # the ramp, the top-level spans and the rest add to setup_s
    spans = notes["setup_spans_s"]
    top = sum(spans[p] for p in ("import", "backend_init", "engine_build",
                                 "first_call", "catalog"))
    assert math.isclose(1.0 + top + got["setup_unspanned_s"], 30.0)
    assert (notes["import_seconds"], notes["compile_cache_entries"],
            notes["setup_s"]) == (2.0, 40.0, 30.0)
    # the counts the metrics leave out, cut where the metrics are
    assert (notes["setup_fetched_count"], notes["setup_nested_traces"],
            notes["program_builds_after_open"]) == (5.0, 1900.0, 1.0)
    assert notes["program_builds"] == {"serve_decode": {
        "trace_s": 0.9123, "nested_traces": 620, "nested_trace_s": 0.7,
        "lower_s": 0.68,
        "backend_s": 0.4, "cache": "hit", "first_call_s": 2.1,
        "custom_calls": 2, "fast_mem_buffers": 91,
        "fast_mem_bytes": 103988744, "temp_bytes": 4096}}
    json.dumps(notes)                   # the line can carry it


def test_a_run_that_fetched_everything_compiled_nothing():
    obs = serve_obs()
    del obs["scrape0"]["program_compiled_total"]
    assert read("setup_compiled_count", obs) == 0.0


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_without_the_series_reports_nothing(name):
    obs = serve_obs()
    obs["scrape0"] = {"jax_compile_total": 7.0, "span_serve_emit_ms_sum": 5.0}
    obs["programs"] = {"old": {"module": "jit_old", "scopes": {}}}
    assert read(name, obs) is None
    assert "program_builds" not in obs["notes"]
    assert "setup_spans_s" not in obs["notes"]


def test_a_training_process_is_read_from_its_registry():
    """No scrape: the readers run in the process and read the registry
    and the catalog as they stand (``compiles_in_window.train`` says
    whether that is still the set-up's record)."""
    from mxtpu import telemetry
    for series, amount in (("program_trace_seconds_total", 1.25),
                           ("program_backend_seconds_total", 0.5),
                           ("program_compiled_total", 1.0)):
        telemetry.counter(series, program="selftest_step").inc(amount)
    with telemetry.setup_span("state_alloc"):
        pass
    now = setup_reads.parse_exposition(telemetry.prometheus())
    row = {"trace_s": 1.25, "cache": "miss"}
    obs = {"end_to_end": {"setup_s": 34.0}, "traffic": {}, "compiles": 0,
           "programs": {"selftest_step": row}}
    assert read("setup_trace_s", obs) \
        == now["program_trace_seconds_total"] >= 1.25
    assert read("setup_compiled_count", obs) >= 1.0
    assert read("setup_state_alloc_s", obs) >= 0.0
    assert read("setup_unspanned_s", obs) == pytest.approx(
        34.0 - now["setup_spanned_seconds_total"])
    assert obs["notes"]["program_builds"]["selftest_step"]["cache"] == "miss"
    assert obs["notes"]["program_builds_after_open"] == 0


def test_the_new_entries_keep_the_rules_of_an_entry():
    """What ``test_benchmark_json.py`` holds an entry to, for the six:
    its keys, unit and source, one spelling of its layer, its reader's
    file, and ``moves`` reported in every cell the metric lists
    (``setup_s`` is in every cell)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]
    mine = bench["per_layer"][-len(METRICS):]
    assert [m["name"] for m in mine] == list(METRICS)
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["source"]) == METRICS[m["name"]]
        assert (m["layer"], m["moves"], m["better"]) == (
            "program build", "setup_s", "lower")
        assert m["workloads"] == cells
        assert os.path.exists(os.path.join(
            GRID, "readers", m["name"] + ".py")), m["name"]
    assert not [m["name"] for m in bench["per_layer"][:-len(METRICS)]
                if m["layer"].lower() == "program build"
                or m["moves"] == "setup_s"]
