"""The readers of the program's own instruments (PR 25) on observations
written by hand: the arithmetic, and ``None`` wherever the program,
the scrape or the trace does not hold what a reader needs."""
import math

import pytest

import run as grid_run
import trace_reduce as tr


def read(name, obs):
    return grid_run.load_module("readers", name).read(obs)


def hist(name, total, count):
    return {name + "_sum": total, name + "_count": count,
            name + "_bucket": 3.0 * count}


def serve_obs():
    """10 first tokens between the scrapes: the gateway saw 300 ms
    each, the engine 80 + 4 + 210."""
    s0, s1 = {}, {}
    for name, t0, n0, t1, n1 in [
            ("gateway_ttft_ms", 900.0, 3, 3900.0, 13),
            ("serve_ttft_queue_ms", 100.0, 3, 900.0, 13),
            ("serve_ttft_admit_ms", 9.0, 3, 49.0, 13),
            ("serve_ttft_first_wait_ms", 600.0, 3, 2700.0, 13),
            ("span_serve_sweep_pick_ms", 1.0, 50, 11.0, 650),
            ("span_serve_admit_ms", 100.0, 50, 600.0, 650),
            ("span_serve_decode_dispatch_ms", 20.0, 50, 320.0, 650),
            ("span_serve_emit_ms", 5.0, 50, 215.0, 650),
            ("span_serve_readback_ms", 4000.0, 50, 52000.0, 650)]:
        s0.update(hist(name, t0, n0))
        s1.update(hist(name, t1, n1))
    return {"scrape0": s0, "scrape1": s1, "seconds": 51.0}


def test_ttft_parts_add_to_the_gateways_mean():
    obs = serve_obs()
    parts = [read(n, obs) for n in ("ttft_queue_ms", "ttft_admit_ms",
                                    "ttft_first_wait_ms",
                                    "ttft_gateway_ms")]
    assert parts[:3] == [80.0, 4.0, 210.0]
    assert math.isclose(parts[3], 6.0)
    assert math.isclose(sum(parts), 300.0)      # the gateway's mean


def test_engine_host_share_leaves_the_readback_out():
    # 10 + 500 + 300 + 210 ms of work in a 51 s window
    assert math.isclose(read("engine_host_share", serve_obs()),
                        100.0 * 1020.0 / 51000.0)


@pytest.mark.parametrize("name", ["ttft_queue_ms", "ttft_admit_ms",
                                  "ttft_first_wait_ms", "ttft_gateway_ms",
                                  "engine_host_share"])
def test_a_program_without_the_series_reports_nothing(name):
    """The parent commit has ``gateway_ttft_ms`` and the dispatch span
    and none of the rest."""
    obs = serve_obs()
    for s in (obs["scrape0"], obs["scrape1"]):
        for key in list(s):
            if key.startswith(("serve_ttft", "span_serve_sweep",
                               "span_serve_admit", "span_serve_emit")):
                del s[key]
    assert read(name, obs) is None
    assert read(name, {"seconds": 51.0}) is None
    # and no first token between the scrapes is no mean
    obs = serve_obs()
    obs["scrape0"] = dict(obs["scrape1"])
    if name != "engine_host_share":
        assert read(name, obs) is None


def ev(kind, name, t0, t1):
    return {"dev": 0, "kind": kind, "name": name, "t0": t0, "t1": t1}


def traced(module, catalog_module=None, scopes=None):
    """Two executions of ``module`` (a while holding three fusions,
    then a copy) and one of another program, over 10 s."""
    events = [ev("marker", tr.MARKER, 0.0, 10.0)]
    for t in (0.0, 4.0):
        events += [ev("module", f"{module}(77123)", t, t + 3.0),
                   ev("op", "while.1", t, t + 2.5),
                   ev("op", "fusion.1", t, t + 1.0),
                   ev("op", "fusion.2", t + 1.0, t + 1.5),
                   ev("op", "fusion.3", t + 1.5, t + 2.0),
                   ev("op", "copy.9", t + 2.5, t + 3.0)]
    events += [ev("module", "jit_other(5)", 8.0, 9.0),
               ev("op", "fusion.1", 8.0, 9.0)]
    if scopes is None:
        scopes = {"while.1": ("", False),
                  "fusion.1": ("sampler", False),
                  "fusion.2": ("kv_gather", True),
                  "fusion.3": ("attention/flash", True),
                  "copy.9": ("", False)}
    return {"reduced": tr.reduce(events),
            "config": {"programs": {"decode": "jit__unknown|decode_slots",
                                    "train": "_step"}},
            "programs": {"w": {"module": catalog_module or module,
                               "scopes": scopes}}}


def test_decode_scopes_split_the_programs_self_time():
    obs = traced("jit_decode_slots_paged")
    # per execution: while 0.5 s of its own, sampler 1.0, kv_gather
    # 0.5, attention 0.5, copy 0.5 = 3.0; the other program's
    # fusion.1 is not the decode program's
    got = {n: read(n, obs) for n in ("sampler_dev_share",
                                     "kv_gather_dev_share",
                                     "decode_unscoped_dev_share")}
    assert math.isclose(got["sampler_dev_share"], 100.0 / 3.0)
    assert math.isclose(got["kv_gather_dev_share"], 50.0 / 3.0)
    assert math.isclose(got["decode_unscoped_dev_share"], 100.0 / 3.0)
    shares = obs["notes"]["decode_scope_shares"]
    assert math.isclose(sum(shares.values()), 100.0, abs_tol=0.01)
    assert shares["attention"] == pytest.approx(16.667, abs=0.001)
    assert obs["notes"]["decode_unmapped_share"] == 0.0


def test_an_instruction_the_map_lacks_counts_as_unscoped():
    obs = traced("jit_decode_slots_paged", scopes={
        "while.1": ("", False), "fusion.1": ("sampler", False),
        "fusion.2": ("kv_gather", False), "copy.9": ("", False)})
    assert math.isclose(read("decode_unscoped_dev_share", obs), 50.0)
    assert obs["notes"]["decode_unmapped_share"] == pytest.approx(16.667)


def test_train_scopes_are_shares_of_busy_time():
    obs = traced("jit_train_step", scopes={
        "while.1": ("", False), "fusion.1": ("xent", False),
        "fusion.2": ("mlp", True), "fusion.3": ("xent", True),
        "copy.9": ("", False)})
    # busy 7 s: remat 2 x (0.5 + 0.5), xent 2 x (1.0 + 0.5)
    assert math.isclose(read("remat_dev_share", obs), 100.0 * 2.0 / 7.0)
    assert math.isclose(read("xent_dev_share", obs), 100.0 * 3.0 / 7.0)


@pytest.mark.parametrize("name", ["sampler_dev_share", "kv_gather_dev_share",
                                  "decode_unscoped_dev_share",
                                  "remat_dev_share", "xent_dev_share"])
def test_no_map_no_trace_or_no_program_is_none(name):
    module = ("jit_train_step" if name in ("remat_dev_share",
                                           "xent_dev_share")
              else "jit_decode_slots_paged")
    assert read(name, traced(module)) is not None
    # the catalog holds another program only (or none: the parent)
    assert read(name, traced(module, catalog_module="jit_else")) is None
    obs = traced(module)
    obs["programs"] = {}
    assert read(name, obs) is None
    # no traced run
    obs = traced(module)
    del obs["reduced"]
    assert read(name, obs) is None
    # the configuration names no such program
    obs = traced(module)
    obs["config"] = {"programs": {}}
    assert read(name, obs) is None


def test_the_programs_own_catalog_is_asked_when_obs_has_none():
    """On the chip ``obs`` has no ``programs``: the reader asks
    ``mxtpu.telemetry``, which holds nothing called jit_nowhere."""
    obs = traced("jit_nowhere_decode_slots")
    del obs["programs"]
    assert read("sampler_dev_share", obs) is None


def test_train_dispatch_is_the_median_of_the_windows_spans():
    def span(dur, name="train.step_dispatch"):
        return {"name": name, "ph": "X", "ts": 0, "dur": dur}
    events = ([span(90000)] * 2            # warm-up: the compile
              + [span(5, "serve.emit")]
              + [span(d) for d in (2000, 2400, 2200, 9000, 2100)])
    obs = {"trace_events": events, "attempted": 5}
    assert read("train_dispatch_ms", obs) == 2.2
    assert read("train_dispatch_ms", dict(obs, attempted=0)) is None
    assert read("train_dispatch_ms",
                {"trace_events": [], "attempted": 5}) is None


def test_toy_cells_report_the_programs_spans_end_to_end():
    """Through ``run_cell`` on the CPU stand-in: the serve cell's line
    holds the TTFT parts and the engine's host share, the train cell's
    its dispatch time. (The stand-in has no module line, so the scope
    readers report nothing here; their arithmetic is tested above.)"""
    import tiny
    r = tiny.run("tiny-chat", seconds=3.0, trace=True)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    parts = [m[n] for n in ("ttft_queue_ms", "ttft_admit_ms",
                            "ttft_first_wait_ms", "ttft_gateway_ms")]
    assert all(p >= 0 for p in parts[:3]) and parts[3] > -1.0, parts
    assert 0 < m["engine_host_share"] < 100
    assert "sampler_dev_share" not in m
    r = tiny.run("tiny-pretrain", seconds=3.0, trace=True)
    assert 0 < r["metrics"]["train_dispatch_ms"]["value"] < 1000
