"""The SambaY family's cell off the chip: the published widths of the
real configuration file, the new driver end to end at a toy width on
the CPU stand-in (``configs/tiny-sambay-serve.json``, the real traffic
file with its lengths divided by 8), and each new reader on a hand-made
``obs``."""
import copy
import json
import math
import os

import pytest

import tiny

GRID = os.path.dirname(tiny.HERE)
CELL = "phi4flash-agent-closed32"
NEW = ("ssm_dev_share", "window_attn_dev_share", "cross_attn_dev_share",
       "gmu_dev_share", "prefill_chunk_dev_ms")
# the accepted decode-program metrics, which move ``itl_p95_ms``
JOINED = ("decode_step_dev_ms", "sampler_dev_share", "kv_gather_dev_share",
          "decode_unscoped_dev_share")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(tiny.ROOT, "BENCHMARK.json")


def test_the_configuration_is_the_published_one_uncut(bench):
    entry = next(c for c in bench["configs"]
                 if c["name"] == "phi-4-mini-flash-serve")
    cfg = load(tiny.ROOT, entry["file"])
    assert cfg["source"] == entry["source"]
    assert entry["reduced"] == [] and cfg["reduced"] == {}
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["hidden_size"] // cfg["num_attention_heads"],
            cfg["vocab_size"]) == (2560, 10240, 40, 20, 64, 200064)
    assert (cfg["num_hidden_layers"], cfg["sliding_window"],
            cfg["mb_per_layer"], cfg["max_position_embeddings"],
            cfg["layer_norm_eps"], cfg["tie_word_embeddings"]) == (
                32, 512, 2, 262144, 1e-5, True)
    # every size the published file lacks is written down as assumed
    assert {"d_state", "d_conv", "expand", "dt_rank", "layer_norm",
            "positional_encoding", "gmu_memory", "differential_attention",
            "weights"} <= set(cfg["assumed"])
    assert os.path.exists(os.path.join(GRID, "drivers",
                                       cfg["kind"] + ".py"))
    # the program's config object holds the same widths
    from run import load_module
    got = load_module("drivers", cfg["kind"]).sambay_config(cfg, cfg["run"])
    assert (got.dim, got.hidden_dim, got.n_heads, got.n_kv_heads,
            got.head_dim, got.vocab_size, got.n_layers, got.d_inner,
            got.d_state, got.rank, got.n_pairs, got.n_cross) == (
                2560, 10240, 40, 20, 64, 200064, 32, 5120, 16, 160, 8, 7)
    # the check batch's prompts are three windows long
    assert cfg["check"]["prompt_cap"] >= 3 * cfg["sliding_window"]
    eng = cfg["run"]["engine"]
    assert eng["n_pages"] == eng["max_slots"] * (
        eng["max_len"] // eng["page_size"]) + 1


def test_the_cell_and_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-serve", "agent-closed32", 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    # TTFT and tokens/s ride in the line's notes: ~30 first tokens a
    # window hold no bound, and which 24-31 prompts a window admits
    # moves its tokens by 1.3-2.6% (six chip runs, twice). The p95 gap
    # is a decode step and one prefill chunk, whatever the prompts
    assert e2e == {"itl_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert set(mine) == {*NEW, *JOINED}
    assert all(m["moves"] == "itl_p95_ms" for m in mine.values())
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["layer"] == "serve programs"
        assert os.path.exists(os.path.join(GRID, "readers", name + ".py"))
    traffic = load(GRID, "traffic", cell["traffic"] + ".json")
    cfg = load(GRID, "configs", "phi-4-mini-flash-serve.json")
    # the mix ISSUE 27 fixed before any code was written
    assert traffic["prompt"] == {"law": "lognormal", "median": 2048,
                                 "sigma": 0.5, "lo": 1024, "hi": 4096}
    assert traffic["output"] == {"law": "lognormal", "median": 192,
                                 "sigma": 0.6, "lo": 64, "hi": 768}
    assert (traffic["pool"], traffic["ramp_s"]) == (64, 10)
    assert traffic["sampling"] == {"temperature": 0.6, "top_p": 0.95}
    assert (traffic["prompt"]["hi"] + traffic["output"]["hi"]
            <= cfg["run"]["engine"]["max_len"])
    assert traffic["arrival"]["callers"] == cfg["run"]["engine"]["max_slots"]
    # a prompt is prefilled in chunks of two windows, which divide a row
    eng = cfg["run"]["engine"]
    assert eng["prefill_chunk"] == 2 * cfg["sliding_window"] == 1024
    assert eng["max_len"] % eng["prefill_chunk"] == 0


def toy_parts(bench):
    """The real cell's metric tables over the toy configuration and
    the real traffic file cut to the toy engine's rows."""
    import run as grid_run
    parts = grid_run.load_cell(bench, CELL, tiny.ROOT)
    parts = copy.deepcopy(parts)
    parts["config"] = load(tiny.HERE, "configs", "tiny-sambay-serve.json")
    t = parts["traffic"]
    t["arrival"]["callers"] = 8
    t["ramp_s"] = 1
    for law, by in ((t["prompt"], 8), (t["output"], 64)):
        for k in ("median", "lo", "hi"):
            law[k] = law[k] // by
    return parts


@pytest.mark.parametrize("trace", [False, True])
def test_driver_end_to_end_on_the_cpu_stand_in(bench, trace):
    import run as grid_run
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "peaks": None}
    r = grid_run.run_cell(toy_parts(bench), device, 2147483659, 4.0, trace,
                          lambda s: None)
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["notes"]["check_worst_gap"] <= 0.001
    # the program's scan against the reference's recurrence, float32
    assert 0 <= r["notes"]["check_scan_gap"] <= 1e-5
    assert r["notes"]["state_bytes_per_slot"] > 0
    assert r["notes"]["serve_tok_s"] > 0
    for m in r["metrics"].values():
        assert math.isfinite(m["value"]) and m["unit"]
    if trace:
        # the stand-in trace has operations and no programs: what
        # needs a program reports nothing, and no metric of another
        # cell is reported
        assert set(r["metrics"]) <= {*NEW, *JOINED}
        assert {"decode_batch_mean", "kv_pages_peak_share",
                "engine_host_share"} <= set(r["notes"])
        assert 0 < r["device"]["busy_s"] and "breakdown" in r
    else:
        assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
        assert r["metrics"]["itl_p95_ms"]["value"] > 0


def hand_made_obs():
    """A traced window of 2 s: the decode program ran four times, the
    program of a prompt's earlier chunks three times and of its last
    chunk once; the decode program's operations under every scope."""
    scopes = {"f.ssm": ("ssm", False), "f.win": ("window_attention", False),
              "f.win2": ("window_attention/attention", False),
              "f.full": ("attention/attention", False),
              "f.cross": ("cross_attention/attention", False),
              "f.gmu": ("gmu", False), "f.mlp": ("mlp", False),
              "f.loop": ("", False)}
    self_s = {"f.ssm": 0.10, "f.win": 0.05, "f.win2": 0.15, "f.full": 0.02,
              "f.cross": 0.18, "f.gmu": 0.04, "f.mlp": 0.40, "f.loop": 0.06}
    ops = [{"name": n, "program": "jit_decode_slots_paged#1", "self": s}
           for n, s in self_s.items()]
    ops.append({"name": "fusion.1",
                "program": "jit_prefill_slot_paged_last_b1024#2",
                "self": 0.07})
    modules = [{"name": "jit_decode_slots_paged(1)", "t0": 0.25 * i,
                "t1": 0.25 * i + 0.25} for i in range(4)]
    modules += [{"name": "jit_prefill_slot_paged_chunk_b1024(3)",
                 "t0": 1.0 + 0.1 * i, "t1": 1.06 + 0.1 * i}
                for i in range(3)]
    modules.append({"name": "jit_prefill_slot_paged_last_b1024(2)",
                    "t0": 1.3, "t1": 1.37})
    return {
        "config": {"programs": {"decode": "jit__unknown|decode_slots",
                                "prefill": "jit__unknown|prefill_slot"}},
        "programs": {"serve_decode": {"module": "jit_decode_slots_paged",
                                      "scopes": scopes}},
        "reduced": {"window_s": 2.0, "devices": {0: {
            "ops": ops, "modules": modules, "busy_s": 1.3}}}}


def test_each_new_reader_on_a_hand_made_obs():
    from run import load_module
    import trace_reduce
    obs = hand_made_obs()
    assert trace_reduce.first_device(obs["reduced"]) is not None
    read = {n: load_module("readers", n).read(obs) for n in NEW}
    assert read["ssm_dev_share"] == pytest.approx(10.0)
    assert read["window_attn_dev_share"] == pytest.approx(20.0)
    assert read["cross_attn_dev_share"] == pytest.approx(20.0)
    assert read["gmu_dev_share"] == pytest.approx(4.0)
    # the costlier of the two prefill programs: the last chunk's
    assert read["prefill_chunk_dev_ms"] == pytest.approx(70.0)
    shares = obs["notes"]["decode_scope_shares"]
    assert sum(shares.values()) == pytest.approx(100.0)
    assert load_module("readers", JOINED[0]).read(obs) == pytest.approx(250.0)
    # a program without the scopes (the parent commit) reports nothing
    bare = hand_made_obs()
    bare["programs"] = {}
    for n in NEW[:-1] + JOINED[1:]:
        assert load_module("readers", n).read(bare) is None
    # a window with no admission, or no trace, reports no chunk time
    bare["reduced"]["devices"][0]["modules"] = obs["reduced"]["devices"][
        0]["modules"][:4]
    assert load_module("readers", "prefill_chunk_dev_ms").read(bare) is None
    assert load_module("readers", "prefill_chunk_dev_ms").read(
        {"config": bare["config"]}) is None
