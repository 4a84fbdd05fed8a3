"""The Nemotron-H family's cell off the chip: the published widths of the
real configuration file against the catalog's row, the driver end to end
at a toy width on the CPU stand-in (``configs/tiny-nemotron-h-serve.json``,
the real traffic file with its lengths cut to the toy engine's rows),
and each new reader and counting function on a hand-made ``obs``."""
import copy
import json
import math
import os

import pytest

import tiny

GRID = os.path.dirname(tiny.HERE)
CELL = "nemotron3-longctx-closed32"
CONFIG = "nemotron-3-nano-30b-a3b-d9-serve"
NEW = ("ssd_chunk_roofline_share", "ssm_state_roofline_share",
       "relu2_moe_roofline_share")
# the experts touched, over the expert layers the pattern lays out
# (``moe_experts_touched_mean`` divides by ``first_k_dense_replace``'s)
TOUCHED = "hybrid_moe_experts_touched_mean"
# the accepted metrics this cell is appended to, which move ``itl_p95_ms``
JOINED = ("decode_step_dev_ms", "prefill_chunk_dev_ms", "sampler_dev_share",
          "kv_gather_dev_share", "decode_unscoped_dev_share", "ssm_dev_share",
          "moe_dev_share", "moe_dispatch_dev_share")
SETUP = ("setup_trace_s", "setup_lower_s", "setup_backend_s",
         "setup_compiled_count", "setup_state_alloc_s", "setup_unspanned_s")
# the catalog's row (model-configs guide, architectures.jsonl,
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), every number of its ``config``
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(tiny.ROOT, "BENCHMARK.json")


def test_the_configuration_is_the_published_one_cut_in_depth_only(bench):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(tiny.ROOT, entry["file"])
    assert cfg["source"] == entry["source"]
    cut = ["num_hidden_layers", "hybrid_override_pattern"]
    assert entry["reduced"] == cut == list(cfg["reduced"])
    for key, value in PUBLISHED.items():
        if key not in cut:
            assert cfg[key] == value, key
    layers = cfg["reduced"]["num_hidden_layers"]
    assert (layers["published"], layers["here"]) == (52, 9)
    pattern = cfg["reduced"]["hybrid_override_pattern"]
    assert pattern["published"] == PUBLISHED["hybrid_override_pattern"]
    # the first whole period, 4 : 4 : 1, at or above every floor
    assert cfg["hybrid_override_pattern"] == pattern["here"] == \
        PUBLISHED["hybrid_override_pattern"][:9]
    assert [cfg["hybrid_override_pattern"].count(k) for k in "ME*"] == \
        [4, 4, 1]
    from ssd_counts import expert_layers
    assert expert_layers(cfg) == 4 and "first_k_dense_replace" not in cfg
    assert {"no_rope", "group_norm", "e_score_correction_bias",
            "sampling", "unread_keys"} <= set(cfg["assumed"])
    assert os.path.exists(os.path.join(GRID, "drivers",
                                       cfg["kind"] + ".py"))
    assert os.path.exists(os.path.join(
        GRID, "reference", cfg["family"]["reference"] + ".py"))
    from run import load_module
    _, got, _ = load_module("drivers", cfg["kind"]).family_of(cfg)
    assert (got.dim, got.n_heads, got.n_kv_heads, got.head_dim,
            got.mamba_heads, got.mamba_head_dim, got.n_groups, got.d_state,
            got.d_conv, got.ssd_chunk, got.d_inner, got.conv_dim,
            got.moe_hidden_dim, got.shared_hidden_dim, got.n_routed_experts,
            got.experts_per_tok, got.vocab_size, got.n_layers,
            got.routed_scaling_factor) == (
                2688, 32, 2, 128, 64, 64, 8, 128, 4, 128, 4096, 6144, 1856,
                3712, 128, 6, 131072, 9, 2.5)
    # the check batch's prompts are three chunks long, the last partial
    eng = cfg["run"]["engine"]
    assert 2 * eng["prefill_chunk"] < cfg["check"]["prompt_cap"] \
        < 3 * eng["prefill_chunk"]
    assert cfg["check"]["prompt_cap"] % cfg["chunk_size"]
    assert eng["n_pages"] == eng["max_slots"] * (
        eng["max_len"] // eng["page_size"]) + 1
    assert eng["max_len"] % eng["prefill_chunk"] == 0


def test_parameter_and_byte_reckoning():
    """The configuration's numbers from its widths: 6.07 B parameters,
    1,024 bytes a token in the pool, 2.10 MB of state a slot and
    layer."""
    from ssd_counts import (decode_state_bytes, relu2_expert_bytes,
                            ssd_chunk, ssm_layers, state_bytes)
    cfg = load(GRID, "configs", CONFIG + ".json")
    d, H, p = (cfg["hidden_size"], cfg["mamba_num_heads"],
               cfg["mamba_head_dim"])
    G, n = cfg["n_groups"], cfg["ssm_state_size"]
    conv = H * p + 2 * G * n
    mamba = (d * (H * p + conv + H) + H * p * d + conv * 5 + 3 * H
             + H * p + d)
    assert round(mamba / 1e6, 2) == 38.74
    f, fs, E = (cfg["moe_intermediate_size"],
                cfg["moe_shared_expert_intermediate_size"],
                cfg["n_routed_experts"])
    moe = E * 2 * d * f + 2 * d * fs + d * E + E + d
    assert round(moe / 1e6, 1) == 1297.5
    hd = cfg["head_dim"]
    attn = d * hd * (2 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"]) + d
    assert round(attn / 1e6, 2) == 23.40
    total = 4 * mamba + 4 * moe + attn + 2 * cfg["vocab_size"] * d + d
    assert round(total / 1e9, 2) == 6.07
    assert ssm_layers(cfg) == 4
    assert state_bytes(cfg) == 64 * 64 * 128 * 4
    assert decode_state_bytes(cfg, 32) == 2 * 32 * 4 * state_bytes(cfg)
    assert relu2_expert_bytes(cfg) == 2 * 2688 * 1856 * 2
    # a 1024-token chunk: 8 SSD chunks a layer
    one = ssd_chunk(cfg, 1024)
    assert one["flops"] == 4 * 2.0 * 8 * (
        8 * 128 * 128 * 128 + 64 * 128 * 128 * 64 + 2 * 64 * 128 * 64 * 128)
    assert one["bytes"] == 4 * (1024 * (2 * 4096 + 2 * 1024) * 2
                                + 1024 * 64 * 4 + 2 * state_bytes(cfg))


def test_the_cell_and_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longctx-closed32", 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"itl_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert set(mine) == {*NEW, TOUCHED, *JOINED, *SETUP}
    assert "moe_roofline_share" not in mine
    assert "moe_experts_touched_mean" not in mine
    for name in (*NEW, TOUCHED):
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["layer"] == "serve programs"
        assert mine[name]["moves"] == "itl_p95_ms"
        assert os.path.exists(os.path.join(GRID, "readers", name + ".py"))
    traffic = load(GRID, "traffic", cell["traffic"] + ".json")
    cfg = load(GRID, "configs", CONFIG + ".json")
    assert traffic["prompt"] == {"law": "lognormal", "median": 12288,
                                 "sigma": 0.5, "lo": 4096, "hi": 24576}
    assert traffic["output"] == {"law": "lognormal", "median": 768,
                                 "sigma": 0.5, "lo": 256, "hi": 2048}
    assert (traffic["pool"], traffic["ramp_s"]) == (64, 15)
    assert traffic["sampling"] == {"temperature": 0.7, "top_p": 0.95}
    assert traffic["requests"] == {"kind": "independent"}
    assert (traffic["prompt"]["hi"] + traffic["output"]["hi"]
            == cfg["run"]["engine"]["max_len"])
    assert traffic["arrival"] == {
        "kind": "closed", "callers": cfg["run"]["engine"]["max_slots"]}
    assert cfg["run"]["engine"]["prefix_cache"] is False


def toy_parts(bench):
    """The real cell's metric tables over the toy configuration and
    the real traffic file cut to the toy engine's rows."""
    import run as grid_run
    parts = copy.deepcopy(grid_run.load_cell(bench, CELL, tiny.ROOT))
    parts["config"] = load(tiny.HERE, "configs",
                           "tiny-nemotron-h-serve.json")
    t = parts["traffic"]
    t["arrival"]["callers"] = 8
    t["ramp_s"] = 1
    for law in (t["prompt"], t["output"]):
        for k in ("median", "lo", "hi"):
            law[k] = law[k] // 48
    return parts


@pytest.mark.parametrize("trace", [False, True])
def test_driver_end_to_end_on_the_cpu_stand_in(bench, trace):
    import run as grid_run
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "peaks": None}
    r = grid_run.run_cell(toy_parts(bench), device, 2147483659, 4.0, trace,
                          lambda s: None)
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0
    notes = r["notes"]
    assert notes["check_worst_gap"] <= 0.001
    assert 0 <= notes["check_router_gap"] <= 1e-5
    assert notes["check_router_flips"] == 0
    assert 0 <= notes["check_layer_gap"] <= 1e-5
    assert len(notes["check_layer_gaps"]) == 9
    assert notes["check_router_places"] == 4 * 4 * 308
    # the served slot's state, from the engine's bank after the prompt's
    # chunks and the decode steps, against the reference's recurrence a
    # token at a time
    assert 0 <= notes["check_state_gap"] <= 1e-5
    assert notes["check_state_idle_kept"] is True
    assert notes["check_state_dtype"] == "float32"
    assert notes["check_state_steps"] in (7, 8)
    assert notes["check_state_tokens"] > 8 * notes["check_state_steps"]
    assert notes["serve_tok_s"] > 0 and notes["live_tokens_mean"] > 0
    assert notes["decode_attention"] == "gathered"
    for m in r["metrics"].values():
        assert math.isfinite(m["value"]) and m["unit"]
    if trace:
        assert set(r["metrics"]) <= {*NEW, TOUCHED, *JOINED, *SETUP}
        assert 1 <= r["metrics"][TOUCHED]["value"] <= 16
        assert 0 < r["device"]["busy_s"] and "breakdown" in r
    else:
        assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
        assert r["metrics"]["itl_p95_ms"]["value"] > 0


def hand_made_obs():
    """A traced window in which the decode program ran four times (its
    recurrence 1 ms a run, its grouped products 40 ms) and the last-chunk
    program twice (its SSD 2 ms a run); over the whole window 100 steps
    emitted 3,200 tokens and touched 39,600 experts with 76,800
    assignments (99 experts a layer and step, 32 tokens)."""
    scopes = {"f.ssm": ("ssm", False), "f.state": ("ssm/ssm_state", False),
              "f.gmm": ("moe_experts", False), "f.loop": ("", False)}
    last = {"g.state": ("ssm/ssm_state", False), "g.ssm": ("ssm", False),
            "g.rest": ("moe_experts", False)}
    ops = [{"name": n, "program": "jit_decode_slots_paged#1", "self": s}
           for n, s in (("f.ssm", 0.02), ("f.state", 0.004),
                        ("f.gmm", 0.16), ("f.loop", 0.05))]
    ops += [{"name": n, "program": "jit_prefill_slot_paged_last_b1024#2",
             "self": s} for n, s in (("g.state", 0.004), ("g.ssm", 0.01),
                                     ("g.rest", 0.03))]
    modules = [{"name": "jit_decode_slots_paged(1)", "t0": 0.1 * i,
                "t1": 0.1 * i + 0.06} for i in range(4)]
    modules += [{"name": "jit_prefill_slot_paged_last_b1024(2)",
                 "t0": 0.5 + 0.1 * i, "t1": 0.55 + 0.1 * i}
                for i in range(2)]
    config = load(GRID, "configs", CONFIG + ".json")
    return {
        "config": config,
        "device": {"peaks": {"flops_bf16": 197e12,
                             "hbm_bytes_per_s": 819e9}},
        "programs": {
            "serve_decode": {"module": "jit_decode_slots_paged",
                             "scopes": scopes},
            "serve_prefill_last_b1024": {
                "module": "jit_prefill_slot_paged_last_b1024",
                "scopes": last}},
        "scrape0": {"serve_moe_experts_touched_total": 400.0,
                    "serve_moe_assignments_total": 768.0,
                    "serve_moe_load_max_share_count": 1.0,
                    "serve_steps_total": 1.0, "serve_tokens_total": 32.0},
        "scrape1": {"serve_moe_experts_touched_total": 40000.0,
                    "serve_moe_assignments_total": 77568.0,
                    "serve_moe_load_max_share_count": 101.0,
                    "serve_steps_total": 101.0,
                    "serve_tokens_total": 3232.0},
        "reduced": {"window_s": 1.0, "devices": {0: {
            "ops": ops, "modules": modules, "busy_s": 0.5}}}}


def test_each_new_reader_on_a_hand_made_obs():
    from run import load_module
    obs = hand_made_obs()
    read = {n: load_module("readers", n).read(obs) for n in NEW}
    cfg = obs["config"]
    state = 64 * 64 * 128 * 4
    # 32 slots x 2.10 MB x 4 layers, read and written: 0.54 GB, 0.655 ms
    # at 819 GB/s, over the 1 ms a step the recurrence took here
    assert read["ssm_state_roofline_share"] == pytest.approx(
        100 * 2 * 32 * state * 4 / 819e9 / 0.001)
    # 396 experts a step x 9.98 MB = 3.95 GB: 4.82 ms, over 40 ms
    assert read["relu2_moe_roofline_share"] == pytest.approx(
        100 * 396 * 2 * 2688 * 1856 * 2 / 819e9 / 0.04)
    assert obs["notes"]["relu2_moe_bound"] == "memory"
    from ssd_counts import ssd_chunk
    need = ssd_chunk(cfg, 1024)
    assert read["ssd_chunk_roofline_share"] == pytest.approx(
        100 * max(need["flops"] / 197e12, need["bytes"] / 819e9) / 0.002)
    assert obs["notes"]["ssd_chunk_bound"] == "memory"
    assert all(0 < read[n] < 100 for n in NEW)
    # a program without the scopes (the parent commit), a device without
    # peaks, or no trace: nothing, and no error
    bare = hand_made_obs()
    bare["programs"] = {}
    assert all(load_module("readers", n).read(bare) is None for n in NEW)
    bare = hand_made_obs()
    bare["device"] = {"peaks": None}
    assert all(load_module("readers", n).read(bare) is None for n in NEW)
    bare = hand_made_obs()
    del bare["reduced"]
    assert all(load_module("readers", n).read(bare) is None for n in NEW)
    other = hand_made_obs()                # a family with no such scope
    other["programs"]["serve_decode"]["scopes"] = {"f.loop": ("", False)}
    other["programs"]["serve_prefill_last_b1024"]["scopes"] = {}
    assert all(load_module("readers", n).read(other) is None for n in NEW)


def test_the_experts_touched_over_the_patterns_expert_layers():
    """39,600 experts over 100 steps and the pattern's 4 expert layers:
    99 a layer and step; no counter, or a configuration with no pattern
    (kanana's), reads nothing."""
    from run import load_module
    read = load_module("readers", TOUCHED).read
    assert read(hand_made_obs()) == pytest.approx(99.0)
    bare = hand_made_obs()
    del bare["scrape1"]["serve_moe_experts_touched_total"]
    assert read(bare) is None
    other = hand_made_obs()
    other["config"] = load(GRID, "configs", "kanana-2-30b-a3b-d8-serve.json")
    assert read(other) is None


@pytest.mark.parametrize("control", ["relu", "shared_dropped", "rope",
                                     "wrong_slot", "state_bf16",
                                     "step_e4m3", "router_bf16"])
def test_each_fault_reads_not_correct_on_the_stand_in(bench, control):
    """Every control of ``nemotron_h_controls.py`` that is the cell with
    a fault comes out ``correct: false`` by the harness's comparison at
    the toy width."""
    import nemotron_h_controls
    import run as grid_run
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "peaks": None}
    undo = nemotron_h_controls.apply(control)
    try:
        r = grid_run.run_cell(toy_parts(bench), device, 5, 2.0, False,
                              lambda s: None)
    finally:
        undo()
    assert r["correct"] is False, r["notes"]
    notes = r["notes"]
    if control == "state_bf16":
        assert notes["check_state_dtype"] == "bfloat16"
        assert notes["check_state_gap"] > 1e-4
    if control == "step_e4m3":
        # the prefill is the program's own: the decode steps alone
        assert notes["check_state_gap"] > 10 * notes["check_state_tol"]
    if control == "router_bf16":
        assert notes["check_router_gap"] > 100 * notes["check_router_tol"]


def test_the_lowered_reference_reads_not_correct_on_the_stand_in(bench):
    import gen
    import run as grid_run
    from nemotron_h_controls import judged
    from retention_controls import OPERANDS, operands_control
    parts = toy_parts(bench)
    config = judged(parts["config"])
    ok, worst, notes = operands_control(
        config, gen.Traffic(parts["traffic"], 5, config["vocab_size"]),
        grid_run.load_module("drivers", config["kind"]), 5,
        *OPERANDS["operands_e4m3"], log=lambda s: None)
    assert not ok
    assert notes["check_layer_gap"] > 100 * config["check"]["layer_tol"]
