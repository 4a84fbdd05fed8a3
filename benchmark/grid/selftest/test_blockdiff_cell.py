"""The block-diffusion family's cell off the chip: the published widths
of the real configuration file against the catalog's row, the new
driver end to end at a toy width on the CPU stand-in
(``configs/tiny-blockdiff-serve.json``, the real traffic file with its
lengths cut to the toy engine's rows), each new reader on a hand-made
``obs``, ``block_gqa_counts`` against hand arithmetic, and the three
controls, each ``correct: false`` through the harness's own comparison."""
import copy
import json
import math
import os

import pytest

import tiny

GRID = os.path.dirname(tiny.HERE)
CELL = "sdar-reason-closed32"
CONFIG = "sdar-30b-a3b-chat-d6-serve"
NEW = ("block_passes_per_block", "block_attn_dev_share", "unmask_dev_share",
       "block_attn_roofline_share")
JOINED = ("decode_step_dev_ms", "sampler_dev_share", "kv_gather_dev_share",
          "decode_unscoped_dev_share", "moe_dev_share",
          "moe_dispatch_dev_share", "moe_experts_touched_mean",
          "moe_roofline_share", "setup_trace_s", "setup_lower_s",
          "setup_backend_s", "setup_compiled_count", "setup_state_alloc_s",
          "setup_unspanned_s")
# the catalog's row (model-configs guide, architectures.jsonl,
# SDAR-30B-A3B-Chat), every key of its ``config``
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


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
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    for key, value in PUBLISHED.items():
        if key != "num_hidden_layers":
            assert cfg[key] == value, key
    cut = cfg["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["here"]) == (48, 6) \
        and cfg["num_hidden_layers"] == 6
    assert {"block_length", "denoising_steps", "remasking", "mask_token_id",
            "head_norms", "first_k_dense_replace", "sampling",
            "weights"} <= set(cfg["assumed"])
    assert (cfg["block_length"], cfg["denoising_steps"], cfg["remasking"],
            cfg["mask_token_id"], cfg["first_k_dense_replace"]) == (
                4, 4, "low_confidence_static", 151669, 0)
    assert os.path.exists(os.path.join(GRID, "drivers",
                                       cfg["kind"] + ".py"))
    assert os.path.exists(os.path.join(
        GRID, "reference", cfg["family"]["reference"] + ".py"))
    from run import load_module
    _, got, _ = load_module("drivers", cfg["kind"]).family_of(cfg)
    assert (got.dim, got.n_heads, got.n_kv_heads, got.head_dim,
            got.moe_hidden_dim, got.n_experts, got.experts_per_tok,
            got.vocab_size, got.n_layers, got.block_length,
            got.denoising_steps, got.per_pass, got.mask_token_id) == (
                2048, 32, 4, 128, 768, 128, 8, 151936, 6, 4, 4, 1, 151669)
    eng, check = cfg["run"]["engine"], cfg["check"]
    assert eng["n_pages"] == eng["max_slots"] * (
        eng["max_len"] // eng["page_size"]) + 1
    assert eng["page_size"] % cfg["block_length"] == 0
    assert eng["prefill_chunk"] == eng["min_bucket"] == 1024
    # an odd cap: a prompt it cuts keeps a remainder; at least 4 blocks
    assert check["prompt_cap"] % cfg["block_length"]
    assert check["new_tokens"] >= 4 * cfg["block_length"]
    assert check["also"] == ["layers", "router_softmax", "unmask", "passes"]
    for name in ("tol", "router_tol", "layer_tol", "conf_tol", "pass_tol"):
        assert len(check[name + "_why"]) > 200, name


def test_parameter_and_byte_reckoning():
    """The configuration's numbers from its widths: 623.1 M a layer,
    4.36 B in all, 2,048 bytes a token and layer, a pool of 1.26 GB."""
    from block_gqa_counts import block_step_bytes, kv_token_layer_bytes
    from expert_latent_counts import expert_layers, expert_matrices_bytes
    cfg = load(GRID, "configs", CONFIG + ".json")
    d, H, G, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    attn = 2 * d * H * hd + 2 * d * G * hd
    experts = cfg["num_experts"] * 3 * d * cfg["moe_intermediate_size"]
    layer = attn + experts + d * cfg["num_experts"] + 2 * d + 2 * hd
    assert round(attn / 1e6, 2) == 18.87
    assert round(experts / 1e6, 2) == 603.98
    assert round(layer / 1e6, 1) == 623.1
    total = cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d
    assert round(total / 1e9, 2) == 4.36
    assert kv_token_layer_bytes(cfg) == 2048
    assert kv_token_layer_bytes(cfg) * cfg["num_hidden_layers"] == 12288
    assert block_step_bytes(cfg, 1000) == 1000 * 2048 * 6
    eng = cfg["run"]["engine"]
    assert round(eng["n_pages"] * eng["page_size"] * 12288 / 1e9, 2) == 1.26
    assert expert_layers(cfg) == 6
    assert expert_matrices_bytes(cfg) == 3 * 2048 * 768 * 2


def test_the_cell_and_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-closed32", 1)
    assert "80th percentile of the waits between blocks" in cell["why"]
    assert "itl_p50_ms reads ~0" in cell["why"]
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"itl_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert set(mine) == {*NEW, *JOINED}
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "itl_p95_ms"
        assert mine[name]["layer"] == "serve programs"
        assert os.path.exists(os.path.join(GRID, "readers", name + ".py"))
    traffic = load(GRID, "traffic", cell["traffic"] + ".json")
    cfg = load(GRID, "configs", CONFIG + ".json")
    # the mix ISSUE 38 fixed before any code was written
    assert traffic["prompt"] == {"law": "lognormal", "median": 256,
                                 "sigma": 0.6, "lo": 64, "hi": 1024}
    assert traffic["output"] == {"law": "lognormal", "median": 1024,
                                 "sigma": 0.4, "lo": 512, "hi": 2048}
    assert (traffic["pool"], traffic["ramp_s"]) == (64, 15)
    assert traffic["sampling"] == {"temperature": 0.7, "top_p": 0.95}
    assert traffic["requests"] == {"kind": "independent"}
    assert (traffic["prompt"]["hi"] + traffic["output"]["hi"]
            <= cfg["run"]["engine"]["max_len"])
    assert traffic["arrival"] == {
        "kind": "closed", "callers": cfg["run"]["engine"]["max_slots"]}


def toy_parts(bench):
    """The real cell's metric tables over the toy configuration and the
    real traffic file cut to the toy engine's rows."""
    import run as grid_run
    parts = copy.deepcopy(grid_run.load_cell(bench, CELL, tiny.ROOT))
    parts["config"] = load(tiny.HERE, "configs", "tiny-blockdiff-serve.json")
    t = parts["traffic"]
    t["arrival"]["callers"] = 8
    t["ramp_s"] = 1
    for law, by in ((t["prompt"], 8), (t["output"], 16)):
        for k in ("median", "lo", "hi"):
            law[k] = law[k] // by
    return parts


DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1, "peaks": None}


@pytest.mark.parametrize("trace", [False, True])
def test_driver_end_to_end_on_the_cpu_stand_in(bench, trace):
    import run as grid_run
    r = grid_run.run_cell(toy_parts(bench), DEVICE, 2147483659, 4.0, trace,
                          lambda s: None)
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0
    notes = r["notes"]
    assert notes["check_worst_gap"] <= 0.001
    assert notes["check_order_retries"] == 0
    assert 0 <= notes["check_router_gap"] <= 1e-5
    assert notes["check_router_flips"] == 0
    assert 0 <= notes["check_layer_gap"] <= 1e-5
    assert len(notes["check_layer_gaps"]) == 3
    assert 0 <= notes["check_conf_gap"] <= 1e-5
    assert notes["check_unmask_same"] and notes["check_unmask_in_nucleus"]
    # the dynamic threshold took more positions than one a slot
    cases = notes["check_unmask_cases"]
    assert cases["greedy.dynamic"]["positions_taken"] \
        > cases["greedy.static"]["positions_taken"] == 8
    assert 0 <= notes["check_pass_gap"] <= 1e-5
    # three slots a pass apart, three blocks each: never fewer than two
    # abreast, and the bank ran more passes than one slot's blocks take
    assert notes["check_pass_count"] > 3 * 5 and notes["check_pass_idle_kept"]
    assert notes["check_pass_abreast"] == 3
    assert notes["check_pass_rows"] >= 3 * 3 * 4 * 4
    assert notes["serve_tok_s"] > 0 and notes["live_tokens_mean"] > 0
    assert notes["decode_attention"] == "gathered"
    assert 0 <= notes["block_waits_with_prefill_share"] <= 100
    # three gaps in four lie inside a block
    assert notes["itl_p50_ms"] < 0.25 * r["notes"]["ttft_p50_ms"]
    for m in r["metrics"].values():
        assert math.isfinite(m["value"]) and m["unit"]
    if trace:
        assert set(r["metrics"]) <= {*NEW, *JOINED}
        assert 4.0 <= r["metrics"]["block_passes_per_block"]["value"] <= 5.0
        assert 1 <= r["metrics"]["moe_experts_touched_mean"]["value"] <= 16
        assert 0 < r["device"]["busy_s"] and "breakdown" in r
    else:
        assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
        assert r["metrics"]["itl_p95_ms"]["value"] > 0


@pytest.mark.parametrize("control,by", [
    ("commit_skipped", "check_pass_gap"), ("blind_block", "check_pass_gap"),
    ("wrong_slot", "check_worst_gap"), ("conf_bf16", "check_conf_gap")])
def test_each_control_comes_out_not_correct(bench, control, by):
    """A commit pass skipped (the block's stored keys are a mask's), a
    step that does not see the block's own keys, a step that walks
    another slot's pages, a confidence ranked in bfloat16: each through the harness's own comparison, each ``correct:
    false`` by the limit that is there for it."""
    import run as grid_run
    from blockdiff_controls import apply
    parts = toy_parts(bench)
    undo = apply(control)
    try:
        r = grid_run.run_cell(parts, DEVICE, 2147483659, 2.0, False,
                              lambda s: None)
    finally:
        undo()
    assert r["correct"] is False
    limit = parts["config"]["check"][
        {"check_pass_gap": "pass_tol", "check_worst_gap": "tol",
         "check_conf_gap": "conf_tol"}[by]]
    assert r["notes"][by] > 10 * limit, r["notes"]
    assert r["failed"] == 0          # the window's counts still hold


@pytest.mark.parametrize("bits, correct", [((8, 23), True),
                                           ((5, 3), False)])
def test_the_reference_with_lowered_operands_comes_out_not_correct(
        bench, bits, correct):
    """``pass_tol``'s and ``layer_tol``'s second reading, through
    ``check_batch`` itself and over ``passes``' own prompts: the plain
    reference in the program's place is correct as it is (float32: the
    stand-in is sound) and not correct with float8_e4m3's mantissa on
    its operands."""
    import gen
    from run import load_module
    from blockdiff_controls import operands_control
    parts = toy_parts(bench)
    config = parts["config"]
    ok, worst, notes = operands_control(
        config, gen.Traffic(parts["traffic"], 11, config["vocab_size"]),
        load_module("drivers", config["kind"]), 11, *bits,
        log=lambda s: None)
    assert ok is correct
    if correct:
        assert worst == 0 and notes["check_pass_gap"] <= 1e-6
    else:
        assert notes["check_pass_gap"] > 100 * config["check"]["pass_tol"]
        assert notes["check_layer_gap"] > 100 * config["check"]["layer_tol"]


def hand_made_obs():
    """A traced window of 2 s in which the step program ran four times;
    over the whole window 1000 slot-passes committed 200 blocks, and the
    slots held 30,000 live tokens."""
    scopes = {"f.attn": ("block_attention", False),
              "f.unmask": ("unmask", False), "f.draw": ("sampler", False),
              "f.gmm": ("moe_experts", False), "f.loop": ("", False)}
    self_s = {"f.attn": 0.08, "f.unmask": 0.02, "f.draw": 0.10,
              "f.gmm": 0.70, "f.loop": 0.10}
    ops = [{"name": n, "program": "jit_block_step_slots_paged#1", "self": s}
           for n, s in self_s.items()]
    modules = [{"name": "jit_block_step_slots_paged(1)", "t0": 0.25 * i,
                "t1": 0.25 * i + 0.25} for i in range(4)]
    return {
        "config": load(GRID, "configs", CONFIG + ".json"),
        "device": {"peaks": {"flops_bf16": 197e12,
                             "hbm_bytes_per_s": 819e9}},
        "programs": {"serve_decode": {
            "module": "jit_block_step_slots_paged", "scopes": scopes}},
        "scrape0": {"serve_block_passes_total": 100.0,
                    "serve_blocks_committed_total": 20.0},
        "scrape1": {"serve_block_passes_total": 1100.0,
                    "serve_blocks_committed_total": 220.0},
        "pages": {"peak_used": 10, "total": 100,
                  "live_tokens_mean": 30000.0},
        "reduced": {"window_s": 2.0, "devices": {0: {
            "ops": ops, "modules": modules, "busy_s": 1.0}}}}


def test_each_new_reader_on_a_hand_made_obs():
    from run import load_module
    obs = hand_made_obs()
    read = {n: load_module("readers", n).read(obs) for n in NEW}
    assert read["block_passes_per_block"] == pytest.approx(5.0)
    assert read["block_attn_dev_share"] == pytest.approx(8.0)
    assert read["unmask_dev_share"] == pytest.approx(2.0)
    # 30,000 live tokens x 2,048 B x 6 layers = 0.369 GB: 0.45 ms, over
    # the 20 ms a pass the attention took here
    assert read["block_attn_roofline_share"] == pytest.approx(
        100 * 30000 * 2048 * 6 / 819e9 / 0.02)
    assert all(0 < read[n] < 100 for n in NEW)
    # a program without the scopes or the counters (the parent commit),
    # a device without peaks, or no trace: nothing, and no error
    bare = hand_made_obs()
    bare["programs"] = {}
    assert all(load_module("readers", n).read(bare) is None
               for n in NEW if n != "block_passes_per_block")
    bare = hand_made_obs()
    bare["scrape0"], bare["scrape1"] = {}, {"serve_steps_total": 5.0}
    assert load_module("readers", "block_passes_per_block").read(bare) is None
    bare = hand_made_obs()
    bare["device"] = {"peaks": None}
    assert load_module("readers",
                       "block_attn_roofline_share").read(bare) is None
    other = hand_made_obs()                # a family with no such scope
    other["programs"]["serve_decode"]["scopes"] = {"f.loop": ("", False)}
    for n in NEW:
        if n != "block_passes_per_block":
            assert load_module("readers", n).read(other) is None, n
    bare = hand_made_obs()
    del bare["reduced"]
    assert all(load_module("readers", n).read(bare) is None
               for n in NEW if n != "block_passes_per_block")
