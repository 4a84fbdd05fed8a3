"""The latent-attention, routed-expert family's cell off the chip: the
published widths of the real configuration file against the catalog's
row, the new driver end to end at a toy width on the CPU stand-in
(``configs/tiny-latent-moe-serve.json``, the real traffic file with its
lengths cut to the toy engine's rows), and each new reader and counting
function on a hand-made ``obs``."""
import copy
import json
import math
import os

import pytest

import tiny

GRID = os.path.dirname(tiny.HERE)
CELL = "kanana2-rag-closed32"
CONFIG = "kanana-2-30b-a3b-d8-serve"
NEW = ("moe_dev_share", "moe_dispatch_dev_share", "mla_attn_dev_share",
       "moe_experts_touched_mean", "moe_roofline_share",
       "mla_decode_roofline_share")
# the accepted decode- and prefill-program metrics, which move
# ``itl_p95_ms``
JOINED = ("decode_step_dev_ms", "prefill_chunk_dev_ms", "sampler_dev_share",
          "kv_gather_dev_share", "decode_unscoped_dev_share")
# the catalog's row (model-configs guide, architectures.jsonl,
# kanana-2-30b-a3b-instruct-2601), every number of its ``config``
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}


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
    assert (cut["published"], cut["here"]) == (48, cfg["num_hidden_layers"])
    # one leading dense layer and at least four expert layers
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert {"rope_pairing", "e_score_correction_bias", "topk_weights",
            "weights", "sampling", "cache_row"} <= set(cfg["assumed"])
    assert os.path.exists(os.path.join(GRID, "drivers",
                                       cfg["kind"] + ".py"))
    assert os.path.exists(os.path.join(
        GRID, "reference", cfg["family"]["reference"] + ".py"))
    # the program's config object holds the same widths
    from run import load_module
    _, got, _ = load_module("drivers", cfg["kind"]).family_of(cfg)
    assert (got.dim, got.n_heads, got.qk_head_dim, got.v_head_dim,
            got.row_dim, got.row_stored, got.hidden_dim,
            got.moe_hidden_dim, got.n_routed_experts, got.experts_per_tok,
            got.n_shared_experts, got.vocab_size, got.first_k_dense,
            got.n_moe_layers, got.routed_scaling_factor) == (
                2048, 32, 192, 128, 576, 640, 6144, 768, 128, 6, 2, 128256,
                1, cfg["num_hidden_layers"] - 1, 2.448)
    # the check batch's prompts are two chunks long
    eng = cfg["run"]["engine"]
    assert eng["prefill_chunk"] < cfg["check"]["prompt_cap"] \
        <= 2 * eng["prefill_chunk"]
    assert eng["n_pages"] == eng["max_slots"] * (
        eng["max_len"] // eng["page_size"]) + 1
    assert eng["max_len"] % eng["prefill_chunk"] == 0


def test_parameter_and_byte_reckoning(bench):
    """The configuration's numbers from its widths: 5.07 B parameters,
    1,152 bytes a token and layer counted, 1,280 stored."""
    from expert_latent_counts import (expert_layers, expert_matrices_bytes,
                                      latent_decode_step_bytes,
                                      latent_row_bytes)
    cfg = load(GRID, "configs", CONFIG + ".json")
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    attn = (d * H * cfg["qk_head_dim"]
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * H * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * d + 2 * d + cfg["kv_lora_rank"])
    assert round(attn / 1e6, 2) == 26.35
    moe = (cfg["n_routed_experts"] * 3 * d * cfg["moe_intermediate_size"]
           + 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
           + d * cfg["n_routed_experts"] + cfg["n_routed_experts"])
    assert round(moe / 1e6, 2) == 613.68          # 640.0 with attention
    total = (cfg["num_hidden_layers"] * attn + expert_layers(cfg) * moe
             + 3 * d * cfg["intermediate_size"]
             + 2 * cfg["vocab_size"] * d + d)
    assert round(total / 1e9, 2) == 5.07
    assert latent_row_bytes(cfg) == 1152
    assert expert_matrices_bytes(cfg) == 3 * 2048 * 768 * 2
    assert latent_decode_step_bytes(cfg, 1000) == 1000 * 1152 * 8


def test_the_cell_and_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "rag-closed32", 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
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
    cfg = load(GRID, "configs", CONFIG + ".json")
    # the mix ISSUE 31 fixed before any code was written
    assert traffic["prompt"] == {"law": "lognormal", "median": 3584,
                                 "sigma": 0.5, "lo": 1024, "hi": 4096}
    assert traffic["output"] == {"law": "lognormal", "median": 160,
                                 "sigma": 0.6, "lo": 48, "hi": 768}
    assert (traffic["pool"], traffic["ramp_s"]) == (64, 15)
    assert traffic["sampling"] == {"temperature": 0.7, "top_p": 0.95}
    assert traffic["requests"] == {"kind": "independent"}
    assert (traffic["prompt"]["hi"] + traffic["output"]["hi"]
            <= cfg["run"]["engine"]["max_len"])
    assert traffic["arrival"] == {
        "kind": "closed", "callers": cfg["run"]["engine"]["max_slots"]}


def toy_parts(bench):
    """The real cell's metric tables over the toy configuration and
    the real traffic file cut to the toy engine's rows."""
    import run as grid_run
    parts = copy.deepcopy(grid_run.load_cell(bench, CELL, tiny.ROOT))
    parts["config"] = load(tiny.HERE, "configs",
                           "tiny-latent-moe-serve.json")
    t = parts["traffic"]
    t["arrival"]["callers"] = 8
    t["ramp_s"] = 1
    for law, by in ((t["prompt"], 8), (t["output"], 48)):
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
    notes = r["notes"]
    assert notes["check_worst_gap"] <= 0.001
    # the program's router against the reference's, float32 both
    assert 0 <= notes["check_router_gap"] <= 1e-5
    assert notes["check_router_flips"] == 0
    # each layer alone against the reference's layer, float32 both
    assert 0 <= notes["check_layer_gap"] <= 1e-5
    assert len(notes["check_layer_gaps"]) == 3
    assert notes["check_router_places"] == 4 * 2 * 200
    assert notes["serve_tok_s"] > 0 and notes["live_tokens_mean"] > 0
    assert notes["decode_attention"] == "gathered"
    for m in r["metrics"].values():
        assert math.isfinite(m["value"]) and m["unit"]
    if trace:
        # the stand-in has no peaks and its trace no programs: what
        # needs either reports nothing; the counter's mean is there
        assert set(r["metrics"]) <= {*NEW, *JOINED}
        assert 1 <= r["metrics"]["moe_experts_touched_mean"]["value"] <= 16
        assert {"decode_batch_mean", "kv_pages_peak_share",
                "engine_host_share"} <= set(notes)
        assert 0 < r["device"]["busy_s"] and "breakdown" in r
    else:
        assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
        assert r["metrics"]["itl_p95_ms"]["value"] > 0


def hand_made_obs():
    """A traced window of 2 s in which the decode program ran four
    times; over the whole window 100 steps touched 68,600 experts with
    134,400 assignments (98 experts a layer and step, 32 tokens), and
    the slots held 100,000 live tokens."""
    scopes = {"f.router": ("moe_router", False),
              "f.sort": ("moe_dispatch", False),
              "f.gmm": ("moe_experts", False),
              "f.shared": ("moe_shared", False),
              "f.mla": ("mla_attention", False),
              "f.gather": ("kv_gather", False), "f.loop": ("", False)}
    self_s = {"f.router": 0.01, "f.sort": 0.04, "f.gmm": 0.40,
              "f.shared": 0.05, "f.mla": 0.20, "f.gather": 0.20,
              "f.loop": 0.10}
    ops = [{"name": n, "program": "jit_decode_slots_paged#1", "self": s}
           for n, s in self_s.items()]
    modules = [{"name": "jit_decode_slots_paged(1)", "t0": 0.25 * i,
                "t1": 0.25 * i + 0.25} for i in range(4)]
    config = load(GRID, "configs", CONFIG + ".json")
    return {
        "config": config,
        "device": {"peaks": {"flops_bf16": 197e12,
                             "hbm_bytes_per_s": 819e9}},
        "programs": {"serve_decode": {"module": "jit_decode_slots_paged",
                                      "scopes": scopes}},
        "scrape0": {"serve_moe_experts_touched_total": 1000.0,
                    "serve_moe_assignments_total": 2000.0,
                    "serve_moe_load_max_share_count": 10.0},
        "scrape1": {"serve_moe_experts_touched_total": 69600.0,
                    "serve_moe_assignments_total": 136400.0,
                    "serve_moe_load_max_share_count": 110.0},
        "pages": {"peak_used": 10, "total": 100,
                  "live_tokens_mean": 100000.0},
        "reduced": {"window_s": 2.0, "devices": {0: {
            "ops": ops, "modules": modules, "busy_s": 1.0}}}}


def test_each_new_reader_on_a_hand_made_obs():
    from run import load_module
    obs = hand_made_obs()
    read = {n: load_module("readers", n).read(obs) for n in NEW}
    assert read["moe_dev_share"] == pytest.approx(50.0)
    assert read["moe_dispatch_dev_share"] == pytest.approx(4.0)
    assert read["mla_attn_dev_share"] == pytest.approx(20.0)
    assert read["moe_experts_touched_mean"] == pytest.approx(98.0)
    # 686 experts a step x 9.44 MB = 6.47 GB: 7.90 ms at 819 GB/s,
    # over the 100 ms a step the products took here
    assert read["moe_roofline_share"] == pytest.approx(
        100 * 686 * 3 * 2048 * 768 * 2 / 819e9 / 0.1)
    assert obs["notes"]["moe_bound"] == "memory"
    # 100,000 live tokens x 1,152 B x 8 layers = 0.92 GB: 1.125 ms,
    # over the 50 ms a step the attention took here
    assert read["mla_decode_roofline_share"] == pytest.approx(
        100 * 100000 * 1152 * 8 / 819e9 / 0.05)
    assert all(0 < read[n] < 100 for n in NEW)
    # a program without the scopes or the counters (the parent commit),
    # a device without peaks, or no trace: nothing, and no error
    bare = hand_made_obs()
    bare["programs"] = {}
    assert all(load_module("readers", n).read(bare) is None
               for n in NEW if n != "moe_experts_touched_mean")
    bare = hand_made_obs()
    bare["scrape0"], bare["scrape1"] = {}, {"serve_steps_total": 5.0}
    for n in ("moe_experts_touched_mean", "moe_roofline_share"):
        assert load_module("readers", n).read(bare) is None
    bare = hand_made_obs()
    bare["device"] = {"peaks": None}
    for n in ("moe_roofline_share", "mla_decode_roofline_share"):
        assert load_module("readers", n).read(bare) is None
    sambay = hand_made_obs()               # a family with no such scope
    sambay["programs"]["serve_decode"]["scopes"] = {
        "f.loop": ("", False), "f.gather": ("kv_gather", False)}
    for n in NEW:
        if n != "moe_experts_touched_mean":
            assert load_module("readers", n).read(sambay) is None, n
    bare = hand_made_obs()
    del bare["reduced"]
    assert all(load_module("readers", n).read(bare) is None
               for n in NEW if n != "moe_experts_touched_mean")
