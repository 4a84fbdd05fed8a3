"""The power-retention family's cell off the chip: the published widths
of the real configuration file against the catalog's row, the new
driver end to end at a toy width on the CPU stand-in
(``configs/tiny-retention-serve.json``, the real traffic file with its
lengths cut to the toy engine's positions), each new reader on a
hand-made ``obs``, ``retention_counts`` against hand arithmetic, and the
controls the limits are set against (``retention_controls.py``: the
reference with lowered operands, a bank held in bf16) through the
harness's own comparison."""
import copy
import json
import math
import os

import pytest

import tiny

GRID = os.path.dirname(tiny.HERE)
CELL = "brumby-longgen-closed16"
CONFIG = "brumby-14b-base-d8-serve"
NEW = ("retention_dev_share", "retention_decode_roofline_share",
       "retention_chunk_stall_ms")
# the accepted decode-program metrics, which move ``itl_p95_ms``
# (``prefill_chunk_dev_ms`` is NOT joined: the traced 4 s of a window see
# no admission of this cell's 16 long streams in about one run of seven,
# and a traced line that lacks a listed metric is refused; the chunk is
# read over the whole window by ``retention_chunk_stall_ms``)
JOINED = ("decode_step_dev_ms", "sampler_dev_share", "kv_gather_dev_share",
          "decode_unscoped_dev_share")
# the catalog's row (model-configs guide, architectures.jsonl,
# Brumby-14B-Base), every number of its ``config``
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
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
    assert (cut["published"], cut["here"]) == (40, cfg["num_hidden_layers"])
    assert cfg["num_hidden_layers"] >= 5          # ISSUE 33's floor
    assert {"power", "gate", "gate_bias", "eps", "scale", "norms_and_rope",
            "sampling", "weights"} <= set(cfg["assumed"])
    assert os.path.exists(os.path.join(GRID, "drivers",
                                       cfg["kind"] + ".py"))
    assert os.path.exists(os.path.join(
        GRID, "reference", cfg["family"]["reference"] + ".py"))
    # the program's config object holds the same widths, and its state
    # the type the file states
    import jax.numpy as jnp
    from run import load_module
    _, got, _ = load_module("drivers", cfg["kind"]).family_of(cfg)
    assert (got.dim, got.n_heads, got.n_kv_heads, got.head_dim,
            got.hidden_dim, got.vocab_size, got.n_layers, got.power,
            got.state_rows) == (5120, 40, 8, 128, 17408, 151936,
                                cfg["num_hidden_layers"], 2, 8320)
    assert jnp.dtype(cfg["run"]["state_dtype"]) == got.state_dtype
    # the check batch's prompts are two chunks long, then decode
    eng = cfg["run"]["engine"]
    assert eng["prefill_chunk"] < cfg["check"]["prompt_cap"] \
        <= 2 * eng["prefill_chunk"]
    assert cfg["check"]["new_tokens"] >= 32
    assert cfg["check"]["also"] == ["layers", "state"]
    assert (eng["n_pages"], eng["prefix_cache"]) == (0, False)


def test_parameter_and_byte_reckoning():
    """The configuration's numbers from its widths: 330.35 M parameters
    a layer, 4.20 B in all; 34.08 MB of state a slot and layer."""
    from retention_counts import (decode_step_bytes, feature_rows,
                                  state_bytes)
    cfg = load(GRID, "configs", CONFIG + ".json")
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = (2 * d * H * hd + 2 * d * G * hd + d * G + G + 2 * hd
             + 3 * d * cfg["intermediate_size"] + 2 * d)
    assert round(layer / 1e6, 2) == 330.35
    total = cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d
    assert round(total / 1e9, 2) == 4.20
    assert feature_rows(cfg) == 128 * 129 // 2 == 8256
    assert state_bytes(cfg) == 8 * 8256 * 129 * 4 == 34080768
    # 16 slots x 8 layers, read once and written once: 8.72 GB
    assert decode_step_bytes(cfg, 16) == 2 * 16 * 8 * 34080768
    assert round(decode_step_bytes(cfg, 16) / 819e9 * 1e3, 2) == 10.65


def test_the_cell_and_its_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longgen-closed16", 1)
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
    # the mix ISSUE 33 fixed before any code was written
    assert traffic["prompt"] == {"law": "lognormal", "median": 1024,
                                 "sigma": 0.5, "lo": 512, "hi": 2048}
    assert traffic["output"] == {"law": "lognormal", "median": 1024,
                                 "sigma": 0.5, "lo": 512, "hi": 3072}
    assert (traffic["pool"], traffic["ramp_s"]) == (64, 15)
    assert traffic["sampling"] == {"temperature": 0.7, "top_p": 0.95}
    assert traffic["requests"] == {"kind": "independent"}
    assert (traffic["prompt"]["hi"] + traffic["output"]["hi"]
            <= cfg["run"]["engine"]["max_len"])
    assert traffic["arrival"] == {
        "kind": "closed", "callers": cfg["run"]["engine"]["max_slots"]}


def toy_parts(bench):
    """The real cell's metric tables over the toy configuration and
    the real traffic file cut to the toy engine's positions."""
    import run as grid_run
    parts = copy.deepcopy(grid_run.load_cell(bench, CELL, tiny.ROOT))
    parts["config"] = load(tiny.HERE, "configs",
                           "tiny-retention-serve.json")
    t = parts["traffic"]
    t["arrival"]["callers"] = 8
    t["ramp_s"] = 1
    for law, by in ((t["prompt"], 8), (t["output"], 32)):
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
    # each layer alone against the reference's layer, float32 both
    assert 0 <= notes["check_layer_gap"] <= 1e-5
    assert len(notes["check_layer_gaps"]) == 3
    # a slot's state after 192 tokens in chunks of 128, seated over
    # another prompt's, and 8 steps of a bank of 8 slots, float32
    assert 0 <= notes["check_state_gap"] <= 1e-5
    assert notes["check_state_dtype"] == "float32"
    assert notes["check_state_path"] == "state"
    assert notes["check_state_idle_kept"] is True
    assert notes["serve_tok_s"] > 0
    # no pages: nothing to count tokens in, a fixed block a slot
    assert notes["live_tokens_mean"] == 0
    assert notes["state_reserved_bytes"] == 8 * 3 * 2 * 144 * 17 * 4
    assert notes["decode_attention"] == "state"
    for m in r["metrics"].values():
        assert math.isfinite(m["value"]) and m["unit"]
    if trace:
        # the stand-in has no peaks and its trace no programs: what
        # needs either reports nothing
        assert set(r["metrics"]) <= {*NEW, *JOINED}
        assert {"decode_batch_mean", "engine_host_share"} <= set(notes)
        assert 0 < r["device"]["busy_s"] and "breakdown" in r
    else:
        assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
        assert r["metrics"]["itl_p95_ms"]["value"] > 0


def test_a_state_held_in_bfloat16_fails_the_state_check(bench):
    """The control ``check.state_tol`` is set against: the same check
    with the family's bank in bf16."""
    import jax
    import jax.numpy as jnp
    from dataclasses import replace
    from run import load_module
    config = toy_parts(bench)["config"]
    driver = load_module("drivers", config["kind"])
    module, cfg, reference = driver.family_of(config)
    params = module.init_params(cfg, jax.random.PRNGKey(0))
    seqs = [(jax.random.randint(jax.random.PRNGKey(1), (200,), 0,
                                cfg.vocab_size), [])]
    ok, notes = driver.state_check(config, module, cfg, reference, params,
                                   seqs, 7, lambda s: None)
    assert ok and notes["check_state_gap"] <= 1e-5
    held = replace(cfg, state_dtype=jnp.bfloat16)
    ok, notes = driver.state_check(config, module, held, reference, params,
                                   seqs, 7, lambda s: None)
    assert not ok and notes["check_state_gap"] > 1e-3
    assert notes["check_state_dtype"] == "bfloat16"
    assert notes["check_state_idle_kept"] is True


@pytest.mark.parametrize("bits, correct", [((8, 23), True),
                                           ((5, 3), False)])
def test_the_reference_with_lowered_operands_comes_out_not_correct(
        bench, bits, correct):
    """The control ``check.tol`` and ``check.layer_tol`` are set
    against, through ``check_batch`` itself: the plain reference in the
    program's place is correct as it is (float32: the stand-in is sound)
    and not correct with float8_e4m3's mantissa on its operands."""
    import gen
    from run import load_module
    from retention_controls import operands_control
    parts = toy_parts(bench)
    config = parts["config"]
    ok, worst, notes = operands_control(
        config, gen.Traffic(parts["traffic"], 11, config["vocab_size"]),
        load_module("drivers", config["kind"]), 11, *bits,
        log=lambda s: None)
    assert ok is correct
    assert "check_state_gap" not in notes       # it holds no state
    if correct:
        assert worst == 0 and notes["check_layer_gap"] <= 1e-6
    else:
        assert worst > config["check"]["tol"]
        assert notes["check_layer_gap"] > 100 * config["check"]["layer_tol"]


def hand_made_obs():
    """A traced window of 2 s in which the decode program ran four
    times, a quarter of a second each; over the whole window of 30 s
    100 steps emitted 1,550 tokens (15.5 slots running) and 8 prefill
    chunks were dispatched."""
    scopes = {"f.state": ("retention_state", False),
              "f.gate": ("retention_gate", False),
              "f.mlp": ("mlp", False), "f.loop": ("", False)}
    self_s = {"f.state": 0.50, "f.gate": 0.02, "f.mlp": 0.38,
              "f.loop": 0.10}
    ops = [{"name": n, "program": "jit_decode_slots_paged#1", "self": s}
           for n, s in self_s.items()]
    modules = [{"name": "jit_decode_slots_paged(1)", "t0": 0.25 * i,
                "t1": 0.25 * i + 0.25} for i in range(4)]
    return {
        "config": load(GRID, "configs", CONFIG + ".json"),
        "device": {"peaks": {"flops_bf16": 197e12,
                             "hbm_bytes_per_s": 819e9}},
        "programs": {"serve_decode": {"module": "jit_decode_slots_paged",
                                      "scopes": scopes}},
        "seconds": 30.0,
        "scrape0": {"serve_steps_total": 10.0, "serve_tokens_total": 150.0,
                    "span_serve_prefill_ms_count": 3.0},
        "scrape1": {"serve_steps_total": 110.0,
                    "serve_tokens_total": 1700.0,
                    "span_serve_prefill_ms_count": 11.0},
        "reduced": {"window_s": 2.0, "devices": {0: {
            "ops": ops, "modules": modules, "busy_s": 1.0}}}}


def test_each_new_reader_on_a_hand_made_obs():
    from run import load_module
    obs = hand_made_obs()
    read = {n: load_module("readers", n).read(obs) for n in NEW}
    assert read["retention_dev_share"] == pytest.approx(52.0)
    # 15.5 slots x 2 x 34.08 MB x 8 layers = 8.45 GB: 10.3 ms at 819
    # GB/s, over the 125 ms a step the scope took here
    assert read["retention_decode_roofline_share"] == pytest.approx(
        100 * 15.5 * 2 * 34080768 * 8 / 819e9 / 0.125)
    assert all(0 < read[n] < 100 for n in NEW[:2])
    # 30 s less 100 steps of 250 ms, over 8 chunks
    assert read["retention_chunk_stall_ms"] == pytest.approx(
        1e3 * (30.0 - 100 * 0.25) / 8)
    # a program without the scopes (the parent commit, another family),
    # a device without peaks, no counters or no trace: nothing, no error
    bare = hand_made_obs()
    bare["programs"] = {}
    assert all(load_module("readers", n).read(bare) is None
               for n in NEW[:2])
    other = hand_made_obs()
    other["programs"]["serve_decode"]["scopes"] = {
        "f.loop": ("", False), "f.mlp": ("mlp", False)}
    assert all(load_module("readers", n).read(other) is None
               for n in NEW[:2])
    bare = hand_made_obs()
    bare["device"] = {"peaks": None}
    assert load_module("readers", NEW[1]).read(bare) is None
    bare = hand_made_obs()
    bare["scrape0"], bare["scrape1"] = {}, {}
    assert all(load_module("readers", n).read(bare) is None
               for n in NEW[1:])
    # a window in which no chunk was dispatched has none to time
    bare = hand_made_obs()
    bare["scrape1"]["span_serve_prefill_ms_count"] = 3.0
    assert load_module("readers", NEW[2]).read(bare) is None
    bare = hand_made_obs()
    del bare["reduced"]
    assert all(load_module("readers", n).read(bare) is None for n in NEW)
