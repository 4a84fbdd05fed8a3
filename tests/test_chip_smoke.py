"""``chip_smoke.py`` off the chip: its ``train`` and ``serve`` phase
functions run at ``tiny`` widths on the CPU backend when called
directly (so a break in them shows here, not on a paid chip call), and
``python chip_smoke.py`` itself cannot pass without a TPU. Also the
compile-cache helper it and ``bench.py`` share."""
import os
import subprocess
import sys
from dataclasses import replace

import jax
import pytest

import llama_refs
from mxtpu import runtime
from mxtpu.models import llama

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def test_train_phase_runs_tiny_on_cpu():
    cfg = replace(llama.CONFIGS["tiny"], attn_impl="flash", remat=True,
                  remat_policy="dots_no_batch", max_seq_len=64)
    info = chip_smoke.phase_train(cfg, 8, 64, 3, expect_attn="blockwise")
    # off the chip the program holds the scan and the phase says so;
    # main() asks for "pallas", which only a TPU program can hold
    assert info["attention"] == "blockwise"
    assert info["loss"][1] < info["loss"][0]


def test_serve_phase_runs_tiny_on_cpu():
    cfg = llama_refs.serve_config()
    jobs = chip_smoke.make_jobs(
        cfg.vocab_size, ((5, 6, 0.0), (12, 8, 0.7), (12, 4, 0.0)),
        per_shape=2, shared_prefix=9)
    info, streams = chip_smoke.phase_serve(
        cfg, jobs, max_slots=2, max_len=32, min_bucket=4, page_size=8,
        n_pages=25, must_match=True,   # float32, highest (conftest)
        expect_attention="gathered")
    assert [len(s) for s in streams] == [j["mnew"] for j in jobs]
    assert info["compiles"] <= info["compile_bound"]
    assert info["prefix_hits"] >= 1 and info["identical"] == "6/6"


def test_pages_kernel_phase_runs_tiny_on_cpu():
    """The kernel-against-gathered leg, interpreted, at toy widths: a
    length of 1, a full row and both sides of a page's edge among its
    ragged slots."""
    info = chip_smoke.phase_pages_kernel(
        slots=6, n_heads=4, n_kv_heads=2, head_dim=16, page_size=4,
        capacity=32, layers=2, interpret=True)
    assert info["lengths"] == [1, 32]      # the phase holds the numbers
    assert info["largest_output"] > 0.0


def test_sampler_search_phase_runs_tiny_on_cpu():
    """The search-against-the-frozen-sort leg, the kernel interpreted:
    a vocabulary of no whole tile and one of two, ``top_p`` alone and
    ``top_k`` on every other row; the phase holds the thresholds."""
    info = chip_smoke.phase_sampler_search(
        shapes=((5, 1031, 0.7), (8, 256, 0.6)), calls=1, interpret=True)
    assert info["path"] == "interpreted"
    assert info["cutoffs_equal_to_sort"] == "26/26"
    assert info["worst_slack"] <= 2e-6
    assert set(info["ms_5x1031"]) == {"sort", "search_jnp", "ships"}


@pytest.mark.parametrize("floor", [0.0, 60.0], ids=["keeps_all", "keeps_none"])
def test_serve_warm_setup_phase_runs_tiny_on_cpu(tmp_path, floor):
    """The set-up leg at toy widths: two builds of the engine, a row a
    program's first call in each. Against a cache directory that keeps
    everything (threshold 0) the second build fetches every program it
    asks the backend for and compiles none of its own again; against
    one that keeps nothing (no toy program compiles for a minute) the
    program's record reads ``unwritten`` for the engine's programs, and
    the phase names them and does not fail them for missing again."""
    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        info = chip_smoke.phase_serve_warm_setup(
            llama_refs.serve_config(), buckets=(4, 8), max_slots=2,
            max_len=32, min_bucket=4, page_size=8, n_pages=25,
            prefix_cache=True)
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
    assert list(info["first_calls_s"]) == list(info["second_calls_s"]) \
        == ["b4", "b8"]
    programs = ["serve_decode", "serve_prefill_b4", "serve_prefill_b8"]
    assert info["sampler"] == "search"
    assert set(programs) <= set(info["unwritten"]) if floor else \
        info["unwritten"] == []
    for name in programs:
        # jax counts a miss where it writes what it compiled
        assert info["first"][name]["cache"] == (
            "unwritten" if floor else "miss"), info["first"]
        row = info["second"][name]
        assert row["cache"] == ("unwritten" if floor else "hit"), \
            info["second"]
        assert {"trace_s", "nested_traces", "lower_s", "backend_s",
                "first_call_s"} <= set(row), row
        assert row["nested_traces"] > 0 and row["first_call_s"] > 0
    assert set(info["second"]["others"]) == {"trace_s", "lower_s",
                                             "backend_s"}


def test_latent_kernel_phase_runs_tiny_on_cpu():
    info = chip_smoke.phase_latent_kernel(
        slots=6, n_heads=4, row=128, value_dim=96, page_size=16,
        capacity=64, layers=2, interpret=True)
    assert info["lengths"] == [1, 64]
    assert info["largest_output"] > 0.0


def test_sambay_kernel_phase_runs_tiny_on_cpu():
    """Two pools of token rows, three heads end to end, a page's edge
    (16) and the full row among the lengths; one timed read."""
    info = chip_smoke.phase_sambay_kernel(
        slots=5, n_heads=6, kv_heads=3, head_dim=128, page_size=4,
        capacity=32, lengths=(16, 32), reads=1, interpret=True)
    assert info["lengths"] == [16, 32]
    assert info["largest_output"] > 0.0 and info["kernel_ms"] > 0.0


def test_serve_sambay_phase_runs_tiny_on_cpu():
    """The second family's leg: every kind of layer at toy widths,
    prompts over three windows, both passes float32 here."""
    from mxtpu.models import sambay
    cfg = sambay.CONFIGS["tiny"]
    jobs = chip_smoke.make_jobs(cfg.vocab_size,
                                ((11, 5, 0.0), (30, 6, 0.0)),
                                per_shape=2, shared_prefix=0)
    info = chip_smoke.phase_serve_family(
        cfg, jobs, max_slots=2, max_len=96, min_bucket=16, page_size=8)
    assert info["requests"] == 8
    assert info["worst_gap_float32"] <= 1e-3


def test_serve_latent_moe_phase_runs_tiny_on_cpu():
    """The third family's leg: latent attention and routed experts at
    toy widths, the longer prompt in two chunks, both passes float32
    here."""
    from mxtpu.models import latent_moe
    cfg = latent_moe.CONFIGS["tiny"]
    jobs = chip_smoke.make_jobs(cfg.vocab_size,
                                ((11, 5, 0.0), (30, 6, 0.0)),
                                per_shape=1, shared_prefix=0)
    info = chip_smoke.phase_serve_family(
        cfg, jobs, max_slots=2, max_len=96, min_bucket=16, page_size=8,
        prefill_chunk=16)
    assert info["requests"] == 4
    assert info["worst_gap_float32"] <= 1e-3


def test_retention_kernel_phase_runs_tiny_on_cpu():
    """Three slots, ten query heads over two KV heads of 128 (five
    tiles of the state a head), the kernel in interpret mode."""
    info = chip_smoke.phase_retention_kernel(
        slots=3, n_heads=10, kv_heads=2, head_dim=128, steps=1,
        interpret=True)
    assert info["state_bytes_a_layer"] == 3 * 2 * 128 * 8320 * 4
    assert info["largest_output"] > 0.0 and info["kernel_ms"] > 0.0


def test_serve_retention_phase_runs_tiny_on_cpu():
    """The fourth family's leg: power-retention layers at toy widths,
    the longer prompt in two chunks, a pool of no pages, both passes
    float32 here."""
    from mxtpu.models import retention
    cfg = retention.CONFIGS["tiny"]
    jobs = chip_smoke.make_jobs(cfg.vocab_size,
                                ((11, 5, 0.0), (30, 6, 0.0)),
                                per_shape=1, shared_prefix=0)
    info = chip_smoke.phase_serve_family(
        cfg, jobs, max_slots=2, max_len=96, min_bucket=16, page_size=8,
        prefill_chunk=16, expect_attention="state",
        expect_attention_f32="state")
    assert info["requests"] == 4
    assert info["worst_gap_float32"] <= 1e-3


def test_serve_blockdiff_phase_runs_tiny_on_cpu():
    """The fifth family's leg: blocks of diffusion over routed experts
    at toy widths, a prompt with a remainder, the longer one in two
    chunks, each stream replayed against ``forward``; both passes
    float32 here."""
    from mxtpu.models import blockdiff_moe
    cfg = blockdiff_moe.CONFIGS["tiny"]
    jobs = chip_smoke.make_jobs(cfg.vocab_size,
                                ((11, 6, 0.0), (30, 9, 0.0)),
                                per_shape=1, shared_prefix=0)
    info = chip_smoke.phase_serve_family(
        cfg, jobs, max_slots=2, max_len=96, min_bucket=16, page_size=8,
        prefill_chunk=16)
    assert info["requests"] == 4
    assert info["worst_gap_float32"] <= 1e-3


def test_main_fails_without_a_chip():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "FAIL device" in out.stdout
    assert '"ok"' not in out.stdout


def test_compile_cache_helper(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves jax's own
    setting alone; unset, it names one fixed directory inside the
    checkout — the same from another process."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert runtime.use_compile_cache() == \
        jax.config.jax_compilation_cache_dir
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    runtime.use_compile_cache()
    want = os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", want)]

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c",
         "from mxtpu import runtime; print(runtime.use_compile_cache())"],
        capture_output=True, text=True, timeout=120, cwd="/",
        env={**env, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == want
