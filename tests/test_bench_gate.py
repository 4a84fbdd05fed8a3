"""The whole-model perf gate must FLAG a seeded 10% step-time
regression and PASS an unchanged baseline (ISSUE 3 acceptance; the
model-level sibling of tests/test_opperf_gate.py).

The fast tests go through ``--replay`` (pure measure-file-vs-baseline
compare — deterministic, no model runs), so the 10%-regression
contract is tier-1. One case runs the real CLI, so the ``bench.py
gate`` dispatch and its exit code stay covered; the rest call
``main_gate`` in this process (a fresh interpreter per case cost the
tier-1 budget half a minute). The slow test runs the live measurement
path end to end on the CPU-safe smoke config with an
MXTPU_BENCH_INJECT-seeded slowdown."""
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH = os.path.join(REPO, "bench.py")
sys.path.insert(0, REPO)
import bench  # noqa: E402


def _gate(args, inject=""):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("MXTPU_BENCH_INJECT", None)
    if inject:
        env["MXTPU_BENCH_INJECT"] = inject
    return subprocess.run(
        [sys.executable, BENCH, "gate"] + args,
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)


def _replay(capsys, run, base):
    """``bench.py gate --replay run --baseline base`` without the
    interpreter start-up."""
    capsys.readouterr()
    try:
        rc = bench.main_gate(["--replay", run, "--baseline", base])
    except SystemExit as e:
        rc = e.code
    return types.SimpleNamespace(returncode=rc,
                                 stdout=capsys.readouterr().out)


def _write(path, configs, tolerance=1.05):
    with open(path, "w") as f:
        json.dump({"configs": configs, "tolerance": tolerance}, f)
    return str(path)


BASE = {
    "resnet50": {"step_ms": 112.24, "mfu": 0.277},
    "resnet50_s2d": {"step_ms": 95.0, "mfu": 0.327},
    "bert_base": {"step_ms": 105.89, "mfu": 0.435},
}


def test_gate_replay_passes_unchanged_baseline(tmp_path, capsys):
    base = _write(tmp_path / "base.json", BASE)
    run = _write(tmp_path / "run.json", BASE)
    out = _replay(capsys, run, base)
    assert out.returncode == 0, out.stdout[-800:]
    assert "bench_gate: OK" in out.stdout


def test_gate_replay_flags_10pct_regression(tmp_path, capsys):
    base = _write(tmp_path / "base.json", BASE)
    slowed = {k: dict(v, step_ms=round(v["step_ms"] * 1.10, 2))
              for k, v in BASE.items()}
    run = _write(tmp_path / "run.json", slowed)
    # the real CLI: argv dispatch in main(), exit code to the shell
    out = _gate(["--replay", run, "--baseline", base])
    assert out.returncode == 1, (out.stdout[-800:], out.stderr[-500:])
    assert "REGRESSION" in out.stdout
    # one regressed config among healthy ones is still a failure
    one = dict(BASE, resnet50_s2d=dict(BASE["resnet50_s2d"],
                                       step_ms=round(95.0 * 1.10, 2)))
    run = _write(tmp_path / "run.json", one)
    out = _replay(capsys, run, base)
    assert out.returncode == 1
    assert "REGRESSION resnet50_s2d" in out.stdout


def test_gate_replay_missing_config_fails_and_new_config_passes(
        tmp_path, capsys):
    base = _write(tmp_path / "base.json", BASE)
    # missing: the baseline is a contract
    run = _write(tmp_path / "run.json",
                 {k: v for k, v in BASE.items() if k != "bert_base"})
    out = _replay(capsys, run, base)
    assert out.returncode == 1
    assert "MISSING bert_base" in out.stdout
    # extra configs (e.g. a new stem variant awaiting its first chip
    # measurement) are reported but do not gate
    run = _write(tmp_path / "run.json",
                 dict(BASE, llama_509m={"step_ms": 252.5}))
    out = _replay(capsys, run, base)
    assert out.returncode == 0
    assert "new llama_509m" in out.stdout


def test_committed_baseline_is_gateable(tmp_path, capsys):
    """A baseline in the shape ``gate --update`` writes on the gated
    machine (meta and provenance beside the configs, the default band)
    must parse and replay-pass against itself. None is committed: the
    machine that measures takes its own."""
    path = str(tmp_path / "baseline_models.json")
    with open(path, "w") as f:
        json.dump({"configs": BASE, "tolerance": 1.25,
                   "meta": {"device_kind": "TPU v5 lite", "n_devices": 1},
                   "_provenance": "bench.py gate --update"}, f)
    out = _replay(capsys, path, path)
    assert out.returncode == 0, out.stdout[-800:]
    assert "3 configs within 1.25x" in out.stdout


@pytest.mark.slow
def test_gate_live_smoke_measure_and_injected_slowdown(tmp_path):
    """End-to-end measurement path on CPU: self-baseline the smoke
    config, pass a clean re-run at a generous tolerance, then fail it
    with an MXTPU_BENCH_INJECT seeded slowdown that exceeds the band
    (CPU timing jitter makes a literal 10% live check flaky; the exact
    10% logic contract is the fast replay tests above)."""
    base = str(tmp_path / "self.json")
    out = _gate(["--configs", "smoke_llama", "--baseline", base,
                 "--update"])
    assert out.returncode == 0, out.stderr[-2000:]
    out = _gate(["--configs", "smoke_llama", "--baseline", base,
                 "--tolerance", "2.0"])
    assert out.returncode == 0, out.stdout[-800:]
    out = _gate(["--configs", "smoke_llama", "--baseline", base,
                 "--tolerance", "2.0"], inject="smoke_llama:3.0")
    assert out.returncode == 1, out.stdout[-800:]
    assert "REGRESSION smoke_llama" in out.stdout
