"""The opperf regression gate must FAIL on an injected slowdown and
pass clean (VERDICT r4 #3 'done' criterion). Runs the compare logic on
the CPU backend against a freshly-made baseline so the test is
platform-independent; the real CI gate compares the chip sweep against
a ``benchmark/opperf/baseline_tpu.json`` taken on the gated machine."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
OPPERF = os.path.join(REPO, "benchmark", "opperf", "opperf.py")
# ops chosen to be comfortably over the 0.5 ms gate floor on CPU
OPS = "Convolution,dot,softmax"


def _run(tmp_path, extra, inject=""):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if inject:
        env["MXTPU_OPPERF_INJECT"] = inject
    return subprocess.run(
        [sys.executable, OPPERF, "--ops", OPS, "--iters", "3"] + extra,
        capture_output=True, text=True, timeout=600, env=env)


@pytest.mark.slow
def test_opperf_gate_fails_on_injected_slowdown(tmp_path):
    base = str(tmp_path / "base.json")
    out = _run(tmp_path, ["--json", base])
    assert out.returncode == 0, out.stderr[-1000:]
    entries = {r["op"]: r["fwd_ms"] for r in json.load(open(base))}
    assert set(entries) == set(OPS.split(","))

    # clean compare passes
    out = _run(tmp_path, ["--compare", base])
    assert out.returncode == 0, (out.stdout[-800:], out.stderr[-500:])
    assert "opperf gate: OK" in out.stdout

    # a 50 ms/call injected slowdown on one op must fail persistently
    # (the gate re-times violators, so the injection must stay active)
    out = _run(tmp_path, ["--compare", base], inject="dot:50")
    assert out.returncode == 1, out.stdout[-800:]
    assert "REGRESSION dot" in out.stdout

    # missing op in the fresh sweep also fails (baseline is a contract)
    out = _run(tmp_path, ["--compare", base, "--ops", "dot,softmax"])
    assert out.returncode == 1, out.stdout[-800:]
    assert "missing from sweep" in out.stdout
