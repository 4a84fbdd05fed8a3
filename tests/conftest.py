"""Test configuration: force an 8-device virtual CPU mesh (SURVEY.md §4.2 —
the rebuild's analogue of the reference's local-tracker distributed tests:
sharding/collective tests run on virtual devices, no TPU pod needed).

Must set env before jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never open a chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402  (after env setup)

jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
# float32 tests compare against NumPy ground truth — use exact f32 matmuls
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


# Session-scoped llama serve scaffolding (the tier-1 budget seam —
# llama_refs.py): ONE tiny config + weight tree per session, shared
# by test_serve*/test_gateway/test_fleet so generate references
# memoize across files instead of recomputing per module.
@pytest.fixture(scope="session")
def serve_cfg():
    import llama_refs
    return llama_refs.serve_config()


@pytest.fixture(scope="session")
def serve_params(serve_cfg):
    import llama_refs
    return llama_refs.serve_weights(0)


@pytest.fixture(scope="session")
def serve_params_b(serve_cfg):
    import llama_refs
    return llama_refs.serve_weights(1)


def pytest_sessionfinish(session, exitstatus):
    """Lockcheck verdict (CI ``lockcheck_smoke``): when the run was
    driven with MXTPU_ANALYSIS_LOCKCHECK=1, every lock acquisition was
    recorded — fail the session if any observed order contradicts
    itself or the static lock graph (docs/lint.md §MXL203)."""
    if os.environ.get("MXTPU_ANALYSIS_LOCKCHECK") != "1":
        return
    from mxtpu.contrib.analysis import lockcheck
    if not lockcheck.installed():
        return
    bad = lockcheck.violations()
    if bad:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        for v in bad:
            tr.write_line(f"lockcheck: {v}", red=True)
        session.exitstatus = 1
