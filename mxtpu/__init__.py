"""mxtpu — a TPU-native deep-learning framework with MXNet's capabilities.

A ground-up rebuild of the Apache MXNet 1.x surface (reference:
yuantangliang/incubator-mxnet) on the JAX/XLA/Pallas stack:

- ``mx.nd`` imperative arrays  → jax.Array + async PJRT dispatch
- ``mx.autograd``              → tape over jax.vjp
- ``mx.gluon`` + hybridize()   → jax.jit whole-graph compilation
- ``mx.kv`` KVStore            → XLA collectives over the ICI mesh
- ``mx.sym`` Symbol            → lazy tracer lowering to the same ops

Typical use, unchanged from the reference except the context::

    import mxtpu as mx
    net.initialize(ctx=mx.tpu())
"""
import time as _time
_T_IMPORT = _time.perf_counter_ns()     # setup.import starts here

from . import base                                          # noqa: E402

# Dtype policy (TPU-native): 64-bit dtypes are demoted to 32-bit by default
# — float64 has no TPU hardware path and int64 indexing costs bandwidth.
# Set MXNET_ENABLE_X64=1 before import for full 64-bit support (CPU workflows,
# the reference's large-tensor mode; tests/conftest.py enables it).
if base.env_bool("MXNET_ENABLE_X64", False,
                 "Enable 64-bit dtypes (jax_enable_x64)."):
    import jax as _jax
    _jax.config.update("jax_enable_x64", True)

# Numeric sanitizer (SURVEY §5.2; VERDICT r2 #7): the NaiveEngine
# switch serializes dispatch but cannot see INSIDE a jitted program —
# this can. Every jitted computation is checked for NaNs on return and,
# on a hit, re-run op-by-op to name the producing primitive
# (FloatingPointError). Debug tool: disables jit caching benefits.
if base.env_bool("MXTPU_DEBUG_NANS", False,
                 "Abort on NaN inside jitted programs, with op "
                 "attribution (jax_debug_nans)."):
    import jax as _jax
    _jax.config.update("jax_debug_nans", True)

# Lockset sanitizer (docs/lint.md §MXL203): patch the threading lock
# factories BEFORE any mxtpu class constructs one, so every serve/
# fleet/kvstore lock records real acquisition orders for the mxlint
# lock-graph cross-check. Loaded by file path: the normal package
# route (mxtpu.contrib.analysis) imports back through mxtpu.contrib
# and would be circular this early; registering the canonical module
# name makes later `from mxtpu.contrib.analysis import lockcheck`
# resolve to this same instance.
if base.env_bool("MXTPU_ANALYSIS_LOCKCHECK", False,
                 "Record runtime lock-acquisition orders and fail on "
                 "contradictions with the static lock graph "
                 "(diagnostic; see docs/lint.md)."):
    import importlib.util as _ilu
    import os as _os
    import sys as _sys
    _lc_path = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                             "contrib", "analysis", "lockcheck.py")
    _lc_spec = _ilu.spec_from_file_location(
        "mxtpu.contrib.analysis.lockcheck", _lc_path)
    _lockcheck = _ilu.module_from_spec(_lc_spec)
    _sys.modules[_lc_spec.name] = _lockcheck
    _lc_spec.loader.exec_module(_lockcheck)
    _lockcheck.install()

from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from .ndarray import random
from . import autograd

__version__ = "0.1.0"


def __getattr__(name):
    # heavier subsystems load lazily to keep `import mxtpu` fast
    import importlib
    lazy = {"gluon", "optimizer", "metric", "initializer", "lr_scheduler",
            "callback", "kvstore", "io", "image", "symbol", "profiler",
            "test_utils", "util", "runtime", "recordio", "np", "npx",
            "sym", "model", "engine", "parallel", "models", "ops",
            "utils", "amp", "contrib", "rnn", "serde", "module", "mod",
            "monitor", "operator", "checkpoint", "native", "rtc",
            "visualization", "viz", "serve", "telemetry"}
    if name in lazy:
        mod = {"sym": "mxtpu.symbol", "np": "mxtpu.numpy",
               "npx": "mxtpu.numpy_extension",
               "rnn": "mxtpu.gluon.rnn",
               "mod": "mxtpu.module",
               "viz": "mxtpu.visualization"}.get(name, f"mxtpu.{name}")
        try:
            m = importlib.import_module(mod)
        except ModuleNotFoundError as e:
            raise AttributeError(
                f"module 'mxtpu' has no attribute {name!r}") from e
        globals()[name] = m
        return m
    if name == "kv":
        m = importlib.import_module("mxtpu.kvstore")
        globals()["kv"] = m
        return m
    raise AttributeError(f"module 'mxtpu' has no attribute {name!r}")


_T_IMPORTED = _time.perf_counter_ns()   # and ends here: telemetry's
# ``start_setup_record`` makes the span of the two stamps
