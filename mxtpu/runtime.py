"""mx.runtime — compiled-feature introspection (reference
``python/mxnet/runtime.py`` over ``src/libinfo.cc`` [path cites —
unverified]).

The reference reported build-time flags (USE_CUDA, USE_MKLDNN, ...);
here features reflect the live jax/XLA environment, probed once.
"""
from __future__ import annotations

import os
from typing import Dict

__all__ = ["Feature", "Features", "feature_list", "use_compile_cache"]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere that outlives
    the process, and return the directory in use. Entry points
    (``chip_smoke.py``, ``bench.py``, the benchmark's ``run.py``) call
    this before anything compiles; ``import mxtpu`` does not.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set — jax reads it itself,
    so nothing is touched. Otherwise the cache lives at a FIXED path
    in the checkout (``<repo>/.jax_cache``): the same from every
    process, so a second run of the same command hits. jax's default
    thresholds stay: programs that compile in under a second are not
    written.

    Being the first call of every entry point, it is also where a
    process's set-up starts to be told:
    ``telemetry.start_setup_record()``."""
    import jax
    from . import telemetry
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO, ".jax_cache"))
    cache_dir = jax.config.jax_compilation_cache_dir
    telemetry.start_setup_record(cache_dir)
    return cache_dir


class Feature:
    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _probe() -> Dict[str, bool]:
    import jax
    platforms = set()
    try:
        platforms = {d.platform for d in jax.devices()}
    except Exception:
        pass
    try:
        import tensorflow  # noqa: F401
        has_tf_codec = True
    except Exception:
        has_tf_codec = False
    return {
        "TPU": "tpu" in platforms,
        "CPU": True,
        "CUDA": "gpu" in platforms or "cuda" in platforms,
        "CUDNN": False,
        "NCCL": False,
        "MKLDNN": False,
        "OPENMP": True,
        "BLAS_OPEN": True,
        "X64": bool(jax.config.jax_enable_x64),
        "DIST_KVSTORE": True,        # jax.distributed backend
        "INT64_TENSOR_SIZE": bool(jax.config.jax_enable_x64),
        "SIGNAL_HANDLER": True,
        "PROFILER": True,
        "TUTORIALS_EXIST": False,
        "OPENCV": False,
        "IMAGE_CODEC": has_tf_codec,
        "F16C": False,
        "JEMALLOC": False,
    }


class Features(dict):
    """Dict of Feature (reference ``mx.runtime.Features``)."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            cls.instance.update(
                {k: Feature(k, v) for k, v in _probe().items()})
        return cls.instance

    def __repr__(self):
        return "[" + ", ".join(repr(v) for v in self.values()) + "]"

    def is_enabled(self, name: str) -> bool:
        feat = self.get(name.upper())
        return bool(feat and feat.enabled)


def feature_list():
    return list(Features().values())
