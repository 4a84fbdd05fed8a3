"""Recompile watcher: turn silent XLA recompilation into a counted,
attributed runtime event.

Two hooks, independent and complementary:

1. **Global compile listener** (:func:`install`) — registers a
   ``jax`` monitoring listener for backend-compile durations, so EVERY
   compilation in the process increments ``jax_compile_total`` and
   lands in the compile-seconds histogram + flight recorder. Cheap,
   process-wide, no per-call overhead.
2. **Per-program watcher** (:func:`watch`) — wraps one jitted callable
   and checks its jit-cache size around each call (the same
   ``_cache_size()`` counter the serve churn test gates on). When the
   cache grows, the call's abstract signature — shapes, dtypes and
   shardings of every argument leaf — is recorded as the *cache key*
   that caused the compile. Growth beyond ``expected`` increments
   ``recompile_total{fn=...}`` with the offending key in the flight
   recorder: the trimmed-vs-padded ``PartitionSpec`` class of bug
   (PR 4, found by bisection) now surfaces at runtime as an anomalous
   counter whose recorded keys differ only in their spec strings.

``watch`` deliberately refuses a callable without ``_cache_size`` —
a silent no-op watcher would make the no-retrace contract vacuously
true exactly when a retrace bug could hide (same policy as
``ServeEngine.compile_count``).
"""
from __future__ import annotations

import functools
import logging
import threading
import time
from typing import Any, Callable, List, Optional

from . import perfscope as _perfscope

__all__ = ["install", "watch", "watch_jit", "WatchedFunction",
           "describe_args"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_install_lock = threading.Lock()
_installed = False
_MAX_KEY_CHARS = 512


def install() -> bool:
    """Register the process-wide compile listener (idempotent).
    Returns True once the listener is active."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        # the public jax.monitoring API; if jax ever drops it this
        # import raises — a listener that silently counts nothing
        # would make "zero compiles in the window" trivially true
        import jax.monitoring as _mon
        from . import _metrics, flight as _fl
        from .registry import SECONDS_BUCKETS as _SECONDS

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event != _COMPILE_EVENT:
                return
            try:
                # resolve the registry PER EVENT (compiles are rare):
                # capturing it at install time would freeze the no-op
                # registry forever if telemetry was disabled then
                m = _metrics()
                m.counter("jax_compile_total",
                          "Backend compilations observed process-wide "
                          "(jax monitoring listener)").inc()
                m.histogram("jax_compile_seconds",
                            "Backend compile durations",
                            buckets=_SECONDS).observe(duration)
                _fl().record("compile", "backend_compile",
                             dur_s=round(duration, 4))
            except Exception:       # a listener must never break jit
                pass

        _mon.register_event_duration_secs_listener(_on_duration)
        _installed = True
        return True


def _leaf_desc(leaf: Any) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        r = repr(leaf)
        return r if len(r) <= 32 else r[:29] + "..."
    desc = f"{getattr(dtype, 'name', dtype)}{list(shape)}"
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is not None:
        desc += f"@{spec}"
    return desc


def describe_args(args: tuple, kwargs: dict) -> str:
    """A stable human-readable cache key for a jit call: every leaf's
    shape/dtype (+ sharding spec when placed) in tree order. Two calls
    that hit different jit-cache entries describe differently — shape,
    dtype, OR sharding-spec drift all show up in the string."""
    import jax
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    key = "(" + ", ".join(_leaf_desc(l) for l in leaves) + ")"
    if len(key) > _MAX_KEY_CHARS:
        import hashlib
        h = hashlib.sha1(key.encode()).hexdigest()[:12]
        key = key[:_MAX_KEY_CHARS] + f"...#{h}"
    return key


class WatchedFunction:
    """A jitted callable with compile attribution. Transparent:
    attributes (``_cache_size``, ``lower``, ...) delegate to the
    wrapped function, so existing jit-cache gates keep working."""

    def __init__(self, fn: Callable, name: str,
                 expected: Optional[int] = 1,
                 loop: Optional[str] = None):
        if not hasattr(fn, "_cache_size"):
            raise TypeError(
                f"watch() needs a jitted callable with _cache_size "
                f"(got {type(fn).__name__}) — a watcher that cannot "
                "see the cache cannot attribute recompiles")
        self._fn = fn
        self.name = name
        self.expected = expected
        self.compiles: List[str] = []       # cache key per compile
        if loop is not None:
            _perfscope.scope().set_loop(name, loop)

    def __call__(self, *args, **kwargs):
        fn = self._fn
        before = fn._cache_size()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        t1 = time.perf_counter()
        after = fn._cache_size()
        if after > before:
            self._on_compile(args, kwargs, after)
        # perfscope step accounting: inter-dispatch gaps drive the
        # live MFU/MBU/goodput gauges + the step-anomaly detector
        _perfscope.scope().on_call(self.name, t0, t1)
        return out

    def _on_compile(self, args, kwargs, cache_size: int) -> None:
        from . import _metrics, flight as _fl
        # a fresh compiled variant: catalog its XLA cost model (the
        # lowering is still cached, so this is analysis, not a second
        # compile; profile_program never raises)
        _perfscope.scope().profile_program(self._fn, self.name,
                                           args, kwargs)
        key = describe_args(args, kwargs)
        self.compiles.append(key)
        m = _metrics()
        m.counter("compile_events_total",
                  "Compilations per watched program", fn=self.name).inc()
        if self.expected is not None and cache_size > self.expected:
            m.counter(
                "recompile_total",
                "Watched-program compilations beyond the expected "
                "count — an anomaly (shape churn, spec mismatch)",
                fn=self.name).inc()
            _fl().record("recompile", self.name, key=key,
                         cache_size=cache_size, expected=self.expected)
            logging.getLogger(__name__).warning(
                "telemetry: unexpected recompile of %s (cache size %d "
                "> expected %d) for signature %s", self.name,
                cache_size, self.expected, key)
        else:
            _fl().record("compile", self.name, key=key,
                         cache_size=cache_size)

    def __getattr__(self, name: str):
        return getattr(self.__dict__["_fn"], name)


def watch(fn: Callable, name: str,
          expected: Optional[int] = 1,
          loop: Optional[str] = None) -> WatchedFunction:
    """Wrap a jitted callable with compile attribution. ``expected``
    is the compile budget (cache entries) this program should ever
    need — 1 for a fixed-shape program; None disables the anomaly
    counter (compiles are still attributed). ``loop`` tags the
    program for perfscope's ``goodput_ratio{loop=...}`` gauge
    (``"train"`` / ``"serve"``)."""
    return WatchedFunction(fn, name, expected=expected, loop=loop)


def watch_jit(fn: Callable, name: str, program: str,
              expected: Optional[int] = 1, loop: Optional[str] = None,
              **jit_kwargs) -> WatchedFunction:
    """``watch(jax.jit(fn, **jit_kwargs), name, ...)`` with the compiled
    module called ``jit_<program>``: a jit of a ``functools.partial`` or
    a lambda is ``jit__unknown`` / ``jit__lambda`` in every trace and
    compiler dump, so each watched program is compiled under a stable
    name taken from the model function it runs, unique per compiled
    variant (``prefill_slot_paged_b256``). ``name`` stays the watch
    name operators know (``serve_prefill_b256``);
    ``telemetry.programs()`` holds both. The fresh partial also gives
    every caller a jit cache of its own."""
    import jax
    named = functools.partial(fn)
    named.__name__ = named.__qualname__ = program
    return WatchedFunction(jax.jit(named, **jit_kwargs), name,
                           expected=expected, loop=loop)
