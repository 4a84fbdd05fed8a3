"""Recompile watcher: turn silent XLA recompilation into a counted,
attributed runtime event.

Two hooks, independent and complementary:

1. **Global compile listener** (:func:`install`) — registers ``jax``
   monitoring listeners for the three phases of a build (trace,
   lowering to MLIR, backend compile or fetch) and the persistent
   cache's answers, so EVERY compilation in the process increments
   ``jax_compile_total`` and lands in the compile-seconds histogram +
   flight recorder, and every phase's seconds go to the program that
   was being called on the thread (``telemetry.programs()``, the
   ``program_*_total`` series, the ``setup.trace`` / ``setup.lower`` /
   ``setup.backend`` spans) or, outside every watched call, to
   ``others``. Cheap, process-wide, no per-call overhead.
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
from . import scopes as _scopes

__all__ = ["install", "watch", "watch_jit", "WatchedFunction",
           "describe_args"]

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PHASES = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
           _COMPILE_EVENT: "backend"}
# what the persistent cache says inside a backend event: asked, then
# found, or compiled and written; asked and neither is a program jax
# compiled under its thresholds (a second to compile) and did not write
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "unwritten",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss"}
_install_lock = threading.Lock()
_installed = False
_MAX_KEY_CHARS = 512
# per thread: ``current`` the WatchedFunction being called (or
# _CATALOGUING), ``open`` the phases jax has entered and not left,
# ``cache`` the cache's answer inside the open backend phase
_tls = threading.local()
_CATALOGUING = object()
_build_lock = threading.Lock()


_PHASE_HELP = {
    "trace": "Seconds tracing a program to a jaxpr (inclusive of the "
             "functions traced inside it)",
    "lower": "Seconds lowering a program's jaxpr to MLIR (a kernel's "
             "Mosaic lowering included)",
    "backend": "Seconds in the backend: compile, or fetch from the "
               "persistent cache"}


def _on_phase_open(event: str, value: float, **kw) -> None:
    # jax records a scalar (the start time) as it enters a phase
    if event in _PHASES:
        _tls.open = getattr(_tls, "open", 0) + 1


def _on_cache_event(event: str, **kw) -> None:
    answer = _CACHE_EVENTS.get(event)
    if answer is not None:
        _tls.cache = answer


def _on_phase(event: str, start: float, end: float,
              fun_name: str = "", **kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    try:
        _phase_closed(phase, start, end, fun_name)
    except Exception:       # a listener must never break jit
        pass


def _phase_closed(phase: str, start: float, end: float,
                  fun_name: str) -> None:
    from . import _metrics, flight as _fl, record_setup
    from .registry import SECONDS_BUCKETS as _SECONDS
    still_open = _tls.open = max(0, getattr(_tls, "open", 1) - 1)
    secs = end - start
    # resolve the registry PER EVENT (compiles are rare): capturing it
    # at install time would freeze the no-op registry forever if
    # telemetry was disabled then
    m = _metrics()
    cache = None
    if phase == "backend":
        cache, _tls.cache = getattr(_tls, "cache", None) or "off", None
        m.counter("jax_compile_total",
                  "Backend compilations observed process-wide "
                  "(jax monitoring listener)").inc()
        m.histogram("jax_compile_seconds", "Backend compile durations",
                    buckets=_SECONDS).observe(secs)
        _fl().record("compile", "backend_compile", dur_s=round(secs, 4))
    current = getattr(_tls, "current", None)
    if current is _CATALOGUING:
        # reading a built program back retraces it from jax's cache:
        # no build's seconds
        return
    name = current.name if current is not None else _scopes.OTHERS
    # tracing calls a program ``f``, lowering and the backend ``jit(f)``
    own = current is None or fun_name in (
        current.fun_name, f"jit({current.fun_name})")
    build = _scopes.building(name)
    nested = phase == "trace" and (still_open or not own)
    fetched = cache == "hit"
    with _build_lock:           # ``others`` is every thread's
        if nested:
            build.nested_traces += 1
            if still_open <= 1:     # the deeper ones lie inside these
                build.nested_trace_s += secs
        else:
            setattr(build, phase + "_s", getattr(build, phase + "_s") + secs)
        if cache is not None:
            build.fetched += fetched
            build.compiled += not fetched
            if own:
                build.cache = cache
    if nested:
        # a jnp function traced under the program's own trace (or under
        # its lowering: a kernel's body); inside ``trace_s`` already
        m.counter("program_nested_traces_total",
                  "Traces of other functions inside a program's build",
                  program=name).inc()
        return
    m.counter(f"program_{phase}_seconds_total", _PHASE_HELP[phase],
              program=name).inc(secs)
    if cache is not None:
        m.counter("program_fetched_total" if fetched
                  else "program_compiled_total",
                  "Executables the persistent cache held" if fetched
                  else "Executables the backend compiled (written to "
                  "the persistent cache or not)", program=name).inc()
    # on the ring's clock (perf_counter); jax stamps time.time()
    to_ring = time.perf_counter() - time.time()
    record_setup(phase, int((start + to_ring) * 1e6),
                 int((end + to_ring) * 1e6),
                 parent="setup.first_call" if current is not None else None,
                 program=name, fun_name=fun_name)


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
        _mon.register_scalar_listener(_on_phase_open)
        _mon.register_event_listener(_on_cache_event)
        _mon.register_event_time_span_listener(_on_phase)
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


def note_remat_plan(saved: tuple, saved_bytes: int) -> None:
    """The remat plan a model made while the calling thread's watched
    program was traced, onto that build's record (outside a watched
    call: nowhere)."""
    current = getattr(_tls, "current", None)
    if current is not None and current is not _CATALOGUING:
        build = _scopes.building(current.name)
        build.remat_plan, build.remat_saved_bytes = saved, saved_bytes


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
        # what jax's compile events call it
        self.fun_name = getattr(fn, "__name__", name)
        self.expected = expected
        self.compiles: List[str] = []       # cache key per compile
        if loop is not None:
            _perfscope.scope().set_loop(name, loop)

    def __call__(self, *args, **kwargs):
        fn = self._fn
        before = fn._cache_size()
        # the compile listener gives what jax builds inside this call
        # to this program
        _tls.current = self
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        finally:
            t1 = time.perf_counter()
            _tls.current = None
        after = fn._cache_size()
        if after > before:
            self._on_compile(args, kwargs, after, t0, t1)
        # perfscope step accounting: inter-dispatch gaps drive the
        # live MFU/MBU/goodput gauges + the step-anomaly detector
        _perfscope.scope().on_call(self.name, t0, t1)
        return out

    def _on_compile(self, args, kwargs, cache_size: int,
                    t0: float, t1: float) -> None:
        from . import _metrics, flight as _fl, record_setup, setup_span
        record_setup("first_call", int(t0 * 1e6), int(t1 * 1e6),
                     program=self.name)
        # a fresh compiled variant: catalog its XLA cost model and its
        # text (the lowering is still cached, so this is analysis, not
        # a second compile; profile_program never raises). Under a span:
        # what the instrument itself costs a set-up
        with setup_span("catalog", program=self.name):
            _tls.current = _CATALOGUING
            try:
                _perfscope.scope().profile_program(self._fn, self.name,
                                                   args, kwargs)
            finally:
                _tls.current = None
        _scopes.built(self.name, t1 - t0)
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
