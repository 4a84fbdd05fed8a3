"""mxtpu.telemetry — unified runtime observability (docs/observability.md).

One process-wide, thread-safe layer with four pieces:

- **metrics registry** (labelled ``Counter``/``Gauge``/``Histogram``
  with fixed-bucket percentiles) — ``telemetry.counter("name").inc()``,
  exported as a Prometheus text dump (:func:`prometheus`) or a human
  table (:func:`summary`);
- **span tracing** — ``with telemetry.span("prefill", bucket=256):``
  emits chrome://tracing-compatible events alongside the XLA trace
  ``mx.profiler`` owns (host dispatch time here, device time there);
- **flight recorder** — a bounded ring of recent events that
  ``PreemptionGuard``/crash paths dump to disk (:func:`flight`);
- **recompile watcher** — every backend compilation is counted
  process-wide, and :func:`watch`-wrapped programs attribute each
  compile to its cache key, so an anomalous ``recompile_total`` points
  at the offending signature instead of a bisection session.
  :func:`watch_jit` also compiles the program under a stable module
  name, and :func:`programs` maps each compiled program's instructions
  back to the ``jax.named_scope`` they were traced under
  (``telemetry/scopes.py``), so a device trace can be read by scope.

Enabled by default; ``MXTPU_TELEMETRY=0`` turns every recording call
into a no-op (handles created while disabled never record — the knob
is read when a handle is created, keeping the hot path branch-free).
The instrument classes themselves (``telemetry.Histogram()`` etc.)
always work when constructed directly — subsystems use them for
private resettable stats regardless of the global knob.

Instrumented out of the box: ``mxtpu.serve.ServeEngine`` (queue/slots/
admission/latency/spans), the sharded train step + ``DevicePrefetcher``
+ ``Speedometer`` (step-time split), and the ``kvstore`` client/server
(retries, dedups, reconnects, snapshot timing, frame sizes).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Sequence

from ..base import env_bool
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       escape_label_value, interval_percentile,
                       BYTES_BUCKETS, LATENCY_MS_BUCKETS,
                       SECONDS_BUCKETS)
from .flight import (FlightRecorder, default_flight_path,
                     process_role, set_process_role)
from . import tracing as _tracing
from .tracing import (Span, clear_trace, current_depth, dump_trace,
                      trace_events)
from . import perfscope
from .perfscope import goodput_gauge, profile_program
from .scopes import Program, programs
from .watcher import WatchedFunction, describe_args, watch, watch_jit
from .watcher import install as install_compile_listener
from . import watcher as _watcher

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "FlightRecorder", "Span", "WatchedFunction", "TraceContext",
    "RegistryServer", "SLOTracker",
    "counter", "gauge", "histogram", "span", "span_factory", "instant",
    "setup_span", "setup_phase", "record_setup", "start_setup_record",
    "record_remat_plan",
    "registry", "flight", "enabled", "enable", "reset",
    "prometheus", "summary", "dump_trace", "trace_events",
    "clear_trace", "current_depth", "describe_args", "watch",
    "watch_jit", "Program", "programs",
    "install_compile_listener", "default_flight_path",
    "process_role", "set_process_role", "escape_label_value",
    "interval_percentile", "federate_text", "parse_prometheus",
    "distributed", "perfscope", "profile_program", "goodput_gauge",
    "LATENCY_MS_BUCKETS", "BYTES_BUCKETS", "SECONDS_BUCKETS",
]

class _GuardedFlight(FlightRecorder):
    """The process singleton: honors the MXTPU_TELEMETRY kill switch
    dynamically (unlike metric handles, flight callers hold the
    singleton long-term, so the check belongs at record time). A
    directly-constructed FlightRecorder is never gated."""

    def record(self, kind, name, **fields):
        if _enabled:
            super().record(kind, name, **fields)


_REGISTRY = MetricsRegistry()
_FLIGHT = _GuardedFlight()
_enabled = env_bool(
    "MXTPU_TELEMETRY", True,
    "Master switch for the runtime telemetry layer (metrics, spans, "
    "flight recorder). 0 disables all recording.")


def enabled() -> bool:
    return _enabled


def enable(on: bool = True) -> None:
    """Runtime override of MXTPU_TELEMETRY (tests; emergency off
    switch). Affects handles created AFTER the call."""
    global _enabled
    _enabled = bool(on)


def registry() -> MetricsRegistry:
    """The process-wide registry (always real — exporters read it even
    when recording is disabled)."""
    return _REGISTRY


def flight() -> FlightRecorder:
    return _FLIGHT


# -- no-op handles (returned while disabled) -------------------------------
class _Noop:
    def inc(self, amount: float = 1.0) -> None: pass
    def dec(self, amount: float = 1.0) -> None: pass
    def set(self, value: float) -> None: pass
    def observe(self, value: float) -> None: pass
    value = 0.0
    count = 0


_NOOP = _Noop()


class _NoopRegistry:
    def counter(self, *a, **k): return _NOOP
    def gauge(self, *a, **k): return _NOOP
    def histogram(self, *a, **k): return _NOOP


_NOOP_REGISTRY = _NoopRegistry()


def _metrics():
    """Registry for WRITERS: the real one when enabled, no-ops when
    not (instrumentation sites call this once at handle creation)."""
    return _REGISTRY if _enabled else _NOOP_REGISTRY


def counter(name: str, help: str = "", **labels):
    return _metrics().counter(name, help, **labels)


def gauge(name: str, help: str = "", **labels):
    return _metrics().gauge(name, help, **labels)


def histogram(name: str, help: str = "",
              buckets: Optional[Sequence[float]] = None, **labels):
    return _metrics().histogram(name, help, buckets=buckets, **labels)


def span(name: str, histogram_name: Optional[str] = None, **args):
    """A traced span. When telemetry is disabled this still returns a
    working ``Span`` timer but records nothing. ``histogram_name``
    additionally feeds the duration (ms) into that registry histogram;
    the span lands in the flight recorder and, while a profiler
    session is open, in its trace."""
    return span_factory(name, histogram_name)(**args)


def span_factory(name: str, histogram_name: Optional[str] = None,
                 flight: bool = True):
    """Pre-bind a span's registry histogram once and return a callable
    producing spans — the hot-path form (per decode step / train step,
    ``span()``'s per-call interning would take the registry lock every
    iteration). ``flight=False`` keeps the span out of the flight
    ring: a span entered every step would push the rare records the
    ring exists for (compiles, recompiles, anomalies) out of it within
    seconds."""
    if not _enabled:
        return lambda **args: Span(name, record=False, **args)
    h = histogram(f"span_{histogram_name or name}_ms".replace(".", "_"),
                  f"Span durations: {name}") \
        if histogram_name is not False else None
    ring = _FLIGHT if flight else None

    def make(**args):
        return Span(name, histogram=h, flight=ring, **args)
    return make


class _SetupSpan(Span):
    """A ``setup.<phase>`` span; one that no other ``setup.*`` span
    encloses also counts its seconds as named set-up time."""

    def __exit__(self, *exc) -> bool:
        out = super().__exit__(*exc)
        _setup_named(self.parent, self.duration_ms / 1e3)
        return out


def _setup_named(parent: Optional[str], seconds: float) -> None:
    if not (parent or "").startswith("setup."):
        counter("setup_spanned_seconds_total",
                "Seconds under the setup.* spans that no other "
                "setup.* span encloses: the set-up time the program "
                "can name").inc(seconds)


def _setup_histogram(phase: str):
    return histogram(f"span_setup_{phase}_ms",
                     f"Span durations: setup.{phase}")


def setup_span(phase: str, **args) -> Span:
    """``with telemetry.setup_span("state_alloc"):`` -- a span
    ``setup.<phase>`` around work done once before the first useful
    step (``docs/observability.md`` lists the phases). In the ring with
    its parent, in ``span_setup_<phase>_ms``, in the flight recorder."""
    if not _enabled:
        return Span("setup." + phase, record=False, **args)
    return _SetupSpan("setup." + phase, histogram=_setup_histogram(phase),
                      flight=_FLIGHT, **args)


def setup_phase(phase: str):
    """Decorator: every call of the function runs under
    ``setup_span(phase)`` (a constructor, a builder)."""
    def wrap(fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with setup_span(phase):
                return fn(*args, **kwargs)
        return spanned
    return wrap


def record_setup(phase: str, start_us: int, end_us: int,
                 parent: Optional[str] = None, **args) -> None:
    """A ``setup.<phase>`` span that is already over, on the ring's
    clock (``tracing.record_span``): the call that turned out to build
    a program, a compile phase jax clocked itself."""
    if not _enabled:
        return
    parent = parent or _tracing.current_span()
    _tracing.record_span("setup." + phase, start_us, end_us,
                         parent=parent, **args)
    _setup_histogram(phase).observe((end_us - start_us) / 1e3)
    _setup_named(parent, (end_us - start_us) / 1e6)


_setup_started = False


def start_setup_record(cache_dir: Optional[str] = None) -> None:
    """Start telling where this process's set-up goes; an entry point's
    first call (``runtime.use_compile_cache()`` makes it), once a
    process. Installs the compile listener before anything can compile;
    records ``setup.import`` (and the gauge ``import_seconds``) from the
    two stamps ``mxtpu/__init__.py`` took; STARTS THE BACKEND, under
    ``setup.backend_init`` (the process's first ``jax.devices()``: what
    configures platforms or ``jax.distributed`` comes before this
    call); and reads the size of jax's persistent cache directory once
    (``compile_cache_entries``, ``compile_cache_bytes``)."""
    global _setup_started
    install_compile_listener()
    if _setup_started or not _enabled:
        return
    _setup_started = True
    import jax
    import mxtpu
    t0, t1 = mxtpu._T_IMPORT // 1000, mxtpu._T_IMPORTED // 1000
    record_setup("import", t0, t1)
    gauge("import_seconds", "Seconds `import mxtpu` took in this process "
          "(the setup.import span)").set((t1 - t0) / 1e6)
    with setup_span("backend_init"):
        jax.devices()
    sizes = []
    try:
        if cache_dir:
            with os.scandir(cache_dir) as entries:
                sizes = [e.stat().st_size for e in entries if e.is_file()]
    except OSError:             # not made yet: jax makes it on a write
        pass
    gauge("compile_cache_entries", "Files in jax's persistent compilation "
          "cache directory when the process started").set(len(sizes))
    gauge("compile_cache_bytes", "Their bytes").set(sum(sizes))


def record_remat_plan(saved: Sequence[str], saved_bytes: int) -> None:
    """A model has decided, while a train step was traced, which named
    activations its checkpointed layers keep (``models/llama.py``
    ``remat_plan``): ``saved`` the names (none: every layer is
    recomputed whole), ``saved_bytes`` their bytes a device. Goes to the
    gauge ``train_remat_saved_bytes`` and onto the record of the program
    being built (``programs()[...].remat_plan``)."""
    gauge("train_remat_saved_bytes",
          "Bytes a device of the activations the checkpointed layers "
          "keep for the backward pass").set(saved_bytes)
    _watcher.note_remat_plan(tuple(saved), int(saved_bytes))


def instant(name: str, **args) -> None:
    """An instant trace event (no-op while disabled)."""
    if _enabled:
        _tracing.instant(name, **args)


def prometheus() -> str:
    return _REGISTRY.prometheus()


def summary() -> str:
    return _REGISTRY.summary()


def reset() -> None:
    """Zero metrics, clear trace events and the flight ring (test
    isolation). Handles held by instrumentation stay valid."""
    global _setup_started
    _REGISTRY.reset()
    clear_trace()
    _FLIGHT.clear()
    # perfscope's rolling windows + ledger entries are test-visible
    # state too (the cost catalog survives — program costs don't rot)
    perfscope.reset()
    _setup_started = False


# the distributed layer registers the tracing context provider at
# import; imported LAST — it reads this module's registry lazily
from . import distributed                                  # noqa: E402
from .distributed import (TraceContext, RegistryServer,    # noqa: E402
                          SLOTracker, federate_text,
                          parse_prometheus)
