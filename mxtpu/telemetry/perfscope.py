"""Perfscope — live roofline attribution, HBM ledger, step anomalies.

Three bench rounds of flat MFU showed the repo can *measure* that it
is slow but cannot say *where*: the numbers that explain a slow step
(per-program FLOPs/bytes from XLA's cost model, peak HBM, slot-bank
waste) were computed inside ``bench.py`` and thrown away. This module
makes them an always-on runtime layer on the PR 5/PR 8 telemetry
substrate:

- **program cost catalog** — :func:`profile_program` reads XLA's
  ``cost_analysis()`` and ``memory_analysis()`` once per compiled
  variant of a watched program (``telemetry.watch`` calls it on every
  observed compile, so the train step, the fused step, and every serve
  program get it for free) and publishes ``mxtpu_program_flops``,
  ``mxtpu_program_bytes_accessed``, ``mxtpu_program_peak_hbm_bytes``,
  arithmetic intensity, and a roofline class (``compute_bound`` vs
  ``memory_bound`` at the device's FLOP/byte knee). Both analyses come
  from the EXECUTABLE, on every backend (libtpu has none for an
  uncompiled program): ``fn.lower`` after a call returns jax's cached
  lowering, which holds the executable that call just built, so
  ``.compile()`` on it builds nothing. An executable is one device's
  partition of the program, so the catalog's numbers are PER DEVICE
  and the gauges divide by one device's peak. AOT paths pass their
  ``Compiled`` to :func:`program_costs`.
- **live MFU / MBU** — :meth:`PerfScope.on_call` keeps a rolling
  window of inter-dispatch gaps per program. Dispatch itself is async
  (host time is microseconds), but the gap between consecutive
  dispatches of a steady loop tracks the device step time: the loop
  is paced by the previous step's readback. Catalog flops/bytes over
  the rolling mean gap give ``mxtpu_mfu{program}`` and
  ``mxtpu_hbm_bw_util{program}``. The ratio math lives in ONE helper
  pair (:func:`mfu` / :func:`hbm_bw_util`) that ``bench.py`` also
  calls, so offline and live MFU cannot disagree by construction.
- **HBM ledger** — :class:`HBMLedger` accounts device-resident bytes
  by category (params / optimizer / kv_page_pool / workspace),
  publishes ``mxtpu_hbm_ledger_bytes{category}`` +
  ``mxtpu_hbm_headroom_bytes``, and leaves an OOM-adjacent flight
  record when headroom first dips below
  ``MXTPU_TELEMETRY_PERF_HEADROOM_BYTES``.
- **step-anomaly detector** — per-program rolling median/MAD over the
  same gaps; a gap beyond ``median + k*MAD`` emits a ``perf.anomaly``
  instant, a flight record naming the program, and increments
  ``mxtpu_step_anomalies_total{program}``. Gaps longer than
  ``MXTPU_TELEMETRY_PERF_IDLE_S`` are treated as the loop being idle
  (a parked serve engine), not as a slow step: they reset the window
  instead of tripping the detector.

Goodput unification: :func:`goodput_gauge` is the ONE definition of
``mxtpu_goodput_ratio{loop=...}`` (the ``cancel_counter`` pattern) —
the elastic driver sets ``loop="elastic"`` from its committed-step
accounting, and programs registered with a loop (``watch(...,
loop="train"/"serve")``) get a step-pacing goodput (fraction of wall
the window spent at median pace) published automatically.

Everything here is exception-safe and honors the master
``MXTPU_TELEMETRY`` switch plus its own ``MXTPU_TELEMETRY_PERF`` knob:
a cost-analysis failure must never break a train or serve loop.
"""
from __future__ import annotations

import logging
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ..base import env_bool, env_float, env_int
from . import scopes as _scopes

__all__ = [
    "DeviceSpec", "ProgramCost", "PerfScope", "HBMLedger",
    "device_spec", "spec_for", "mfu", "hbm_bw_util", "roofline_class",
    "profile_program", "program_costs", "on_call", "scope", "catalog",
    "ledger", "goodput_gauge", "tree_bytes", "reset",
]

_log = logging.getLogger(__name__)

# -- knobs (registered in docs/env_var.md via the base helpers) ------------
_PERF_ON = env_bool(
    "MXTPU_TELEMETRY_PERF", True,
    "Perfscope layer (program cost catalog, live MFU/MBU, step-anomaly "
    "detector). 0 disables it while leaving the rest of telemetry on.")
_WINDOW = env_int(
    "MXTPU_TELEMETRY_PERF_WINDOW", 64,
    "Rolling window (steps) for per-program MFU/MBU/goodput gauges and "
    "the anomaly detector's median/MAD.")
_ANOMALY_K = env_float(
    "MXTPU_TELEMETRY_PERF_ANOMALY_K", 8.0,
    "Step-anomaly threshold: a step gap beyond median + k*MAD of the "
    "rolling window trips mxtpu_step_anomalies_total + a flight record.")
_MIN_SAMPLES = env_int(
    "MXTPU_TELEMETRY_PERF_MIN_SAMPLES", 8,
    "Gaps required in a program's window before the anomaly detector "
    "arms (median/MAD over fewer steps is noise).")
_IDLE_S = env_float(
    "MXTPU_TELEMETRY_PERF_IDLE_S", 2.0,
    "A dispatch gap longer than this is the loop being IDLE (parked "
    "serve engine between requests), not a slow step: the program's "
    "rolling window resets instead of flagging an anomaly.")
_PEAK_FLOPS = env_float(
    "MXTPU_TELEMETRY_PERF_PEAK_FLOPS", 0.0,
    "Override the device's peak FLOP/s for MFU/roofline math "
    "(0 = use the built-in table keyed on device_kind).")
_PEAK_BW = env_float(
    "MXTPU_TELEMETRY_PERF_PEAK_BW", 0.0,
    "Override the device's peak HBM bytes/s for MBU/roofline math "
    "(0 = built-in table).")
_HBM_BYTES = env_float(
    "MXTPU_TELEMETRY_PERF_HBM_BYTES", 0.0,
    "Override the per-device HBM capacity for the ledger's headroom "
    "gauge (0 = device.memory_stats() when available, else the "
    "built-in table).")
_HEADROOM_BYTES = env_float(
    "MXTPU_TELEMETRY_PERF_HEADROOM_BYTES", 0.0,
    "When hbm_headroom_bytes first drops below this, record an "
    "OOM-adjacent flight event with the full ledger breakdown "
    "(0 = disabled; set ~1e9 on real chips).")


# -- device roofline specs -------------------------------------------------
@dataclass(frozen=True)
class DeviceSpec:
    """Per-chip peaks used for MFU/MBU and the roofline knee. The bf16
    matmul peak is the MFU convention every published number uses."""
    kind: str
    peak_flops: float        # bf16 FLOP/s, one chip
    peak_bw: float           # HBM bytes/s, one chip
    hbm_bytes: float         # HBM capacity, one chip

    @property
    def knee(self) -> float:
        """FLOP/byte where the roofline turns: programs with lower
        arithmetic intensity are memory-bound on this chip."""
        return self.peak_flops / self.peak_bw


# matched by substring of jax's device_kind, first hit wins. TPU rows
# are the published per-chip peaks (Google Cloud TPU documentation).
# The CPU row is nominal — it only lets CPU CI classify programs
# deterministically; nothing measured on a chip may reach it.
_SPECS: Tuple[Tuple[Tuple[str, ...], DeviceSpec], ...] = (
    (("v6e", "trillium"), DeviceSpec("v6e", 918e12, 1640e9, 32e9)),
    (("v5p",), DeviceSpec("v5p", 459e12, 2765e9, 95e9)),
    (("v5e", "v5 lite", "v5litepod"), DeviceSpec("v5e", 197e12,
                                                 819e9, 16e9)),
    (("v4",), DeviceSpec("v4", 275e12, 1228e9, 32e9)),
    (("cpu",), DeviceSpec("cpu", 5e11, 5e10, 16e9)),
)


def spec_for(kind: str) -> DeviceSpec:
    """The roofline spec for a device_kind string (e.g. ``"v5e"`` for
    bench gates that model v5e serving while running on CPU). A kind
    the table does not hold raises: an MFU against someone else's peak
    is not a number."""
    k = str(kind).lower()
    for keys, spec in _SPECS:
        if any(key in k for key in keys):
            return spec
    raise KeyError(
        f"no roofline peaks for device_kind {kind!r}; add a row to "
        "mxtpu.telemetry.perfscope._SPECS with its source")


def _apply_overrides(spec: DeviceSpec) -> DeviceSpec:
    if not (_PEAK_FLOPS or _PEAK_BW or _HBM_BYTES):
        return spec
    return DeviceSpec(spec.kind,
                      _PEAK_FLOPS or spec.peak_flops,
                      _PEAK_BW or spec.peak_bw,
                      _HBM_BYTES or spec.hbm_bytes)


def device_spec() -> DeviceSpec:
    """The current process's device spec (first jax device), with the
    MXTPU_TELEMETRY_PERF_PEAK_* env overrides applied."""
    import jax
    return _apply_overrides(spec_for(jax.devices()[0].device_kind))


# -- the shared ratio helpers (bench.py + live gauges) ---------------------
def mfu(flops: float, seconds: float,
        peak_flops: Optional[float] = None) -> float:
    """Model FLOPs utilization: useful flops / (wall seconds x peak).
    THE one definition — ``bench.py`` passes its analytic flops and
    the v5e peak; the live gauges pass catalog flops and the local
    device peak. Pass ``peak_flops`` explicitly to pin the
    denominator (a gate record must not change meaning with the CI
    host's silicon)."""
    if seconds <= 0:
        return 0.0
    peak = device_spec().peak_flops if peak_flops is None else peak_flops
    return flops / seconds / peak if peak > 0 else 0.0


def hbm_bw_util(nbytes: float, seconds: float,
                peak_bw: Optional[float] = None) -> float:
    """Memory-bandwidth utilization: bytes accessed / (wall seconds x
    peak HBM bandwidth) — MBU, the serving-side twin of MFU."""
    if seconds <= 0:
        return 0.0
    peak = device_spec().peak_bw if peak_bw is None else peak_bw
    return nbytes / seconds / peak if peak > 0 else 0.0


def roofline_class(flops: float, bytes_accessed: float,
                   spec: Optional[DeviceSpec] = None) -> str:
    """``compute_bound`` iff arithmetic intensity (flops per byte
    accessed) is at or past the device's roofline knee."""
    sp = spec or device_spec()
    if bytes_accessed <= 0:
        return "compute_bound"
    return ("compute_bound" if flops / bytes_accessed >= sp.knee
            else "memory_bound")


# -- program cost catalog --------------------------------------------------
@dataclass
class ProgramCost:
    """One watched program's XLA cost-model summary, per device (latest
    compiled variant; ``variants`` counts how many signatures were
    seen)."""
    name: str
    flops: float
    bytes_accessed: float
    transcendentals: float = 0.0
    argument_bytes: Optional[float] = None
    output_bytes: Optional[float] = None
    temp_bytes: Optional[float] = None
    peak_hbm_bytes: Optional[float] = None
    variants: int = 1
    spec: DeviceSpec = field(default_factory=device_spec)

    @property
    def intensity(self) -> float:
        return (self.flops / self.bytes_accessed
                if self.bytes_accessed > 0 else float("inf"))

    @property
    def klass(self) -> str:
        return roofline_class(self.flops, self.bytes_accessed, self.spec)


def _extract_costs(obj) -> Tuple[float, float, float]:
    """flops / bytes accessed / transcendentals from either AOT shape
    of ``cost_analysis()``: a Compiled returns a list of per-module
    dicts (or one dict), a Lowered returns one flat dict — or, on
    libtpu, nothing: there only an executable has costs."""
    ca = obj.cost_analysis()
    if ca is None:
        raise ValueError(
            "this backend gives no cost analysis before compilation; "
            "pass the compiled program (lowered.compile())")
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return (float(ca.get("flops", 0.0) or 0.0),
            float(ca.get("bytes accessed", 0.0) or 0.0),
            float(ca.get("transcendentals", 0.0) or 0.0))


def _extract_memory(compiled) -> Dict[str, float]:
    """memory_analysis() fields by portable names; peak falls back to
    args+out+temp when the backend doesn't report it (CPU)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    out: Dict[str, float] = {}
    for src, dst in (("argument_size_in_bytes", "argument_bytes"),
                     ("output_size_in_bytes", "output_bytes"),
                     ("temp_size_in_bytes", "temp_bytes"),
                     ("peak_memory_in_bytes", "peak_hbm_bytes")):
        v = getattr(mem, src, None)
        if v is not None:
            out[dst] = float(v)
    if "peak_hbm_bytes" not in out and {
            "argument_bytes", "output_bytes", "temp_bytes"} <= out.keys():
        out["peak_hbm_bytes"] = (out["argument_bytes"]
                                 + out["output_bytes"]
                                 + out["temp_bytes"])
    return out


def program_costs(compiled, name: Optional[str] = None,
                  spec: Optional[DeviceSpec] = None) -> Dict[str, Any]:
    """Cost + memory summary of an AOT ``Lowered``/``Compiled`` object
    as one plain dict — the shared helper the bench gate records read
    instead of ad-hoc inline ``memory_analysis()`` calls. With
    ``name``, the result also enters the live catalog (so an AOT
    bench's programs appear in the same roofline table). ``spec``
    pins the roofline knee (bench's v5e-story gates run on CPU)."""
    flops, nbytes, trans = _extract_costs(compiled)
    mem = _extract_memory(compiled) if hasattr(
        compiled, "memory_analysis") else {}
    sp = spec or device_spec()
    out = {"flops": flops, "bytes_accessed": nbytes,
           "transcendentals": trans,
           "roofline": roofline_class(flops, nbytes, sp), **mem}
    if name is not None:
        scope().register_cost(ProgramCost(
            name=name, flops=flops, bytes_accessed=nbytes,
            transcendentals=trans, spec=sp,
            argument_bytes=mem.get("argument_bytes"),
            output_bytes=mem.get("output_bytes"),
            temp_bytes=mem.get("temp_bytes"),
            peak_hbm_bytes=mem.get("peak_hbm_bytes")))
    return out


def tree_bytes(tree: Any) -> int:
    """Total array bytes in a pytree (the ledger's accounting unit;
    sharded arrays count their GLOBAL logical bytes)."""
    import jax
    return int(sum(getattr(l, "nbytes", 0)
                   for l in jax.tree_util.tree_leaves(tree)))


# -- HBM ledger ------------------------------------------------------------
class HBMLedger:
    """Per-process device-memory accounting. Entries are keyed
    (category, name) and last-write-wins, so a re-built trainer or a
    restarted engine replaces its own entry instead of double
    counting. Publishes ``hbm_ledger_bytes{category}`` and
    ``hbm_headroom_bytes`` on every change; the first dip below the
    headroom knob leaves an OOM-adjacent flight record with the full
    breakdown (edge-triggered — an OOM post-mortem needs one record,
    not a ring full of them)."""

    def __init__(self, headroom_bytes: Optional[float] = None):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str], int] = {}
        self._low_latched = False
        self._headroom_knob = (_HEADROOM_BYTES if headroom_bytes is None
                               else float(headroom_bytes))

    def account(self, category: str, nbytes: int,
                name: str = "default") -> None:
        with self._lock:
            self._entries[(category, name)] = int(nbytes)
        self._publish()

    def account_tree(self, category: str, tree: Any,
                     name: str = "default") -> None:
        self.account(category, tree_bytes(tree), name=name)

    def release(self, category: str, name: str = "default") -> None:
        with self._lock:
            self._entries.pop((category, name), None)
        self._publish()

    def breakdown(self) -> Dict[str, int]:
        with self._lock:
            out: Dict[str, int] = {}
            for (cat, _), n in self._entries.items():
                out[cat] = out.get(cat, 0) + n
            return out

    def total(self) -> int:
        return sum(self.breakdown().values())

    def capacity(self) -> float:
        """Per-process HBM budget: the device's own bytes_limit when
        it reports one (TPU), else the spec table / env override."""
        try:
            import jax
            stats = jax.devices()[0].memory_stats()
            if stats and stats.get("bytes_limit"):
                return float(stats["bytes_limit"])
        except Exception:
            pass
        return device_spec().hbm_bytes

    def headroom(self) -> float:
        return self.capacity() - self.total()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._low_latched = False

    def _publish(self) -> None:
        try:
            from . import _metrics, flight as _fl
            m = _metrics()
            per_cat = self.breakdown()
            for cat, n in per_cat.items():
                m.gauge("hbm_ledger_bytes",
                        "Accounted device-resident bytes by category "
                        "(params/optimizer/kv_page_pool/workspace)",
                        category=cat).set(n)
            head = self.headroom()
            m.gauge("hbm_headroom_bytes",
                    "HBM capacity minus every accounted allocation — "
                    "how close this process is to OOM").set(head)
            with self._lock:
                trip = (self._headroom_knob > 0
                        and head < self._headroom_knob
                        and not self._low_latched)
                if trip:
                    self._low_latched = True
                elif head >= self._headroom_knob:
                    self._low_latched = False
            if trip:
                _fl().record(
                    "perf", "hbm_headroom_low",
                    headroom_bytes=int(head),
                    capacity_bytes=int(self.capacity()),
                    threshold_bytes=int(self._headroom_knob),
                    **{f"bytes_{c}": n for c, n in per_cat.items()})
        except Exception:        # accounting must never break training
            pass


# -- rolling per-program step accounting -----------------------------------
class _Window:
    __slots__ = ("gaps", "last_end", "loop", "warned")

    def __init__(self, maxlen: int):
        self.gaps: deque = deque(maxlen=maxlen)
        self.last_end: Optional[float] = None
        self.loop: Optional[str] = None
        self.warned = False


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    return s[len(s) // 2]


def goodput_gauge(loop: str):
    """``mxtpu_goodput_ratio{loop=...}`` — the ONE definition (the
    ``cancel_counter`` pattern): train, elastic, and serve goodput
    must scrape as one family, not three spellings."""
    from . import _metrics
    return _metrics().gauge(
        "goodput_ratio",
        "Useful fraction of wall time by loop (1.0 = every wall "
        "second was a committed step at nominal pace)", loop=loop)


class PerfScope:
    """The per-process attribution engine. The module-level singleton
    (:func:`scope`) is what ``telemetry.watch`` feeds; tests construct
    their own with tighter knobs. All public entry points swallow
    exceptions — perf attribution must never break the loop it
    measures."""

    def __init__(self, window: Optional[int] = None,
                 anomaly_k: Optional[float] = None,
                 min_samples: Optional[int] = None,
                 idle_s: Optional[float] = None,
                 spec: Optional[DeviceSpec] = None):
        self.window = int(window or _WINDOW)
        self.anomaly_k = float(_ANOMALY_K if anomaly_k is None
                               else anomaly_k)
        self.min_samples = int(_MIN_SAMPLES if min_samples is None
                               else min_samples)
        self.idle_s = float(_IDLE_S if idle_s is None else idle_s)
        self._spec = spec
        self.catalog: Dict[str, ProgramCost] = {}
        self._windows: Dict[str, _Window] = {}
        self._loops: Dict[str, str] = {}
        self._published_class: Dict[str, str] = {}
        self.ledger = HBMLedger()
        self._lock = threading.Lock()

    # the knob gate: handles are NOT captured at construction (unlike
    # metric handles) because tests flip telemetry.enable() per test
    def _on(self) -> bool:
        from . import enabled
        return _PERF_ON and enabled()

    def spec(self) -> DeviceSpec:
        return self._spec or device_spec()

    # -- catalog ----------------------------------------------------------
    def set_loop(self, program: str, loop: Optional[str]) -> None:
        if loop:
            with self._lock:
                self._loops[program] = loop

    def register_cost(self, cost: ProgramCost) -> None:
        with self._lock:
            prev = self.catalog.get(cost.name)
            if prev is not None:
                cost.variants = prev.variants + 1
            self.catalog[cost.name] = cost
        self._publish_cost(cost)

    def profile_program(self, fn_or_compiled, name: str,
                        args: tuple = (), kwargs: Optional[dict] = None
                        ) -> Optional[ProgramCost]:
        """Catalog one program from its executable. Accepts an AOT
        ``Compiled``, a ``Lowered`` (compiled here), or a jitted
        callable + the args of a call it has just served: ``fn.lower``
        re-traces from the tracing cache and returns the cached
        lowering, whose ``.compile()`` hands back the executable that
        call built — no second XLA compile. It is safe even when the
        args were just donated: lowering only reads shape/dtype/
        sharding metadata, which survives deletion."""
        if not self._on():
            return None
        try:
            obj = fn_or_compiled
            if not hasattr(obj, "cost_analysis"):
                obj = obj.lower(*args, **(kwargs or {}))
            if hasattr(obj, "compile"):
                obj = obj.compile()
            flops, nbytes, trans = _extract_costs(obj)
            mem = _extract_memory(obj)
            cost = ProgramCost(
                name=name, flops=flops, bytes_accessed=nbytes,
                transcendentals=trans, spec=self.spec(),
                argument_bytes=mem.get("argument_bytes"),
                output_bytes=mem.get("output_bytes"),
                temp_bytes=mem.get("temp_bytes"),
                peak_hbm_bytes=mem.get("peak_hbm_bytes"))
            self.register_cost(cost)
            # the same executable names its instructions' scopes, and
            # says what XLA built: Mosaic calls, fast-memory placement
            _scopes.register(name, obj, temp_bytes=mem.get("temp_bytes"))
            return cost
        except Exception as e:
            w = self._window(name)
            if not w.warned:
                w.warned = True
                _log.warning("perfscope: cost analysis failed for "
                             "%s (%r) — program stays uncataloged",
                             name, e)
            return None

    def _publish_cost(self, cost: ProgramCost) -> None:
        try:
            from . import _metrics
            m = _metrics()
            lbl = {"program": cost.name}
            m.gauge("program_flops",
                    "XLA cost-model FLOPs per execution of the "
                    "program (one device's partition)",
                    **lbl).set(cost.flops)
            m.gauge("program_bytes_accessed",
                    "XLA cost-model bytes accessed per execution",
                    **lbl).set(cost.bytes_accessed)
            if cost.peak_hbm_bytes is not None:
                m.gauge("program_peak_hbm_bytes",
                        "Peak HBM during one execution "
                        "(memory_analysis)", **lbl).set(
                            cost.peak_hbm_bytes)
            if cost.bytes_accessed > 0:
                m.gauge("program_arithmetic_intensity",
                        "FLOPs per byte accessed — compare against "
                        "the device knee", **lbl).set(cost.intensity)
            klass = cost.klass
            prev = self._published_class.get(cost.name)
            help_ = ("1 for the program's side of the device's "
                     "FLOP/byte knee")
            if prev is not None and prev != klass:
                m.gauge("program_roofline", help_, program=cost.name,
                        **{"class": prev}).set(0)
            m.gauge("program_roofline", help_, program=cost.name,
                    **{"class": klass}).set(1)
            self._published_class[cost.name] = klass
        except Exception:
            pass

    # -- live step accounting ---------------------------------------------
    def _window(self, name: str) -> _Window:
        with self._lock:
            w = self._windows.get(name)
            if w is None:
                w = self._windows[name] = _Window(self.window)
            return w

    def on_call(self, name: str, t_start: float, t_end: float) -> None:
        """One dispatch of a watched program: fold the inter-dispatch
        gap into the rolling window and refresh the program's MFU /
        MBU / goodput gauges + anomaly detector. Called on every
        train/serve step — must stay cheap and never raise."""
        if not self._on():
            return
        try:
            self._on_call(name, t_start, t_end)
        except Exception:
            pass

    def _on_call(self, name: str, t_start: float, t_end: float) -> None:
        w = self._window(name)
        last = w.last_end
        w.last_end = t_end
        if last is None:
            return
        gap = t_end - last
        if gap <= 0:
            return
        if gap > self.idle_s:
            w.gaps.clear()          # the loop was parked, not slow
            return
        from . import _metrics, flight as _fl, instant
        m = _metrics()
        m.counter("program_wall_ms_total",
                  "Wall time attributed to the program's dispatch "
                  "loop (sum of inter-dispatch gaps)",
                  program=name).inc(gap * 1e3)
        if len(w.gaps) >= self.min_samples:
            med = _median(list(w.gaps))
            mad = _median([abs(g - med) for g in w.gaps])
            # floor MAD at 2% of median: a perfectly steady window
            # would otherwise flag microsecond jitter
            thresh = med + self.anomaly_k * max(mad, 0.02 * med)
            if gap > thresh:
                m.counter("step_anomalies_total",
                          "Steps beyond median + k*MAD of the "
                          "program's rolling window",
                          program=name).inc()
                _fl().record("perf", "step_anomaly", program=name,
                             gap_ms=round(gap * 1e3, 3),
                             median_ms=round(med * 1e3, 3),
                             mad_ms=round(mad * 1e3, 3),
                             k=self.anomaly_k)
                instant("perf.anomaly", program=name,
                        gap_ms=round(gap * 1e3, 3))
        w.gaps.append(gap)
        self._refresh_gauges(name, w, m)

    def _refresh_gauges(self, name: str, w: _Window, m) -> None:
        if not w.gaps:
            return
        mean_gap = sum(w.gaps) / len(w.gaps)
        cost = self.catalog.get(name)
        if cost is not None and mean_gap > 0:
            sp = self.spec()
            # catalog costs are one device's partition, so is the peak
            m.gauge("mfu",
                    "Live model-FLOPs utilization over the rolling "
                    "window (catalog flops / mean dispatch gap / "
                    "device peak)", program=name).set(
                        mfu(cost.flops, mean_gap,
                            peak_flops=sp.peak_flops))
            m.gauge("hbm_bw_util",
                    "Live HBM-bandwidth utilization over the rolling "
                    "window (catalog bytes / mean dispatch gap / "
                    "device peak bandwidth)", program=name).set(
                        hbm_bw_util(cost.bytes_accessed, mean_gap,
                                    peak_bw=sp.peak_bw))
        loop = self._loops.get(name)
        if loop:
            med = _median(list(w.gaps))
            total = sum(w.gaps)
            if total > 0:
                goodput_gauge(loop).set(
                    min(1.0, med * len(w.gaps) / total))

    # -- lifecycle --------------------------------------------------------
    def reset(self) -> None:
        """Drop rolling windows + ledger entries (test isolation; the
        catalog survives — program costs don't rot)."""
        with self._lock:
            self._windows.clear()
        self.ledger.clear()


# -- module singleton ------------------------------------------------------
_scope: Optional[PerfScope] = None
_scope_lock = threading.Lock()


def scope() -> PerfScope:
    global _scope
    if _scope is None:
        with _scope_lock:
            if _scope is None:
                _scope = PerfScope()
    return _scope


def profile_program(fn_or_compiled, name: str, args: tuple = (),
                    kwargs: Optional[dict] = None
                    ) -> Optional[ProgramCost]:
    return scope().profile_program(fn_or_compiled, name, args, kwargs)


def on_call(name: str, t_start: float, t_end: float) -> None:
    scope().on_call(name, t_start, t_end)


def catalog() -> Dict[str, ProgramCost]:
    return dict(scope().catalog)


def ledger() -> HBMLedger:
    return scope().ledger


def reset() -> None:
    scope().reset()
