"""Device idle gaps named by what the host was doing: beside
``trace_reduce`` (which names a gap by the programs on either side of
it), for a program whose spans are in the profiler's trace.

The program's ``telemetry.Span`` is also a
``jax.profiler.TraceAnnotation``, so a traced run's xplane file holds
its spans (``serve.sweep_pick``, ``serve.admit``, ``serve.prefill``,
``serve.decode_step``, ``serve.readback``, ``serve.emit``,
``train.step_dispatch``) in the host plane, on the device lines' clock.
:func:`host_spans` reads them; :func:`name_gaps` cuts every idle gap of
a device at the span boundaries of the loop thread (the engine's or the
trainer's: the thread that holds most of those spans) and gives each
piece to the innermost span open over it, or to none.

Nothing in ``run.py`` calls this yet: it is for reading a trace kept
with ``GRID_TRACE_DUMP`` (``python benchmark/grid/span_reduce.py
<trace.xplane.pb>`` prints the table).
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional

# the program's span names: <layer>.<phase>
SPAN = re.compile(r"(serve|train|gateway|kvstore)\.[\w.]+$")
NONE = "(no span)"


def host_spans(path: str, pattern: "re.Pattern" = SPAN
               ) -> List[Dict[str, Any]]:
    """The host planes' events whose names match ``pattern``, as
    ``{"name", "thread", "t0", "t1"}`` with times in seconds on the
    trace's clock (the one ``trace_reduce.events_from_xplane`` uses)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            # threads share names (python3): the line's place tells
            # them apart
            thread = f"{line.name}#{i}"
            for ev in line.events:
                if pattern.match(ev.name):
                    t0 = ev.start_ns * 1e-9
                    out.append({"name": ev.name, "thread": thread,
                                "t0": t0,
                                "t1": t0 + ev.duration_ns * 1e-9})
    return out


def loop_thread(spans: List[Dict[str, Any]]) -> Optional[str]:
    """The thread that holds most spans: the engine's loop (five phase
    spans a step) or the trainer's."""
    count: Dict[str, int] = defaultdict(int)
    for s in spans:
        count[s["thread"]] += 1
    return max(count, key=count.get) if count else None


def name_gaps(spans: List[Dict[str, Any]], gaps: List[Dict[str, Any]],
              thread: Optional[str] = None) -> List[Dict[str, Any]]:
    """Each gap (``{"t0", "t1", ...}``, as ``trace_reduce.reduce``
    lists them) with ``parts``: seconds of it under each innermost
    span open on ``thread`` (default: :func:`loop_thread`), ``NONE``
    for the part under no span; and ``span``: the name holding most of
    it."""
    thread = thread or loop_thread(spans)
    mine = sorted((s for s in spans if s["thread"] == thread),
                  key=lambda s: s["t0"])
    out = []
    for g in gaps:
        over = [s for s in mine if s["t1"] > g["t0"] and s["t0"] < g["t1"]]
        cuts = sorted({g["t0"], g["t1"]}
                      | {t for s in over for t in (s["t0"], s["t1"])
                         if g["t0"] < t < g["t1"]})
        parts: Dict[str, float] = defaultdict(float)
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            # innermost = the latest to open among those open here
            open_ = [s for s in over if s["t0"] <= mid < s["t1"]]
            name = max(open_, key=lambda s: s["t0"])["name"] \
                if open_ else NONE
            parts[name] += b - a
        out.append(dict(g, parts=dict(parts),
                        span=max(parts, key=parts.get) if parts else NONE))
    return out


def table(named: List[Dict[str, Any]]) -> List[List[Any]]:
    """Idle seconds by span, largest first: ``[name, seconds, gaps it
    holds most of, longest such gap in ms]``."""
    secs: Dict[str, float] = defaultdict(float)
    held: Dict[str, List[float]] = defaultdict(list)
    for g in named:
        for name, s in g["parts"].items():
            secs[name] += s
        held[g["span"]].append(g["t1"] - g["t0"])
    return [[name, s, len(held[name]),
             1e3 * max(held[name], default=0.0)]
            for name, s in sorted(secs.items(), key=lambda kv: -kv[1])]


def gaps_by_span(path: str, dev: Optional[int] = None,
                 inside: bool = False) -> Dict[str, Any]:
    """The whole reduction on one trace file: the idle gaps of device
    ``dev`` (default: the first) between executions (``inside``: also
    those between two operations of one execution), named."""
    import trace_reduce
    reduced = trace_reduce.reduce(trace_reduce.events_from_xplane(path))
    devs = reduced["devices"]
    d = devs[min(devs) if dev is None else dev] if devs else None
    spans = host_spans(path)
    gaps = [g for g in (d["gaps"] if d else [])
            if inside or not g.get("inside")]
    named = name_gaps(spans, gaps)
    return {"thread": loop_thread(spans), "n_spans": len(spans),
            "window_s": reduced["window_s"],
            "idle_s": sum(g["t1"] - g["t0"] for g in gaps),
            "gaps": named, "table": table(named)}


if __name__ == "__main__":
    got = gaps_by_span(sys.argv[1], inside="--inside" in sys.argv)
    print(f"loop thread {got['thread']!r}, {got['n_spans']} spans, "
          f"{len(got['gaps'])} gaps, idle {1e3 * got['idle_s']:.3f} ms "
          f"of {got['window_s']} s")
    for name, s, n, longest in got["table"]:
        print(f"{1e3 * s:10.3f} ms  {name:24s} holds most of {n} gaps"
              f" (longest {longest:.3f} ms)")
