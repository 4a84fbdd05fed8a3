"""From the profiler's trace to what the per-layer readers read, in
two steps that can be checked apart.

Step 1, :func:`events_from_xplane`: the ``.xplane.pb`` file -> a flat
list of events ``{"dev", "kind", "name", "t0", "t1"}`` with times in
seconds. ``kind`` is ``module`` (one execution of a compiled program,
the device's "XLA Modules" line; its name keeps the program's id,
because every jit of a ``functools.partial`` is called
``jit__unknown``), ``op`` (one operation of it, the "XLA Ops" line,
under the name of its HLO instruction: a Pallas kernel's is the
kernel's own name), ``async`` (an operation in flight from its
``-start`` to its ``-done``, the "Async XLA Ops" line: copies, slices
and, on the 2x2, the collective-permutes FSDP's gathers are made of) or
``marker`` (the window). The v5e's trace carries
no framework scope on an operation, so a ``jax.named_scope`` is not
visible here; kernels are found by their instruction names.

Step 2, :func:`reduce`: the event list -> per device the busy time
(the union of its operation intervals), every operation's self time
(its duration less the operations nested in it: a ``while`` holds its
body), the program each operation ran in, and the idle gaps named by
the programs on either side.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import tempfile
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

# the traced part of a window is wrapped in a TraceAnnotation of this
# name; it is the window on the trace's own clock
MARKER = "grid_window"
# how much of a traced run's window the profiler sees (at most half)
TRACE_S = 4.0


@contextlib.contextmanager
def profiled():
    """The JAX profiler around the body, Python tracing off (it would
    slow the host it measures), the body wrapped in the marker. Yields
    the directory that :func:`collect` then reduces and removes."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix="grid_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(MARKER):
            yield trace_dir
    finally:
        jax.profiler.stop_trace()


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def events_from_xplane(path: str) -> List[Dict[str, Any]]:
    """Step 1. Device planes are ``/device:TPU:<n>``. Where there is
    none (the CPU rehearsal) the host's XLA client threads, whose
    events carry an ``hlo_op``, stand in as device 0, so that the same
    code runs end to end off the chip; a number from there is never
    reported as a device metric."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: List[Dict[str, Any]] = []
    planes = list(data.planes)
    device = [p for p in planes if re.match(r"/device:TPU:\d+$", p.name)]
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER:
                    t0 = ev.start_ns * 1e-9
                    out.append({"dev": -1, "kind": "marker",
                                "name": MARKER, "t0": t0,
                                "t1": t0 + ev.duration_ns * 1e-9})
    for plane in device:
        dev = int(plane.name.rsplit(":", 1)[1])
        for line in plane.lines:
            kind = {"XLA Ops": "op", "XLA Modules": "module",
                    "Async XLA Ops": "async"}.get(line.name)
            if kind is None:
                continue
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                out.append({"dev": dev, "kind": kind,
                            "name": short_name(ev.name), "t0": t0,
                            "t1": t0 + ev.duration_ns * 1e-9})
    if device:
        return out
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" not in stats:
                    continue
                t0 = ev.start_ns * 1e-9
                out.append({"dev": 0, "kind": "op", "name": ev.name,
                            "t0": t0,
                            "t1": t0 + ev.duration_ns * 1e-9})
    return out


def collect(trace_dir: str) -> Dict[str, Any]:
    """Both steps on the profiler's directory, which is then removed.
    With ``GRID_TRACE_DUMP=<dir>`` in the environment (for reading a
    trace by hand; the driver of the benchmark never sets it) the
    trace file and :func:`describe`'s account of it are kept there."""
    import json
    import shutil
    path = find_xplane(trace_dir)
    events = events_from_xplane(path) if path else []
    keep = os.environ.get("GRID_TRACE_DUMP")
    if keep and path:
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, "describe.json"), "w") as f:
            json.dump(describe(path), f, indent=1)
        shutil.copy(path, os.path.join(keep, "trace.xplane.pb"))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduce(events)


def short_name(hlo: str) -> str:
    """An operation event is named by its whole HLO instruction,
    ``%fusion.3 = bf16[..] fusion(..)``: keep ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_of(module_name: str) -> str:
    """``jit__unknown(7348267849827546891)`` -> ``jit__unknown#6891``:
    the id tells programs of one name apart; four digits are enough to
    read."""
    m = re.match(r"(.*)\((\d+)\)$", module_name)
    return f"{m.group(1)}#{m.group(2)[-4:]}" if m else module_name


def most_run(modules: List[Dict[str, Any]], pattern: str) -> str:
    """The program matching ``pattern`` that ran most often: among a
    serving engine's programs that is the decode step (a request is
    one prefill and tens of steps), whatever their names."""
    count: Dict[str, int] = defaultdict(int)
    for m in modules:
        p = program_of(m["name"])
        if re.search(pattern, p):
            count[p] += 1
    return max(count, key=count.get) if count else ""


def _union(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Step 2. Returns ``{"window_s", "devices": {dev: {"busy_s",
    "ops": [...], "modules": [...], "gaps": [...], "async": [...]}}}``. An op gains
    ``self`` (seconds) and ``program``; a gap is ``{"t0", "t1",
    "before", "after", "inside"}`` with the programs that ended before
    and started after it (``inside``: between two operations of one
    execution). The window is the ``grid_window`` marker where
    the events hold one (events are cut to it), else the events'
    extent."""
    by_dev: Dict[int, Dict[str, list]] = defaultdict(
        lambda: {"op": [], "module": [], "async": []})
    marker = next((e for e in events if e["kind"] == "marker"), None)
    events = [e for e in events if e["kind"] != "marker"]
    window_s = None
    if marker is not None:
        # the window is the marker; what sticks out of it is cut off
        w0, w1 = marker["t0"], marker["t1"]
        events = [dict(e, t0=max(e["t0"], w0), t1=min(e["t1"], w1))
                  for e in events if e["t1"] > w0 and e["t0"] < w1]
        window_s = w1 - w0
    elif events:
        window_s = (max(e["t1"] for e in events)
                    - min(e["t0"] for e in events))
    for ev in events:
        by_dev[ev["dev"]][ev["kind"]].append(ev)
    devices = {}
    for dev, kinds in sorted(by_dev.items()):
        mods = sorted(kinds["module"], key=lambda e: e["t0"])
        starts = [m["t0"] for m in mods]
        ops = sorted(kinds["op"], key=lambda e: (e["t0"], -e["t1"]))
        stack: List[Dict[str, Any]] = []
        for op in ops:
            op["self"] = op["t1"] - op["t0"]
            while stack and stack[-1]["t1"] <= op["t0"]:
                stack.pop()
            if stack:
                stack[-1]["self"] -= op["t1"] - op["t0"]
            stack.append(op)
            i = bisect.bisect_right(starts, op["t0"]) - 1
            op["program"] = (program_of(mods[i]["name"])
                             if i >= 0 and op["t0"] < mods[i]["t1"]
                             else "")
        busy = _union([(e["t0"], e["t1"]) for e in (ops or mods)])
        # busy time is what runs; what is only in flight is not in it
        gaps = []
        ends = sorted(mods, key=lambda e: e["t1"])
        end_times = [m["t1"] for m in ends]
        for (_, a), (b, _) in zip(busy, busy[1:]):
            k = bisect.bisect_right(starts, a) - 1
            if k >= 0 and b <= mods[k]["t1"]:
                # between two operations of one execution
                inside = program_of(mods[k]["name"])
                gaps.append({"t0": a, "t1": b, "before": inside,
                             "after": inside, "inside": True})
                continue
            i = bisect.bisect_right(end_times, a + 1e-9) - 1
            j = bisect.bisect_left(starts, b - 1e-9)
            gaps.append({
                "t0": a, "t1": b, "inside": False,
                "before": program_of(ends[i]["name"]) if i >= 0 else "",
                "after": (program_of(mods[j]["name"])
                          if j < len(mods) else "")})
        devices[dev] = {"busy_s": sum(b - a for a, b in busy),
                        "ops": ops, "modules": mods, "gaps": gaps,
                        "async": kinds["async"]}
    return {"window_s": window_s, "devices": devices}


def covered_s(events: List[Dict[str, Any]]) -> float:
    """Seconds covered by at least one of ``events``."""
    return sum(b - a for a, b in _union([(e["t0"], e["t1"])
                                         for e in events]))


def first_device(reduced: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    devs = reduced["devices"]
    return devs[min(devs)] if devs else None


def busy_s(reduced: Dict[str, Any]) -> float:
    """Busy seconds averaged over the devices in the trace."""
    devs = reduced["devices"].values()
    return sum(d["busy_s"] for d in devs) / len(devs) if devs else 0.0


def breakdown(reduced: Dict[str, Any], top: int = 10) -> Dict[str, list]:
    """The ten operations with most self time on the first device,
    and its idle time by the programs on either side of the gap (ten
    largest sums; the name carries how many gaps and the longest)."""
    d = first_device(reduced)
    if d is None:
        return {"device_ops": [], "idle_gaps": []}
    by_op: Dict[str, float] = defaultdict(float)
    for op in d["ops"]:
        by_op[f"{op['program']}:{op['name']}"] += op["self"]
    by_gap: Dict[str, List[float]] = defaultdict(list)
    for g in d["gaps"]:
        name = (f"inside {g['before']}" if g.get("inside")
                else f"{g['before']}>{g['after']}")
        by_gap[name].append(g["t1"] - g["t0"])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(by_gap.items(), key=lambda kv: -sum(kv[1]))[:top]
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[f"{k} n={len(v)} max_ms={1e3 * max(v):.3f}",
                       sum(v)] for k, v in gaps]}


def describe(path: str, per_line: int = 6) -> Dict[str, Any]:
    """What a trace file holds, for reading one by hand: planes, lines,
    event counts and the first events of each line with their stats."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {"n": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "dur_ns": e.duration_ns,
                 "stats": {k: str(v)[:300] for k, v in e.stats}}
                for e in evs[:per_line]]}
        out[plane.name] = lines
    return out
