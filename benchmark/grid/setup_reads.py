"""What the readers of the program's own record of its set-up share:
the series as they stood when the window opened, the ``setup.*`` spans'
seconds, and the catalog's record of every program's build.

A serve driver scrapes ``/metrics`` at ``t_open`` (``obs["scrape0"]``):
everything in it happened before the window. A training process has no
gateway to scrape; its readers run in it, as ``train_dispatch_ms`` does,
and read the registry as it stands after the window, which is the
set-up's record only while ``compiles_in_window.train`` is 0 (nothing
was built once the window was open). A program without the series (an
older commit) gives ``None`` everywhere here and the metric is left
out.
"""
from __future__ import annotations

from typing import Dict, Optional

# what the line's note keeps of a program's build
BUILD_FIELDS = ("trace_s", "nested_traces", "nested_trace_s", "lower_s",
                "backend_s", "cache", "first_call_s", "custom_calls",
                "fast_mem_buffers", "fast_mem_bytes", "temp_bytes")
_SPAN = "span_setup_"
# a note's name and the series it reads, as it stood when the window
# opened
NOTED = {"import_seconds": "import_seconds",
         "compile_cache_entries": "compile_cache_entries",
         "compile_cache_bytes": "compile_cache_bytes",
         "setup_fetched_count": "program_fetched_total",
         "setup_nested_traces": "program_nested_traces_total"}


def parse_exposition(text: str) -> Dict[str, float]:
    """Prometheus text -> {series name: sum over its label sets}, the
    ``mxtpu_`` prefix taken off: what the serve drivers' ``scrape``
    makes of ``GET /metrics``."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0].removeprefix("mxtpu_")
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            pass
    return out


def before_window(obs: dict) -> Dict[str, float]:
    """The program's series when the window opened. The first reader to
    ask also leaves the set-up's anatomy in the line's notes."""
    if "_before_window" not in obs:
        series = obs.get("scrape0")
        if series is None:
            from mxtpu import telemetry
            series = parse_exposition(telemetry.prometheus())
        obs["_before_window"] = series
        _note(obs, series)
    return obs["_before_window"]


def total(obs: dict, name: str) -> Optional[float]:
    """Counter ``name``, all programs; None where the program has no
    such series."""
    return before_window(obs).get(name)


def span_seconds(obs: dict, phase: str) -> Optional[float]:
    """Seconds under the ``setup.<phase>`` spans."""
    ms = before_window(obs).get(f"{_SPAN}{phase}_ms_sum")
    return None if ms is None else ms / 1e3


def builds(obs: dict) -> Dict[str, dict]:
    """{watch name: its build}: ``obs["programs"]`` (the self-test's
    hand-made catalog) or the program's own, the rows that hold a
    build's fields. The catalog is read AFTER the window (the scrape
    sums a series over its programs, so no table can be made of it):
    :func:`builds_after_open` says whether it is still the set-up's."""
    progs = obs.get("programs")
    if progs is None:
        from mxtpu import telemetry
        read = getattr(telemetry, "programs", None)
        progs = read() if read is not None else {}
    out = {}
    for name, p in progs.items():
        row = p if isinstance(p, dict) else vars(p)
        if "trace_s" in row:
            out[name] = {k: _rounded(row.get(k)) for k in BUILD_FIELDS}
    return out


def builds_after_open(obs: dict) -> Optional[float]:
    """Executables compiled or fetched once the window was open (a
    serve cell: the second scrape less the first; a training cell: the
    driver's own count of backend events, ``obs["compiles"]``). While
    it is 0 the catalog read after the window is the set-up's record."""
    first, last = obs.get("scrape0"), obs.get("scrape1")
    if first is None or last is None:
        return obs.get("compiles")
    return sum(last.get(k, 0.0) - first.get(k, 0.0)
               for k in ("program_compiled_total", "program_fetched_total"))


def _rounded(v):
    return round(v, 4) if isinstance(v, float) else v


def _note(obs: dict, series: Dict[str, float]) -> None:
    notes = obs.setdefault("notes", {})
    spans = {k[len(_SPAN):-len("_ms_sum")]: round(v / 1e3, 4)
             for k, v in sorted(series.items())
             if k.startswith(_SPAN) and k.endswith("_ms_sum")}
    if spans:
        # a traced line has no end-to-end metrics: what the spans, the
        # ramp and ``setup_unspanned_s`` add to rides with them
        notes["setup_spans_s"] = spans
        notes["setup_s"] = obs["end_to_end"]["setup_s"]
    for note, name in NOTED.items():
        if name in series:
            notes[note] = series[name]
    table = builds(obs)
    if table:
        notes["program_builds"] = table
        after = builds_after_open(obs)
        if after is not None:
            notes["program_builds_after_open"] = after
