"""What the readers of the program's own instruments share: a
histogram's mean between the two scrapes, and a traced program's self
time by the ``jax.named_scope`` its operations came from.

The scope of an operation is not in the trace (the v5e trace names an
operation by its HLO instruction, ``fusion.13``); the program keeps,
per compiled program, ``{instruction: (scope path, rematerialised)}``
read from the executable's own text (``mxtpu.telemetry.programs()``).
The readers run in the process that compiled the programs, so they ask
it directly. A program without that catalog (an older commit) gives
``None`` everywhere here and the metric is left out.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, Optional, Tuple

UNSCOPED = ""


def hist_sum(obs: dict, name: str, part: str = "_sum") -> Optional[float]:
    """The change of histogram ``name``'s ``_sum`` (or ``_count``)
    between the two scrapes; None where the program has no such
    series."""
    s0, s1 = obs.get("scrape0"), obs.get("scrape1")
    if not s0 or not s1 or name + part not in s1:
        return None
    return s1[name + part] - s0.get(name + part, 0.0)


def hist_mean(obs: dict, name: str) -> Optional[float]:
    """Mean of histogram ``name`` over the observations made between
    the two scrapes."""
    n = hist_sum(obs, name, "_count")
    return hist_sum(obs, name) / n if n else None


def catalog(obs: dict) -> Dict[str, Dict[str, Tuple[str, bool]]]:
    """{module name: its instruction map}. ``obs["programs"]`` (the
    self-test's hand-made one) wins; else the program's own catalog,
    empty where the program has none."""
    progs = obs.get("programs")
    if progs is None:
        from mxtpu import telemetry
        read = getattr(telemetry, "programs", None)
        progs = read() if read is not None else {}
        if progs:
            # what reading the maps cost the run's set-up, all programs
            obs.setdefault("notes", {})["scope_maps_s"] = round(
                sum(p.parse_s for p in progs.values()), 4)
    out = {}
    for p in progs.values():
        module = p["module"] if isinstance(p, dict) else p.module
        scopes = p["scopes"] if isinstance(p, dict) else p.scopes
        if scopes:
            out[module] = scopes
    return out


def program_scopes(obs: dict, pattern_key: str) -> Optional[Dict[str, Any]]:
    """The most-run program matching the configuration's
    ``programs[pattern_key]`` on the first device, its operations'
    self time by top-level scope, and the device's busy time:
    ``{"program", "self_s", "by_scope": {scope: s}, "remat_s",
    "unmapped_s", "busy_s"}``; None without a trace, a pattern, such a
    program or its map. The model's scopes do not nest, so an
    operation's scope is the first of its path; ``""`` is under none
    (an instruction the map does not hold counts there too, and in
    ``unmapped_s``)."""
    cache = obs.setdefault("_program_scopes", {})   # readers share it
    if pattern_key not in cache:
        cache[pattern_key] = _program_scopes(obs, pattern_key)
    return cache[pattern_key]


def _program_scopes(obs: dict, pattern_key: str) -> Optional[Dict[str, Any]]:
    from trace_reduce import first_device, most_run
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    pat = obs["config"].get("programs", {}).get(pattern_key)
    if d is None or not pat or not d["ops"]:
        return None
    program = most_run(d["modules"], pat)
    scopes = catalog(obs).get(program.split("#")[0])
    if not program or scopes is None:
        return None
    by_scope: Dict[str, float] = defaultdict(float)
    remat = unmapped = total = 0.0
    for op in d["ops"]:
        if op["program"] != program:
            continue
        path, rematted = scopes.get(op["name"], (None, False))
        if path is None:
            unmapped += op["self"]
            path = UNSCOPED
        by_scope[path.split("/")[0]] += op["self"]
        remat += op["self"] if rematted else 0.0
        total += op["self"]
    if total <= 0:
        return None
    return {"program": program, "self_s": total,
            "by_scope": dict(by_scope), "remat_s": remat,
            "unmapped_s": unmapped, "busy_s": d["busy_s"]}


def decode_scope_share(obs: dict, scope: str) -> Optional[float]:
    """Self time of the decode program's operations under ``scope`` as
    a share (%) of the program's self time. The whole split goes into
    the line's notes, so the rows can be seen to add to 100."""
    got = program_scopes(obs, "decode")
    if got is None:
        return None
    obs.setdefault("notes", {})["decode_scope_shares"] = {
        k or "unscoped": round(100.0 * v / got["self_s"], 3)
        for k, v in sorted(got["by_scope"].items(), key=lambda kv: -kv[1])}
    obs["notes"]["decode_unmapped_share"] = round(
        100.0 * got["unmapped_s"] / got["self_s"], 3)
    return 100.0 * got["by_scope"].get(scope, 0.0) / got["self_s"]
