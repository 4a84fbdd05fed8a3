"""A traced program's self time under a NESTED scope.
``program_reads.program_scopes`` gives a program's time by the FIRST
name of each operation's scope path (``ssm`` for ``ssm/ssm_state``);
this gives the time of the operations whose path holds ``scope`` at any
depth, from the same catalog (``program_reads.catalog``), and how many
times the program ran in the traced window."""
from __future__ import annotations

from typing import Optional, Tuple


def nested_scope_s(obs: dict, pattern_key: str,
                   scope: str) -> Optional[Tuple[float, int]]:
    """(self seconds of the operations under ``scope`` in the most-run
    program matching the configuration's ``programs[pattern_key]`` on
    the first device, runs of that program); None without a trace, a
    pattern, such a program or its map."""
    from program_reads import catalog
    from trace_reduce import first_device, most_run, program_of
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    pat = obs["config"].get("programs", {}).get(pattern_key)
    if d is None or not pat or not d["ops"]:
        return None
    program = most_run(d["modules"], pat)
    scopes = catalog(obs).get(program.split("#")[0]) if program else None
    if not scopes:
        return None
    took = sum(op["self"] for op in d["ops"] if op["program"] == program
               and scope in scopes.get(op["name"], ("", False))[0].split("/"))
    runs = sum(program_of(m["name"]) == program for m in d["modules"])
    return took, runs
