"""What a prefill adds to the gap it falls in: the median device
duration of one execution of a prefill program in the traced window,
of the costliest one where there are several (an engine that prefills
in chunks runs one program for a prompt's last chunk, which also seats
and samples, and another for the chunks before it). The prefill
programs are the modules matching the configuration's
``programs.prefill`` pattern other than the decode program, as
``prefill_dev_share`` finds them."""
import re
from statistics import median


def read(obs):
    from trace_reduce import first_device, most_run, program_of
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    progs = obs["config"].get("programs", {})
    if d is None or not progs.get("prefill"):
        return None
    decode = most_run(d["modules"], progs.get("decode", "$^"))
    durs = {}
    for m in d["modules"]:
        prog = program_of(m["name"])
        if prog != decode and re.search(progs["prefill"], prog):
            durs.setdefault(prog, []).append(m["t1"] - m["t0"])
    return 1e3 * max(map(median, durs.values())) if durs else None
