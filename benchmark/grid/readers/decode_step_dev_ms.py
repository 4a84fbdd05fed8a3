"""Median device duration of one execution of the decode program in
the traced window. The decode program is the one, among the modules
matching the configuration's ``programs.decode`` pattern, that ran
most often (the engine's jitted partials all carry one name today)."""
from statistics import median


def read(obs):
    from trace_reduce import first_device, most_run, program_of
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    pat = obs["config"].get("programs", {}).get("decode")
    if d is None or not pat:
        return None
    decode = most_run(d["modules"], pat)
    durs = [m["t1"] - m["t0"] for m in d["modules"]
            if program_of(m["name"]) == decode]
    return 1e3 * median(durs) if durs else None
