"""The share of the traced window in which no operation ran on the
device: 1 - busy / window."""


def read(obs):
    from trace_reduce import busy_s
    r = obs.get("reduced")
    if not r or not r["window_s"] or not r["devices"]:
        return None
    return 100.0 * (1.0 - busy_s(r) / r["window_s"])
