"""The share of the traced steps in which no operation ran on device
0: 1 - busy / window."""


def read(obs):
    from trace_reduce import first_device
    r = obs.get("reduced")
    d = first_device(r) if r else None
    if d is None or not r["window_s"]:
        return None
    return 100.0 * (1.0 - d["busy_s"] / r["window_s"])
