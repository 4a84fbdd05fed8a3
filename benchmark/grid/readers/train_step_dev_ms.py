"""Device-0 busy time per training step: the union of its operation
intervals over the traced steps, divided by their number."""


def read(obs):
    from trace_reduce import first_device
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    if d is None or not obs.get("traced_steps"):
        return None
    return 1e3 * d["busy_s"] / obs["traced_steps"]
