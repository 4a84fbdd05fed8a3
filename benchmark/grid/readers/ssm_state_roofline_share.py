"""The decode step's Mamba-2 recurrence as a share of its roofline: the
least time the chip could take to move what a step's recurrence has to
— every running slot's state read once and written once a Mamba layer,
``ssd_counts.decode_state_bytes`` over the HBM peak — over the time a
step's operations under ``ssm_state`` (inside ``ssm``) took in the
decode program. Running slots a step are ``decode_batch_mean``'s
counters (tokens emitted over decode steps, from the two scrapes); the
time is the scope's self time in the traced window over the decode
program's runs in it. Memory-bound by construction: a step does about 6
operations a state element against its 8 bytes moved."""


def read(obs):
    from scope_reads import nested_scope_s
    from ssd_counts import decode_state_bytes
    peaks = obs["device"].get("peaks")
    got = nested_scope_s(obs, "decode", "ssm_state")
    if not peaks or got is None or "scrape0" not in obs:
        return None
    a, b = obs["scrape0"], obs["scrape1"]
    steps = b.get("serve_steps_total", 0.0) - a.get("serve_steps_total", 0.0)
    took, runs = got
    if steps <= 0 or took <= 0 or not runs:
        return None
    running = (b.get("serve_tokens_total", 0.0)
               - a.get("serve_tokens_total", 0.0)) / steps
    need = decode_state_bytes(obs["config"], running) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * need / (took / runs)
