"""The retention decode step's share of its roofline: the least time
the chip could take to move what a step's retention has to — every
running slot's state read once and written once a layer,
``retention_counts.decode_step_bytes`` over the HBM peak — over the
time a step's operations under ``retention_state`` took in the decode
program. Running slots a step are ``decode_batch_mean``'s counters
(tokens emitted over decode steps, from the two scrapes); the time is
the scope's self time in the traced window over the decode program's
runs in it. Memory-bound by construction: a step does about 2 x (1 + 5)
operations a state element (the update and five query heads' read-out)
against its 8 bytes moved, 1.5 a byte, under the chip's 240."""


def read(obs):
    from program_reads import program_scopes
    from retention_counts import decode_step_bytes
    from trace_reduce import first_device, program_of
    peaks = obs["device"].get("peaks")
    got = program_scopes(obs, "decode")
    if not peaks or got is None or "scrape0" not in obs:
        return None
    a, b = obs["scrape0"], obs["scrape1"]
    steps = b.get("serve_steps_total", 0.0) - a.get("serve_steps_total", 0.0)
    took = got["by_scope"].get("retention_state", 0.0)
    runs = sum(program_of(m["name"]) == got["program"]
               for m in first_device(obs["reduced"])["modules"])
    if steps <= 0 or took <= 0 or not runs:
        return None
    running = (b.get("serve_tokens_total", 0.0)
               - a.get("serve_tokens_total", 0.0)) / steps
    need = decode_step_bytes(obs["config"], running) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * need / (took / runs)
