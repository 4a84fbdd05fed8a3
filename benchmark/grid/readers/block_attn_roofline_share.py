"""The block step's attention as a share of its roofline: the least
time the chip could take to read what a pass's attention needs — every
live token's keys and values once a layer, ``block_gqa_counts.
block_step_bytes`` over the HBM peak — over the time a pass's
operations under ``block_attention`` took in the step program. Live
tokens are the mean of the driver's 50 ms samples of the engine's own
host-side accounting over the window; the time is the scope's self time
in the traced window over the step program's runs in it. Memory-bound
by construction: a slot's block is 4 x 8 query rows a KV head, 32
operations a byte of keys, under the chip's 240."""


def read(obs):
    from block_gqa_counts import block_step_bytes
    from program_reads import program_scopes
    from trace_reduce import first_device, program_of
    peaks = obs["device"].get("peaks")
    got = program_scopes(obs, "decode")
    live = (obs.get("pages") or {}).get("live_tokens_mean")
    if not peaks or got is None or not live:
        return None
    took = got["by_scope"].get("block_attention", 0.0)
    runs = sum(program_of(m["name"]) == got["program"]
               for m in first_device(obs["reduced"])["modules"])
    if took <= 0 or not runs:
        return None
    need = block_step_bytes(obs["config"], live) / peaks["hbm_bytes_per_s"]
    return 100.0 * need / (took / runs)
