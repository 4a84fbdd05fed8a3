"""The latent decode attention's share of its roofline: the least time
the chip could take to read what a step's attention needs — every live
token's row (512 + 64 values) once a layer, ``expert_latent_counts.
latent_decode_step_bytes`` over the HBM peak — over the time a step's
operations under ``mla_attention`` took in the decode program. Live
tokens are the mean of the driver's 50 ms samples of the engine's own
host-side accounting over the window; the time is the scope's self time
in the traced window over the decode program's runs in it. Memory-bound
by construction: the absorbed form does ~2 x 2 x 576 operations a head
and row, 128 a byte, under the chip's 240."""


def read(obs):
    from expert_latent_counts import latent_decode_step_bytes
    from program_reads import program_scopes
    from trace_reduce import first_device, program_of
    peaks = obs["device"].get("peaks")
    got = program_scopes(obs, "decode")
    live = (obs.get("pages") or {}).get("live_tokens_mean")
    if not peaks or got is None or not live:
        return None
    took = got["by_scope"].get("mla_attention", 0.0)
    runs = sum(program_of(m["name"]) == got["program"]
               for m in first_device(obs["reduced"])["modules"])
    if took <= 0 or not runs:
        return None
    need = latent_decode_step_bytes(obs["config"], live) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * need / (took / runs)
