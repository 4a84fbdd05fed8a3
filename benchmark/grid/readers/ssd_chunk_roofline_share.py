"""The chunked SSD's share of its roofline in the program that runs a
prompt's last chunk: the least time the chip could take for one
chunk's recurrence over every Mamba layer — the larger of operations
over the bf16 peak and bytes over the HBM peak,
``ssd_counts.ssd_chunk`` at ``run.engine.prefill_chunk`` positions (the
chunk the program is compiled for, padding included) — over the time a
run's operations under ``ssm_state`` (inside ``ssm``) took. The time is
the scope's self time in the traced window over the program's runs in
it (``programs.last_chunk`` finds the program). The note says which of
the two bounds."""


def read(obs):
    from scope_reads import nested_scope_s
    from ssd_counts import ssd_chunk
    peaks = obs["device"].get("peaks")
    got = nested_scope_s(obs, "last_chunk", "ssm_state")
    if not peaks or got is None or got[0] <= 0 or not got[1]:
        return None
    cfg = obs["config"]
    need = ssd_chunk(cfg, cfg["run"]["engine"]["prefill_chunk"])
    by_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = need["flops"] / peaks["flops_bf16"]
    obs.setdefault("notes", {})["ssd_chunk_bound"] = (
        "memory" if by_bytes >= by_flops else "compute")
    return 100.0 * max(by_bytes, by_flops) / (got[0] / got[1])
