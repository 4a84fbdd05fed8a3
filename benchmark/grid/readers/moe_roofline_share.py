"""The grouped expert products' share of their roofline in the decode
program: the least time the chip could take for a step's products —
the larger of (bytes of the touched experts' three matrices) / HBM
peak and (2 x 3 x hidden x expert width x assignments) / bf16 peak,
``expert_latent_counts.experts_step`` — over the time a step's
operations under ``moe_experts`` took. Touched experts and assignments
a step are the program's own counts over the window (the counters
``serve_moe_experts_touched_total``, ``serve_moe_assignments_total``
over the steps ``serve_moe_load_max_share`` saw); the time is the
scope's self time in the traced window over the decode program's runs
in it. The note says which of the two bounds."""


def read(obs):
    from expert_latent_counts import experts_step
    from program_reads import hist_sum, program_scopes
    from trace_reduce import first_device, program_of
    peaks = obs["device"].get("peaks")
    got = program_scopes(obs, "decode")
    steps = hist_sum(obs, "serve_moe_load_max_share", "_count")
    if not peaks or got is None or not steps:
        return None
    took = got["by_scope"].get("moe_experts", 0.0)
    runs = sum(program_of(m["name"]) == got["program"]
               for m in first_device(obs["reduced"])["modules"])
    if took <= 0 or not runs:
        return None
    s0, s1 = obs["scrape0"], obs["scrape1"]

    def a_step(name):
        return (s1[name] - s0.get(name, 0.0)) / steps
    need = experts_step(obs["config"],
                        a_step("serve_moe_experts_touched_total"),
                        a_step("serve_moe_assignments_total"))
    by_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = need["flops"] / peaks["flops_bf16"]
    obs.setdefault("notes", {})["moe_bound"] = (
        "memory" if by_bytes >= by_flops else "compute")
    return 100.0 * max(by_bytes, by_flops) / (took / runs)
