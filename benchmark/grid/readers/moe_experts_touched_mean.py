"""Experts that got at least one token, a layer and decode step, of
``n_routed_experts``: the counter ``serve_moe_experts_touched_total``
(counted in the decode program, over the slots that ran, and carried
out beside the sampled tokens) over the steps the histogram
``serve_moe_load_max_share`` saw and the expert layers."""


def read(obs):
    from expert_latent_counts import expert_layers
    from program_reads import hist_sum
    steps = hist_sum(obs, "serve_moe_load_max_share", "_count")
    s0, s1 = obs.get("scrape0"), obs.get("scrape1")
    name = "serve_moe_experts_touched_total"
    if not steps or name not in s1:
        return None
    return (s1[name] - s0.get(name, 0.0)) / steps \
        / expert_layers(obs["config"])
