"""Experts that got at least one token, a layer and decode step, of
``n_routed_experts``, where ``hybrid_override_pattern`` lays the expert
layers out among layers of other kinds: ``moe_experts_touched_mean``'s
counts (the counter ``serve_moe_experts_touched_total``, counted in the
decode program over the slots that ran and carried out beside the
sampled tokens, over the steps the histogram
``serve_moe_load_max_share`` saw) over the pattern's expert layers
(``ssd_counts.expert_layers``)."""


def read(obs):
    from program_reads import hist_sum
    from ssd_counts import expert_layers
    steps = hist_sum(obs, "serve_moe_load_max_share", "_count")
    s0, s1 = obs.get("scrape0"), obs.get("scrape1")
    name = "serve_moe_experts_touched_total"
    config = obs.get("config") or {}
    if (not steps or not s1 or name not in s1
            or "hybrid_override_pattern" not in config):
        return None
    return (s1[name] - s0.get(name, 0.0)) / steps / expert_layers(config)
