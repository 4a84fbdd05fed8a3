"""Admissions in the window that were seated on cached prefix pages,
as a share of all admissions. Exact counts, from the two scrapes."""


def read(obs):
    if "scrape0" not in obs:
        return None
    a, b = obs["scrape0"], obs["scrape1"]
    hits = (b.get("serve_prefix_cache_hits_total", 0.0)
            - a.get("serve_prefix_cache_hits_total", 0.0))
    misses = (b.get("serve_prefix_cache_misses_total", 0.0)
              - a.get("serve_prefix_cache_misses_total", 0.0))
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
