"""Tokens emitted per decode step over the window: how full the slot
bank ran. Exact counts, from the two scrapes."""


def read(obs):
    if "scrape0" not in obs:
        return None
    a, b = obs["scrape0"], obs["scrape1"]
    steps = b.get("serve_steps_total", 0.0) - a.get("serve_steps_total", 0.0)
    if steps <= 0:
        return None
    return (b.get("serve_tokens_total", 0.0)
            - a.get("serve_tokens_total", 0.0)) / steps
