"""Requests the gateway refused, as a share of those sent in the
window: the difference of ``gateway_shed_total`` between the two
scrapes over ``attempted``."""


def read(obs):
    if "scrape0" not in obs or not obs["attempted"]:
        return None
    shed = (obs["scrape1"].get("gateway_shed_total", 0.0)
            - obs["scrape0"].get("gateway_shed_total", 0.0))
    return 100.0 * shed / obs["attempted"]
