"""Programs JAX built or fetched while the window was open: the
difference of ``jax_compile_total`` between the two scrapes. Warm-up
is complete when this is 0."""


def read(obs):
    if "scrape0" not in obs:
        return None
    return (obs["scrape1"].get("jax_compile_total", 0.0)
            - obs["scrape0"].get("jax_compile_total", 0.0))
