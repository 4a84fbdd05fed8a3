"""The most pages in use at any sample of the window, as a share of
the pool (slots' pages and the prefix cache's together)."""


def read(obs):
    pages = obs.get("pages")
    if not pages or not pages["total"]:
        return None
    return 100.0 * pages["peak_used"] / pages["total"]
