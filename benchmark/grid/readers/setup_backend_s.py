"""Seconds of set-up spent in the backend, compiling or fetching from
the persistent cache (``program_backend_seconds_total``, all programs,
at the window's opening)."""


def read(obs):
    from setup_reads import total
    return total(obs, "program_backend_seconds_total")
