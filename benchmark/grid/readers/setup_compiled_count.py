"""Executables the backend compiled during set-up, not fetched: written
to the persistent cache or (compiled under jax's thresholds) not
(``program_compiled_total``, all programs, at the window's opening). A
warm run's are the ``unwritten``: the watched programs' one or two
(``copy_page``) and ``others``' many, 24 (chat) to 121 (brumby) a run
on the chip at PR 36 (agent 70, rag 74, train 38). The first thing to
read when ``setup_s`` jumps. A program that fetched everything has no
such series yet, and reads 0."""


def read(obs):
    from setup_reads import total
    if total(obs, "program_backend_seconds_total") is None:
        return None
    return total(obs, "program_compiled_total") or 0.0
