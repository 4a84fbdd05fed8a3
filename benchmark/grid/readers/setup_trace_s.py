"""Seconds of set-up spent tracing programs to jaxprs: every program's
own trace, the functions traced inside it included
(``program_trace_seconds_total``, all programs, at the window's
opening)."""


def read(obs):
    from setup_reads import total
    return total(obs, "program_trace_seconds_total")
