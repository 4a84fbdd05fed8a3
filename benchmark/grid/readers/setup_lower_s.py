"""Seconds of set-up spent lowering jaxprs to MLIR, a kernel's Mosaic
lowering included (``program_lower_seconds_total``, all programs, at
the window's opening)."""


def read(obs):
    from setup_reads import total
    return total(obs, "program_lower_seconds_total")
