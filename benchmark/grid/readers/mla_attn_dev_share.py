"""Self time of the decode program's operations under ``mla_attention``
(latent attention in the absorbed form: the absorption, the scores and
the values) as a share of the program's self time
(``program_reads.decode_scope_share``). Nothing where no operation
runs under it."""


def read(obs):
    from program_reads import decode_scope_share
    return decode_scope_share(obs, "mla_attention") or None
