"""Self time of the decode program's operations under the scopes
``attention`` (the one full-attention layer) and ``cross_attention``
(the layers that read its keys and values): all the reads of the
shared cache, as a share of the program's self time in the traced
window (``program_reads.decode_scope_share``)."""


def read(obs):
    from program_reads import decode_scope_share
    full = decode_scope_share(obs, "attention")
    cross = decode_scope_share(obs, "cross_attention")
    return None if full is None or cross is None else full + cross
