"""Self time of the step program's operations under ``block_attention``
(a block of query rows a slot over the cache and the block itself) as a
share of the program's self time (``program_reads.decode_scope_share``).
Nothing where no operation runs under it."""


def read(obs):
    from program_reads import decode_scope_share
    return decode_scope_share(obs, "block_attention") or None
