"""Self time of the step program's operations under ``unmask`` (the
candidates' confidence, the ranking, the transfer and the block's
bookkeeping) as a share of the program's self time
(``program_reads.decode_scope_share``). Nothing where no operation runs
under it."""


def read(obs):
    from program_reads import decode_scope_share
    return decode_scope_share(obs, "unmask") or None
