"""Self time of the decode program's operations under the scope
``kv_gather`` (every slot's pages gathered into a contiguous row, in
every layer) as a share of the program's self time in the traced
window (``program_reads``)."""


def read(obs):
    from program_reads import decode_scope_share
    return decode_scope_share(obs, "kv_gather")
