"""Self time of the decode program's operations under the scope
``window_attention`` (``models/sambay.py``) as a share of the program's self time
in the traced window (``program_reads.decode_scope_share``)."""


def read(obs):
    from program_reads import decode_scope_share
    return decode_scope_share(obs, "window_attention")
