"""Self time of the decode program's operations under the retention
layer's own scopes — ``retention_state`` (decay, the rank-one update,
the read-out, the layer's slice of the bank) and ``retention_gate``
(``mxtpu/ops/retention.py``, ``mxtpu/models/retention.py``) — as a
share of the program's self time in the traced window
(``program_reads.decode_scope_share``). Nothing where no operation runs
under ``retention_state`` (a program without the layer)."""


def read(obs):
    from program_reads import decode_scope_share
    parts = [decode_scope_share(obs, s)
             for s in ("retention_state", "retention_gate")]
    if any(p is None for p in parts) or not parts[0]:
        return None
    return sum(parts)
