"""Self time of the decode program's operations under the four scopes
of a routed expert layer (``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_shared``: ``mxtpu/parallel/moe.py``) as a share
of the program's self time in the traced window
(``program_reads.decode_scope_share``)."""


def read(obs):
    from program_reads import decode_scope_share
    parts = [decode_scope_share(obs, s) for s in
             ("moe_router", "moe_dispatch", "moe_experts", "moe_shared")]
    if any(p is None for p in parts) or not parts[2]:
        return None
    return sum(parts)
