"""Self time of the decode program's operations under ``moe_dispatch``
(the sort by expert, the permute, the unpermute and the weighted sum)
as a share of the program's self time: what routing costs beside the
products. Nothing where the program has no expert layer."""


def read(obs):
    from program_reads import decode_scope_share
    if not decode_scope_share(obs, "moe_experts"):
        return None
    return decode_scope_share(obs, "moe_dispatch")
