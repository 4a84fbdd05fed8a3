"""Self time of the decode program's operations that the program's
catalog puts under no model scope, as a share of the program's self
time in the traced window: the layer scan's slicing and write-back of
the pool slabs and the copies XLA inserted. Also the instrument's own
health: an operation the catalog does not hold counts here, and the
line's note ``decode_unmapped_share`` says how much that was."""


def read(obs):
    from program_reads import UNSCOPED, decode_scope_share
    return decode_scope_share(obs, UNSCOPED)
