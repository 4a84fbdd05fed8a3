"""Self time of the train step's operations that the program's catalog
marks rematerialised (traced under ``rematted_computation``: the
backward pass's second run of a checkpointed layer) as a share of
device 0's busy time in the traced steps (``program_reads``)."""


def read(obs):
    from program_reads import program_scopes
    got = program_scopes(obs, "train")
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * got["remat_s"] / got["busy_s"]
