"""Self time of the train step's operations under the scope ``xent``
(the head matmul, the chunked softmax and the NLL: forward, backward
and its recomputation) as a share of device 0's busy time in the
traced steps (``program_reads``)."""


def read(obs):
    from program_reads import program_scopes
    got = program_scopes(obs, "train")
    if got is None or not got["busy_s"]:
        return None
    obs.setdefault("notes", {})["train_scope_shares"] = {
        k or "unscoped": round(100.0 * v / got["busy_s"], 3)
        for k, v in sorted(got["by_scope"].items(), key=lambda kv: -kv[1])}
    return 100.0 * got["by_scope"].get("xent", 0.0) / got["busy_s"]
