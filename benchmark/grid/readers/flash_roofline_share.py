"""The flash-attention kernels' share of their roofline on device 0:
the least time the chip could take for the kernel calls the trace
holds, over the time they took.

The calls are the operations whose instruction names match the
configuration's ``programs.flash_kernels`` (the Pallas kernels' own
names: ``flash_attention.N`` forward, ``flash_mha_bwd_dkv..`` and
``flash_mha_bwd_dq..`` backward; a layer recomputed in the backward
pass runs its forward kernel again, which the trace counts because it
ran). Each call's operations and bytes
come from the shapes (``flops.flash_call``; K and V arrive repeated
to the query heads, so that is what the call reads); its least time is
the larger of operations / peak FLOP/s and bytes / peak bytes/s. The
note says which of the two bounds."""
import re


def read(obs):
    from flops import flash_call
    from trace_reduce import first_device
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    peaks = obs["device"].get("peaks")
    pat = obs["config"].get("programs", {}).get("flash_kernels")
    if d is None or not peaks or not pat:
        return None
    calls = [op for op in d["ops"] if re.search(pat, op["name"])]
    took = sum(op["self"] for op in calls)
    if not calls or took <= 0:
        return None
    cfg = obs["config"]
    shape = flash_call(obs["batch_per_chip"], cfg["num_attention_heads"],
                       cfg["num_attention_heads"],
                       obs["traffic"]["seq_len"], cfg["head_dim"])

    def least(kind, share=1.0):
        c = shape[kind]
        return share * max(c["flops"] / peaks["flops_bf16"],
                           c["bytes"] / peaks["hbm_bytes_per_s"])
    need = 0.0
    for op in calls:
        if "bwd_dkv" in op["name"]:
            need += least("bwd", 0.6)     # 3 of the backward's 5 matmuls
        elif "bwd_dq" in op["name"]:
            need += least("bwd", 0.4)     # S again, dP, dQ
        else:
            need += least("fwd")
    obs.setdefault("notes", {})["flash_bound"] = (
        "compute" if shape["fwd"]["flops"] / peaks["flops_bf16"]
        >= shape["fwd"]["bytes"] / peaks["hbm_bytes_per_s"] else "memory")
    return 100.0 * need / took
