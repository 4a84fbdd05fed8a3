"""The share of device 0's busy time in the traced steps during which
a collective (all-gather, reduce-scatter, all-reduce, all-to-all,
collective-permute) was executing or in flight: the union of their
intervals on the operations line and on the async line, overlapped by
compute or not. On the 2x2 FSDP's gathers and the gradient reduction
are rings of collective-permutes, which live on the async line."""
import re

_COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")


def read(obs):
    from trace_reduce import covered_s, first_device
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    if d is None or not d["busy_s"] or not d["ops"]:
        return None
    found = [e for e in d["ops"] + d["async"]
             if _COLLECTIVE.search(e["name"])]
    return 100.0 * covered_s(found) / d["busy_s"]
