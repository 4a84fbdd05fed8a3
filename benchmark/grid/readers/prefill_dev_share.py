"""Device time spent in prefill programs as a share of the device's
busy time in the traced window: the modules matching
``programs.prefill`` other than the decode program (see
``decode_step_dev_ms``; one name covers both today)."""
import re


def read(obs):
    from trace_reduce import first_device, most_run
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    progs = obs["config"].get("programs", {})
    if (d is None or not progs.get("prefill") or not d["busy_s"]
            or not d["ops"]):
        return None
    decode = most_run(d["modules"], progs.get("decode", "$^"))
    t = sum(op["self"] for op in d["ops"]
            if op["program"] != decode
            and re.search(progs["prefill"], op["program"]))
    return 100.0 * t / d["busy_s"]
