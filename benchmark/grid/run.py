"""One cell, once:

    python benchmark/grid/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration is ``configs/<config>.json``, its traffic
``traffic/<traffic>.json``, its driver ``drivers/<kind>.py`` by the
configuration's ``kind``, and each of its per-layer metrics
``readers/<metric>.py`` — all found by name, so a later PR adds cells,
mixes and metrics as files and entries and edits nothing here.

Fails, non-zero and with no result line, when JAX finds no TPU, a
``device_kind`` that ``peaks.json`` does not hold, or fewer chips than
the cell asks for. The last line of standard output is the result.
"""
from __future__ import annotations

import time
T_PROCESS = time.monotonic()        # set-up counts from here

import argparse                      # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_module(kind: str, name: str):
    """``drivers/<name>.py`` or ``readers/<name>.py``, by path: a
    metric's name may hold dots, which an import statement cannot."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"grid_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str, base: str = ROOT) -> dict:
    """Everything one cell is made of, from the names in ``bench``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; "
                         f"there are {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(base, cfg_entry["file"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])
    return {
        "cell": cell, "config": config,
        "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def device_or_die(chips: int) -> dict:
    """The device as JAX reports it. Anything but enough TPUs of a
    kind in ``peaks.json`` ends the run: a measurement path has no
    fallback."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"no TPU: jax reports {len(devs)} "
                         f"{d0.platform!r} device(s)")
    peaks = load_json(HERE, "peaks.json")
    if d0.device_kind not in peaks:
        raise SystemExit(f"device_kind {d0.device_kind!r} is not in "
                         "peaks.json")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), jax "
                         f"reports {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips, "peaks": peaks[d0.device_kind]}


def per_layer(parts: dict, obs: dict) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in parts["per_layer"]:
        value = load_module("readers", m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(parts: dict, device: dict, seed: int, seconds: float,
             trace: bool, log=print) -> dict:
    """Drive one cell and shape the result line. ``device`` comes from
    :func:`device_or_die` on the chip; the self-test hands in a CPU
    stand-in with no peaks."""
    driver = load_module("drivers", parts["config"]["kind"])
    obs = driver.run(parts, device, seed, seconds, trace, T_PROCESS, log)
    if trace:
        metrics = per_layer(parts, obs)
    else:
        metrics = {m["name"]: {"value": float(obs["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in parts["end_to_end"]}
    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = obs["memory_peak_bytes"]
    result = {"correct": bool(obs["correct"]),
              "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]),
              "metrics": metrics, "device": dev}
    if trace:
        import trace_reduce
        dev["busy_s"] = trace_reduce.busy_s(obs["reduced"])
        dev["window_s"] = obs["reduced"]["window_s"]
        result["breakdown"] = trace_reduce.breakdown(obs["reduced"])
    result["notes"] = obs.get("notes", {})
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    # the compile cache before anything can compile: a fixed directory
    # in the checkout unless JAX_COMPILATION_CACHE_DIR names another
    from mxtpu import runtime
    runtime.use_compile_cache()

    parts = load_cell(load_json(ROOT, "BENCHMARK.json"), args.workload)
    device = device_or_die(parts["cell"]["chips"])
    result = run_cell(parts, device, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
