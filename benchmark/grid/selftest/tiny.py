"""The self-test's way into the harness: the tiny cells of
``cells.json`` under the real ``BENCHMARK.json``'s metrics, on a CPU
stand-in for the device (no peaks, so nothing that needs one is
reported)."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
STAND_IN = {"serve": "tiny-chat", "train": "tiny-pretrain"}


def bench():
    """cells.json's cells with BENCHMARK.json's metric tables; a
    metric listed for a real cell is listed for the tiny cells of the
    same kind."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(HERE, "cells.json")) as f:
        tiny = json.load(f)
    kind_of = {}
    for c in real["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            kind_of[c["name"]] = json.load(f)["kind"]
    cell_kind = {w["name"]: kind_of[w["config"]] for w in real["workloads"]}
    tiny_kind = {}
    for w in tiny["workloads"]:
        cfg = next(c for c in tiny["configs"] if c["name"] == w["config"])
        with open(os.path.join(HERE, cfg["file"])) as f:
            tiny_kind[w["name"]] = json.load(f)["kind"]
    for table in ("end_to_end", "per_layer"):
        tiny[table] = []
        for m in real[table]:
            m = dict(m)
            if "workloads" in m:
                kinds = {cell_kind[w] for w in m["workloads"]}
                m["workloads"] = [n for n, k in tiny_kind.items()
                                  if k in kinds]
            tiny[table].append(m)
    return tiny


def run(workload, seed=3, seconds=3.0, trace=False, log=lambda s: None):
    import run as grid_run
    parts = grid_run.load_cell(bench(), workload, HERE)
    device = {"platform": "cpu", "kind": "cpu",
              "count": parts["cell"]["chips"], "peaks": None}
    return grid_run.run_cell(parts, device, seed, seconds, trace, log)
