"""``BENCHMARK.json`` against the contract's rules that a file can be
held to off the chip, and against the files it names."""
import json
import os
import re

import pytest

GRID = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(GRID))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark/grid"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    names = set()
    for table in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[table]:
            assert NAME.match(e["name"]), e["name"]
            assert (table, e["name"]) not in names
            names.add((table, e["name"]))
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_name_finds_its_file(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/grid/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert os.path.exists(os.path.join(
            GRID, "drivers", cfg["kind"] + ".py"))
        # no width may be cut: Mistral-7B-v0.3 as published
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            GRID, "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            GRID, "readers", m["name"] + ".py")), m["name"]


def test_every_cell_reports_what_the_contract_asks(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]

    def cells_of(m):
        return set(m.get("workloads", cells))
    for c in cells:
        assert any(c in cells_of(m) for m in bench["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(c in cells_of(m) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        # the metric it moves is reported wherever it is
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m["name"]
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
