"""BENCHMARK.json against the shape its contract fixes: keys, names, units,
every file a cell needs, and a chip-time budget that still fits with 24 cells."""

import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_file(run):
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"] and bench["command"][-1] == "chipbench/run.py"
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and NAME.match(c["name"])
        assert c["file"].startswith("chipbench/") and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert run.load_json(os.path.join(ROOT, c["file"]))["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        cell = run.load_cell(w["name"])  # every file the cell names is there
        assert cell["limits"] and os.path.isfile(os.path.join(BENCH, "drivers", cell["traffic"]["driver"] + ".py"))
        assert {m["name"] for m in cell["end_to_end"]} > {"setup_s"} and cell["per_layer"]
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end_to_end and len(end_to_end) == len(bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert set(m["workloads"]) <= set(end_to_end[m["moves"]].get("workloads", cells))
    seconds = bench["run_seconds"]
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200
