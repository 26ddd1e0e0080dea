"""``BENCHMARK.json`` holds to the benchmark's contract, and every entry
it names is found: each configuration's file and graph generator, each
cell's traffic mix, a reader for each metric, and the program calls the
readers declare."""

from __future__ import annotations

import json
import os
import re

import pytest

from conftest import ROOT
from yardstick import harness, hooks

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def _text(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level():
    assert set(SPEC) == KEYS
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_texts():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _text(c["source"]) and _text(c["why"]) and c["reduced"] == []
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _text(w["why"]) and w["chips"] in (1, 4) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_contract():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m["workloads"]) <= cells if "workloads" in m else True
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert _text(m["layer"]) and m["moves"] in e2e and "bound" not in m
        for cell in m["workloads"]:
            assert cell in cells
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or cell in moved["workloads"]
    for cell in cells:
        reported = [m for m in SPEC["end_to_end"] if "workloads" not in m or cell in m["workloads"]]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in SPEC["per_layer"])


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(name):
    cell = harness.load_cell(name)
    assert cell.config["reduced"] == [] and cell.config["control"]["serve"]
    assert cell.mix["loop"] in ("open", "closed")
    assert callable(harness.load_generator(cell.config["graph"]["generator"]).generate)
    readers = [harness.load_reader(m["name"]) for m in cell.end_to_end + cell.per_layer]
    assert all(callable(r.read) for r in readers)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


def test_files_named_by_config_and_metric_exist():
    for c in SPEC["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("bench/") and os.path.isfile(path)
        assert json.load(open(path))["name"] == c["name"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert harness.reader_path(m["name"]).startswith(os.path.join(ROOT, "bench", "metrics"))


def test_declared_spans_name_program_calls():
    import importlib

    from repro.serve import QueryService

    readers = [harness.load_reader(m["name"]) for m in SPEC["per_layer"]]
    specs = hooks.declared(readers)
    assert {"flush", "plan_request", "s1_collect", "s2_execute"} <= {s["name"] for s in specs}
    for spec in specs:
        owner = QueryService if spec["on"] == "service" else importlib.import_module(spec["on"])
        assert callable(getattr(owner, spec["call"])), spec


def test_reader_falls_back_to_the_name_before_the_last_dot():
    assert harness.reader_path("flush_ms.some_new_cell").endswith("flush_ms.py")
    with pytest.raises(FileNotFoundError):
        harness.reader_path("no_such_metric.closed")
