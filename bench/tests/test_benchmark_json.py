"""BENCHMARK.json keeps to the characters, keys and files the
benchmark's contract allows, and every name it gives has its file."""

import json
import re

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16


def test_metric_keys_and_sources():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        # setup_s is reported in every cell, those added later too.
        assert m["name"] != "setup_s" or "workloads" not in m
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_every_name_has_its_file():
    root, bench = harness.ROOT, harness.BENCH
    for c in BENCH["configs"]:
        spec = json.loads((root / c["file"]).read_text())
        assert c["file"].startswith("bench/")
        assert spec["reduced"] == c["reduced"]
        assert (bench / "reference" / f"{spec['reference']}.py").exists()
    for w in BENCH["workloads"]:
        mix = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (bench / "traffic" / f"{mix['kind']}.py").exists()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.metric_module(m["name"]).read)


def test_every_cell_reports_what_it_must():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics("per_layer")
