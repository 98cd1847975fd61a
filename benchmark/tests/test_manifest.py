"""Lint of BENCHMARK.json against the files it names."""

import importlib
import json
import os
import re

import pytest

from benchmark.harness import bytes_model, templates
from benchmark.harness.manifest import BENCH, ROOT, Cell, load_manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


def test_shape_of_the_manifest():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"]
    assert 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(len(M["workloads"]) // 2, 1)
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in M["end_to_end"])


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in M[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry and group != "end_to_end":
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text, (entry["name"], key)
    assert len(names) == len(set(names))
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_has_its_files_and_its_metrics(name):
    cell = Cell(M, name)
    entry = next(c for c in M["configs"] if c["name"] == cell.config["name"])
    assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
    assert cell.config["name"] == name.split(".")[0]
    assert cell.config["nodes"] > 0
    for key in ("source", "reduced", "assumed", "guarantees", "layout",
                "parity", "scheduler_flags", "assign_program"):
        assert key in cell.config, key
    templates.resolve(templates.NODE_TEMPLATES, cell.config["node_template"])
    for pods in ("init_pods", "measured_pods"):
        templates.resolve(templates.POD_TEMPLATES,
                          cell.config[pods]["template"])
    assert cell.traffic["mode"] in ("saturate", "paced")
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in e2e, (name, m["name"])
    assert bytes_model.assign_bytes(cell.config, cell.chips) > 0


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"]])
def test_every_layer_metric_has_its_reader(metric):
    entry = next(m for m in M["per_layer"] if m["name"] == metric)
    mod = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    assert callable(mod.read)
    for key in ("layer", "unit", "source", "moves"):
        assert mod.META[key] == entry[key], (metric, key)
    for cell in entry.get("workloads", CELLS):
        assert cell in CELLS


def test_layer_files_beyond_the_manifest_belong_to_the_prepared_cells():
    from benchmark.tests.rehearse import PREPARED

    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
             if f.endswith(".py") and f != "__init__.py"}
    prepared = {n for e in PREPARED.values() for n in e["layer_files"]}
    assert files - {m["name"] for m in M["per_layer"]} == prepared


def test_config_files_match_their_entries():
    for entry in M["configs"]:
        with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["reduced"] == entry["reduced"]
        assert any(w["config"] == entry["name"] for w in M["workloads"])


def test_peaks_are_keyed_by_device_kind():
    assert bytes_model.peak_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError):
        bytes_model.peaks("an unknown chip")
