"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric sits in a file of its
own and is found here by its name, so a later PR adds a cell by adding files
and entries and edits nothing."""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix
    and the metrics that apply to it."""

    def __init__(self, manifest: dict, name: str,
                 entry: dict | None = None) -> None:
        """``entry`` stands in for a cell BENCHMARK.json does not list yet,
        with the metric entries it would bring (the benchmark's own tests
        rehearse the prepared paced and four-chip cells that way)."""
        found = [w for w in manifest["workloads"] if w["name"] == name]
        if entry is None and not found:
            known = ", ".join(w["name"] for w in manifest["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {known})")
        entry = entry or found[0]
        self.name = name
        self.chips: int = entry["chips"]
        self.config = _load(os.path.join(
            BENCH, "configs", entry["config"] + ".json"))
        self.traffic = _load(os.path.join(
            BENCH, "traffic", entry["traffic"] + ".json"))
        like = entry.get("metrics_of", name)
        self.end_to_end = entry.get("end_to_end", []) + [
            m for m in manifest["end_to_end"]
            if like in m.get("workloads", [like])]
        self.per_layer = entry.get("per_layer", []) + [
            m for m in manifest["per_layer"]
            if like in m.get("workloads", [like])]


def layer_reader(metric_name: str):
    """``benchmark/layer_metrics/<name>.py``: META and read(run)."""
    return importlib.import_module(f"benchmark.layer_metrics.{metric_name}")
