"""One whole CPU rehearsal of ``run.py``'s phases at a tiny cluster, each in
a process of its own (the scheduler wants the main thread and SIGTERM), and
the refusal to run from the command line without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.manifest import load_manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "JAX_ENABLE_COMPILATION_CACHE": "false"}


def rehearse_lines(cell: str, trace: int, *more: str, env=ENV) -> list[dict]:
    """Every JSON line of one rehearsal: the phases, then the result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "rehearse.py"), cell, str(trace), *more],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]


def rehearse(cell: str, trace: int, env=ENV) -> dict:
    return rehearse_lines(cell, trace, env=env)[-1]


@pytest.mark.parametrize("cell,metrics", [
    ("basic-5k.saturate", {"pods_bound_per_s", "setup_s"}),
    ("basic-5k.paced", {"bind_latency_p50_ms", "bind_latency_p99_ms",
                        "setup_s"}),
    ("podaffinity-5k.saturate", {"pods_bound_per_s", "setup_s"}),
])
def test_end_to_end_run(cell, metrics):
    line = rehearse(cell, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"


def test_traced_run_on_a_mesh_reports_the_counter_metrics():
    env = {**ENV, "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    line = rehearse("basic-15k-mesh.saturate", 1, env)
    assert line["correct"] is True and line["device"]["count"] == 4
    # no TPU: the device-trace metrics find nothing to read and are left
    # out; every other reader of the cell it borrows its metrics from reads
    per_layer = load_manifest()["per_layer"]
    assert set(line["metrics"]) == {
        m["name"] for m in per_layer
        if "basic-5k.saturate" in m["workloads"]
        and m["source"] != "device_trace"}
    assert len(line["metrics"]) >= 20


def test_a_traced_run_ends_its_load_where_its_window_ends():
    """100 nodes hold 4000 pods. The 4 s window makes about half of them;
    the window and a profiler that takes 8 s to hand its trace over, with
    the load left running, would make them all: the cluster full, its
    unschedulable pods compiling new shapes, and no result. How many pods
    the window itself makes is the host's speed and is not asserted."""
    lines = rehearse_lines("basic-5k.saturate", 1, "100",
                           "--slow-profiler", "8")
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    by_phase = {doc["phase"]: doc for doc in lines[:-1]}
    window, drain = by_phase["window"], by_phase["drain"]
    assert window["capacity"] == 4000
    # at most the one bulk create (32 pods here) that was in flight at t1
    assert 0 <= drain["created"] - window["created_at_t1"] <= 32
    assert window["compiles_in_window"] == 0
    assert by_phase["phases"]["stop_trace_s"] >= 8.0


@pytest.mark.parametrize("cell", ["basic-5k.saturate",
                                  "podaffinity-5k.saturate"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(cell):
    """The whole run with the timed path broken underneath: the engine's
    answer is altered before the scheduler binds by it. Every pod is bound
    and acknowledged, the rate reads as ever, and ``correct`` is false: the
    validity check over ALL bindings finds the over-committed node."""
    line = rehearse_lines(cell, 0, "--fault", "all-on-one-node")[-1]
    assert line["correct"] is False and line["failed"] == 0
    checks = line["checks"]
    assert checks["invalid_bindings"][0] > 0 == checks["invalid_bindings"][1]
    assert checks["ack_store_problems"] == [0, 0]
    assert line["metrics"]["pods_bound_per_s"]["value"] > 0


def test_the_command_line_needs_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "basic-5k.saturate", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "device_stamp() found" in proc.stderr
    assert not any(line.startswith('{"correct"')
                   for line in proc.stdout.splitlines())


def test_the_benchmark_alone_gives_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's files."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "basic-5k.saturate", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=ENV, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "not the program" in proc.stderr
