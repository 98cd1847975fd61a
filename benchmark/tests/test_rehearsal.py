"""One whole CPU rehearsal of ``run.py``'s phases at a tiny cluster, each in
a process of its own (the scheduler wants the main thread and SIGTERM), and
the refusal to run from the command line without a TPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "JAX_ENABLE_COMPILATION_CACHE": "false"}


def rehearse(cell: str, trace: int, env=ENV) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "rehearse.py"), cell, str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


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
    # no TPU: the device-trace metrics find nothing to read and are left out
    assert set(line["metrics"]) == {
        "loop_idle_share", "api_rpcs_per_pod", "api_wire_bytes_per_pod",
        "encode_share", "encode_cache_hit_rate", "transfer_bytes_per_cycle",
        "assign_wait_share", "apiserver_cpu_share", "scheduler_cpu_share",
        "generator_cpu_share"}


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
