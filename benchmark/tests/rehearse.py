"""One whole run of ``run.py``'s phases on the CPU at a tiny cluster: the
only way to reach the harness without a TPU, and only from here (a function
argument of ``run_cell``, never a flag or an environment variable).

    python benchmark/tests/rehearse.py <workload> <trace 0|1> [nodes] \
        [--slow-profiler SECONDS] [--fault all-on-one-node]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


#: cells that are prepared (configuration, traffic, readers, this rehearsal)
#: but not in BENCHMARK.json until they have their runs on the chip, with the
#: entries they would bring
PREPARED = {
    "basic-15k-mesh.saturate": {
        "config": "basic-15k-mesh", "traffic": "saturate", "chips": 4,
        "metrics_of": "basic-5k.saturate",
        "layer_files": ["collective_share"]},
    "basic-5k.paced": {
        "config": "basic-5k", "traffic": "paced", "chips": 1,
        "end_to_end": [
            {"name": "bind_latency_p50_ms", "unit": "ms"},
            {"name": "bind_latency_p99_ms", "unit": "ms"}],
        "layer_files": [
            "loop_idle_share_paced", "queue_wait_p50_ms", "bind_rtt_p50_ms",
            "device_idle_share_paced", "generator_late_p99_ms",
            "apiserver_cpu_share_paced"]},
}


def tiny(cell, nodes: int):
    """The cell's own configuration and traffic, cut to a toy: control flow
    and counts only, no timing means anything."""
    cell.config = {**cell.config, "nodes": nodes,
                   "init_pods": {**cell.config["init_pods"], "count": 24}}
    if cell.traffic["mode"] == "saturate":
        # one bulk create standing: every batch is 32 pods, one rung
        cell.traffic = {**cell.traffic, "standing_pods": 32,
                        "bulk_create": 32}
    else:
        cell.traffic = {**cell.traffic, "rate_pods_per_s": 150,
                        "ladder": [8, 16, 32, 64]}
    return cell


def slow_profiler(seconds: float) -> None:
    """The chip's profiler takes seconds to minutes to hand its trace over
    (it grows with the pods bound in the traced seconds); the CPU's takes
    none. Wrapped HERE, in the rehearsal's own process: the harness has no
    such switch."""
    from benchmark.harness import phases

    stop_profiler = phases.stop_profiler

    def slow_stop_profiler(session) -> bytes:
        time.sleep(seconds)
        return stop_profiler(session)

    phases.stop_profiler = slow_stop_profiler


def all_on_one_node(cell) -> None:
    """The fault a check of ``correct`` has to catch: an answer altered
    where it is produced. The cell's engine still runs, and the scheduler
    is handed node 0 for every pod it placed: the store agrees with every
    ack, and the bindings over-commit the node. Planted HERE, in the
    rehearsal's own process."""
    import importlib

    import jax.numpy as jnp

    flags = cell.config["scheduler_flags"]
    engine = flags[flags.index("--engine") + 1]
    # where the Scheduler's constructor finds its engine
    module = importlib.import_module(
        {"greedy": "kubetpu.sched.scheduler",
         "batched": "kubetpu.assign.batched"}[engine])
    name = f"{engine}_assign_device"
    real = getattr(module, name)

    def faulty(batch, params):
        assignments, state = real(batch, params)
        return jnp.where(assignments >= 0, 0, assignments), state

    setattr(module, name, faulty)


def main(argv: list[str]) -> int:
    from benchmark.harness import manifest
    from benchmark.harness.manifest import Cell, load_manifest
    from benchmark.harness.phases import run_cell

    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("trace", type=int, choices=(0, 1))
    # room for every pod the window sends: 40 pods a node
    ap.add_argument("nodes", type=int, nargs="?", default=256)
    ap.add_argument("--slow-profiler", type=float, metavar="SECONDS")
    ap.add_argument("--fault", choices=("all-on-one-node",))
    args = ap.parse_args(argv)
    name = args.workload
    entry = PREPARED.get(name)
    if entry is not None and "layer_files" in entry:
        entry = {**entry, "per_layer": [
            {"name": n, **manifest.layer_reader(n).META}
            for n in entry["layer_files"]]}
    cell = tiny(Cell(load_manifest(), name, entry), args.nodes)
    # a 99th percentile wants a thousand pods due inside the window
    seconds = 4.0 if cell.traffic["mode"] == "saturate" else 8.0
    if args.slow_profiler is not None:
        slow_profiler(args.slow_profiler)
        # a cluster this small is full within seconds: do not wait two
        # minutes to hear it
        cell.traffic = {**cell.traffic, "drain_timeout_s": 10}
    if args.fault is not None:
        all_on_one_node(cell)
    line = run_cell(name, seed=7, seconds=seconds, trace=bool(args.trace),
                    t_start=T_START, platform="cpu", cell=cell)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
