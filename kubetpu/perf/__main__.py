"""CLI: python -m kubetpu.perf [--case NAME] [--workload NAME] [--label L]

Prints one JSON line per workload result (the perf-dash-style emission the
reference's benchmark mode produces)."""

from __future__ import annotations

import argparse
import json

from . import (
    TEST_CASES,
    run_label,
    run_workload,
    run_workload_multiprocess,
)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", help="test case name (see --list)")
    ap.add_argument("--workload", help="workload name within the case")
    ap.add_argument("--label", default=None,
                    help="run all workloads with this label (e.g. performance)")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--max-batch", type=int, default=1024)
    ap.add_argument("--timeout", type=float, default=1800.0)
    ap.add_argument("--engine", default="greedy",
                    choices=["greedy", "batched", "packing"],
                    help="assignment engine (assign.greedy scan, "
                         "assign.batched capacity-coupled rounds, or "
                         "assign.packing constraint-based packing)")
    ap.add_argument("--pipeline", default="off", choices=["on", "off"],
                    help="two-stage pipelined cycles with device-resident "
                         "node state + delta uploads (parity with the "
                         "serial loop is guaranteed; 'off' to debug)")
    ap.add_argument("--encode-cache", default="on", choices=["on", "off"],
                    help="event-time template-keyed pod encoding (bit-"
                         "identical to fresh encode; 'off' to debug)")
    ap.add_argument("--bulk", default="on", choices=["on", "off"],
                    help="opportunistic API-plane batching: cycle-boundary "
                         "bulk bind/status RPCs + batched informer polls "
                         "(bindings identical to per-call; 'off' to debug)")
    ap.add_argument("--mesh", default="off", choices=["on", "off", "auto"],
                    help="shard the node axis over a device mesh "
                         "(Scheduler(mesh=…)): sharded resident node block "
                         "+ SPMD engines; assignments bit-identical to "
                         "single-device, 'on' requires >1 device")
    ap.add_argument("--flight-recorder", default="on", choices=["on", "off"],
                    help="scheduling flight recorder + per-pod staged "
                         "latency attribution (decision records, "
                         "staged_latency_ms/soak fields); 'off' is the "
                         "overhead escape hatch")
    ap.add_argument("--fullstack", action="store_true",
                    help="drive the workload through the FULL stack: an "
                         "in-process REST apiserver + RemoteStore + "
                         "informers + HTTP binds (the direct-vs-fullstack "
                         "delta is the apiserver tax)")
    ap.add_argument("--wire", default="binary", choices=["binary", "json"],
                    help="fullstack wire protocol: 'binary' negotiates the "
                         "compact binary codec via Accept/Content-Type "
                         "(bindings pod-for-pod identical to JSON); 'json' "
                         "is the escape hatch. The record embeds the codec "
                         "actually negotiated plus wire_bytes_per_pod")
    ap.add_argument("--watch-fanout", type=int, default=0,
                    help="fullstack only: N extra concurrent pod watchers "
                         "against the apiserver (the big-cluster watch "
                         "fan-out load the serialize-once body ring "
                         "exists for)")
    ap.add_argument("--telemetry", default="off", choices=["on", "off"],
                    help="fullstack only: run the full telemetry plane "
                         "alongside the workload — an HTTP collector, "
                         "traceparent on every RPC, both processes' "
                         "exporters on their cadence; the record embeds "
                         "span totals + the drop counter")
    ap.add_argument("--sentinel", default="off",
                    choices=["on", "off", "spike"],
                    help="fullstack or --trace: ride the anomaly "
                         "sentinel on the scheduler's cycle boundary "
                         "(run-scaled rule windows; the record embeds "
                         "its lifecycle stats and the clean/false-"
                         "positive verdict); 'spike' additionally "
                         "injects a one-shot scheduling stall mid-run "
                         "and reports the fire→bundle→resolve verdict. "
                         "With --trace the burn budget is the profile's "
                         "declared slo_budget_ms")
    ap.add_argument("--processes", type=int, default=0,
                    help="with --fullstack: run the apiserver and N "
                         "scheduler replicas as separate OS PROCESSES "
                         "under the launch supervisor "
                         "(kubetpu.launch.Cluster) — no shared GIL, "
                         "components talk only through the apiserver, and "
                         "the run joins on the store-verified exactly-"
                         "once binding parity (a miss FAILS the run). "
                         "0 = in-process modes below")
    ap.add_argument("--fanout-procs", type=int, default=0,
                    help="multi-process only: spread --watch-fanout over "
                         "M dedicated watch-driver processes (default: "
                         "one driver process when --watch-fanout > 0)")
    ap.add_argument("--persistence", default="off", metavar="DIR|off",
                    help="multi-process only: run the apiserver child "
                         "with --persistence DIR (WAL + snapshots); the "
                         "SIGTERM cascade rides the graceful close")
    ap.add_argument("--restart", default="on-failure:2",
                    metavar="never|on-failure[:max]",
                    help="multi-process only: per-scheduler supervisor "
                         "restart policy — a replica that dies is "
                         "respawned and re-federates")
    ap.add_argument("--artifacts-dir", default=None,
                    help="dump per-workload diagnosis artifacts here: the "
                         "cycle trace as Perfetto-loadable Chrome-trace "
                         "JSON, a /metrics snapshot, and the device-side "
                         "per-cycle counter records (joined by cycle id)")
    ap.add_argument("--trace", default=None, metavar="PROFILE",
                    help="replay a trace-shaped workload profile "
                         "(perf.workloads.TRACE_PROFILES; see --list) "
                         "instead of an op-list case: the record carries "
                         "admission_p99_ms vs the profile's SLO budget, "
                         "peak_rss_bytes, and the encode-cache re-encode "
                         "accounting. Honors --fullstack/--engine/"
                         "--max-batch/--wire")
    ap.add_argument("--trace-nodes", type=int, default=None,
                    help="override the trace profile's initial node count "
                         "(the 50k/100k scale-frontier rungs)")
    ap.add_argument("--trace-wall-budget", type=float, default=None,
                    help="hard wall budget (s) for the trace stage: past "
                         "it the replay stops and emits a TRUNCATED but "
                         "parseable record")
    args = ap.parse_args(argv)

    if args.list:
        for case in TEST_CASES.values():
            for wl in case.workloads:
                extra = f" threshold={wl.threshold}" if wl.threshold else ""
                print(f"{case.name}/{wl.name}{extra} {list(wl.labels)}")
        from .workloads import TRACE_PROFILES

        for tp in TRACE_PROFILES.values():
            print(f"trace:{tp.name} nodes={tp.nodes} "
                  f"slo={tp.slo_budget_ms}ms — {tp.description}")
        return

    if args.trace:
        from . import TRACE_PROFILES, run_workload_trace

        tp = TRACE_PROFILES[args.trace]
        if args.trace_nodes is not None:
            tp = tp.scaled(f"{args.trace_nodes}n", nodes=args.trace_nodes)
        r = run_workload_trace(
            tp,
            mode=("fullstack" if args.fullstack else "direct"),
            engine=args.engine,
            max_batch=args.max_batch,
            timeout_s=args.timeout,
            wall_budget_s=args.trace_wall_budget,
            encode_cache=(args.encode_cache == "on"),
            wire=args.wire,
            artifacts_dir=args.artifacts_dir,
            sentinel=(args.sentinel != "off"),
            sentinel_spike=(args.sentinel == "spike"),
        )
        print(json.dumps(r.to_json()))
        return

    kwargs = dict(
        max_batch=args.max_batch, timeout_s=args.timeout,
        engine=args.engine, artifacts_dir=args.artifacts_dir,
        pipeline=(args.pipeline == "on"),
        encode_cache=(args.encode_cache == "on"),
        bulk=(args.bulk == "on"),
        mesh=args.mesh,   # resolve_mesh handles on/off/auto
        flight_recorder=(args.flight_recorder == "on"),
    )
    if args.processes:
        # the honest deployment shape: real OS processes (acceptance:
        # python -m kubetpu.perf --fullstack --processes N)
        if not args.fullstack:
            ap.error("--processes requires --fullstack (there is no "
                     "direct-mode multi-process deployment)")
        case = TEST_CASES[args.case]
        workloads = (
            [w for w in case.workloads if w.name == args.workload]
            if args.workload else list(case.workloads)
        )
        for wl in workloads:
            r = run_workload_multiprocess(
                case, wl,
                replicas=args.processes,
                wire=args.wire,
                engine=args.engine,
                max_batch=args.max_batch,
                timeout_s=args.timeout,
                bulk=(args.bulk == "on"),
                persistence=(
                    None if args.persistence == "off" else args.persistence
                ),
                telemetry=(args.telemetry == "on"),
                watch_fanout=args.watch_fanout,
                fanout_procs=args.fanout_procs,
                restart=args.restart,
            )
            print(json.dumps(r.to_json()))
        return
    if args.fullstack:
        from . import run_workload_full_stack

        case = TEST_CASES[args.case]
        workloads = (
            [w for w in case.workloads if w.name == args.workload]
            if args.workload else list(case.workloads)
        )
        for wl in workloads:
            r = run_workload_full_stack(
                case, wl, wire=args.wire, watch_fanout=args.watch_fanout,
                telemetry=(args.telemetry == "on"),
                sentinel=(args.sentinel != "off"),
                sentinel_spike=(args.sentinel == "spike"),
                **kwargs,
            )
            print(json.dumps(r.to_json()))
        return
    if args.label:
        for r in run_label(args.label, **kwargs):
            print(json.dumps(r.to_json()))
        return

    case = TEST_CASES[args.case]
    workloads = (
        [w for w in case.workloads if w.name == args.workload]
        if args.workload else list(case.workloads)
    )
    for wl in workloads:
        r = run_workload(case, wl, **kwargs)
        print(json.dumps(r.to_json()))


if __name__ == "__main__":
    main()
