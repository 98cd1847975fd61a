"""Headline benchmarks through the REAL scheduler loop.

Each stage drives one (scheduler_perf case, workload, engine) triple through
``kubetpu.perf.runner.run_workload`` — the full loop: queue (backoff/hints),
cache/incremental snapshot, host encode, device assign (greedy scan or
batched rounds), async bind dispatch — and prints ONE JSON line with the
bind-time SchedulingThroughput average and p99 attempt latency, exactly the
metric the reference asserts thresholds on
(test/integration/scheduler_perf/scheduler_perf.go:352-359).

Workloads and thresholds (BASELINE.md, reference performance-config.yaml):
- SchedulingPodAffinity 5000Nodes_5000Pods — 70 pods/s floor (the hardest
  quadratic workload, affinity/performance-config.yaml:96)
- TopologySpreading 5000Nodes_5000Pods — 460 pods/s
  (topology_spreading/performance-config.yaml:53)
- SchedulingBasic 5000Nodes_10000Pods — 680 pods/s
  (misc/performance-config.yaml:59)

Stages run hardest-thesis-first so a late failure cannot zero the round's
evidence; every line is flushed as it completes. XLA compilation happens
in a warmup before each measured phase (a long-lived scheduler compiles
once at startup — steady-state throughput is the comparable number; the
reference's Go binary is precompiled) and is additionally cached on disk
across runs via the JAX persistent compilation cache, placed by
``import kubetpu`` (JAX_COMPILATION_CACHE_DIR when set, else
``<checkout>/.jax_cache``).

This is a device benchmark: it needs a TPU. With none visible ``main()``
says what JAX found instead and exits non-zero — nothing runs on the CPU
under a device metric's name. A stage that raises still prints its line
(``value: 0.0`` plus ``error``) so later stages run, and the exit code is
then non-zero. The multi-process ladders pin their scheduler CHILDREN to
the CPU (``MP_CHILD_ENV``): they measure the control plane, not the chip.

The FINAL stdout line repeats the strongest quadratic-workload result under
the metric name ``BestQuadratic_…``; the full per-stage evidence is the
preceding lines.
"""

import json
import os
import sys
import time

import kubetpu  # enables x64, places the compile cache

# (case, workload, engine, mode, max_batch, pipeline, bulk, mesh); ordered: quadratic/
# batched evidence first. "fullstack" drives the SAME op list through an
# in-process REST apiserver + RemoteStore + informers + HTTP binds — the
# reference harness's own shape (util.go:96) — so the direct-vs-fullstack
# delta (the apiserver tax) is measured, not assumed. pipeline=True runs the
# two-stage pipelined cycle (device-resident node block + delta uploads);
# each serial/pipelined pair on the same workload feeds one
# PipelineComparison line (cycles/sec up, transfer-bytes/cycle down), each
# bulk/nobulk fullstack pair feeds one APIPlaneComparison line
# (rpcs_per_scheduled_pod down ≥5×, the API-plane acceptance evidence), and
# each mesh/nomesh pair at fixed cluster size feeds one ShardingComparison
# line (1-chip vs N-chip pods/s — the mesh-sharded-assignment evidence).
STAGES = [
    ("SchedulingPodAffinity", "5000Nodes_5000Pods", "batched", "direct", 1024, False, True, False),
    ("SchedulingBasic", "5000Nodes_10000Pods", "batched", "direct", 1024, True, True, False),
    ("SchedulingBasic", "5000Nodes_10000Pods", "batched", "direct", 1024, False, True, False),
    ("TopologySpreading", "5000Nodes_5000Pods", "batched", "direct", 1024, False, True, False),
    ("SchedulingBasic", "5000Nodes_10000Pods", "greedy", "direct", 1024, True, True, False),
    ("SchedulingBasic", "5000Nodes_10000Pods", "greedy", "direct", 1024, False, True, False),
    ("SchedulingBasic", "5000Nodes_10000Pods", "greedy", "fullstack", 1024, False, True, False),
    ("SchedulingPodAffinity", "5000Nodes_5000Pods", "batched", "fullstack", 1024, False, True, False),
    # the r05-comparable fullstack rows (the encode-cache acceptance is
    # judged against r05's 500-node cpu numbers: 503.7 and 279.9);
    # the bulk/nobulk 500Nodes pair is the APIPlaneComparison evidence
    ("SchedulingBasic", "500Nodes", "greedy", "fullstack", 128, False, True, False),
    ("SchedulingBasic", "500Nodes", "greedy", "fullstack", 128, False, False, False),
    # the flight-recorder overhead budget (<5% fullstack throughput): the
    # SAME judged fullstack row with --flight-recorder off; the pair feeds
    # one FlightRecorderOverhead comparison line (9th tuple slot = off)
    ("SchedulingBasic", "500Nodes", "greedy", "fullstack", 128, False, True, False, False),
    ("SchedulingPodAffinity", "500Nodes", "batched", "fullstack", 128, False, True, False),
    # the encode-cache win measured beyond the 2 classic fullstack rows:
    # spreading through the stack, and recreate-churn driving the
    # informer→invalidate→re-encode path end to end
    ("TopologySpreading", "5000Nodes_5000Pods", "greedy", "fullstack", 1024, False, True, False),
    ("SchedulingWithMixedChurn", "5000Nodes_10000Pods", "greedy", "fullstack", 1024, False, True, False),
    ("SchedulingWithMixedChurn", "5000Nodes_10000Pods", "greedy", "direct", 1024, False, True, False),
    # the utilization-vs-throughput frontier (PR 19): the skewed-size +
    # priority-tier bin-pack workload once per engine — the three rows feed
    # one PackingComparison_* line per (workload, mode): packing must cut
    # nodes_used_at_steady_state ≥10% vs greedy while holding ≥0.8× the
    # batched engine's pods/s (the acceptance frontier), with the
    # priority_slo_hit_rate and warm-solver solver_iters_per_cycle evidence
    # riding every packing row
    ("BinPacking", "1000Nodes_3000Pods", "greedy", "direct", 256, False, True, False),
    ("BinPacking", "1000Nodes_3000Pods", "batched", "direct", 256, False, True, False),
    ("BinPacking", "1000Nodes_3000Pods", "packing", "direct", 256, False, True, False),
    ("BinPacking", "200Nodes", "greedy", "fullstack", 128, False, True, False),
    ("BinPacking", "200Nodes", "batched", "fullstack", 128, False, True, False),
    ("BinPacking", "200Nodes", "packing", "fullstack", 128, False, True, False),
    # the mesh tier AFTER every previously-judged acceptance row (each 15k
    # stage can burn its full 300s timeout — it must not push judged rows
    # past the budget cutoff): 15k nodes — the cluster size one chip can't
    # hold comfortably — sharded over the mesh vs single-chip
    ("SchedulingBasic", "15000Nodes", "batched", "direct", 1024, False, True, True),
    ("SchedulingBasic", "15000Nodes", "batched", "direct", 1024, False, True, False),
    ("TopologySpreading", "5000Nodes_5000Pods", "greedy", "direct", 1024, False, True, False),
    ("SchedulingPodAffinity", "5000Nodes_5000Pods", "greedy", "direct", 1024, True, True, False),
    ("SchedulingPodAffinity", "5000Nodes_5000Pods", "greedy", "direct", 1024, False, True, False),
]
TOTAL_BUDGET_S = 1500.0     # skip remaining stages past this
STAGE_TIMEOUT_S = 300.0     # per-phase settle timeout inside the runner

# --- active-active federation ladder (sched.federation) --------------------
# N full scheduler replicas (each on its own loop thread) against ONE
# in-process apiserver, on the r05-judged fullstack row: the HA scaling
# curve ROADMAP item 3 has named since PR 6. The race-mode ladder measures
# conflict rate vs throughput as overlap grows (1 replica = the ladder's
# baseline); the recovery stage kills a replica mid-bench and measures the
# survivors re-absorbing its partition. Runs AFTER every previously-judged
# stage — its own budget so the required FederationScaling_* evidence
# always lands.
FEDERATION_CASE = ("SchedulingBasic", "500Nodes", "greedy", 128)
FEDERATION_LADDER = (1, 2, 4)
FEDERATION_MODE = "race"
FEDERATION_BUDGET_S = 420.0

# --- binary wire-protocol ladder (kubetpu.api.codec) ------------------------
# The fullstack 1k/2k/5k-node ladder under heavy watch fan-out (hundreds of
# concurrent watchers — the big-cluster load the serialize-once body ring +
# binary codec exist for), each rung run with --wire json AND --wire binary:
# per-rung records embed wire_codec/wire_bytes_per_pod, and each pair feeds
# one WireCodecComparison_* line (wire-byte reduction — acceptance ≥60% —
# plus fullstack throughput speedup and the PR-8 soak p99_flat verdict).
# The workload is control-plane-bound (the kernel is tiny); its own budget
# so the required evidence always lands.
WIRE_LADDER = (
    ("SchedulingBasic", "1000Nodes", "greedy", 256),
    ("SchedulingBasic", "2000Nodes", "greedy", 256),
    ("SchedulingBasic", "5000Nodes_1000Pods", "greedy", 256),
)
WIRE_FANOUT = 200
WIRE_BUDGET_S = 900.0

# --- durable control plane (kubetpu.store.wal) ------------------------------
# ROADMAP item 2's scenarios: crash/restart recovery at 5k nodes x 50k pods
# (half bound — the exactly-once parity check runs after recovery), the
# 200-watcher reconnect relist storm, and the steady-state WAL on/off
# overhead. Control-plane-bound (no device work), full-size shapes; own
# budget so the evidence always lands.
# benchdiff gates recovery_s and wal_overhead_frac.
DURABILITY_SHAPE = (5000, 50000)        # nodes, pods
DURABILITY_WATCHERS = 200
DURABILITY_BUDGET_S = 240.0
#: the durability ladder's measured cold-recovery wall, stashed for the
#: replicated-failover stage's hot-vs-cold verdict (filled when the
#: CrashRecovery stage runs; the failover stage re-measures inline when
#: it ran first or the durability stage failed)
_COLD_RECOVERY: dict = {}

# --- multi-process control plane (kubetpu.launch) ---------------------------
# THE honest deployment shape (ROADMAP item 1): apiserver + N scheduler
# replicas as REAL OS processes under the launch supervisor — no shared
# GIL, components talk only through the apiserver, every record joins on
# the store-verified exactly-once binding parity (a miss ERRORS the stage;
# benchdiff treats that as a regression). Two ladders, each with its own
# budget so the deferred headlines always land:
# - FederationScaling_mp_{1,2,4}sched on the judged 500-node fullstack row
#   (the real N-replica speedup + conflict curve PR 9 deferred), plus a
#   replica-kill recovery stage where the supervisor's restart policy
#   respawns the victim and it re-federates mid-run;
# - WireCodecComparison_mp_{1k,2k,5k} — binary vs JSON with the 200-watcher
#   fan-out load carried by SEPARATE watch-driver processes (the honest run
#   at PR 10's >=10x-at-5k wire claim).
# Children always pin JAX_PLATFORMS=cpu: a TPU host is single-owner
# (libtpu), so N scheduler processes cannot share it — the mp ladders
# measure the CONTROL PLANE; the kernel tier is measured direct-mode above.
MP_CHILD_ENV = {"JAX_PLATFORMS": "cpu"}
MP_FEDERATION_CASE = ("SchedulingBasic", "500Nodes", "greedy", 128)
MP_FEDERATION_LADDER = (1, 2, 4)
MP_FEDERATION_MODE = "race"
MP_FEDERATION_BUDGET_S = 600.0
MP_WIRE_LADDER = (
    ("SchedulingBasic", "1000Nodes", "greedy", 256),
    ("SchedulingBasic", "2000Nodes", "greedy", 256),
    ("SchedulingBasic", "5000Nodes_1000Pods", "greedy", 256),
)
MP_WIRE_FANOUT = 200
MP_WIRE_FANOUT_PROCS = 4
MP_WIRE_BUDGET_S = 900.0

# --- replicated read plane (kubetpu.store.replication) ----------------------
# The WAL log-shipping plane's two headline claims, both under REAL OS
# processes:
# - ReadScaling_mp_{1,2,4}api: the judged 5k-node fullstack row with the
#   200-watcher fan-out load, once per apiserver count — with followers
#   present the Cluster round-robins the watch drivers over them, so the
#   leader keeps its cycles for writers; each rung carries the PEAK
#   follower replication lag sampled over the measured window
#   (follower_lag_ms — the read plane's honesty counter), and each >1
#   rung's line carries throughput_speedup vs the 1-apiserver baseline
#   (benchdiff's speedup gate);
# - ReplicatedFailover_* / FailoverVsColdRecovery_*: the 5k x 50k write
#   storm through a 3-apiserver plane, leader SIGKILLed after the
#   followers catch up — failover_to_serving_s (kill -> a follower wins
#   the lease by log position AND serves reads AND accepts a write) must
#   come in strictly under the durability ladder's cold CrashRecovery
#   recovery_s wall (the verdict line benchdiff gates with no tolerance).
# Children pin JAX_PLATFORMS=cpu like every mp ladder.
READ_PLANE_CASE = ("SchedulingBasic", "5000Nodes_1000Pods", "greedy", 256)
READ_PLANE_LADDER = (1, 2, 4)
# covers the 3-rung star ladder plus the chained 4api rung (PR-18's
# leader-egress evidence rides the same shape with --replication-chain)
READ_PLANE_BUDGET_S = 1200.0
FAILOVER_LEASE_S = 0.5
FAILOVER_APISERVERS = 3

# --- scale frontier: trace-shaped workloads (ROADMAP item 5) ----------------
# Seeded deterministic traces (perf.workloads.TRACE_PROFILES) replayed
# against the real loop in DIRECT mode: diurnal arrivals + flash-crowd
# bursts, autoscaler node add/drain waves (append-incremental encode +
# scoped cache extension + incremental reshard), rolling-update trains, and
# the mixed multi-tenant profile — each record carries admission_p99_ms vs
# its declared SLO budget, peak_rss_bytes, encode-cache hit rate and the
# re-encode accounting, all benchdiff-gated. The 50k/100k rungs are the
# first bench evidence past 15k nodes; every rung has a HARD wall budget —
# a rung that blows it emits a TRUNCATED but parseable record instead of
# eating the bench wall (benchdiff flags newly-truncated stages).
# (profile, suffix, {nodes + param overrides}, max_batch, engine, wall_s
#  [, mode]) — mode defaults to "direct"; "fullstack" replays through the
# REST apiserver + informers so enqueue→bind spans the whole control plane
TRACE_STAGES = [
    ("diurnal-burst", "5k", dict(nodes=5000), 128, "greedy", 180.0),
    ("node-wave", "5k", dict(nodes=5000, wave_nodes=512, ramp_s=3.0),
     128, "greedy", 180.0),
    ("rolling-update", "2k", dict(nodes=2000), 128, "greedy", 150.0),
    ("multitenant", "2k", dict(nodes=2000), 128, "greedy", 180.0),
    # the packing rung on the PR-14 mixed-tenant trace: priority tiers +
    # gangs + spread under churn through the constraint solver — the
    # record's solver_iters_per_cycle is the warm-start-under-churn
    # evidence benchdiff gates (+50%)
    ("multitenant", "2k-packing", dict(nodes=2000), 128, "packing", 180.0),
    # the scale rungs: 50k direct (burst + node-wave — the acceptance
    # pair), then the 100k attempt (expected to brush its wall on small
    # hosts; the truncated record is the honest evidence). Budgets are
    # per-RUNG, calibrated ~2x this host's first measured p99 so slo_ok
    # flags real decay, not run noise
    ("diurnal-burst", "50k",
     dict(nodes=50000, duration_s=20.0, base_rate=15.0, peak_rate=80.0,
          bursts=2, burst_pods=100, slo_budget_ms=8000.0),
     128, "greedy", 420.0),
    ("node-wave", "50k",
     dict(nodes=50000, duration_s=20.0, pod_rate=25.0, waves=1,
          wave_nodes=1000, ramp_s=4.0, slo_budget_ms=6000.0),
     128, "greedy", 420.0),
    ("diurnal-burst", "100k",
     dict(nodes=100000, duration_s=15.0, base_rate=10.0, peak_rate=50.0,
          bursts=1, burst_pods=100, slo_budget_ms=12000.0),
     128, "greedy", 420.0),
    # the first FULLSTACK 50k rung (ROADMAP 5a): the same burst shape
    # through the REST apiserver + informers — the control-plane trace
    # tax the direct rung cannot see. The budget is looser than the
    # direct rung's because every arrival is an RPC and every bind a
    # watch round trip; the wall cap keeps a blowout truncated-but-
    # parseable like the 100k attempt
    ("diurnal-burst", "50k-fs",
     dict(nodes=50000, duration_s=20.0, base_rate=15.0, peak_rate=80.0,
          bursts=2, burst_pods=100, slo_budget_ms=15000.0),
     128, "greedy", 600.0, "fullstack"),
    # --- PR-20 topology rungs: rack/slice-labeled fleets through the
    # gang placement stack. A "topology" override key flips the
    # scheduler's --topology mode per rung (popped before scaled(), like
    # nodes). Each record carries slices_free_at_steady_state,
    # fragmentation_index and gang_admission_p99_ms (benchdiff-gated).
    # slice-fragmentation runs as an on/off PAIR on the same seeded
    # trace — the free-slice delta between the two records is the
    # fragmentation-avoidance evidence.
    ("train-serve-churn", "512", dict(nodes=512, topology="on"),
     64, "greedy", 240.0),
    ("slice-fragmentation", "on", dict(nodes=256, topology="on"),
     64, "greedy", 200.0),
    ("slice-fragmentation", "off", dict(nodes=256),
     64, "greedy", 200.0),
    ("gang-contention", "128", dict(nodes=128, topology="on"),
     64, "greedy", 180.0),
]
TRACE_BUDGET_S = 3200.0  # raised for the four PR-20 topology rungs

# --- list/relist at scale (paginated watch-cache reads) ---------------------
# ListScaling_{5k,20k,50k}Nodes: K full informer relists (RemoteStore paged
# walks — limit/continue pages pinned to one snapshot rv) over an apiserver
# holding N nodes; each rung records the per-relist wall p99 (list_p99_ms,
# benchdiff-gated +50% AND >100ms), bytes/relist and pages/relist off the
# client's relist accounting (bytes_per_relist gated +50%), and the max
# single page shipped. Every walk is parity-checked in the runner — a
# dropped/duplicated key raises, it never lands as a slow green number.
# (nodes, relists, wall_s)
LIST_SCALING_LADDER = (
    (5000, 12, 90.0),
    (20000, 8, 150.0),
    (50000, 5, 240.0),
)
LIST_SCALING_BUDGET_S = 480.0

# --- trace vs the mp lease federation (ROADMAP 5b) --------------------------
# One rung: the diurnal-burst arrival shape paced through the admin
# RemoteStore against 2 REAL scheduler processes in lease partition, with a
# forced handover — the last replica SIGKILLed at the trace midpoint, the
# supervisor respawning it and its keyspace riding a lease takeover — so the
# record's admission_p99_ms SPANS the handover (the SLO price of losing a
# federated scheduler under live trace load; benchdiff gates it against the
# declared budget). Shape is modest (mp children are the cost); the budget
# absorbs the lease-expiry gap a handover inserts.
# arrival shape sized UNDER this host's measured mp capacity (~25 pods/s
# across 2 lease schedulers) so admission p99 measures the burst + the
# forced handover stall, not an unbounded queue backlog
TRACE_FEDERATION_PROFILE = dict(
    nodes=1000, duration_s=15.0, base_rate=8.0, peak_rate=24.0,
    bursts=1, burst_pods=60, slo_budget_ms=20000.0,
)
TRACE_FEDERATION_BUDGET_S = 420.0

# --- telemetry plane (kubetpu.telemetry) ------------------------------------
# The <5% overhead budget for the FULL telemetry plane — collector over
# HTTP, traceparent on every RPC, 1 s export cadence from both processes —
# measured as an on/off pair on the judged 500-node fullstack row; one
# TelemetryOverhead_* line per pair (within_budget = ratio >= 0.95,
# spans_dropped asserted zero), benchdiff-gated via telemetry_overhead_frac.
TELEMETRY_CASE = ("SchedulingBasic", "500Nodes", "greedy", 128)
TELEMETRY_BUDGET_S = 240.0

# --- anomaly sentinel (kubetpu.telemetry.sentinel) --------------------------
# Two stages. (1) SentinelOverhead_*: the sentinel riding the judged 500-node
# fullstack row's cycle boundary (bench-scaled rule windows, 0.25 s cadence)
# vs off — <5% budget (within_budget = ratio >= 0.95), benchdiff-gated via
# sentinel_overhead_frac, and the on-half's run must be CLEAN (zero alerts
# fired — the false-positive assert; the admission burn rule stays dormant on
# the bulk-create row because it declares no slo_budget_ms, so the verdict
# covers the budget-free outlier/ratio rules that ARE live). (2)
# SentinelSpike_*: a paced trace replay (declared slo_budget_ms — the honest
# venue: bulk-create tail queue-wait blows any fixed budget even when healthy)
# with a one-shot 6 s scheduler stall injected a third of the way through;
# value=1.0 iff the full fire→bundle→resolve chain held.
SENTINEL_BUDGET_S = 420.0
SENTINEL_SPIKE_PROFILE = dict(
    nodes=1000, duration_s=12.0, base_rate=20.0, peak_rate=60.0,
    bursts=1, burst_pods=50, slo_budget_ms=2000.0,
)

QUADRATIC = {"SchedulingPodAffinity", "TopologySpreading"}


def _status(msg: str) -> None:
    print(f"## bench: {msg}", file=sys.stderr, flush=True)


def _backend() -> str:
    import jax

    return jax.default_backend()


#: metric names of the stages that emitted an ``error`` line — a stage that
#: raises still prints its record (so later stages run and the evidence is
#: whole), and ``main()`` turns a non-empty list into a non-zero exit
FAILED: list = []


def _emit(line: dict) -> None:
    if "error" in line:
        FAILED.append(line.get("metric", "?"))
    print(json.dumps(line), flush=True)


def run_stage(
    case: str, workload: str, engine: str,
    mode: str = "direct", max_batch: int = 1024,
    profile_dir: str | None = None,
    pipeline: bool = False,
    bulk: bool = True,
    mesh: bool = False,
    flight_recorder: bool = True,
    wire: str = "binary",
    watch_fanout: int = 0,
    telemetry: bool = False,
    sentinel: bool = False,
) -> dict:
    import contextlib

    from kubetpu.perf.runner import (
        round_latency_ms,
        run_workload,
        run_workload_full_stack,
    )

    runner = run_workload if mode == "direct" else run_workload_full_stack
    ctx: "contextlib.AbstractContextManager" = contextlib.nullcontext()
    if profile_dir is not None:
        # XLA device trace of the measured stage (where device time goes —
        # view with xprof/tensorboard); recorded alongside BENCH results
        from kubetpu.tracing import device_profile

        ctx = device_profile(profile_dir)
    # per-stage diagnosis artifacts (Chrome trace + /metrics snapshot +
    # device cycle records) land next to the bench JSON; set
    # BENCH_ARTIFACTS_DIR= (empty) to disable
    artifacts_dir = os.environ.get(
        "BENCH_ARTIFACTS_DIR", "bench_artifacts"
    ) or None
    extra = {}
    if mode != "direct":
        # the wire seam exists only on the REST hop: direct mode has no
        # apiserver, so the flags stay out of its runner call
        extra = {"wire": wire, "watch_fanout": watch_fanout,
                 "telemetry": telemetry, "sentinel": sentinel}
    t0 = time.perf_counter()
    with ctx:
        r = runner(
            case, workload, engine=engine, timeout_s=STAGE_TIMEOUT_S,
            max_batch=max_batch, artifacts_dir=artifacts_dir,
            pipeline=pipeline, bulk=bulk,
            mesh=("auto" if mesh else None),
            flight_recorder=flight_recorder,
            **extra,
        )
    wall = time.perf_counter() - t0
    suffix = "" if mode == "direct" else "_fullstack"
    if pipeline:
        suffix += "_pipelined"
    if not bulk:
        suffix += "_nobulk"
    if mesh:
        suffix += "_mesh"
    if not flight_recorder:
        suffix += "_norecorder"
    if mode != "direct" and wire != "binary":
        suffix += "_jsonwire"
    if watch_fanout:
        suffix += f"_{watch_fanout}watchers"
    if telemetry:
        suffix += "_telemetry"
    if sentinel:
        suffix += "_sentinel"
    out = {
        "metric": f"{case}_{workload}_{engine}{suffix}",
        "value": round(r.throughput, 1),
        "unit": "pods/s",
        "vs_baseline": (
            round(r.vs_threshold, 2) if r.vs_threshold is not None else None
        ),
        "threshold": r.threshold,
        "scheduled": r.scheduled,
        "measure_pods": r.measure_pods,
        "duration_s": round(r.duration_s, 2),
        "cycles": r.cycles,
        "engine": engine,
        "mode": mode,
        "backend": _backend(),
        "wall_s": round(wall, 1),
    }
    if pipeline:
        out["pipeline"] = True
    if not bulk:
        out["bulk"] = False
    if mesh:
        # self-describing multichip evidence: how many devices the stage
        # actually sharded over ("auto" quietly runs 1-chip when nothing
        # else is visible — the record must say so)
        out["n_devices"] = r.n_devices
        out["mesh_shape"] = list(r.mesh_shape)
        if r.collective_wall_s is not None:
            out["collective_wall_s"] = round(r.collective_wall_s, 6)
    # the API-plane acceptance metrics (fullstack): round trips per
    # scheduled pod + the dispatcher's mean bulk micro-batch size
    if r.rpcs_per_scheduled_pod is not None:
        # 4 decimals: the best bulk runs land WELL under 0.01 RPCs/pod and
        # a 2-decimal round would zero out the comparison's denominator
        out["rpcs_per_scheduled_pod"] = round(r.rpcs_per_scheduled_pod, 4)
    # the wire-protocol acceptance metrics (fullstack): the codec the
    # client actually NEGOTIATED (a fallback shows as "json", not as a
    # silently slow binary run) + apiserver payload bytes per scheduled pod
    if r.wire_codec:
        out["wire_codec"] = r.wire_codec
    if r.wire_bytes_per_pod is not None:
        out["wire_bytes_per_pod"] = round(r.wire_bytes_per_pod, 1)
    if r.watch_fanout:
        out["watch_fanout"] = r.watch_fanout
    if r.dispatcher_batch_mean is not None:
        out["dispatcher_batch_mean"] = round(r.dispatcher_batch_mean, 1)
    if r.dispatcher_errors:
        out["dispatcher_errors"] = r.dispatcher_errors
    if r.cycles_per_sec is not None:
        out["cycles_per_sec"] = round(r.cycles_per_sec, 2)
    if r.transfer_bytes_per_cycle is not None:
        out["transfer_bytes_per_cycle"] = round(r.transfer_bytes_per_cycle)
    if r.batch_bytes_per_cycle is not None:
        out["batch_bytes_per_cycle"] = round(r.batch_bytes_per_cycle)
    if r.resident_bytes:
        out["resident_bytes"] = r.resident_bytes
    if r.pipeline_replays:
        out["pipeline_replays"] = r.pipeline_replays
    # host-encode evidence: per-cycle encode wall, its share of the cycle
    # (tentpole target ≤ 0.40; r05 fullstack trace showed 0.86), hit rate
    if r.encode_ms_per_cycle is not None:
        out["encode_ms_per_cycle"] = round(r.encode_ms_per_cycle, 2)
    if r.encode_wall_frac is not None:
        out["encode_wall_frac"] = round(r.encode_wall_frac, 3)
    if r.encode_cache_hit_rate is not None:
        out["encode_cache_hit_rate"] = round(r.encode_cache_hit_rate, 4)
    if r.threshold_note:
        out["threshold_note"] = r.threshold_note
    # the packing-frontier evidence (PR 19): steady-state node footprint,
    # high-priority admission rate, warm-started solver iterations, and
    # the exact weight vector the run solved under (reproducibility)
    if r.nodes_used_at_steady_state is not None:
        out["nodes_used_at_steady_state"] = r.nodes_used_at_steady_state
    if r.priority_slo_hit_rate is not None:
        out["priority_slo_hit_rate"] = round(r.priority_slo_hit_rate, 4)
    if r.solver_iters_per_cycle is not None:
        out["solver_iters_per_cycle"] = round(r.solver_iters_per_cycle, 2)
    if r.packing_weights is not None:
        out["packing_weights"] = r.packing_weights
    if r.p99_attempt_latency_ms is not None:
        # rounded in ONE place (perf.runner.round_latency_ms), identically
        # to WorkloadResult.to_json — benchdiff between a runner emission
        # and a bench emission must never see a phantom rounding delta
        out["p99_attempt_latency_ms"] = round_latency_ms(
            r.p99_attempt_latency_ms
        )
    if r.staged_latency_ms is not None:
        # the per-pod attribution vector (queue_wait/encode/kernel/dispatch/
        # bind_rtt/e2e, + api_ingest/informer through the full stack):
        # where the p99 went, not just what it was
        out["staged_latency_ms"] = r.staged_latency_ms
    if r.soak is not None:
        out["soak"] = r.soak
    if not flight_recorder:
        out["flight_recorder"] = False
    if r.telemetry is not None:
        # the telemetry-plane evidence: span totals + the drop counter
        # the TelemetryOverhead gate asserts stayed zero
        out["telemetry"] = r.telemetry
    if r.sentinel is not None:
        # the anomaly-sentinel evidence: lifecycle counters + the alert
        # list the zero-false-positive gate reads (clean run => clean)
        out["sentinel"] = r.sentinel
    if r.metrics_snapshot is not None:
        # post-run metrics snapshot (p50/p99 from the scheduler histograms,
        # schedule_attempts by result): every BENCH line carries its own
        # diagnosis instead of pointing at a scrape that no longer exists
        out["metrics"] = r.metrics_snapshot
    if r.artifacts:
        out["artifacts"] = r.artifacts
    return out


def _emit_pipeline_comparisons(done: dict) -> None:
    """One PipelineComparison line per (case, workload, engine, mode) that
    ran BOTH serial and pipelined: the tentpole's acceptance evidence —
    cycles/sec up, transfer-bytes/cycle down, throughput side by side —
    embedded in the bench artifact itself."""
    for key, pair in sorted(done.items()):
        ser, pipe = pair.get(False), pair.get(True)
        if not ser or not pipe or "error" in ser or "error" in pipe:
            continue
        case, workload, engine, mode, _bulk = key
        line = {
            "metric": f"PipelineComparison_{case}_{workload}_{engine}",
            "unit": "ratio",
            "mode": mode,
            "backend": ser.get("backend"),
            "serial": {
                k: ser.get(k) for k in (
                    "value", "cycles_per_sec", "transfer_bytes_per_cycle",
                    "batch_bytes_per_cycle", "duration_s",
                ) if ser.get(k) is not None
            },
            "pipelined": {
                k: pipe.get(k) for k in (
                    "value", "cycles_per_sec", "transfer_bytes_per_cycle",
                    "batch_bytes_per_cycle", "resident_bytes",
                    "pipeline_replays", "duration_s",
                ) if pipe.get(k) is not None
            },
        }
        s_cps, p_cps = ser.get("cycles_per_sec"), pipe.get("cycles_per_sec")
        if s_cps and p_cps:
            line["cycles_per_sec_speedup"] = round(p_cps / s_cps, 3)
            line["value"] = round(p_cps / s_cps, 3)
        s_tb = ser.get("transfer_bytes_per_cycle")
        p_tb = pipe.get("transfer_bytes_per_cycle")
        if s_tb and p_tb:
            line["transfer_bytes_ratio"] = round(p_tb / s_tb, 4)
        if ser.get("value") and pipe.get("value"):
            line["throughput_speedup"] = round(pipe["value"] / ser["value"], 3)
        _emit(line)


def _emit_api_plane_comparisons(done: dict) -> None:
    """One APIPlaneComparison line per fullstack (case, workload, engine)
    that ran BOTH bulk and single-op: the API-plane acceptance evidence —
    rpcs_per_scheduled_pod dropping (target ≥5×) and throughput side by
    side — embedded in the bench artifact itself."""
    for key, pair in sorted(done.items()):
        single, bulked = pair.get(False), pair.get(True)
        if not single or not bulked or "error" in single or "error" in bulked:
            continue
        case, workload, engine, mode, _pipeline = key
        if mode != "fullstack":
            continue
        fields = (
            "value", "rpcs_per_scheduled_pod", "dispatcher_batch_mean",
            "duration_s",
        )
        line = {
            "metric": f"APIPlaneComparison_{case}_{workload}_{engine}",
            "unit": "ratio",
            "mode": mode,
            "backend": bulked.get("backend"),
            "single": {
                k: single.get(k) for k in fields
                if single.get(k) is not None
            },
            "bulk": {
                k: bulked.get(k) for k in fields
                if bulked.get(k) is not None
            },
        }
        s_rpc = single.get("rpcs_per_scheduled_pod")
        b_rpc = bulked.get("rpcs_per_scheduled_pod")
        if s_rpc is not None and b_rpc:   # b_rpc kept at 4 decimals; a
            #                               truthy check only guards ÷0
            line["rpcs_reduction"] = round(s_rpc / b_rpc, 2)
            line["value"] = round(s_rpc / b_rpc, 2)
        if single.get("value") and bulked.get("value"):
            line["throughput_speedup"] = round(
                bulked["value"] / single["value"], 3
            )
        _emit(line)


def _emit_flightrecorder_comparisons(done: dict) -> None:
    """One FlightRecorderOverhead line per (case, workload, engine, mode)
    that ran BOTH recorder-on and recorder-off: the <5% overhead budget's
    acceptance evidence — throughput on/off side by side with the measured
    overhead fraction — embedded in the bench artifact itself."""
    for key, pair in sorted(done.items()):
        on, off = pair.get(True), pair.get(False)
        if not on or not off or "error" in on or "error" in off:
            continue
        case, workload, engine, mode = key
        fields = ("value", "duration_s", "p99_attempt_latency_ms")
        line = {
            "metric": f"FlightRecorderOverhead_{case}_{workload}_{engine}",
            "unit": "ratio",
            "mode": mode,
            "backend": on.get("backend"),
            "recorder_on": {
                k: on.get(k) for k in fields if on.get(k) is not None
            },
            "recorder_off": {
                k: off.get(k) for k in fields if off.get(k) is not None
            },
        }
        if on.get("value") and off.get("value"):
            ratio = on["value"] / off["value"]
            line["value"] = round(ratio, 3)
            line["overhead_frac"] = round(max(1.0 - ratio, 0.0), 4)
            # the acceptance gate: recorder + tracing on costs <5%
            line["within_budget"] = ratio >= 0.95
        _emit(line)


def _emit_soak_lines(lines: list) -> None:
    """One SustainedChurn line per churn-case stage that produced a soak
    split: the ROADMAP-2 'p99 flat for minutes, not seconds' gate — first-
    vs second-half p99 with the flatness verdict."""
    for line in lines:
        soak = line.get("soak")
        if not soak or "Churn" not in line.get("metric", ""):
            continue
        _emit({
            "metric": f"SustainedChurn_{line['metric']}",
            "unit": "ratio",
            "value": soak.get("ratio"),
            "p99_first_half_ms": soak.get("p99_first_half_ms"),
            "p99_second_half_ms": soak.get("p99_second_half_ms"),
            "samples": soak.get("samples"),
            "p99_flat": soak.get("p99_flat"),
            "mode": line.get("mode"),
            "backend": line.get("backend"),
        })


def _emit_sharding_comparisons(done: dict) -> None:
    """One ShardingComparison line per (case, workload, engine, mode) that
    ran BOTH single-device and mesh-sharded at the same cluster size: the
    mesh tentpole's acceptance evidence — N-chip vs 1-chip pods/s speedup
    (or, on a virtual CPU mesh, the measured scaling curve with the
    collective tax), embedded in the bench artifact itself."""
    for key, pair in sorted(done.items()):
        single, meshed = pair.get(False), pair.get(True)
        if not single or not meshed or "error" in single or "error" in meshed:
            continue
        case, workload, engine, mode, _pl, _bulk = key
        fields = ("value", "cycles_per_sec", "duration_s")
        line = {
            "metric": f"ShardingComparison_{case}_{workload}_{engine}",
            "unit": "ratio",
            "mode": mode,
            "backend": meshed.get("backend"),
            "n_devices": meshed.get("n_devices"),
            "mesh_shape": meshed.get("mesh_shape"),
            "collective_wall_s": meshed.get("collective_wall_s"),
            "single": {
                k: single.get(k) for k in fields
                if single.get(k) is not None
            },
            "mesh": {
                k: meshed.get(k) for k in fields
                if meshed.get(k) is not None
            },
        }
        if single.get("value") and meshed.get("value"):
            line["throughput_speedup"] = round(
                meshed["value"] / single["value"], 3
            )
            line["value"] = line["throughput_speedup"]
        _emit(line)


def _emit_packing_comparisons(trios: dict) -> None:
    """One PackingComparison line per (case, workload, mode) that ran the
    greedy baseline AND the packing engine (batched joins when its row
    ran): the utilization-vs-throughput frontier — nodes_reduction vs
    greedy (acceptance ≥0.10), pods/s vs the batched engine (acceptance
    ≥0.8×), priority hit rate side by side, and the warm-started solver's
    iterations/cycle — embedded in the bench artifact itself."""
    fields = (
        "value", "nodes_used_at_steady_state", "priority_slo_hit_rate",
        "solver_iters_per_cycle", "duration_s",
    )
    for key, by_engine in sorted(trios.items()):
        g, p = by_engine.get("greedy"), by_engine.get("packing")
        if not g or not p or "error" in g or "error" in p:
            continue
        case, workload, mode = key
        b = by_engine.get("batched")
        if b is not None and "error" in b:
            b = None
        line = {
            "metric": f"PackingComparison_{case}_{workload}",
            "unit": "ratio",
            "mode": mode,
            "backend": p.get("backend"),
            "greedy": {k: g.get(k) for k in fields
                       if g.get(k) is not None},
            "packing": {k: p.get(k) for k in fields
                        if p.get(k) is not None},
        }
        if b is not None:
            line["batched"] = {k: b.get(k) for k in fields
                               if b.get(k) is not None}
        if p.get("packing_weights") is not None:
            line["packing_weights"] = p["packing_weights"]
        g_nodes = g.get("nodes_used_at_steady_state")
        p_nodes = p.get("nodes_used_at_steady_state")
        if g_nodes and p_nodes is not None:
            # the ≥10% acceptance number: steady-state nodes saved
            line["nodes_reduction"] = round(1.0 - p_nodes / g_nodes, 4)
            line["value"] = line["nodes_reduction"]
        if g.get("value") and p.get("value"):
            line["throughput_vs_greedy"] = round(
                p["value"] / g["value"], 3
            )
        if b is not None and b.get("value") and p.get("value"):
            # the ≥0.8× acceptance number: pods/s held vs the fast engine
            line["throughput_vs_batched"] = round(
                p["value"] / b["value"], 3
            )
        _emit(line)


def _federation_record(r, case: str, workload: str, engine: str) -> dict:
    """One bench line for a federated run (the per-N evidence rows the
    FederationScaling lines are derived from)."""
    out = {
        "metric": (
            f"{case}_{workload}_{engine}_fullstack_"
            f"{r.replicas}sched_{r.partition}"
        ),
        "value": round(r.throughput, 1),
        "unit": "pods/s",
        "vs_baseline": (
            round(r.vs_threshold, 2) if r.vs_threshold is not None else None
        ),
        "threshold": r.threshold,
        "scheduled": r.scheduled,
        "measure_pods": r.measure_pods,
        "duration_s": round(r.duration_s, 2),
        "cycles": r.cycles,
        "engine": engine,
        "mode": "fullstack",
        "backend": _backend(),
        "replicas": r.replicas,
        "partition": r.partition,
        "conflicts": r.conflicts,
        "conflict_rate": round(r.conflict_rate or 0.0, 4),
        "binding_parity": r.binding_parity,
    }
    if r.threshold_note:
        out["threshold_note"] = r.threshold_note
    if r.rpcs_per_scheduled_pod is not None:
        out["rpcs_per_scheduled_pod"] = round(r.rpcs_per_scheduled_pod, 4)
    if r.lease_transitions:
        out["lease_transitions"] = r.lease_transitions
    if r.recovery_s is not None:
        out["recovery_s"] = round(r.recovery_s, 3)
    return out


def _run_wire_stages() -> None:
    """The binary-wire fullstack ladder (ROADMAP item 2): each rung runs
    the SAME workload through the REST apiserver with WIRE_FANOUT extra
    concurrent watchers, once per codec — binary (the negotiated compact
    wire) and json (the escape hatch) — and emits one
    WireCodecComparison_* line per rung: apiserver payload bytes per pod
    side by side (wire_bytes_reduction, acceptance ≥0.60), fullstack
    throughput speedup, and both runs' soak p99_flat verdicts."""
    t0 = time.perf_counter()
    for case, workload, engine, max_batch in WIRE_LADDER:
        if time.perf_counter() - t0 > WIRE_BUDGET_S:
            _status(f"wire budget exhausted; skipping {workload}")
            continue
        pair: dict[str, dict] = {}
        for wire in ("json", "binary"):
            elapsed = time.perf_counter() - t0
            if elapsed > WIRE_BUDGET_S:
                _status(f"wire budget exhausted; skipping {workload}/{wire}")
                continue
            _status(f"wire stage: {case}/{workload}/{engine} wire={wire} "
                    f"fanout={WIRE_FANOUT} (t={elapsed:.0f}s)")
            try:
                line = run_stage(
                    case, workload, engine, "fullstack", max_batch,
                    wire=wire, watch_fanout=WIRE_FANOUT,
                )
            except Exception as e:
                _emit({
                    "metric": (
                        f"{case}_{workload}_{engine}_fullstack"
                        f"{'_jsonwire' if wire != 'binary' else ''}"
                        f"_{WIRE_FANOUT}watchers"
                    ),
                    "value": 0.0, "unit": "pods/s", "vs_baseline": 0.0,
                    "engine": engine, "mode": "fullstack",
                    "backend": _backend(), "wire_codec": wire,
                    "watch_fanout": WIRE_FANOUT,
                    "error": f"{type(e).__name__}: {e}",
                })
                _status(f"wire stage FAILED: {workload}/{wire}: {e}")
                continue
            pair[wire] = line
            _emit(line)
            _status(f"wire stage done: {line['metric']} = {line['value']} "
                    f"pods/s ({line.get('wire_bytes_per_pod')} B/pod)")
        jsonl, binl = pair.get("json"), pair.get("binary")
        if not jsonl or not binl:
            continue
        fields = (
            "value", "wire_codec", "wire_bytes_per_pod", "duration_s",
            "p99_attempt_latency_ms",
        )
        comp = {
            "metric": f"WireCodecComparison_{case}_{workload}_{engine}",
            "unit": "ratio",
            "mode": "fullstack",
            "backend": binl.get("backend"),
            "watch_fanout": WIRE_FANOUT,
            "json": {k: jsonl.get(k) for k in fields
                     if jsonl.get(k) is not None},
            "binary": {k: binl.get(k) for k in fields
                       if binl.get(k) is not None},
            "soak_p99_flat": {
                "json": (jsonl.get("soak") or {}).get("p99_flat"),
                "binary": (binl.get("soak") or {}).get("p99_flat"),
            },
        }
        jb = jsonl.get("wire_bytes_per_pod")
        bb = binl.get("wire_bytes_per_pod")
        if jb and bb is not None:
            # the ≥60% acceptance number: payload bytes saved per pod
            comp["wire_bytes_reduction"] = round(1.0 - bb / jb, 4)
        if jsonl.get("value") and binl.get("value"):
            comp["throughput_speedup"] = round(
                binl["value"] / jsonl["value"], 3
            )
            comp["value"] = comp["throughput_speedup"]
        _emit(comp)


def _run_federation_stages() -> None:
    """The federation ladder + recovery stage: per-N bench rows, one
    FederationScaling_* line per rung (throughput speedup vs 1 replica,
    conflict rate, binding parity), and one FederationRecovery_* line from
    the replica-kill stage."""
    from kubetpu.perf.runner import run_workload_federated

    case, workload, engine, max_batch = FEDERATION_CASE
    t0 = time.perf_counter()
    ladder: dict[int, dict] = {}
    for n in FEDERATION_LADDER:
        if time.perf_counter() - t0 > FEDERATION_BUDGET_S:
            _status(f"federation budget exhausted; skipping {n}sched")
            continue
        _status(f"federation stage: {n} replica(s), {FEDERATION_MODE}")
        try:
            r = run_workload_federated(
                case, workload, replicas=n, partition=FEDERATION_MODE,
                engine=engine, max_batch=max_batch,
                timeout_s=STAGE_TIMEOUT_S,
            )
        except Exception as e:
            _emit({
                "metric": (
                    f"{case}_{workload}_{engine}_fullstack_"
                    f"{n}sched_{FEDERATION_MODE}"
                ),
                "value": 0.0, "unit": "pods/s", "vs_baseline": 0.0,
                "engine": engine, "mode": "fullstack",
                "backend": _backend(), "replicas": n,
                "partition": FEDERATION_MODE,
                "error": f"{type(e).__name__}: {e}",
            })
            continue
        line = _federation_record(r, case, workload, engine)
        ladder[n] = line
        _emit(line)
    base = ladder.get(1)
    for n in FEDERATION_LADDER:
        line = ladder.get(n)
        if line is None:
            continue
        scaling = {
            "metric": (
                f"FederationScaling_{case}_{workload}_"
                f"{FEDERATION_MODE}_{n}sched"
            ),
            "unit": "ratio",
            "replicas": n,
            "partition": FEDERATION_MODE,
            "backend": _backend(),
            "throughput": line["value"],
            "conflicts": line["conflicts"],
            "conflict_rate": line["conflict_rate"],
            "binding_parity": line["binding_parity"],
            "measure_pods": line["measure_pods"],
        }
        if base and base.get("value"):
            scaling["value"] = round(line["value"] / base["value"], 3)
            scaling["throughput_speedup"] = scaling["value"]
            scaling["baseline_throughput"] = base["value"]
        else:
            scaling["value"] = None
        _emit(scaling)
    # recovery stage: 2 replicas, hash partition (the dead replica's rank
    # re-absorbs immediately — the recovery time measures the survivors'
    # re-adoption + rescheduling, not a lease expiry floor), kill at 50%
    if time.perf_counter() - t0 <= FEDERATION_BUDGET_S:
        _status("federation stage: replica-kill recovery (2sched, hash)")
        try:
            r = run_workload_federated(
                case, workload, replicas=2, partition="hash",
                engine=engine, max_batch=max_batch,
                timeout_s=STAGE_TIMEOUT_S, kill_replica_at=0.5,
            )
            _emit({
                "metric": (
                    f"FederationRecovery_{case}_{workload}_hash_2sched"
                ),
                "unit": "s",
                "value": (
                    round(r.recovery_s, 3)
                    if r.recovery_s is not None else None
                ),
                "recovery_s": (
                    round(r.recovery_s, 3)
                    if r.recovery_s is not None else None
                ),
                "throughput": round(r.throughput, 1),
                "scheduled": r.scheduled,
                "measure_pods": r.measure_pods,
                "binding_parity": r.binding_parity,
                "all_rescheduled": r.binding_parity == r.measure_pods,
                "conflicts": r.conflicts,
                "replicas": 2,
                "partition": "hash",
                "backend": _backend(),
            })
        except Exception as e:
            _emit({
                "metric": (
                    f"FederationRecovery_{case}_{workload}_hash_2sched"
                ),
                "unit": "s", "value": None, "backend": _backend(),
                "error": f"{type(e).__name__}: {e}",
            })


def _mp_record(r, case: str, workload: str, engine: str,
               metric: str) -> dict:
    """One bench line for a multi-process run: the per-N evidence rows the
    FederationScaling_mp / WireCodecComparison_mp lines derive from —
    every one carries its process count, per-child peak RSS + CPU
    seconds, restart count, and the join-verified binding parity."""
    out = {
        "metric": metric,
        "value": round(r.throughput, 1),
        "unit": "pods/s",
        "vs_baseline": (
            round(r.vs_threshold, 2) if r.vs_threshold is not None else None
        ),
        "threshold": r.threshold,
        "scheduled": r.scheduled,
        "measure_pods": r.measure_pods,
        "duration_s": round(r.duration_s, 2),
        "engine": engine,
        "mode": "multiprocess",
        "backend": "cpu",               # MP_CHILD_ENV pins the children
        "replicas": r.replicas,
        "partition": r.partition,
        "conflicts": r.conflicts,
        "conflict_rate": round(r.conflict_rate or 0.0, 4),
        "binding_parity": r.binding_parity,
        "n_processes": r.n_processes,
        "restarts": r.restarts,
    }
    if r.threshold_note:
        out["threshold_note"] = r.threshold_note
    if r.child_stats is not None:
        out["child_stats"] = r.child_stats
    if r.rpcs_per_scheduled_pod is not None:
        out["rpcs_per_scheduled_pod"] = round(r.rpcs_per_scheduled_pod, 4)
    if r.wire_codec:
        out["wire_codec"] = r.wire_codec
    if r.wire_bytes_per_pod is not None:
        out["wire_bytes_per_pod"] = round(r.wire_bytes_per_pod, 1)
    if r.watch_fanout:
        out["watch_fanout"] = r.watch_fanout
    if r.lease_transitions:
        out["lease_transitions"] = r.lease_transitions
    if r.recovery_s is not None:
        out["recovery_s"] = round(r.recovery_s, 3)
    if r.apiservers > 1:
        out["apiservers"] = r.apiservers
        if r.follower_lag_ms is not None:
            out["follower_lag_ms"] = round(r.follower_lag_ms, 3)
        if r.follower_lag_records is not None:
            out["follower_lag_records"] = r.follower_lag_records
        if r.leader_replication_bytes is not None:
            out["leader_replication_bytes"] = round(
                r.leader_replication_bytes
            )
        if r.replication_chain:
            out["replication_chain"] = True
    return out


def _run_mp_federation_stages() -> None:
    """The cross-process federation ladder + supervisor-restart recovery
    stage: per-N rows, one FederationScaling_mp_* line per rung (REAL
    N-process speedup vs the 1-process baseline, conflict rate, parity),
    and one FederationRecovery_mp_* line from the kill stage (a SIGKILLed
    replica respawned by the restart policy, re-federating mid-run)."""
    from kubetpu.perf.runner import run_workload_multiprocess

    case, workload, engine, max_batch = MP_FEDERATION_CASE
    t0 = time.perf_counter()
    ladder: dict[int, dict] = {}
    for n in MP_FEDERATION_LADDER:
        if time.perf_counter() - t0 > MP_FEDERATION_BUDGET_S:
            _status(f"mp federation budget exhausted; skipping {n}sched")
            continue
        _status(f"mp federation stage: {n} scheduler process(es), "
                f"{MP_FEDERATION_MODE}")
        metric = (
            f"{case}_{workload}_{engine}_mp_{n}sched_{MP_FEDERATION_MODE}"
        )
        try:
            r = run_workload_multiprocess(
                case, workload, replicas=n, partition=MP_FEDERATION_MODE,
                engine=engine, max_batch=max_batch,
                timeout_s=STAGE_TIMEOUT_S, child_env=MP_CHILD_ENV,
            )
        except Exception as e:
            _emit({
                "metric": metric, "value": 0.0, "unit": "pods/s",
                "vs_baseline": 0.0, "engine": engine,
                "mode": "multiprocess", "backend": "cpu", "replicas": n,
                "partition": MP_FEDERATION_MODE,
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"mp federation stage FAILED ({n}sched): {e}")
            continue
        line = _mp_record(r, case, workload, engine, metric)
        ladder[n] = line
        _emit(line)
        _status(f"mp federation stage done: {metric} = {line['value']} "
                f"pods/s (conflict_rate={line['conflict_rate']})")
    base = ladder.get(1)
    for n in MP_FEDERATION_LADDER:
        line = ladder.get(n)
        if line is None:
            continue
        scaling = {
            "metric": (
                f"FederationScaling_mp_{case}_{workload}_"
                f"{MP_FEDERATION_MODE}_{n}sched"
            ),
            "unit": "ratio",
            "mode": "multiprocess",
            "replicas": n,
            "partition": MP_FEDERATION_MODE,
            "backend": "cpu",
            "throughput": line["value"],
            "conflicts": line["conflicts"],
            "conflict_rate": line["conflict_rate"],
            "binding_parity": line["binding_parity"],
            "measure_pods": line["measure_pods"],
            "n_processes": line["n_processes"],
        }
        if base and base.get("value"):
            scaling["value"] = round(line["value"] / base["value"], 3)
            scaling["throughput_speedup"] = scaling["value"]
            scaling["baseline_throughput"] = base["value"]
        else:
            scaling["value"] = None
        _emit(scaling)
    # lease-mode rung (ROADMAP item 1b): the SAME workload with the pod
    # keyspace partitioned by store-backed epoch-fenced leases across 2
    # REAL scheduler processes — measures the lease-handover cost (lease
    # acquisition/renewal riding the shared store) side by side with the
    # race/hash rungs above; conflict_rate should be ~0 (fenced keyspaces
    # don't race) and the delta vs the 2sched race rung is the price of
    # coordination
    if time.perf_counter() - t0 <= MP_FEDERATION_BUDGET_S:
        _status("mp federation stage: 2 scheduler processes, lease "
                "partition (handover-cost rung)")
        metric = f"{case}_{workload}_{engine}_mp_2sched_lease"
        try:
            r = run_workload_multiprocess(
                case, workload, replicas=2, partition="lease",
                engine=engine, max_batch=max_batch,
                timeout_s=STAGE_TIMEOUT_S, child_env=MP_CHILD_ENV,
            )
            line = _mp_record(r, case, workload, engine, metric)
            _emit(line)
            scaling = {
                "metric": (
                    f"FederationScaling_mp_{case}_{workload}_lease_2sched"
                ),
                "unit": "ratio",
                "mode": "multiprocess",
                "replicas": 2,
                "partition": "lease",
                "backend": "cpu",
                "throughput": line["value"],
                "conflicts": line["conflicts"],
                "conflict_rate": line["conflict_rate"],
                "lease_transitions": line.get("lease_transitions", 0),
                "binding_parity": line["binding_parity"],
                "measure_pods": line["measure_pods"],
                "n_processes": line["n_processes"],
            }
            if base and base.get("value"):
                scaling["value"] = round(line["value"] / base["value"], 3)
                scaling["throughput_speedup"] = scaling["value"]
                scaling["baseline_throughput"] = base["value"]
                race2 = ladder.get(2)
                if race2 and race2.get("value"):
                    # the handover cost headline: lease vs race at N=2
                    scaling["vs_race_2sched"] = round(
                        line["value"] / race2["value"], 3
                    )
            else:
                scaling["value"] = None
            _emit(scaling)
            _status(f"mp lease rung done: {metric} = {line['value']} "
                    f"pods/s (lease_transitions="
                    f"{line.get('lease_transitions', 0)})")
        except Exception as e:
            _emit({
                "metric": metric, "value": 0.0, "unit": "pods/s",
                "vs_baseline": 0.0, "engine": engine,
                "mode": "multiprocess", "backend": "cpu", "replicas": 2,
                "partition": "lease",
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"mp lease rung FAILED: {e}")
    # recovery stage: 2 scheduler processes, hash partition (static ranks
    # — the SUPERVISOR answers the death: SIGKILL at 50% of the measured
    # pods, the restart policy respawns the victim, the respawned process
    # re-adopts its rank's backlog via the informer relist, and the run
    # still joins on full parity)
    if time.perf_counter() - t0 <= MP_FEDERATION_BUDGET_S:
        _status("mp federation stage: replica-kill recovery "
                "(2 processes, hash, supervisor restart)")
        metric = f"FederationRecovery_mp_{case}_{workload}_hash_2sched"
        try:
            r = run_workload_multiprocess(
                case, workload, replicas=2, partition="hash",
                engine=engine, max_batch=max_batch,
                timeout_s=STAGE_TIMEOUT_S, kill_replica_at=0.5,
                restart="on-failure:2", child_env=MP_CHILD_ENV,
            )
            _emit({
                "metric": metric,
                "unit": "s",
                "value": (
                    round(r.recovery_s, 3)
                    if r.recovery_s is not None else None
                ),
                "recovery_s": (
                    round(r.recovery_s, 3)
                    if r.recovery_s is not None else None
                ),
                "throughput": round(r.throughput, 1),
                "scheduled": r.scheduled,
                "measure_pods": r.measure_pods,
                "binding_parity": r.binding_parity,
                "all_rescheduled": r.binding_parity == r.measure_pods,
                "restarts": r.restarts,
                "n_processes": r.n_processes,
                "replicas": 2,
                "partition": "hash",
                "mode": "multiprocess",
                "backend": "cpu",
            })
            _status(f"mp recovery done: recovery_s="
                    f"{r.recovery_s and round(r.recovery_s, 3)} "
                    f"(restarts={r.restarts})")
        except Exception as e:
            _emit({
                "metric": metric, "unit": "s", "value": None,
                "mode": "multiprocess", "backend": "cpu",
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"mp recovery stage FAILED: {e}")


def _run_mp_wire_stages() -> None:
    """The honest run at the wire claim: the 1k/2k/5k fullstack ladder
    with apiserver, scheduler, and the 200-watcher fan-out load ALL in
    separate OS processes (the watchers spread over MP_WIRE_FANOUT_PROCS
    watch-driver children), once per codec — one
    WireCodecComparison_mp_* line per rung."""
    from kubetpu.perf.runner import run_workload_multiprocess

    t0 = time.perf_counter()
    for case, workload, engine, max_batch in MP_WIRE_LADDER:
        pair: dict[str, dict] = {}
        for wire in ("json", "binary"):
            elapsed = time.perf_counter() - t0
            if elapsed > MP_WIRE_BUDGET_S:
                _status(f"mp wire budget exhausted; skipping "
                        f"{workload}/{wire}")
                continue
            _status(f"mp wire stage: {case}/{workload}/{engine} "
                    f"wire={wire} fanout={MP_WIRE_FANOUT} over "
                    f"{MP_WIRE_FANOUT_PROCS} procs (t={elapsed:.0f}s)")
            metric = (
                f"{case}_{workload}_{engine}_mp"
                f"{'_jsonwire' if wire != 'binary' else ''}"
                f"_{MP_WIRE_FANOUT}watchers"
            )
            try:
                r = run_workload_multiprocess(
                    case, workload, replicas=1, partition="race",
                    wire=wire, engine=engine, max_batch=max_batch,
                    timeout_s=STAGE_TIMEOUT_S,
                    watch_fanout=MP_WIRE_FANOUT,
                    fanout_procs=MP_WIRE_FANOUT_PROCS,
                    child_env=MP_CHILD_ENV,
                )
            except Exception as e:
                _emit({
                    "metric": metric, "value": 0.0, "unit": "pods/s",
                    "vs_baseline": 0.0, "engine": engine,
                    "mode": "multiprocess", "backend": "cpu",
                    "wire_codec": wire, "watch_fanout": MP_WIRE_FANOUT,
                    "error": f"{type(e).__name__}: {e}",
                })
                _status(f"mp wire stage FAILED: {workload}/{wire}: {e}")
                continue
            line = _mp_record(r, case, workload, engine, metric)
            pair[wire] = line
            _emit(line)
            _status(f"mp wire stage done: {metric} = {line['value']} "
                    f"pods/s ({line.get('wire_bytes_per_pod')} B/pod)")
        jsonl, binl = pair.get("json"), pair.get("binary")
        if not jsonl or not binl:
            continue
        fields = (
            "value", "wire_codec", "wire_bytes_per_pod", "duration_s",
            "rpcs_per_scheduled_pod",
        )
        comp = {
            "metric": f"WireCodecComparison_mp_{case}_{workload}_{engine}",
            "unit": "ratio",
            "mode": "multiprocess",
            "backend": "cpu",
            "watch_fanout": MP_WIRE_FANOUT,
            "fanout_procs": MP_WIRE_FANOUT_PROCS,
            "n_processes": binl.get("n_processes"),
            "json": {k: jsonl.get(k) for k in fields
                     if jsonl.get(k) is not None},
            "binary": {k: binl.get(k) for k in fields
                       if binl.get(k) is not None},
        }
        jb = jsonl.get("wire_bytes_per_pod")
        bb = binl.get("wire_bytes_per_pod")
        if jb and bb is not None:
            comp["wire_bytes_reduction"] = round(1.0 - bb / jb, 4)
        if jsonl.get("value") and binl.get("value"):
            comp["throughput_speedup"] = round(
                binl["value"] / jsonl["value"], 3
            )
            comp["value"] = comp["throughput_speedup"]
        _emit(comp)


def _run_read_plane_stages() -> None:
    """The replicated read plane's evidence (see READ_PLANE_* above):
    the ReadScaling_mp_{1,2,4}api ladder — the judged 5k fullstack row
    with the 200-watcher fan-out spread over followers — then the
    leader-kill failover stage, judged against the durability ladder's
    cold CrashRecovery wall."""
    from kubetpu.perf.runner import (
        run_crash_recovery,
        run_replicated_failover,
        run_workload_multiprocess,
    )

    case, workload, engine, max_batch = READ_PLANE_CASE
    t0 = time.perf_counter()
    ladder: dict[int, dict] = {}
    for n in READ_PLANE_LADDER:
        elapsed = time.perf_counter() - t0
        if elapsed > READ_PLANE_BUDGET_S:
            _status(f"read-plane budget exhausted; skipping {n}api")
            continue
        _status(f"read-plane stage: {n} apiserver(s), "
                f"fanout={MP_WIRE_FANOUT} over {MP_WIRE_FANOUT_PROCS} "
                f"procs (t={elapsed:.0f}s)")
        metric = (
            f"{case}_{workload}_{engine}_mp_{n}api_"
            f"{MP_WIRE_FANOUT}watchers"
        )
        try:
            r = run_workload_multiprocess(
                case, workload, replicas=1, apiservers=n,
                partition="race", wire="binary", engine=engine,
                max_batch=max_batch, timeout_s=STAGE_TIMEOUT_S,
                watch_fanout=MP_WIRE_FANOUT,
                fanout_procs=MP_WIRE_FANOUT_PROCS,
                child_env=MP_CHILD_ENV,
            )
        except Exception as e:
            _emit({
                "metric": metric, "value": 0.0, "unit": "pods/s",
                "vs_baseline": 0.0, "engine": engine,
                "mode": "multiprocess", "backend": "cpu",
                "apiservers": n, "watch_fanout": MP_WIRE_FANOUT,
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"read-plane stage FAILED ({n}api): {e}")
            continue
        line = _mp_record(r, case, workload, engine, metric)
        ladder[n] = line
        _emit(line)
        _status(f"read-plane stage done: {metric} = {line['value']} "
                f"pods/s (follower_lag_ms="
                f"{line.get('follower_lag_ms')})")
    base = ladder.get(1)
    for n in READ_PLANE_LADDER:
        line = ladder.get(n)
        if line is None:
            continue
        scaling = {
            "metric": f"ReadScaling_mp_{n}api",
            "unit": "ratio",
            "mode": "multiprocess",
            "backend": "cpu",
            "case": case,
            "workload": workload,
            "apiservers": n,
            "watch_fanout": MP_WIRE_FANOUT,
            "fanout_procs": MP_WIRE_FANOUT_PROCS,
            "throughput": line["value"],
            "binding_parity": line["binding_parity"],
            "measure_pods": line["measure_pods"],
            "n_processes": line["n_processes"],
        }
        if line.get("follower_lag_ms") is not None:
            scaling["follower_lag_ms"] = line["follower_lag_ms"]
            scaling["follower_lag_records"] = line.get(
                "follower_lag_records"
            )
        if base and base.get("value"):
            scaling["value"] = round(line["value"] / base["value"], 3)
            scaling["throughput_speedup"] = scaling["value"]
            scaling["baseline_throughput"] = base["value"]
        else:
            scaling["value"] = None
        if line.get("leader_replication_bytes") is not None:
            scaling["leader_replication_bytes"] = line[
                "leader_replication_bytes"
            ]
        _emit(scaling)
    # ---- chained shipping at the widest rung: the same 4api shape with
    # follower i tailing follower i-1 (--replication-chain) — the leader
    # ships ONE stream, so its replication egress should land near a
    # third of the star rung's (1 follower's worth vs 3); both rungs
    # carry leader_replication_bytes so the delta is read off the
    # record, not inferred
    chain_n = READ_PLANE_LADDER[-1]
    star = ladder.get(chain_n)
    if (
        chain_n > 2 and star is not None
        and time.perf_counter() - t0 <= READ_PLANE_BUDGET_S
    ):
        _status(f"read-plane stage: {chain_n} apiservers, CHAINED "
                f"replication (leader egress = 1 follower's worth)")
        metric = (
            f"{case}_{workload}_{engine}_mp_{chain_n}api_chained_"
            f"{MP_WIRE_FANOUT}watchers"
        )
        try:
            r = run_workload_multiprocess(
                case, workload, replicas=1, apiservers=chain_n,
                partition="race", wire="binary", engine=engine,
                max_batch=max_batch, timeout_s=STAGE_TIMEOUT_S,
                watch_fanout=MP_WIRE_FANOUT,
                fanout_procs=MP_WIRE_FANOUT_PROCS,
                replication_chain=True, child_env=MP_CHILD_ENV,
            )
            line = _mp_record(r, case, workload, engine, metric)
            _emit(line)
            chained = {
                "metric": f"ReadScaling_mp_{chain_n}api_chained",
                "unit": "ratio",
                "mode": "multiprocess",
                "backend": "cpu",
                "case": case,
                "workload": workload,
                "apiservers": chain_n,
                "replication_chain": True,
                "throughput": line["value"],
                "binding_parity": line["binding_parity"],
                "measure_pods": line["measure_pods"],
                "follower_lag_ms": line.get("follower_lag_ms"),
                "follower_lag_records": line.get("follower_lag_records"),
                "leader_replication_bytes": line.get(
                    "leader_replication_bytes"
                ),
            }
            star_bytes = star.get("leader_replication_bytes")
            chain_bytes = line.get("leader_replication_bytes")
            if star_bytes and chain_bytes:
                # the egress headline: chained leader bytes / star leader
                # bytes (~1/(N-1) when the chain carries the fan-out)
                chained["leader_egress_vs_star"] = round(
                    chain_bytes / star_bytes, 3
                )
                chained["star_leader_replication_bytes"] = star_bytes
            if star.get("value"):
                chained["value"] = round(
                    line["value"] / star["value"], 3
                )
                chained["vs_star_throughput"] = chained["value"]
            else:
                chained["value"] = None
            _emit(chained)
            _status(f"read-plane chained rung done: leader egress "
                    f"{chain_bytes}B vs star {star_bytes}B "
                    f"(ratio={chained.get('leader_egress_vs_star')})")
        except Exception as e:
            _emit({
                "metric": metric, "value": 0.0, "unit": "pods/s",
                "vs_baseline": 0.0, "engine": engine,
                "mode": "multiprocess", "backend": "cpu",
                "apiservers": chain_n, "replication_chain": True,
                "watch_fanout": MP_WIRE_FANOUT,
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"read-plane chained rung FAILED: {e}")
    # ---- leader-kill failover vs the cold-recovery wall
    n_nodes, n_pods = DURABILITY_SHAPE
    fo_metric = (
        f"ReplicatedFailover_{n_nodes}Nodes_{n_pods}Pods_"
        f"{FAILOVER_APISERVERS}api"
    )
    _status(f"read-plane stage: leader-kill failover "
            f"({FAILOVER_APISERVERS} apiservers, {n_nodes}x{n_pods} "
            f"storm, lease={FAILOVER_LEASE_S}s)")
    try:
        fo = run_replicated_failover(
            n_nodes=n_nodes, n_pods=n_pods,
            apiservers=FAILOVER_APISERVERS,
            lease_duration_s=FAILOVER_LEASE_S,
            child_env=MP_CHILD_ENV,
        )
    except Exception as e:
        _emit({
            "metric": fo_metric, "unit": "s", "value": None,
            "mode": "multiprocess", "backend": "cpu",
            "error": f"{type(e).__name__}: {e}",
        })
        _status(f"read-plane failover stage FAILED: {e}")
        return
    _emit({
        "metric": fo_metric,
        "unit": "s",
        "value": fo["failover_to_serving_s"],
        "mode": "multiprocess",
        "backend": "cpu",
        **fo,
    })
    _status(f"read-plane failover done: failover_to_serving_s="
            f"{fo['failover_to_serving_s']} (elected_s="
            f"{fo['elected_s']}, follower_lag_ms="
            f"{fo['follower_lag_ms']}, parity_ok={fo['parity_ok']})")
    cold = _COLD_RECOVERY.get("recovery_s")
    if cold is None:
        # the durability stage didn't run (or failed) — measure the cold
        # wall inline so the verdict always lands
        _status("read-plane stage: cold-recovery wall not measured yet; "
                "running CrashRecovery inline for the verdict")
        try:
            cold = run_crash_recovery(
                n_nodes=n_nodes, n_pods=n_pods,
                watchers=DURABILITY_WATCHERS,
            )["recovery_s"]
        except Exception as e:
            _status(f"inline cold-recovery FAILED: {e}")
            return
    verdict = {
        "metric": f"FailoverVsColdRecovery_{n_nodes}Nodes_{n_pods}Pods",
        "unit": "verdict",
        "value": 1.0 if fo["failover_to_serving_s"] < cold else 0.0,
        "mode": "multiprocess",
        "backend": "cpu",
        "failover_to_serving_s": fo["failover_to_serving_s"],
        "cold_recovery_s": cold,
        "speedup_vs_cold": (
            round(cold / fo["failover_to_serving_s"], 2)
            if fo["failover_to_serving_s"] > 0 else None
        ),
        "apiservers": FAILOVER_APISERVERS,
        "parity_ok": fo["parity_ok"],
    }
    _emit(verdict)
    _status(f"read-plane verdict: failover {fo['failover_to_serving_s']}s "
            f"vs cold {cold}s -> "
            f"{'BEATS' if verdict['value'] else 'LOSES TO'} cold recovery "
            f"({verdict['speedup_vs_cold']}x)")


def _run_durability_stages() -> None:
    """CrashRecovery_* (recovery wall + reconnect relist storm + binding
    parity after a simulated kill) and WALOverhead_* (steady-state
    durability tax, on/off) — the durable-control-plane evidence."""
    from kubetpu.perf.runner import run_crash_recovery, run_wal_overhead

    t0 = time.perf_counter()
    n_nodes, n_pods = DURABILITY_SHAPE
    _status(f"durability stage: crash recovery {n_nodes}x{n_pods}, "
            f"{DURABILITY_WATCHERS} reconnecting watchers")
    try:
        r = run_crash_recovery(
            n_nodes=n_nodes, n_pods=n_pods, watchers=DURABILITY_WATCHERS,
        )
        _COLD_RECOVERY["recovery_s"] = r["recovery_s"]
        _emit({
            "metric": f"CrashRecovery_{n_nodes}Nodes_{n_pods}Pods",
            "unit": "s",
            "value": r["recovery_s"],
            "backend": _backend(),
            **r,
        })
        _status(f"durability stage done: recovered rv {r['rv']} in "
                f"{r['recovery_s']}s (parity_ok={r['parity_ok']}, relist "
                f"storm {r['relist_storm_s']}s)")
    except Exception as e:
        _emit({
            "metric": f"CrashRecovery_{n_nodes}Nodes_{n_pods}Pods",
            "unit": "s", "value": None, "backend": _backend(),
            "error": f"{type(e).__name__}: {e}",
        })
        _status(f"durability stage FAILED: {e}")
    if time.perf_counter() - t0 > DURABILITY_BUDGET_S:
        _status("durability budget exhausted; skipping WALOverhead")
        return
    _status("durability stage: steady-state WAL overhead (on/off)")
    try:
        o = run_wal_overhead()
        _emit({
            "metric": "WALOverhead_bulk_writes",
            "unit": "ratio",
            "value": o["throughput_ratio"],
            "backend": _backend(),
            **o,
        })
        _status(f"durability stage done: WAL on/off ratio "
                f"{o['throughput_ratio']} "
                f"(overhead_frac={o['wal_overhead_frac']})")
    except Exception as e:
        _emit({
            "metric": "WALOverhead_bulk_writes",
            "unit": "ratio", "value": None, "backend": _backend(),
            "error": f"{type(e).__name__}: {e}",
        })
        _status(f"durability stage FAILED: {e}")


def _run_trace_stages() -> None:
    """The scale-frontier ladder (see TRACE_STAGES): one record per rung
    plus one AdmissionSLO_* line (p99 enqueue→bind vs the profile's
    declared budget — the benchdiff-gated SLO evidence)."""
    from kubetpu.perf.runner import run_workload_trace
    from kubetpu.perf.workloads import TRACE_PROFILES

    t0 = time.perf_counter()
    for stage in TRACE_STAGES:
        name, suffix, overrides, max_batch, engine, wall = stage[:6]
        mode = stage[6] if len(stage) > 6 else "direct"
        elapsed = time.perf_counter() - t0
        if elapsed > TRACE_BUDGET_S:
            _status(f"trace budget exhausted; skipping {name}-{suffix}")
            continue
        ov = dict(overrides)
        nodes = ov.pop("nodes", None)
        topology = ov.pop("topology", "off")
        prof = TRACE_PROFILES[name].scaled(suffix, nodes=nodes, **ov)
        metric = f"Trace_{prof.name}_{prof.nodes}Nodes_{engine}"
        _status(f"trace stage: {prof.name} nodes={prof.nodes} mode={mode} "
                f"topology={topology} wall_budget={wall:.0f}s "
                f"(t={elapsed:.0f}s)")
        t_stage = time.perf_counter()
        try:
            r = run_workload_trace(
                prof, mode=mode, engine=engine, max_batch=max_batch,
                timeout_s=wall + 120.0, wall_budget_s=wall,
                topology=topology,
            )
        except Exception as e:
            _emit({
                "metric": metric, "value": 0.0, "unit": "pods/s",
                "engine": engine, "mode": f"trace-{mode}",
                "backend": _backend(), "slo_budget_ms": prof.slo_budget_ms,
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"trace stage FAILED: {prof.name}: {e}")
            continue
        j = r.to_json()
        for drop in ("case", "workload", "metric"):
            j.pop(drop, None)
        line = {
            "metric": metric,
            "unit": "pods/s",
            "engine": engine,
            "mode": f"trace-{mode}",
            "backend": _backend(),
            "nodes": prof.nodes,
            "wall_s": round(time.perf_counter() - t_stage, 1),
            **j,
        }
        _emit(line)
        _status(
            f"trace stage done: {metric} = {line['value']} pods/s "
            f"(admission_p99={line.get('admission_p99_ms')}ms vs "
            f"{prof.slo_budget_ms}ms budget, "
            f"rss={line.get('peak_rss_bytes', 0) // (1024**2)}MB"
            f"{', TRUNCATED' if line.get('truncated') else ''})"
        )
        _emit({
            "metric": f"AdmissionSLO_{prof.name}_{prof.nodes}Nodes",
            "unit": "ms",
            "value": line.get("admission_p99_ms"),
            "admission_p99_ms": line.get("admission_p99_ms"),
            "admission_p50_ms": line.get("admission_p50_ms"),
            "slo_budget_ms": prof.slo_budget_ms,
            "slo_ok": line.get("slo_ok"),
            "peak_rss_bytes": line.get("peak_rss_bytes"),
            "truncated": line.get("truncated", False),
            "scheduled": line.get("scheduled"),
            "nodes": prof.nodes,
            "backend": _backend(),
            "mode": f"trace-{mode}",
        })


def _run_list_scaling_stages() -> None:
    """The LIST-at-scale ladder (see LIST_SCALING_LADDER): one
    ListScaling_{N}Nodes line per rung — per-relist wall p99 over K
    paged informer relists, bytes/pages per relist, max page shipped,
    and the unpaged-GET wall for context. The runner parity-checks
    every walk; a dropped/duplicated key fails the rung."""
    from kubetpu.perf.runner import run_list_scaling

    t0 = time.perf_counter()
    for n_nodes, relists, wall in LIST_SCALING_LADDER:
        elapsed = time.perf_counter() - t0
        if elapsed > LIST_SCALING_BUDGET_S:
            _status(f"list-scaling budget exhausted; skipping "
                    f"{n_nodes} nodes")
            continue
        metric = f"ListScaling_{n_nodes}Nodes"
        _status(f"list-scaling stage: {n_nodes} nodes, {relists} relists "
                f"(t={elapsed:.0f}s)")
        try:
            r = run_list_scaling(
                n_nodes=n_nodes, relists=relists, wall_budget_s=wall,
            )
        except Exception as e:
            _emit({
                "metric": metric, "unit": "ms", "value": None,
                "backend": _backend(), "nodes": n_nodes,
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"list-scaling stage FAILED ({n_nodes}): {e}")
            continue
        _emit({
            "metric": metric,
            "unit": "ms",
            "value": r["list_p99_ms"],
            "backend": _backend(),
            **r,
        })
        _status(f"list-scaling stage done: {metric} p99="
                f"{r['list_p99_ms']}ms, {r['pages_per_relist']} pages/"
                f"relist, {r['bytes_per_relist']} bytes/relist "
                f"(max page {r['max_page_bytes']}B, unpaged "
                f"{r['unpaged_ms']}ms"
                f"{', TRUNCATED' if r['truncated'] else ''})")


def _run_trace_federation_stage() -> None:
    """ROADMAP 5b: the diurnal-burst trace replayed against the
    lease-mode 2-scheduler mp federation with a FORCED lease handover at
    the trace midpoint (see TRACE_FEDERATION_PROFILE) — one record whose
    admission_p99_ms spans the handover, benchdiff-gated against the
    declared SLO budget like every trace record."""
    from kubetpu.perf.runner import run_trace_multiprocess
    from kubetpu.perf.workloads import TRACE_PROFILES

    ov = dict(TRACE_FEDERATION_PROFILE)
    nodes = ov.pop("nodes", None)
    prof = TRACE_PROFILES["diurnal-burst"].scaled("mp", nodes=nodes, **ov)
    metric = f"TraceFederation_{prof.name}_{prof.nodes}Nodes_lease_2sched"
    _status(f"trace-federation stage: {prof.name} nodes={prof.nodes}, "
            f"2 scheduler processes, lease partition, handover at 50%")
    t_stage = time.perf_counter()
    try:
        r = run_trace_multiprocess(
            prof, replicas=2, partition="lease", engine="greedy",
            max_batch=128, timeout_s=TRACE_FEDERATION_BUDGET_S,
            wall_budget_s=TRACE_FEDERATION_BUDGET_S - 60.0,
            handover_at=0.5, child_env=MP_CHILD_ENV,
        )
    except Exception as e:
        _emit({
            "metric": metric, "unit": "ms", "value": None,
            "mode": "trace-multiprocess", "backend": "cpu",
            "slo_budget_ms": prof.slo_budget_ms,
            "error": f"{type(e).__name__}: {e}",
        })
        _status(f"trace-federation stage FAILED: {e}")
        return
    j = r.to_json()
    for drop in ("case", "workload", "metric", "value", "unit"):
        j.pop(drop, None)
    _emit({
        "metric": metric,
        "unit": "ms",
        "value": j.get("admission_p99_ms"),
        "mode": "trace-multiprocess",
        "backend": "cpu",               # MP_CHILD_ENV pins the children
        "nodes": prof.nodes,
        "wall_s": round(time.perf_counter() - t_stage, 1),
        **j,
    })
    _status(
        f"trace-federation stage done: admission_p99="
        f"{j.get('admission_p99_ms')}ms vs {prof.slo_budget_ms}ms budget "
        f"(lease_transitions={j.get('lease_transitions', 0)}, "
        f"recovery_s={j.get('recovery_s')}, restarts={j.get('restarts')}"
        f"{', TRUNCATED' if j.get('truncated') else ''})"
    )


def _run_telemetry_stages() -> None:
    """The telemetry-plane overhead pair: the judged fullstack row with
    the WHOLE plane on (HTTP collector + traceparent propagation + both
    exporters) vs off, one TelemetryOverhead_* line — throughput side by
    side, overhead fraction, the <5% within_budget verdict, and the
    collector's span-drop counter (must be zero for the on-run's trace
    to count as complete evidence)."""
    case, workload, engine, max_batch = TELEMETRY_CASE
    t0 = time.perf_counter()
    pair: dict[bool, dict] = {}
    for on in (True, False):
        if time.perf_counter() - t0 > TELEMETRY_BUDGET_S:
            _status("telemetry budget exhausted; skipping pair half")
            continue
        _status(f"telemetry stage: {case}/{workload}/{engine} "
                f"telemetry={'on' if on else 'off'}")
        # the off-half gets its OWN suffix: run_stage's defaults would
        # otherwise reuse the judged STAGES row's exact metric name, and
        # a duplicate (or an error line under the judged name) would
        # shadow the real acceptance row in benchdiff
        metric_suffix = "_telemetry" if on else "_notelemetry"
        try:
            line = run_stage(
                case, workload, engine, "fullstack", max_batch,
                telemetry=on,
            )
        except Exception as e:
            _emit({
                "metric": (
                    f"{case}_{workload}_{engine}_fullstack{metric_suffix}"
                ),
                "value": 0.0, "unit": "pods/s", "vs_baseline": 0.0,
                "engine": engine, "mode": "fullstack",
                "backend": _backend(),
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"telemetry stage FAILED ({on=}): {e}")
            continue
        if not on:
            line = dict(line, metric=line["metric"] + "_notelemetry")
        pair[on] = line
        _emit(line)
    on_l, off_l = pair.get(True), pair.get(False)
    if not on_l or not off_l:
        return
    fields = ("value", "duration_s", "p99_attempt_latency_ms")
    tele = on_l.get("telemetry") or {}
    comp = {
        "metric": f"TelemetryOverhead_{case}_{workload}_{engine}",
        "unit": "ratio",
        "mode": "fullstack",
        "backend": on_l.get("backend"),
        "telemetry_on": {
            k: on_l.get(k) for k in fields if on_l.get(k) is not None
        },
        "telemetry_off": {
            k: off_l.get(k) for k in fields if off_l.get(k) is not None
        },
        "spans": tele.get("spans"),
        "spans_dropped": tele.get("spans_dropped", 0),
        # complete-evidence assert: a drop would mean the merged trace is
        # lying by omission — the stage itself flags it, not just a reader
        "spans_dropped_zero": tele.get("spans_dropped", 0) == 0,
    }
    if on_l.get("value") and off_l.get("value"):
        ratio = on_l["value"] / off_l["value"]
        comp["value"] = round(ratio, 3)
        comp["telemetry_overhead_frac"] = round(max(1.0 - ratio, 0.0), 4)
        # the acceptance gate: the whole plane costs <5% throughput
        comp["within_budget"] = ratio >= 0.95
    _emit(comp)
    _status(f"telemetry stage done: overhead_frac="
            f"{comp.get('telemetry_overhead_frac')} "
            f"(dropped={comp['spans_dropped']})")


def _run_sentinel_stages() -> None:
    """The anomaly-sentinel acceptance pair (see the SENTINEL_* block):
    the judged fullstack row with the sentinel on vs off (one
    SentinelOverhead_* line: overhead fraction, the <5% within_budget
    verdict, and the on-half's zero-false-positive assert), then the
    SentinelSpike_* trace stage — injected stall, declared SLO budget,
    the fire→bundle→resolve chain as one boolean value."""
    case, workload, engine, max_batch = TELEMETRY_CASE
    t0 = time.perf_counter()
    pair: dict[bool, dict] = {}
    for on in (True, False):
        if time.perf_counter() - t0 > SENTINEL_BUDGET_S:
            _status("sentinel budget exhausted; skipping pair half")
            continue
        _status(f"sentinel stage: {case}/{workload}/{engine} "
                f"sentinel={'on' if on else 'off'}")
        # the off-half gets its OWN suffix: a bare fullstack run would
        # reuse the judged STAGES row's metric name and shadow it (same
        # hazard the telemetry pair documents)
        metric_suffix = "_sentinel" if on else "_nosentinel"
        try:
            line = run_stage(
                case, workload, engine, "fullstack", max_batch,
                sentinel=on,
            )
        except Exception as e:
            _emit({
                "metric": (
                    f"{case}_{workload}_{engine}_fullstack{metric_suffix}"
                ),
                "value": 0.0, "unit": "pods/s", "vs_baseline": 0.0,
                "engine": engine, "mode": "fullstack",
                "backend": _backend(),
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"sentinel stage FAILED ({on=}): {e}")
            continue
        if not on:
            line = dict(line, metric=line["metric"] + "_nosentinel")
        pair[on] = line
        _emit(line)
    on_l, off_l = pair.get(True), pair.get(False)
    if on_l and off_l:
        fields = ("value", "duration_s", "p99_attempt_latency_ms")
        sent = on_l.get("sentinel") or {}
        comp = {
            "metric": f"SentinelOverhead_{case}_{workload}_{engine}",
            "unit": "ratio",
            "mode": "fullstack",
            "backend": on_l.get("backend"),
            "sentinel_on": {
                k: on_l.get(k) for k in fields if on_l.get(k) is not None
            },
            "sentinel_off": {
                k: off_l.get(k) for k in fields if off_l.get(k) is not None
            },
            "evaluations": sent.get("evaluations"),
            "eval_wall_s": sent.get("eval_wall_s"),
            "alerts_fired": sent.get("fired_total", 0),
            # the zero-false-positive assert: a CLEAN judged run must not
            # fire anything — the stage itself flags a lie, not a reader
            "clean": bool(sent.get("clean", False)),
        }
        if on_l.get("value") and off_l.get("value"):
            ratio = on_l["value"] / off_l["value"]
            comp["value"] = round(ratio, 3)
            comp["sentinel_overhead_frac"] = round(max(1.0 - ratio, 0.0), 4)
            # the acceptance gate: the live sentinel costs <5% throughput
            comp["within_budget"] = ratio >= 0.95
        _emit(comp)
        _status(f"sentinel stage done: overhead_frac="
                f"{comp.get('sentinel_overhead_frac')} "
                f"clean={comp['clean']}")
    if time.perf_counter() - t0 > SENTINEL_BUDGET_S:
        _status("sentinel budget exhausted; skipping spike stage")
        return
    from kubetpu.perf.runner import run_workload_trace
    from kubetpu.perf.workloads import TRACE_PROFILES

    prof = TRACE_PROFILES["diurnal-burst"].scaled(
        "sentinel", **SENTINEL_SPIKE_PROFILE
    )
    _status(f"sentinel spike stage: trace {prof.name} nodes={prof.nodes} "
            f"slo={prof.slo_budget_ms}ms")
    metric = f"SentinelSpike_{prof.name}_fullstack"
    try:
        r = run_workload_trace(
            prof, mode="fullstack", max_batch=128, engine="greedy",
            sentinel=True, sentinel_spike=True,
        )
    except Exception as e:
        _emit({
            "metric": metric, "value": 0.0, "unit": "verdict",
            "mode": "trace-fullstack", "backend": _backend(),
            "error": f"{type(e).__name__}: {e}",
        })
        _status(f"sentinel spike stage FAILED: {e}")
        return
    j = r.to_json()
    sent = j.get("sentinel") or {}
    spike = sent.get("spike") or {}
    checks = ("fired", "fired_within_interval", "bundle_captured",
              "bundle_covers_stall", "resolved")
    line = {
        "metric": metric,
        # the acceptance chain as ONE judged bit: stall → matching SLO
        # alert within the detection bound → bundle covering the stall
        # window → resolved after recovery
        "value": 1.0 if all(spike.get(k) for k in checks) else 0.0,
        "unit": "verdict",
        "mode": "trace-fullstack",
        "backend": _backend(),
        "slo_budget_ms": j.get("slo_budget_ms"),
        "admission_p99_ms": j.get("admission_p99_ms"),
        "scheduled": j.get("scheduled"),
        "duration_s": j.get("duration_s"),
        "sentinel": sent,
    }
    _emit(line)
    _status(f"sentinel spike stage done: verdict={line['value']} "
            f"spike={ {k: spike.get(k) for k in checks} }")


def main() -> int:
    device = kubetpu.device_stamp()
    if device["platform"] != "tpu":
        print(f"bench.py needs a TPU and JAX found {device}: nothing run "
              f"(a CPU timing is not a device metric)", file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    best_quadratic: dict | None = None
    best_any: dict | None = None
    # (case, workload, engine, mode, bulk) -> {pipeline: result line}
    pairs: dict = {}
    # (case, workload, engine, mode, pipeline) -> {bulk: result line}
    api_pairs: dict = {}
    # (case, workload, engine, mode, pipeline, bulk) -> {mesh: result line}
    mesh_pairs: dict = {}
    # (case, workload, engine, mode) -> {flight_recorder: result line}
    fr_pairs: dict = {}
    # (case, workload, mode) -> {engine: result line} (PackingComparison)
    packing_trios: dict = {}
    all_lines: list = []
    for stage in STAGES:
        # the optional 9th slot is flight_recorder (default on); only the
        # overhead pair-completers carry it. The optional 10th slot is the
        # wire codec ("binary" default — fullstack stages negotiate the
        # compact binary wire; "json" pins the escape hatch)
        case, workload, engine, mode, max_batch, pipeline, bulk, mesh = (
            stage[:8]
        )
        flight_recorder = stage[8] if len(stage) > 8 else True
        wire = stage[9] if len(stage) > 9 else "binary"
        elapsed = time.perf_counter() - t_start
        if elapsed > TOTAL_BUDGET_S:
            _status(f"budget exhausted ({elapsed:.0f}s); skipping {case}/{engine}")
            continue
        _status(f"stage start: {case}/{workload}/{engine}/{mode}"
                f"{'/pipelined' if pipeline else ''}"
                f"{'/nobulk' if not bulk else ''}"
                f"{'/mesh' if mesh else ''}"
                f"{'/norecorder' if not flight_recorder else ''}"
                f"{'/jsonwire' if wire != 'binary' else ''}"
                f" (t={elapsed:.0f}s)")
        suffix = "" if mode == "direct" else "_fullstack"
        if pipeline:
            suffix += "_pipelined"
        if not bulk:
            suffix += "_nobulk"
        if mesh:
            suffix += "_mesh"
        if not flight_recorder:
            suffix += "_norecorder"
        if mode != "direct" and wire != "binary":
            suffix += "_jsonwire"
        # profile exactly ONE stage: the first quadratic TPU stage (the
        # north-star workload) — the artifact lands in ./xla_profile/
        profile_dir = None
        if (
            _backend() == "tpu" and case in QUADRATIC
            and mode == "direct" and not os.path.isdir("xla_profile")
        ):
            profile_dir = "xla_profile"
        try:
            line = run_stage(case, workload, engine, mode, max_batch,
                             profile_dir=profile_dir, pipeline=pipeline,
                             bulk=bulk, mesh=mesh,
                             flight_recorder=flight_recorder, wire=wire)
            if profile_dir is not None:
                line["xla_profile"] = profile_dir
        except Exception as e:
            _emit({
                "metric": f"{case}_{workload}_{engine}{suffix}", "value": 0.0,
                "unit": "pods/s", "vs_baseline": 0.0, "engine": engine,
                "mode": mode, "backend": _backend(),
                "error": f"{type(e).__name__}: {e}",
            })
            _status(f"stage FAILED: {case}/{workload}/{engine}/{mode}: {e}")
            continue
        if not mesh and flight_recorder:
            pairs.setdefault(
                (case, workload, engine, mode, bulk), {}
            )[pipeline] = line
            api_pairs.setdefault(
                (case, workload, engine, mode, pipeline), {}
            )[bulk] = line
        if not mesh and not pipeline and bulk:
            fr_pairs.setdefault(
                (case, workload, engine, mode), {}
            )[flight_recorder] = line
        if flight_recorder:
            mesh_pairs.setdefault(
                (case, workload, engine, mode, pipeline, bulk), {}
            )[mesh] = line
        if not mesh and not pipeline and bulk and flight_recorder:
            packing_trios.setdefault(
                (case, workload, mode), {}
            )[engine] = line
        all_lines.append(line)
        _emit(line)
        _status(f"stage done: {line['metric']} = {line['value']} pods/s "
                f"({line['vs_baseline']}x baseline)")
        vb = line.get("vs_baseline") or 0.0
        if best_any is None or vb > (best_any.get("vs_baseline") or 0.0):
            best_any = line
        if case in QUADRATIC and (
            best_quadratic is None
            or vb > (best_quadratic.get("vs_baseline") or 0.0)
        ):
            best_quadratic = line
    _emit_pipeline_comparisons(pairs)
    _emit_api_plane_comparisons(api_pairs)
    _emit_sharding_comparisons(mesh_pairs)
    _emit_flightrecorder_comparisons(fr_pairs)
    _emit_packing_comparisons(packing_trios)
    _emit_soak_lines(all_lines)
    # the scale-frontier trace ladder right after the judged in-process
    # rows: its own budget, and every rung is wall-capped so the 100k
    # attempt can never eat the later ladders
    _run_trace_stages()
    _run_wire_stages()
    _run_federation_stages()
    _run_durability_stages()
    # the list/relist-at-scale ladder: in-process like the durability
    # stages, and its 50k rung wants the judged rows already emitted
    _run_list_scaling_stages()
    _run_telemetry_stages()
    _run_sentinel_stages()
    # the multi-process ladders LAST: every in-process judged row has
    # already landed, and the mp stages spawn their own CPU-pinned
    # children regardless of this process's backend
    _run_mp_federation_stages()
    # the trace-vs-lease-federation handover rung rides the mp shape
    _run_trace_federation_stage()
    _run_mp_wire_stages()
    # the replicated read plane last: its ladder reuses the mp wire
    # shape, and the failover verdict wants the durability ladder's
    # cold-recovery wall already measured
    _run_read_plane_stages()
    final = best_quadratic or best_any
    if final is None:
        _emit({
            "metric": "BestQuadratic_none", "value": 0.0, "unit": "pods/s",
            "vs_baseline": 0.0, "backend": _backend(),
            "error": "no stage completed",
        })
    else:
        summary = dict(final)
        prefix = "BestQuadratic_" if best_quadratic is not None else "Best_"
        summary["metric"] = prefix + final["metric"]
        _emit(summary)
    if FAILED:
        _status(f"{len(FAILED)} stage(s) FAILED: {', '.join(FAILED)}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
