"""scheduler_perf runner — drive the REAL scheduler loop through an op list.

The reference harness (test/integration/scheduler_perf/scheduler_perf.go:756)
boots apiserver+etcd+scheduler in one process, feeds API objects, and
measures SchedulingThroughput at bind time. Here the same op lists drive the
full kubetpu ``Scheduler`` — queue (backoff/hints), cache/snapshot, encode,
device greedy scan, async dispatcher — through its informer seam; no HTTP
hop, same semantics.

Threading note: the Scheduler is single-owner (informer callbacks + loop on
one thread), so churn is injected *synchronously* between cycles on the
loop thread, clocked by elapsed wall time against the op's
``intervalMilliseconds`` — equivalent to the reference's churn goroutine
observed at cycle boundaries.

Throughput definition: measured-phase scheduled pods / measured-phase wall
seconds — the average the reference's threshold selector asserts on
(scheduler_perf.go:352-359 "SchedulingThroughput / Average"; collector
util.go:468 samples scheduled-pod deltas every second and averages).
"""

from __future__ import annotations

import hashlib
import os
import time
from dataclasses import dataclass, field

import numpy as np

from ..api import types as t
from ..framework import config as C
from ..metrics.scheduler_metrics import window_quantile_ms
from ..sched.scheduler import Scheduler
from . import workloads as W


def round_latency_ms(v: float | None) -> float | None:
    """THE latency rounding for persisted results (2 decimals) — one
    place, so two emissions of one value never differ by rounding."""
    return None if v is None else round(float(v), 2)


def measured_p99_ms(sched: "Scheduler", prom_base: dict | None) -> float | None:
    """p99 of pod_scheduling_sli_duration_seconds in MILLISECONDS, scoped
    to the measured window (the ``_begin_measured_phase`` baseline): a
    large init phase must not dominate the reported p99s. Shared by both
    run modes; the staged percentiles apply the same scoping per stage."""
    if prom_base is None:
        return None
    return window_quantile_ms(
        sched.metrics.prom.pod_scheduling_sli_duration,
        prom_base.get("sli_duration"),
        0.99,
    )


@dataclass
class WorkloadResult:
    case_name: str
    workload_name: str
    threshold: float | None
    measure_pods: int
    scheduled: int
    duration_s: float
    throughput: float                 # pods/s, the SchedulingThroughput avg
    vs_threshold: float | None        # throughput / threshold
    attempts: int
    cycles: int
    p99_attempt_latency_ms: float | None = None
    threshold_note: str = ""          # derivation of a scaled threshold
    # device-traffic view of the measured phase (from the per-cycle TPU
    # records): cycle rate, ACTUAL host→device bytes per cycle vs what a
    # residency-less encode would have shipped, resident-state size, and
    # how many pipelined cycles were replayed for parity
    cycles_per_sec: float | None = None
    transfer_bytes_per_cycle: float | None = None
    batch_bytes_per_cycle: float | None = None
    resident_bytes: int = 0
    compile_misses: int = 0
    pipeline_replays: int = 0
    # host-encode view of the measured phase: encode-span wall per cycle,
    # its share of the scheduling-cycle wall (the r05 trace showed 86% —
    # the tentpole's target is ≤ 40%), and the encode-cache hit rate
    encode_ms_per_cycle: float | None = None
    encode_wall_frac: float | None = None
    encode_cache_hit_rate: float | None = None
    # the dispatcher's mean bulk micro-batch size and error count over the
    # measured phase
    dispatcher_batch_mean: float | None = None
    dispatcher_errors: int = 0
    # mesh-sharded assignment (parallel.mesh): device count + mesh shape the
    # run was sharded over ((), 1 = single device) and the cross-shard
    # reduction probe — MULTICHIP records must carry their own context
    n_devices: int = 1
    mesh_shape: tuple = ()
    collective_wall_s: float | None = None
    # where the sharded resident block REALLY lives (None when unsharded):
    # block_sharded (False = the whole block fell back to one device),
    # resident_devices (fewest devices any node-major leaf spans) and
    # shards_with_transfer (shards that received routed delta bytes)
    mesh_placement: dict | None = None
    # post-run metric snapshot (SchedulerMetricsRegistry.snapshot): p50/p99
    # from the histograms + schedule_attempts by result — every result JSON
    # carries its own diagnosis
    metrics_snapshot: dict | None = None
    # per-pod staged latency attribution, measured-window scoped
    # (sched.flightrecorder → scheduler_e2e_scheduling_duration_seconds):
    # {stage: {"p50": ms, "p99": ms}} for queue_wait/encode/kernel/
    # dispatch/bind_rtt/e2e (+ api_ingest/informer through the full stack)
    staged_latency_ms: dict | None = None
    # SustainedChurn soak gate: p99 e2e of the measured window's first vs
    # second half + the flatness verdict ("p99 flat for minutes")
    soak: dict | None = None
    # flight recorder + per-pod tracing state for this run (the <5%
    # overhead budget's on/off comparison key)
    flight_recorder: bool = True
    # sha256 over the sorted (pod, node) pairs this run bound: two runs
    # bound the same map, pod for pod, iff their digests are equal
    bindings_digest: str = ""
    # anomaly-sentinel view when a run rode the sentinel (--sentinel):
    # lifecycle stats (evaluations/fired/bundles), the per-alert final
    # states, clean (nothing fired — the false-positive gate), and in
    # spike mode the injected-stall fire→bundle→resolve verdict
    sentinel: dict | None = None
    # --- trace-shaped workloads (run_workload_trace) ---------------------
    # admission-latency SLO: p50/p99 of enqueue→bind over every pod the
    # trace created, judged against the profile's declared budget
    # (slo_ok = p99 <= budget)
    admission_p50_ms: float | None = None
    admission_p99_ms: float | None = None
    slo_budget_ms: float | None = None
    slo_ok: bool | None = None
    # host-memory ceiling of the stage: max RSS sampled per cycle during
    # the measured window
    peak_rss_bytes: int = 0
    # the stage hit its wall budget and emitted a TRUNCATED-but-parseable
    # record instead of running on (the 100k-node rungs)
    truncated: bool = False
    # trace bookkeeping: events replayed / pods created / deleted by the
    # trace / still unbound at the end / node count when it finished, and
    # the encode-cache re-encode accounting (scoped-invalidation evidence)
    trace_stats: dict | None = None
    # --- packing frontier (PR 19) ----------------------------------------
    # utilization-vs-throughput evidence, engine-agnostic so a comparison
    # of the three engines reads the same keys from every run:
    # distinct nodes carrying the measured pods once the run settled, the
    # fraction of high-priority (priority > 0) measured pods that actually
    # bound, and — packing cycles only — the warm-started solver's mean
    # projection-loop iterations per measured cycle + the weight tensor
    # that produced the frontier (reproducible from the JSON alone)
    nodes_used_at_steady_state: int | None = None
    priority_slo_hit_rate: float | None = None
    solver_iters_per_cycle: float | None = None
    packing_weights: dict | None = None
    # --- node-topology axis (PR 20) --------------------------------------
    # slice-level fragmentation evidence on labeled fleets: the topology
    # mode the run used, total labeled TPU slices, how many were FULLY
    # free when the trace settled, the fraction of labeled slices left
    # partially occupied (0 = perfectly defragged), and the p99
    # quorum→admitted gang latency
    # from scheduler_gang_admission_duration_seconds
    topology: str = "off"
    slices_total: int | None = None
    slices_free_at_steady_state: int | None = None
    fragmentation_index: float | None = None
    gang_admission_p99_ms: float | None = None
    # artifact paths written next to the result JSON when tracing is on:
    # chrome trace, /metrics text, device-side cycle records
    artifacts: dict = field(default_factory=dict)

    def to_json(self) -> dict:
        from .. import device_stamp

        out = {
            "case": self.case_name,
            "workload": self.workload_name,
            "metric": "SchedulingThroughput/Average",
            "value": round(self.throughput, 1),
            "unit": "pods/s",
            **device_stamp(),
            "scheduled": self.scheduled,
            "measure_pods": self.measure_pods,
            "duration_s": round(self.duration_s, 3),
            "attempts": self.attempts,
            "cycles": self.cycles,
        }
        if self.threshold is not None:
            out["threshold"] = self.threshold
            out["vs_baseline"] = round(self.vs_threshold, 2)
        if self.threshold_note:
            out["threshold_note"] = self.threshold_note
        if self.p99_attempt_latency_ms is not None:
            out["p99_attempt_latency_ms"] = round_latency_ms(
                self.p99_attempt_latency_ms
            )
        if self.cycles_per_sec is not None:
            out["cycles_per_sec"] = round(self.cycles_per_sec, 2)
        if self.transfer_bytes_per_cycle is not None:
            out["transfer_bytes_per_cycle"] = round(self.transfer_bytes_per_cycle)
        if self.batch_bytes_per_cycle is not None:
            out["batch_bytes_per_cycle"] = round(self.batch_bytes_per_cycle)
        if self.resident_bytes:
            out["resident_bytes"] = self.resident_bytes
        if self.pipeline_replays:
            out["pipeline_replays"] = self.pipeline_replays
        if self.encode_ms_per_cycle is not None:
            out["encode_ms_per_cycle"] = round(self.encode_ms_per_cycle, 2)
        if self.encode_wall_frac is not None:
            out["encode_wall_frac"] = round(self.encode_wall_frac, 3)
        if self.encode_cache_hit_rate is not None:
            out["encode_cache_hit_rate"] = round(self.encode_cache_hit_rate, 4)
        if self.dispatcher_batch_mean is not None:
            out["dispatcher_batch_mean"] = round(self.dispatcher_batch_mean, 1)
        if self.dispatcher_errors:
            out["dispatcher_errors"] = self.dispatcher_errors
        if self.mesh_shape:
            out["n_devices"] = self.n_devices
            out["mesh_shape"] = list(self.mesh_shape)
            if self.collective_wall_s is not None:
                out["collective_wall_s"] = round(self.collective_wall_s, 6)
            out["mesh_placement"] = self.mesh_placement
        if self.staged_latency_ms is not None:
            out["staged_latency_ms"] = self.staged_latency_ms
        if self.soak is not None:
            out["soak"] = self.soak
        if not self.flight_recorder:
            out["flight_recorder"] = False
        if self.bindings_digest:
            out["bindings_digest"] = self.bindings_digest
        if self.compile_misses:
            out["compile_misses"] = self.compile_misses
        if self.admission_p99_ms is not None:
            out["admission_p99_ms"] = round_latency_ms(self.admission_p99_ms)
            if self.admission_p50_ms is not None:
                out["admission_p50_ms"] = round_latency_ms(
                    self.admission_p50_ms
                )
        if self.slo_budget_ms is not None:
            out["slo_budget_ms"] = self.slo_budget_ms
            out["slo_ok"] = self.slo_ok
        if self.peak_rss_bytes:
            out["peak_rss_bytes"] = self.peak_rss_bytes
        if self.truncated:
            out["truncated"] = True
        if self.trace_stats is not None:
            out["trace"] = self.trace_stats
        if self.sentinel is not None:
            out["sentinel"] = self.sentinel
        if self.nodes_used_at_steady_state is not None:
            out["nodes_used_at_steady_state"] = self.nodes_used_at_steady_state
        if self.priority_slo_hit_rate is not None:
            out["priority_slo_hit_rate"] = round(self.priority_slo_hit_rate, 4)
        if self.solver_iters_per_cycle is not None:
            out["solver_iters_per_cycle"] = round(self.solver_iters_per_cycle, 2)
        if self.packing_weights is not None:
            out["packing_weights"] = self.packing_weights
        if self.topology and self.topology != "off":
            out["topology"] = self.topology
        if self.slices_total is not None:
            out["slices_total"] = self.slices_total
        if self.slices_free_at_steady_state is not None:
            out["slices_free_at_steady_state"] = (
                self.slices_free_at_steady_state
            )
        if self.fragmentation_index is not None:
            out["fragmentation_index"] = round(self.fragmentation_index, 4)
        if self.gang_admission_p99_ms is not None:
            out["gang_admission_p99_ms"] = round_latency_ms(
                self.gang_admission_p99_ms
            )
        if self.metrics_snapshot is not None:
            out["metrics"] = self.metrics_snapshot
        if self.artifacts:
            out["artifacts"] = self.artifacts
        return out


def bindings_digest(bound) -> str:
    """Digest of the (pod name, node name) pairs a run bound, order-free:
    two runs bound the same map, pod for pod, iff the digests are equal."""
    return hashlib.sha256(repr(sorted(bound)).encode()).hexdigest()[:16]


def dump_diagnosis_artifacts(
    sched: "Scheduler", artifacts_dir: str, prefix: str
) -> dict[str, str]:
    """Write the run's diagnosis artifacts next to the result JSON: the
    cycle trace as Perfetto-loadable Chrome-trace JSON, a /metrics text
    snapshot, and the device-side per-cycle counter records (joined to the
    trace spans by cycle id). Returns {artifact: path}."""
    import json as _json
    import os

    os.makedirs(artifacts_dir, exist_ok=True)
    base = os.path.join(artifacts_dir, prefix)
    trace_path = sched.tracer.dump_chrome_trace(base + ".trace.json")
    metrics_path = base + ".metrics.prom"
    with open(metrics_path, "w") as f:
        f.write(sched.metrics_text())
    cycles_path = base + ".tpu_cycles.json"
    with open(cycles_path, "w") as f:
        _json.dump(sched.metrics.tpu.records_json(), f)
    return {
        "trace": trace_path,
        "metrics": metrics_path,
        "tpu_cycles": cycles_path,
    }


class _Client:
    """API-server stand-in: dispatcher calls land here; bind/delete feed the
    informer handlers back on the loop thread via a pending queue (the
    watch-event delivery the reference gets from the apiserver)."""

    def __init__(self) -> None:
        self.sched: Scheduler | None = None
        self.bound: list[tuple[str, str]] = []
        import collections
        self._events: "collections.deque" = collections.deque()
        # bind-time counts per namespace: the throughput collector's view
        # (scheduler_perf measures SchedulingThroughput at bind, scoped to
        # the measured op's pods — churn/preemption traffic must not count)
        self.bound_by_ns: "collections.Counter" = collections.Counter()

    def bind(self, pod: t.Pod, node_name: str) -> None:
        self.bound.append((pod.name, node_name))
        self.bound_by_ns[pod.namespace] += 1
        self._events.append(("update", pod, pod.with_node(node_name)))

    def bulk_bind(self, pairs) -> list:
        # direct mode has no RPC to amortize; accepting the micro-batch
        # keeps the dispatch shape (and its batch-size stats) identical to
        # the served path's
        for pod, node_name in pairs:
            self.bind(pod, node_name)
        return [None] * len(pairs)

    def delete_pod(self, pod: t.Pod, reason: str = "") -> None:
        self._events.append(("delete", pod, None))

    def patch_status(self, pod: t.Pod, reason: str, message: str = "") -> None:
        pass

    def nominate(self, pod: t.Pod, node_name: str) -> None:
        pass

    def deliver(self) -> None:
        """Drain informer events on the loop thread."""
        while True:
            try:
                kind, a, b = self._events.popleft()
            except IndexError:
                return
            if kind == "update":
                self.sched.on_pod_update(a, b)
            else:
                self.sched.on_pod_delete(a)


def _begin_measured_phase(sched, warmup: bool, warm_pods):
    """Optionally compile the measured phase's device program (the full
    bucket ladder, so remainder batches hit the compile cache too), then
    snapshot the metric counters (and the histograms, via a prom baseline)
    so the measurement AND the embedded metrics snapshot are scoped to the
    same window — a large init phase must not dominate the reported p99s."""
    if warmup:
        sched.warmup(warm_pods)
    # measured-window baseline for the replay counter (init-phase churn —
    # PV/namespace creation — replays in-flight init cycles and must not
    # pollute the measured-phase evidence)
    sched._measure_replays0 = sched.metrics.pipeline_replays
    # encode-cache hit/miss baseline: the init/warmup misses (first sight
    # of every template) must not dilute the steady-state hit rate
    if sched.encode_cache is not None:
        kinds = ("filter", "score", "request")
        sched._measure_cache0 = (
            sum(sched.encode_cache.hits[k] for k in kinds),
            sum(sched.encode_cache.misses[k] for k in kinds),
        )
    # dispatcher baseline: mean bulk batch size + errors scoped to the
    # measured phase, not the init churn
    sched._measure_disp0 = sched.dispatcher.stats()
    # measured-window start on the lifecycle clock (perf_counter): the
    # soak stage splits the flight recorder's e2e samples at this
    # window's midpoint
    sched._measure_t0_pc = time.perf_counter()
    return (
        sched.metrics.schedule_attempts,
        sched.metrics.cycles,
        sched.metrics.prom.snapshot_baseline(),
    )


def _encode_stats(sched, cycles0: int) -> dict:
    """Measured-phase host-encode summary from the cycle trace spans
    (scoped by cycle id) + the encode-cache counters."""
    out = dict(
        encode_ms_per_cycle=None, encode_wall_frac=None,
        encode_cache_hit_rate=None,
    )
    spans = sched.tracer.recent(1 << 30)
    enc_s = [
        s.duration_s for s in spans
        if s.name == "encode" and s.attrs.get("cycle", 0) > cycles0
    ]
    cyc_s = [
        s.duration_s for s in spans
        if s.name == "scheduling-cycle" and s.attrs.get("cycle", 0) > cycles0
    ]
    if enc_s:
        out["encode_ms_per_cycle"] = 1000.0 * sum(enc_s) / len(enc_s)
    if enc_s and cyc_s and sum(cyc_s) > 0:
        out["encode_wall_frac"] = sum(enc_s) / sum(cyc_s)
    if sched.encode_cache is not None:
        kinds = ("filter", "score", "request")
        h = sum(sched.encode_cache.hits[k] for k in kinds)
        m = sum(sched.encode_cache.misses[k] for k in kinds)
        h0, m0 = getattr(sched, "_measure_cache0", (0, 0))
        dh, dm = h - h0, m - m0
        if dh + dm:
            out["encode_cache_hit_rate"] = dh / (dh + dm)
    return out


def _staged_and_soak(sched, prom_base) -> dict:
    """Measured-window staged percentiles + the SustainedChurn soak split
    (both None when the flight recorder is off or nothing bound)."""
    out = dict(
        staged_latency_ms=None, soak=None,
        flight_recorder=sched.flight_recorder is not None,
    )
    if sched.flight_recorder is None:
        return out
    out["staged_latency_ms"] = sched.metrics.prom.staged_percentiles(
        prom_base
    )
    t0 = getattr(sched, "_measure_t0_pc", None)
    if t0 is not None:
        out["soak"] = sched.flight_recorder.soak_split(
            t0, time.perf_counter()
        )
    return out


def _mesh_stats(sched) -> dict:
    """Mesh context of the run (device count / shape / collective probe) —
    stamped into every record so multichip numbers are self-describing."""
    shape = sched.mesh_shape
    n = 1
    for d in shape:
        n *= d
    placement = None
    block = sched._resident.device
    if sched.mesh is not None and block is not None:
        import jax

        sent = [0] * n
        for r in sched.metrics.tpu.records:
            for i, b in enumerate(r.shard_transfer_bytes or ()):
                sent[i] += b
        placement = dict(
            block_sharded=sched._resident._block_sharded,
            resident_devices=min(
                len(leaf.sharding.device_set)
                for leaf in jax.tree_util.tree_leaves(block)
            ),
            shards_with_transfer=sum(1 for b in sent if b),
        )
    return dict(
        n_devices=n,
        mesh_shape=shape,
        collective_wall_s=sched._collective_wall_s,
        mesh_placement=placement,
    )


def _dispatcher_stats(sched) -> dict:
    """Measured-phase dispatcher summary: mean bulk micro-batch size and
    API-write error count (deltas against the ``_begin_measured_phase``
    baseline)."""
    stats = sched.dispatcher.stats()
    base = getattr(sched, "_measure_disp0", None) or {}
    d_batches = stats["batches"] - base.get("batches", 0)
    d_calls = stats["batched_calls"] - base.get("batched_calls", 0)
    return dict(
        dispatcher_batch_mean=(d_calls / d_batches) if d_batches else None,
        dispatcher_errors=stats["errors"] - base.get("errors", 0),
    )


def _device_traffic_stats(sched, cycles0: int, duration: float) -> dict:
    """Measured-phase device-traffic summary from the per-cycle TPU
    records (joined to the window by cycle id)."""
    recs = [r for r in sched.metrics.tpu.records if r.cycle > cycles0]
    out = dict(
        cycles_per_sec=None, transfer_bytes_per_cycle=None,
        batch_bytes_per_cycle=None, resident_bytes=0,
        compile_misses=sum(1 for r in recs if r.compile_miss),
        pipeline_replays=(
            sched.metrics.pipeline_replays
            - getattr(sched, "_measure_replays0", 0)
        ),
    )
    if recs:
        out["transfer_bytes_per_cycle"] = (
            sum(r.transfer_bytes for r in recs) / len(recs)
        )
        out["batch_bytes_per_cycle"] = (
            sum(r.batch_bytes for r in recs) / len(recs)
        )
        out["resident_bytes"] = max(r.resident_bytes for r in recs)
        if duration > 0:
            out["cycles_per_sec"] = len(recs) / duration
    return out


def _packing_stats(sched, cycles0: int, bound, created) -> dict:
    """Packing-frontier evidence (engine-agnostic keys, PR 19):

    - ``nodes_used_at_steady_state``: distinct nodes carrying the MEASURED
      pods (name prefix ``measure-``) at the end of the run — the
      utilization half of the frontier, comparable across engines.
    - ``priority_slo_hit_rate``: among measured pods created with
      priority > 0, the fraction that actually bound (None when the
      workload has no priority tiers).
    - ``solver_iters_per_cycle``: mean packing-solver iterations over the
      measured cycles' device records (None for greedy/batched — they
      never stamp ``solver_iters``).
    - ``packing_weights``: the weight tensor behind the run, so a
      measured frontier is reproducible from its JSON alone.

    ``bound`` is an iterable of (pod_name, node_name); ``created`` an
    iterable of created Pod objects."""
    bound = list(bound)
    measured_nodes = {
        node for name, node in bound if name.startswith("measure-")
    }
    out: dict = dict(
        nodes_used_at_steady_state=(
            len(measured_nodes) if measured_nodes else None
        ),
        priority_slo_hit_rate=None,
        solver_iters_per_cycle=None,
        packing_weights=None,
    )
    bound_names = {name for name, _ in bound}
    high = [p for p in created
            if p.priority > 0 and p.name.startswith("measure-")]
    if high:
        out["priority_slo_hit_rate"] = (
            sum(1 for p in high if p.name in bound_names) / len(high)
        )
    iters = [
        r.solver_iters for r in sched.metrics.tpu.records
        if r.cycle > cycles0 and r.solver_iters is not None
    ]
    if iters:
        out["solver_iters_per_cycle"] = sum(iters) / len(iters)
    eng = getattr(sched, "_assign_device", None)
    weights = getattr(eng, "weights", None)
    if weights is not None and hasattr(weights, "to_json"):
        out["packing_weights"] = weights.to_json()
    return out


@dataclass
class _Deleter:
    """deletePodsOp with skipWaitToCompletion: drain a namespace's created
    pods at ``per_second`` between cycles (each delete fires the
    AssignedPodDelete event through the queue)."""

    pods: list
    per_second: int
    started_at: float = -1.0
    deleted: int = 0

    def maybe_fire(self, sched: Scheduler, now: float) -> None:
        if self.started_at < 0:
            self.started_at = now
        due = int((now - self.started_at) * self.per_second)
        while self.deleted < min(due, len(self.pods)):
            sched.on_pod_delete(self.pods[self.deleted])
            self.deleted += 1


@dataclass
class _Churn:
    op: W.ChurnOp
    namespace: str
    next_at: float = 0.0
    seq: int = 0
    live: list = field(default_factory=list)   # recreate-mode pool

    def maybe_fire(self, sched: Scheduler, now: float) -> None:
        while now >= self.next_at:
            self.next_at = (self.next_at or now) + self.op.interval_ms / 1000.0
            if self.op.mode == "recreate" and self.op.number and (
                len(self.live) >= self.op.number
            ):
                victim = self.live.pop(0)
                sched.on_pod_delete(victim)
            pod = self.op.template(f"churn-{self.seq}", self.namespace)
            self.seq += 1
            sched.on_pod_add(pod)
            if self.op.mode == "recreate":
                self.live.append(pod)


def _bulk_create(
    remote, kind: str, items: "list[tuple[str, object]]", chunk: int = 256,
) -> None:
    """Create ``items`` through the REST store, one bulk request per
    ``chunk``; the first op an apiserver refuses raises."""
    from ..store.memstore import bulk_result_error

    for i in range(0, len(items), chunk):
        ops = [
            {"op": "create", "key": k, "object": o}
            for k, o in items[i:i + chunk]
        ]
        for res in remote.bulk(kind, ops):
            err = bulk_result_error(res)
            if err is not None:
                raise err


def run_workload(
    case: W.TestCase | str,
    workload: W.Workload | str,
    profile: C.Profile | None = None,
    max_batch: int = 1024,
    timeout_s: float = 1800.0,
    engine: str = "greedy",
    stall_s: float = 15.0,
    warmup: bool = True,
    artifacts_dir: str | None = None,
    pipeline: bool = False,
    encode_cache: bool = True,
    bulk: bool = True,
    mesh=None,
    flight_recorder: bool = True,
) -> WorkloadResult:
    """Execute one (test case, workload) pair and return the measurement.
    ``engine`` selects the assignment engine ("greedy" scan or "batched"
    rounds); ``stall_s`` is how long zero progress must persist before a
    phase gives up (must exceed the queue's max backoff, default 10 s, or
    backed-off pods read as stalls). ``warmup`` compiles the measured
    phase's device programs — the whole bucket ladder — before its clock
    starts (via ``Scheduler.warmup``; no scheduling-state mutation) — a
    long-lived scheduler compiles once at startup, so measured throughput
    is steady-state, like the reference's precompiled binary. ``pipeline``
    runs the two-stage pipelined cycle with the device-resident node block
    (Scheduler(pipeline=True)). ``artifacts_dir`` dumps the run's
    Chrome-trace JSON, /metrics snapshot, and device-side cycle records
    there (see ``dump_diagnosis_artifacts``). ``encode_cache`` toggles the
    event-time template-keyed encode cache (``--encode-cache off`` escape
    hatch — cached and fresh encodes are bit-identical). ``bulk`` toggles
    the dispatcher's cycle-boundary micro-batching (``--bulk off`` escape
    hatch — the off path is pod-for-pod identical). ``mesh`` shards the
    node axis over a device mesh (Scheduler(mesh=…): None/"off", "auto",
    "on", or a jax.sharding.Mesh) — bit-identical assignments, N-chip
    capacity. ``flight_recorder`` toggles the scheduling flight recorder +
    per-pod staged latency attribution (``--flight-recorder off`` is the
    overhead escape hatch)."""
    if isinstance(case, str):
        case = W.TEST_CASES[case]
    if isinstance(workload, str):
        workload = next(w for w in case.workloads if w.name == workload)
    params = dict(workload.params)

    client = _Client()
    sched = Scheduler(
        client, profile=profile or C.Profile(), max_batch=max_batch,
        engine=engine, pipeline=pipeline, encode_cache=encode_cache,
        bulk=bulk, mesh=mesh, flight_recorder=flight_recorder,
        feature_gates=dict(case.feature_gates) if case.feature_gates else None,
    )
    client.sched = sched
    sched.enable_preemption()

    churns: list[_Churn] = []
    deleters: list[_Deleter] = []
    created_by_ns: dict[str, list[t.Pod]] = {}
    measured = 0
    duration = 0.0
    attempts0 = cycles0 = 0
    prom_base = None
    op_ns_counter = 0

    def settle(target: int, namespaces: tuple[str, ...] = ()) -> tuple[int, float]:
        """Run cycles until ``target`` pods of the op's ``namespaces`` are
        BOUND (or stall). Churn fires between cycles; its pods bind in
        their own namespaces and never count toward the op's target (the
        reference scopes SchedulingThroughput to the measured pods too).
        Returns (bound, wall seconds)."""

        def bound_now() -> int:
            return sum(client.bound_by_ns[ns] for ns in namespaces)

        start = bound_now()
        done = 0
        t0 = time.perf_counter()
        deadline = t0 + timeout_s
        last_progress = t0
        while done < target:
            now = time.perf_counter()
            if now > deadline:
                break
            for ch in churns:
                ch.maybe_fire(sched, now)
            for d in deleters:
                d.maybe_fire(sched, now)
            res = sched.schedule_batch()
            client.deliver()
            before = done
            done = bound_now() - start
            if done == before and res["scheduled"] == 0:
                # pods may simply be in backoff (max 10 s by default): only
                # a sustained quiet period is a real stall
                if now - last_progress > stall_s:
                    break
                time.sleep(0.005)
            else:
                last_progress = now
        return done, time.perf_counter() - t0

    created_nodes: list[str] = []
    for op_i, op in enumerate(case.ops):
        if isinstance(op, W.CreateNodesOp):
            n = op.count or params[op.count_param]
            factory = op.template or W.node_default
            for i in range(n):
                node = factory(i, op.zones)
                created_nodes.append(node.name)
                sched.on_node_add(node)
        elif isinstance(op, W.CreateNamespacesOp):
            # namespace objects carry labels for affinity namespaceSelectors
            n = params[op.count_param] if op.count_param else op.count
            for i in range(n):
                sched.on_namespace_add(t.Namespace(
                    name=f"{op.prefix}-{i}", labels=op.labels,
                ))
        elif isinstance(op, W.CreateServiceOp):
            sched.on_service_add(t.Service(
                name=op.name, namespace=op.namespace, selector=op.selector,
            ))
        elif isinstance(op, W.DeletePodsOp):
            deleters.append(_Deleter(
                pods=list(created_by_ns.get(op.namespace, ())),
                per_second=op.per_second,
            ))
        elif isinstance(op, W.CreatePodSetsOp):
            count = params[op.count_param]
            per = params[op.pods_param]
            template = op.template or case.default_pod_template
            total_sets = 0
            for g in range(count):
                ns = f"{op.prefix}-{g}"
                for j in range(per):
                    pod = template(f"set-{op_i}-{g}-{j}", ns)
                    created_by_ns.setdefault(ns, []).append(pod)
                    sched.on_pod_add(pod)
                    total_sets += 1
            settle(total_sets, tuple(
                f"{op.prefix}-{g}" for g in range(count)
            ))
        elif isinstance(op, W.CreatePodGroupsOp):
            from ..api.wrappers import make_pod_group

            groups = params[op.count_param]
            min_count = params[op.min_count_param]
            for g in range(groups):
                sched.on_pod_group_add(make_pod_group(
                    f"{op.prefix}-{g}", namespace=f"{op.prefix}-0",
                    min_count=min_count,
                ))
        elif isinstance(op, W.CreatePodsWithPVsOp):
            from ..api.wrappers import make_pod

            count = params[op.count_param]
            ns = op.namespace or f"pv-{op_i}"
            if op.collect_metrics:
                # warmup shape: plain pods (the PVC mask is a static-sig
                # column; shapes match the measured batch)
                attempts0, cycles0, prom_base = _begin_measured_phase(
                    sched, warmup,
                    [
                        make_pod(f"warmup-pv-{j}", namespace=ns,
                                 cpu_milli=100, memory=500 * 1024**2)
                        for j in range(min(count, sched.max_batch))
                    ],
                )
            for j in range(count):
                pv_name = f"{ns}-pv-{j}"
                sched.on_pv_add(t.PersistentVolume(
                    name=pv_name, driver=op.driver,
                    access_modes=("ReadOnlyMany",), capacity=1024**3,
                    claim_ref=f"{ns}/{ns}-claim-{j}",
                ))
                sched.on_pvc_add(t.PersistentVolumeClaim(
                    name=f"{ns}-claim-{j}", namespace=ns,
                    volume_name=pv_name, access_modes=("ReadOnlyMany",),
                    request=1024**3,
                ))
                sched.on_pod_add(make_pod(
                    f"pvpod-{op_i}-{j}", namespace=ns, cpu_milli=100,
                    memory=500 * 1024**2, creation_index=j,
                    pvcs=(f"{ns}-claim-{j}",),
                ))
            done, secs = settle(count, (ns,))
            if op.collect_metrics:
                measured += done
                duration += secs
        elif isinstance(op, W.CreateExtendedResourcePodsOp):
            from ..api.wrappers import make_pod

            count = params[op.count_param]
            ns = op.namespace
            if op.collect_metrics:
                attempts0, cycles0, prom_base = _begin_measured_phase(
                    sched, warmup,
                    [
                        make_pod(
                            f"warmup-ext-{j}", namespace=ns,
                            requests={f"foo.com/bar-{j}": 1},
                        )
                        for j in range(min(count, sched.max_batch))
                    ],
                )
            for j in range(count):
                sched.on_pod_add(make_pod(
                    f"extpod-{j}", namespace=ns, creation_index=j,
                    requests={f"foo.com/bar-{j}": 1},
                ))
            done, secs = settle(count, (ns,))
            if op.collect_metrics:
                measured += done
                duration += secs
        elif isinstance(op, W.CreateGangPodsOp):
            from ..api.wrappers import make_pod

            groups = params[op.count_param]
            per = params[op.multiplier_param]
            count = groups * per
            if op.collect_metrics:
                # group-lane shapes: one coalesced batch of plain pods
                attempts0, cycles0, prom_base = _begin_measured_phase(
                    sched, warmup,
                    [
                        make_pod(
                            f"warmup-gang-{j}", namespace=op.namespace,
                            cpu_milli=100, memory=100 * 1024**2,
                        )
                        for j in range(min(count, sched.max_batch))
                    ],
                )
            for j in range(count):
                sched.on_pod_add(make_pod(
                    f"gangpod-{j}", namespace=op.namespace,
                    cpu_milli=100, memory=100 * 1024**2,
                    scheduling_group=f"{op.prefix}-{j // per}",
                    creation_index=j,
                ))
            done, secs = settle(count, (op.namespace,))
            if op.collect_metrics:
                measured += done
                duration += secs
        elif isinstance(op, W.CreateResourceDriverOp):
            sched.on_device_class_add(t.DeviceClass(
                name=op.class_name,
                selectors=(t.CELSelector(
                    f'device.driver == "{op.driver}"'
                ),),
            ))
            per_node = params[op.max_claims_param]
            for node_name in created_nodes:
                if not node_name.startswith(op.node_prefix):
                    continue
                sched.on_resource_slice_add(t.ResourceSlice(
                    name=f"slice-{node_name}", driver=op.driver,
                    pool=node_name, node_name=node_name,
                    devices=tuple(
                        t.Device(name=f"device-{d}")
                        for d in range(per_node)
                    ),
                ))
        elif isinstance(op, W.CreateClaimPodsOp):
            from ..api.wrappers import make_pod

            count = params[op.count_param]
            ns = op.namespace

            def claim_pod(name: str, ns: str = ns, op=op) -> t.Pod:
                sched.on_resource_claim_add(t.ResourceClaim(
                    name=f"{name}-claim", namespace=ns,
                    uid=f"{ns}/{name}-claim",
                    requests=(t.DeviceRequest(
                        name="req-0", device_class_name=op.class_name,
                    ),),
                ))
                return make_pod(
                    name, namespace=ns, claims=(f"{name}-claim",),
                )

            if op.collect_metrics:
                attempts0, cycles0, prom_base = _begin_measured_phase(
                    sched, warmup,
                    [
                        claim_pod(f"warmup-dra-{j}")
                        for j in range(min(count, sched.max_batch))
                    ],
                )
            for j in range(count):
                pod = claim_pod(f"drapod-{op_i}-{j}")
                created_by_ns.setdefault(ns, []).append(pod)
                sched.on_pod_add(pod)
            done, secs = settle(count, (ns,))
            if op.collect_metrics:
                measured += done
                duration += secs
        elif isinstance(op, W.ChurnOp):
            churns.append(_Churn(op=op, namespace=f"churn-{len(churns)}"))
        elif isinstance(op, W.BarrierOp):
            sched.run_until_idle()
            client.deliver()
        elif isinstance(op, W.CreatePodsOp):
            count = params[op.count_param]
            template = op.template or case.default_pod_template
            ns = op.namespace or f"namespace-{op_ns_counter}"
            op_ns_counter += 1
            # the op index keeps names unique when several createPods ops
            # share one namespace (MixedSchedulingBasePod does)
            prefix = f"{'measure' if op.collect_metrics else 'init'}-{op_i}"
            if op.collect_metrics:
                attempts0, cycles0, prom_base = _begin_measured_phase(
                    sched, warmup,
                    [
                        template(f"warmup-{op_i}-{j}", ns)
                        for j in range(min(count, sched.max_batch))
                    ],
                )
            for j in range(count):
                pod = template(f"{prefix}-{ns}-{j}", ns)
                created_by_ns.setdefault(ns, []).append(pod)
                sched.on_pod_add(pod)
            if op.skip_wait:
                continue
            done, secs = settle(count, (ns,))
            if op.collect_metrics:
                measured += done
                duration += secs
        else:
            raise TypeError(f"unknown op {op!r}")

    sched.dispatcher.sync()
    client.deliver()
    sched._drain_bind_completions()
    # p99 from the pod_scheduling_sli_duration_seconds HISTOGRAM, scoped to
    # the measured phase (measured_p99_ms — the shared window-scoping
    # helper; histogram_quantile estimation)
    lat = measured_p99_ms(sched, prom_base)
    artifacts: dict[str, str] = {}
    if artifacts_dir is not None:
        artifacts = dump_diagnosis_artifacts(
            sched, artifacts_dir,
            f"{case.name}_{workload.name}_{engine}",
        )
    throughput = measured / duration if duration > 0 else 0.0
    traffic = _device_traffic_stats(sched, cycles0, duration)
    result = WorkloadResult(
        case_name=case.name,
        workload_name=workload.name,
        threshold=workload.threshold,
        threshold_note=workload.threshold_note,
        **traffic,
        **_packing_stats(
            sched, cycles0, client.bound,
            [p for pods in created_by_ns.values() for p in pods],
        ),
        **_encode_stats(sched, cycles0),
        **_dispatcher_stats(sched),
        **_mesh_stats(sched),
        **_staged_and_soak(sched, prom_base),
        measure_pods=sum(
            params[op.count_param]
            for op in case.ops
            if isinstance(op, W.CreatePodsOp) and op.collect_metrics
        ) + sum(
            params[op.count_param] * params[op.multiplier_param]
            for op in case.ops
            if isinstance(op, W.CreateGangPodsOp) and op.collect_metrics
        ) + sum(
            params[op.count_param]
            for op in case.ops
            if isinstance(
                op, (W.CreatePodsWithPVsOp, W.CreateExtendedResourcePodsOp,
                     W.CreateClaimPodsOp)
            ) and op.collect_metrics
        ),
        scheduled=measured,
        duration_s=duration,
        throughput=throughput,
        vs_threshold=(
            throughput / workload.threshold if workload.threshold else None
        ),
        attempts=sched.metrics.schedule_attempts - attempts0,
        cycles=sched.metrics.cycles - cycles0,
        p99_attempt_latency_ms=lat,
        bindings_digest=bindings_digest(client.bound),
        metrics_snapshot=sched.metrics.prom.snapshot(baseline=prom_base),
        artifacts=artifacts,
    )
    sched.close()
    return result


class _RssSampler:
    """Per-stage peak-RSS tracker: samples /proc/self/statm once per
    scheduling cycle (a few µs) and keeps the max. Stage-local on purpose
    — ru_maxrss is process-monotone and would attribute an earlier 100k
    stage's peak to every later record."""

    def __init__(self) -> None:
        self.peak = 0
        self._page = 4096
        self._f = None
        try:
            self._page = os.sysconf("SC_PAGE_SIZE")
            self._f = open("/proc/self/statm", "rb")
        except (OSError, ValueError, AttributeError):
            pass    # no procfs: sample() falls back to the monotone
            #         ru_maxrss (coarser semantics beat a zero)

    def sample(self) -> int:
        if self._f is not None:
            self._f.seek(0)
            rss = int(self._f.read().split()[1]) * self._page
        else:
            import resource

            rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        if rss > self.peak:
            self.peak = rss
        return rss

    def close(self) -> None:
        if self._f is not None:
            self._f.close()


class _TraceDirectDriver:
    """Direct-mode I/O for the trace replay: events land straight on the
    scheduler's informer handlers; bind times come off the in-process
    client."""

    def __init__(self, sched, client) -> None:
        self.sched = sched
        self.client = client
        self._nodes: dict[str, t.Node] = {}

    def add_node(self, node: t.Node) -> None:
        self._nodes[node.name] = node
        self.sched.on_node_add(node)

    def drain_node(self, name: str) -> None:
        node = self._nodes.pop(name, None)
        if node is not None:
            self.sched.on_node_delete(node)

    def create_pod(self, pod: t.Pod) -> None:
        self.sched.on_pod_add(pod)

    def delete_pod(self, key: str, pod: t.Pod) -> None:
        self.sched.on_pod_delete(pod)

    def create_group(self, ev) -> None:
        from ..api.wrappers import make_pod_group

        self.sched.on_pod_group_add(make_pod_group(
            ev.name, namespace=ev.namespace, min_count=ev.min_count,
        ))

    def pump(self) -> bool:
        self.client.deliver()
        return False

    def bind_times(self) -> dict:
        return self.client.bind_times

    def close(self) -> None:
        pass


class _TraceFullstackDriver:
    """Fullstack I/O for the trace replay: pod/node events go through the
    REST apiserver (bulk creates per tick) and come back through the
    informer seam — enqueue→bind spans the whole control plane. PodGroups
    have no REST kind; group events land on the scheduler directly (the
    one documented direct injection)."""

    def __init__(self, sched, remote, informers, client) -> None:
        self.sched = sched
        self.remote = remote
        self.informers = informers
        self.client = client

    def add_node(self, node: t.Node) -> None:
        from ..client.informers import NODES

        self.remote.create(NODES, node.name, node)

    def drain_node(self, name: str) -> None:
        from ..client.informers import NODES

        try:
            self.remote.delete(NODES, name)
        except Exception:
            pass

    def create_pod(self, pod: t.Pod) -> None:
        from ..client.informers import PODS

        self.remote.create(PODS, f"{pod.namespace}/{pod.name}", pod)

    def delete_pod(self, key: str, pod: t.Pod) -> None:
        from ..client.informers import PODS

        try:
            self.remote.delete(PODS, key)
        except Exception:
            pass    # already gone / rebound — the trace goes on

    def create_group(self, ev) -> None:
        from ..api.wrappers import make_pod_group

        self.sched.on_pod_group_add(make_pod_group(
            ev.name, namespace=ev.namespace, min_count=ev.min_count,
        ))

    def pump(self) -> bool:
        return bool(self.informers.pump())

    def bind_times(self) -> dict:
        return self.client.bind_times

    def close(self) -> None:
        pass


def run_workload_trace(
    profile,
    mode: str = "direct",
    engine: str = "greedy",
    max_batch: int = 128,
    timeout_s: float = 600.0,
    stall_s: float = 15.0,
    warmup: bool = True,
    speed: float = 1.0,
    wall_budget_s: float | None = None,
    encode_cache: bool = True,
    scoped_invalidation: bool = True,
    wire: str = "binary",
    artifacts_dir: str | None = None,
    sentinel: bool = False,
    sentinel_spike: bool = False,
    spike_stall_s: float = 6.0,
    topology: str = "off",
) -> WorkloadResult:
    """Replay a ``workloads.TraceProfile`` against the real scheduler loop
    and measure the admission-latency SLO: p50/p99 of enqueue→bind over
    every pod the trace created, judged against the profile's declared
    budget (``slo_ok``), plus per-stage peak RSS, device-resident bytes,
    and the encode-cache re-encode accounting — the scale-frontier record
    shape.

    ``mode``: "direct" (events on the informer handlers — the engine-bound
    number) or "fullstack" (through the REST apiserver + informers —
    enqueue→bind spans the control plane). ``speed`` scales the trace
    clock (2.0 = replay twice as fast). ``wall_budget_s``: hard stage wall
    — when exceeded the replay stops firing, the settle is skipped, and
    the record is emitted TRUNCATED but parseable (a hung 100k-node rung
    must never run on unbounded). ``scoped_invalidation=False``
    pins the encode cache's pre-PR-14 full-epoch flush (the A/B control
    the node-wave evidence is measured against).

    ``sentinel=True`` rides the anomaly sentinel on the loop with the
    profile's DECLARED ``slo_budget_ms`` as the burn-rate budget — the
    honest venue for the admission-SLO rule, because paced arrivals keep
    a clean replay inside budget (bulk-create workloads blow any fixed
    budget on tail queue-wait alone). ``sentinel_spike=True`` injects a
    one-shot ``spike_stall_s`` scheduler stall a third of the way
    through the replay: the loop keeps firing trace arrivals but skips
    the scheduling cycle, so the backlog accrues REAL admission latency
    — the record's ``sentinel.spike`` verdict carries the
    fire→bundle→resolve acceptance.

    ``topology``: the scheduler's ``--topology`` mode. On a profile with
    ``slices > 0`` every node carries the shared rack/slice grammar and
    the record gains the slice-level fragmentation evidence
    (slices_total / slices_free_at_steady_state / fragmentation_index)
    plus gang_admission_p99_ms from the gang-admission histogram."""
    from ..sched.scheduler import Scheduler
    from . import workloads as W

    if isinstance(profile, str):
        profile = W.TRACE_PROFILES[profile]
    events = profile.events()

    sentinel_obj = None
    if sentinel or sentinel_spike:
        from ..telemetry.rules import fast_rules
        from ..telemetry.sentinel import Sentinel as _Sentinel

        sentinel_obj = _Sentinel(
            rules=fast_rules(),
            slo_budget_ms=profile.slo_budget_ms,
            interval_s=0.25,
        )

    srv = remote = informers = None
    if mode == "direct":
        client = _TraceClient()
        sched = Scheduler(
            client, profile=C.Profile(), max_batch=max_batch, engine=engine,
            encode_cache=encode_cache,
            feature_gates={"GenericWorkload": True, "GangScheduling": True},
            sentinel=sentinel_obj if sentinel_obj is not None else False,
            topology=topology,
        )
        client.sched = sched
        driver = _TraceDirectDriver(sched, client)
    elif mode == "fullstack":
        from ..apiserver import APIServer, RemoteStore
        from ..client import SchedulerInformers
        from ..client.informers import NODES

        srv = APIServer().start()
        remote = RemoteStore(srv.url, wire=wire)
        client = _make_trace_store_client(remote)
        sched = Scheduler(
            client, profile=C.Profile(), max_batch=max_batch, engine=engine,
            encode_cache=encode_cache,
            feature_gates={"GenericWorkload": True, "GangScheduling": True},
            sentinel=sentinel_obj if sentinel_obj is not None else False,
            topology=topology,
        )
        informers = SchedulerInformers(remote, sched)
        informers.start()
        driver = _TraceFullstackDriver(sched, remote, informers, client)
    else:
        raise ValueError(f"unknown trace mode {mode!r}")
    if sched.encode_cache is not None and not scoped_invalidation:
        sched.encode_cache.scoped = False
    sched.enable_preemption()

    rss = _RssSampler()
    created_at: dict[str, float] = {}
    deleted: set[str] = set()
    pods_by_key: dict[str, t.Pod] = {}
    truncated = False
    try:
        # initial cluster
        slices = getattr(profile, "slices", 0)
        if mode == "direct":
            for i in range(profile.nodes):
                driver.add_node(W.node_default(i, profile.zones, slices))
        else:
            nodes = [
                W.node_default(i, profile.zones, slices)
                for i in range(profile.nodes)
            ]
            _bulk_create(
                remote, NODES, [(nd.name, nd) for nd in nodes],
            )
            driver.pump()
        if warmup:
            sched.warmup([
                W.build_trace_pod(W.TraceEvent(
                    0.0, "create_pod", f"warm-{j}", "trace-warm",
                ))
                for j in range(min(max_batch, 64))
            ])
        attempts0, cycles0, prom_base = _begin_measured_phase(
            sched, False, [],
        )
        rss.sample()

        t0 = time.perf_counter()
        deadline = t0 + timeout_s
        wall_deadline = (
            t0 + wall_budget_s if wall_budget_s is not None else None
        )
        i = 0
        last_progress = t0
        bound_prev = 0
        spike = {"stall_s": spike_stall_s, "until": None,
                 "start_wall": None, "end_wall": None}
        spike_armed = sentinel_spike

        def live_unbound() -> int:
            bt = driver.bind_times()
            return sum(
                1 for k in created_at if k not in deleted and k not in bt
            )

        while True:
            now = time.perf_counter()
            if wall_deadline is not None and now > wall_deadline:
                truncated = True
                break
            if now > deadline:
                truncated = True
                break
            trace_now = (now - t0) * speed
            fired = 0
            while i < len(events) and events[i].at_s <= trace_now:
                ev = events[i]
                i += 1
                fired += 1
                if ev.kind == "create_pod":
                    pod = W.build_trace_pod(ev)
                    key = f"{ev.namespace}/{ev.name}"
                    created_at[key] = time.perf_counter()
                    pods_by_key[key] = pod
                    driver.create_pod(pod)
                elif ev.kind == "delete_pod":
                    key = f"{ev.namespace}/{ev.name}"
                    deleted.add(key)
                    pod = pods_by_key.get(key)
                    if pod is not None:
                        driver.delete_pod(key, pod)
                elif ev.kind == "add_node":
                    driver.add_node(
                        make_trace_node(ev.name, profile.zones, slices)
                    )
                elif ev.kind == "drain_node":
                    driver.drain_node(ev.name)
                elif ev.kind == "create_group":
                    driver.create_group(ev)
            if spike_armed and i >= max(1, len(events) // 3):
                spike_armed = False
                spike["start_wall"] = time.time()
                spike["until"] = now + spike["stall_s"]
            stalled = (
                spike["until"] is not None and spike["end_wall"] is None
            )
            if stalled and now >= spike["until"]:
                spike["end_wall"] = time.time()
                stalled = False
            moved = driver.pump()
            if stalled:
                # the injected scheduler stall: arrivals keep landing (the
                # pump above) while the cycle is skipped — the backlog
                # accrues REAL admission latency, which is what makes the
                # burn-rate fire distinguishable from a clean replay
                res = {"scheduled": 0}
                time.sleep(0.002)
            else:
                res = sched.schedule_batch()
                driver.pump()
                sched.dispatcher.sync()
                sched._drain_bind_completions()
            rss.sample()
            bound_now = len(driver.bind_times())
            progressed = (
                fired or moved or res["scheduled"]
                or bound_now > bound_prev
            )
            bound_prev = bound_now
            if i >= len(events):
                # replay done: settle until every live pod bound or stall
                if live_unbound() == 0:
                    break
                if progressed:
                    last_progress = now
                elif now - last_progress > stall_s:
                    break
                else:
                    time.sleep(0.002)
            elif progressed:
                last_progress = now
            else:
                # idle until the next event is due (bounded nap)
                time.sleep(min(0.002, max(0.0, (
                    events[i].at_s / speed + t0 - now
                ))))
        duration = time.perf_counter() - t0
        sched.dispatcher.sync()
        driver.pump()
        sched._drain_bind_completions()
        sentinel_report = None
        if sentinel_obj is not None:
            sentinel_report = _sentinel_settle(
                sentinel_obj,
                spike if spike["end_wall"] is not None else None,
            )

        # admission latencies: enqueue→bind per created pod
        bt = driver.bind_times()
        lats = [
            (bt[k] - created_at[k]) * 1000.0
            for k in created_at if k in bt
        ]
        p50 = float(np.percentile(lats, 50)) if lats else None
        p99 = float(np.percentile(lats, 99)) if lats else None
        unbound = live_unbound()
        ec = sched.encode_cache
        trace_stats = {
            "profile": profile.name,
            "seed": profile.seed,
            "events": len(events),
            "fired": i,
            "created": len(created_at),
            "deleted": len(deleted),
            "unbound": unbound,
            "nodes_final": sched.cache.update_snapshot().num_nodes(),
            "samples": len(lats),
        }
        if ec is not None:
            st = ec.stats()
            trace_stats["encode_rebuilt_bytes"] = st["rebuilt_bytes"]
            trace_stats["encode_extended_bytes"] = st["extended_bytes"]
            trace_stats["encode_scoped_extensions"] = st["scoped_extensions"]
            trace_stats["encode_scoped_removals"] = st["scoped_removals"]
            trace_stats["encode_compacted_bytes"] = st["compacted_bytes"]
            trace_stats["encode_invalidations"] = st["invalidations"]
            trace_stats["scoped_invalidation"] = bool(ec.scoped)
        artifacts: dict[str, str] = {}
        if artifacts_dir is not None and not truncated:
            artifacts = dump_diagnosis_artifacts(
                sched, artifacts_dir,
                f"Trace_{profile.name}_{mode}_{engine}",
            )
        measured = len(lats)
        throughput = measured / duration if duration > 0 else 0.0
        traffic = _device_traffic_stats(sched, cycles0, duration)
        topo_stats = _trace_topology_stats(sched)
        return WorkloadResult(
            case_name=f"Trace_{profile.name}",
            workload_name=(
                f"{profile.nodes}Nodes" + ("" if mode == "direct"
                                           else "_fullstack")
            ),
            threshold=None,
            **traffic,
            **_encode_stats(sched, cycles0),
            **_dispatcher_stats(sched),
            **_mesh_stats(sched),
            **_staged_and_soak(sched, prom_base),
            # trace pods are not measure-prefixed: only the solver-side
            # packing stats (iters/weights) populate here; nodes_final in
            # trace_stats already carries the utilization story
            **_packing_stats(sched, cycles0, [], []),
            measure_pods=len(created_at),
            scheduled=measured,
            duration_s=duration,
            throughput=throughput,
            vs_threshold=None,
            attempts=sched.metrics.schedule_attempts - attempts0,
            cycles=sched.metrics.cycles - cycles0,
            p99_attempt_latency_ms=measured_p99_ms(sched, prom_base),
            admission_p50_ms=p50,
            admission_p99_ms=p99,
            slo_budget_ms=profile.slo_budget_ms,
            slo_ok=(
                p99 is not None and p99 <= profile.slo_budget_ms
                and unbound == 0 and not truncated
            ),
            peak_rss_bytes=rss.peak,
            truncated=truncated,
            sentinel=sentinel_report,
            topology=topology,
            slices_total=topo_stats.get("slices_total"),
            slices_free_at_steady_state=topo_stats.get(
                "slices_free_at_steady_state"
            ),
            fragmentation_index=topo_stats.get("fragmentation_index"),
            gang_admission_p99_ms=topo_stats.get("gang_admission_p99_ms"),
            trace_stats=trace_stats,
            metrics_snapshot=sched.metrics.prom.snapshot(baseline=prom_base),
            artifacts=artifacts,
        )
    finally:
        rss.close()
        sched.close()
        if srv is not None:
            srv.close()


def _trace_topology_stats(sched) -> dict:
    """Slice-level fragmentation at trace end, computed host-side from
    the FINAL snapshot in one pass over node infos: a slice is FREE when
    no pod sits anywhere on it; fragmentation_index is the share of
    labeled slices left PARTIALLY occupied (some nodes busy, some free —
    the state that blocks future aligned gangs). gang_admission_p99_ms
    comes from the gang-admission histogram when any gang admitted.
    Empty dict on an unlabeled fleet with no gang observations."""
    from ..state.topology import SLICE_KEY

    snap = sched.cache.update_snapshot()
    occupancy: dict[str, list[int]] = {}
    for info in snap.nodes.values():
        val = info.node.labels_dict().get(SLICE_KEY)
        if val is not None:
            occupancy.setdefault(val, []).append(len(info.pods))
    out: dict = {}
    if occupancy:
        total = len(occupancy)
        free = sum(
            1 for counts in occupancy.values()
            if not any(c > 0 for c in counts)
        )
        partial = sum(
            1 for counts in occupancy.values()
            if any(c > 0 for c in counts) and any(c == 0 for c in counts)
        )
        out["slices_total"] = total
        out["slices_free_at_steady_state"] = free
        out["fragmentation_index"] = partial / total
    h = sched.metrics.prom.gang_admission_duration.merged()
    if h.total:
        out["gang_admission_p99_ms"] = h.quantile(0.99) * 1000.0
    return out


def make_trace_node(
    name: str, zones: tuple[str, ...] = (), slices: int = 0
) -> t.Node:
    """A wave node: default scheduler-perf shape under the trace's own
    name (drains address nodes by name). Zone assignment uses a STABLE
    hash — builtin hash() is salted per process, which would break the
    trace determinism contract across runs. Rack/slice labels come from
    the same ``trace_topology_labels`` grammar as the initial fleet."""
    import zlib

    from ..api.wrappers import make_node

    labels = {W.HOSTNAME_KEY: name}
    if zones:
        labels[W.ZONE_KEY] = zones[zlib.crc32(name.encode()) % len(zones)]
    labels.update(W.trace_topology_labels(name, slices))
    return make_node(
        name, cpu_milli=4000, memory=32 * 1024**3, pods=110, labels=labels,
    )


def _make_trace_store_client(remote):
    """Fullstack trace client: StoreClient + per-pod bind wall stamps
    (dispatcher workers bind concurrently, hence the lock)."""
    import threading

    from ..client import StoreClient

    class _C(StoreClient):
        def __init__(self, store) -> None:
            super().__init__(store)
            self.bind_times: dict[str, float] = {}
            self._bt_lock = threading.Lock()

        def bind(self, pod, node_name) -> None:
            super().bind(pod, node_name)
            with self._bt_lock:
                self.bind_times.setdefault(
                    f"{pod.namespace}/{pod.name}", time.perf_counter()
                )

        def bulk_bind(self, pairs) -> list:
            errs = super().bulk_bind(pairs)
            now = time.perf_counter()
            with self._bt_lock:
                for (pod, _node), err in zip(pairs, errs):
                    if err is None:
                        self.bind_times.setdefault(
                            f"{pod.namespace}/{pod.name}", now
                        )
            return errs

    return _C(remote)


class _TraceClient(_Client):
    """Direct-mode client that stamps per-pod bind wall times (the
    admission-latency denominator)."""

    def __init__(self) -> None:
        super().__init__()
        self.bind_times: dict[str, float] = {}

    def bind(self, pod: t.Pod, node_name: str) -> None:
        super().bind(pod, node_name)
        self.bind_times.setdefault(
            f"{pod.namespace}/{pod.name}", time.perf_counter()
        )


def _sentinel_settle(sentinel, spike: "dict | None",
                     resolve_timeout_s: float = 30.0) -> dict:
    """Post-run sentinel settle: keep evaluating on the real clock until
    every alert that fired has resolved (the recovery half of the
    fire→resolve acceptance — the rule windows slide past the spike and
    the clean streak closes the lifecycle), then fold the evidence into
    the record. ``spike`` carries the injected stall's wall window; with
    it the report adds the fire-latency / bundle-coverage verdicts."""
    import time as _time

    deadline = _time.monotonic() + resolve_timeout_s
    while _time.monotonic() < deadline:
        sentinel.evaluate()
        snap = sentinel.alerts_json()
        if snap["firing"] == 0 and snap["pending"] == 0:
            break
        _time.sleep(sentinel.interval_s)
    out = dict(sentinel.stats())
    snap = sentinel.alerts_json()
    out["alerts"] = [
        {k: a[k] for k in ("rule", "state", "severity", "fires", "value")}
        for a in snap["alerts"]
    ]
    # the zero-false-positive assert for the clean (no-spike) run
    out["clean"] = out["fired_total"] == 0
    if spike is not None:
        target = next(
            (a for a in snap["alerts"]
             if a["rule"] == "admission-slo-burn"),
            None,
        )
        verdict: dict = {
            "stall_s": round(spike["end_wall"] - spike["start_wall"], 3),
            "fired": target is not None and target["fires"] > 0,
            "resolved": target is not None
            and target["state"] == "resolved",
        }
        if target is not None and target.get("fired_at_wall"):
            lat = target["fired_at_wall"] - spike["end_wall"]
            verdict["fire_latency_s"] = round(lat, 3)
            # "within one evaluation interval" — of the bad events
            # becoming VISIBLE, which is one recovery cycle after the
            # stall ends: the backlog's first bind wave (a full-batch
            # encode + dispatch) has to land in the histogram before a
            # single bad observation exists. Two intervals of cadence
            # slack + a 3s bind-wave allowance
            verdict["fired_within_interval"] = (
                lat <= 2 * sentinel.interval_s + 3.0
            )
        bundle = next(
            (b for b in sentinel.bundles_payload()
             if (b.get("trigger") or {}).get("rule")
             == "admission-slo-burn"),
            None,
        )
        verdict["bundle_captured"] = bundle is not None
        if bundle is not None:
            # the trace slice looks back trace_window_s from capture:
            # a capture this close to the stall has the stall in-frame
            verdict["bundle_covers_stall"] = (
                bundle["captured_wall"] - spike["end_wall"]
                <= sentinel.trace_window_s
            )
            verdict["bundle_sections"] = sorted(
                (bundle.get("sections") or {}).keys()
            )
        out["spike"] = verdict
    return out
