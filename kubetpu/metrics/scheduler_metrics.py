"""The scheduler's metric set, with the reference's names and bucket
layouts (pkg/scheduler/metrics/metrics.go):

- scheduling_attempt_duration_seconds{result, profile} (:247, STABLE,
  ExponentialBuckets(0.001, 2, 15))
- scheduling_algorithm_duration_seconds (:252, same buckets)
- pod_scheduling_sli_duration_seconds{attempts} (:316, BETA,
  ExponentialBuckets(0.01, 2, 20)) — e2e from queue entry to bind dispatch
- pod_scheduling_attempts (:327, ExponentialBuckets(1, 2, 5))
- framework_extension_point_duration_seconds{extension_point, status,
  profile} (:344, ExponentialBuckets(0.0001, 2, 12))
- plugin_execution_duration_seconds{plugin, extension_point, status}
  (:353, ExponentialBuckets(0.00001, 1.5, 20)) — per host-side lifecycle
  plugin call; the fused device Filter+Score program cannot be timed
  per-plugin (it is ONE XLA program), so its wall time lands on
  extension_point="Filter+Score" at the framework level instead; the host
  spread encode is timed as plugin="PodTopologySpread",
  extension_point="PreFilter" (``Scheduler._launch_cycle``), beside
  spread_constrained_pods_total, spread_soft_constrained_pods_total and
  spread_policy_pods_total{policy};
  the host inter-pod affinity encode as plugin="InterPodAffinity",
  extension_point="PreFilter", beside podaffinity_pods_total{work},
  podaffinity_filter_pods_total{term} and
  podaffinity_existing_anti_nodes_total
- schedule_attempts_total{result, profile}, preemption_attempts_total,
  preemption_victims (:267 ExponentialBuckets(1, 2, 7)), pending_pods{queue}
"""

from __future__ import annotations

import math
import time

from ..tracing import LOOP_PHASES
from .registry import Histogram, Registry, exponential_buckets

#: the per-pod staged latency attribution vector (sched.flightrecorder):
#: the ONLY legal values of the {stage} label on
#: scheduler_e2e_scheduling_duration_seconds — declared at registration
#: (runtime check) and enforced at parse time by graftcheck MR004.
E2E_STAGES = (
    "api_ingest",       # REST create -> informer delivery (fullstack)
    "informer",         # delivery-handler wall (incl. pre-encode)
    "queue_wait",       # enqueue -> pop, summed across requeue hops
    "encode",           # owning cycle's host-encode wall
    "kernel",           # owning cycle's device-program wall
    "dispatch",         # bind enqueue -> micro-batch execution start
    "bind_rtt",         # bind execution -> completion
    "e2e",              # ingest (or delivery) -> bind ack
)

#: the engine registry (Scheduler(engine=…)): the ONLY legal values of the
#: {engine} label on the packing-objective metric family — declared at
#: registration and enforced at parse time by graftcheck MR004.
ENGINES = (
    "greedy",           # exact reference-semantics per-pod scan
    "batched",          # capacity-coupled rounds (throughput mode)
    "packing",          # constraint-based packing (cluster objectives)
)

#: what the loop's next call did with a cycle dispatched ahead of it: the
#: ONLY legal values of {result} on scheduler_pipeline_cycles_total.
PIPELINE_RESULTS = ("applied", "replayed")

#: which inter-pod affinity kernel had work for a pod: the ONLY legal values
#: of {work} on scheduler_podaffinity_pods_total.
PODAFFINITY_WORK = ("filter", "score")

#: which kind of inter-pod affinity filter slot a pod had: the ONLY legal
#: values of {term} on scheduler_podaffinity_filter_pods_total.
PODAFFINITY_FILTER_TERMS = ("affinity", "anti_affinity",
                            "existing_anti_affinity")

#: which node inclusion policy left nodes out of a spread count: the ONLY
#: legal values of {policy} on scheduler_spread_policy_pods_total.
SPREAD_POLICIES = ("taints", "affinity")


def window_quantile_ms(
    hist: Histogram, baseline: Histogram | None = None, q: float = 0.99
) -> float | None:
    """A histogram quantile in MILLISECONDS scoped to the measurement
    window: with ``baseline`` (an earlier ``merged()`` snapshot) the
    quantile covers only the delta since it — a large init phase must not
    dominate the reported p99s (the perf runner's window-scoping rule,
    shared by both run modes and the staged percentiles). None when the
    window observed nothing."""
    delta = hist.since(baseline) if baseline is not None else hist.merged()
    if delta.total > 0:
        return float(delta.quantile(q) * 1000.0)
    return None


class SchedulerMetricsRegistry:
    """Owns a Registry pre-populated with the scheduler metric set; the
    Scheduler observes into it and /metrics exposes it."""

    def __init__(self) -> None:
        r = Registry()
        self.registry = r
        self.scheduling_attempt_duration = r.histogram(
            "scheduler_scheduling_attempt_duration_seconds",
            "Scheduling attempt latency in seconds (scheduling algorithm + binding)",
            labels=("result", "profile"),
            buckets=exponential_buckets(0.001, 2, 15),
        )
        self.scheduling_algorithm_duration = r.histogram(
            "scheduler_scheduling_algorithm_duration_seconds",
            "Scheduling algorithm latency in seconds",
            buckets=exponential_buckets(0.001, 2, 15),
        )
        self.pod_scheduling_sli_duration = r.histogram(
            "scheduler_pod_scheduling_sli_duration_seconds",
            "E2e latency for a pod being scheduled, from the time the pod "
            "enters the scheduling queue and might involve multiple "
            "scheduling attempts.",
            labels=("attempts",),
            buckets=exponential_buckets(0.01, 2, 20),
        )
        self.pod_scheduling_attempts = r.histogram(
            "scheduler_pod_scheduling_attempts",
            "Number of attempts to successfully schedule a pod.",
            buckets=exponential_buckets(1, 2, 5),
        )
        self.framework_extension_point_duration = r.histogram(
            "scheduler_framework_extension_point_duration_seconds",
            "Latency for running all plugins of a specific extension point.",
            labels=("extension_point", "status", "profile"),
            buckets=exponential_buckets(0.0001, 2, 12),
        )
        self.plugin_execution_duration = r.histogram(
            "scheduler_plugin_execution_duration_seconds",
            "Duration for running a plugin at a specific extension point.",
            labels=("plugin", "extension_point", "status"),
            buckets=exponential_buckets(0.00001, 1.5, 20),
        )
        self.schedule_attempts = r.counter(
            "scheduler_schedule_attempts_total",
            "Number of attempts to schedule pods, by the result.",
            labels=("result", "profile"),
        )
        self.spread_constrained_pods = r.counter(
            "scheduler_spread_constrained_pods_total",
            "Pods of the scheduling cycles that carried or inherited a "
            "topology spread constraint, so that the spread encode and the "
            "spread kernels ran for them; a cycle with none adds nothing.",
        )
        self.spread_soft_constrained_pods = r.counter(
            "scheduler_spread_soft_constrained_pods_total",
            "Of those, the pods with at least one ScheduleAnyway constraint: "
            "the soft spread score ran for them in every step of the assign "
            "scan and in the explain kernel.",
        )
        self.spread_policy_pods = r.counter(
            "scheduler_spread_policy_pods_total",
            "Of the spread-constrained pods, those with a constraint whose "
            "node inclusion policy, under Honor, left at least one node out "
            "of the per-domain counts, by the policy: taints (nodeTaintsPolicy:"
            " an untolerated NoSchedule or NoExecute taint) or affinity "
            "(nodeAffinityPolicy: the pod's nodeSelector or required node "
            "affinity). A pod may count under both.",
            labels=("policy",),
            declared={"policy": SPREAD_POLICIES},
        )
        for policy in SPREAD_POLICIES:
            # both on the first scrape, at zero: a delta meets no gap
            self.spread_policy_pods.labels(policy)
        self.podaffinity_pods = r.counter(
            "scheduler_podaffinity_pods_total",
            "Pods of the scheduling cycles for which an inter-pod affinity "
            "kernel had work, by the kernel: filter (an incoming required "
            "affinity or anti-affinity term, or an existing pod's "
            "anti-affinity that matches the pod) or score (a weighted count "
            "row: the pod's preferred terms, or an existing pod's preferred "
            "or required term that matches it). A pod may count under both; "
            "a cycle without an affinity term adds nothing.",
            labels=("work",),
            declared={"work": PODAFFINITY_WORK},
        )
        for work in PODAFFINITY_WORK:
            # both on the first scrape, at zero: a delta meets no gap
            self.podaffinity_pods.labels(work)
        self.podaffinity_filter_pods = r.counter(
            "scheduler_podaffinity_filter_pods_total",
            "Of the pods counted under work=\"filter\", those with at least "
            "one filter slot of a kind, by the kind: affinity (an incoming "
            "required affinity term), anti_affinity (an incoming required "
            "anti-affinity term) or existing_anti_affinity (an existing "
            "pod's required anti-affinity term that matches the pod). A pod "
            "may count under several.",
            labels=("term",),
            declared={"term": PODAFFINITY_FILTER_TERMS},
        )
        for term in PODAFFINITY_FILTER_TERMS:
            # all three on the first scrape, at zero: a delta meets no gap
            self.podaffinity_filter_pods.labels(term)
        self.podaffinity_existing_anti_nodes = r.counter(
            "scheduler_podaffinity_existing_anti_nodes_total",
            "Summed over the pods of the scheduling cycles, the nodes that "
            "existing pods' required anti-affinity terms matching the pod "
            "refuse it, at the counts the cycle started from: pods the same "
            "batch places earlier are not in it.",
        )
        self.pipeline_cycles = r.counter(
            "scheduler_pipeline_cycles_total",
            "Scheduling cycles whose device program was dispatched ahead "
            "of the loop's next call (the two-stage cycle), by what the "
            "next call did with the result: applied as it stood, or "
            "thrown away and replayed serially because the cluster moved "
            "under it. A cycle launched and synced in one call (nothing "
            "queued behind it, a mixed-profile pop, a replay) adds nothing.",
            labels=("result",),
            declared={"result": PIPELINE_RESULTS},
        )
        for result in PIPELINE_RESULTS:
            # both on the first scrape, at zero: a delta meets no gap
            self.pipeline_cycles.labels(result)
        self.preemption_attempts = r.counter(
            "scheduler_preemption_attempts_total",
            "Total preemption attempts in the cluster till now",
        )
        self.preemption_victims = r.histogram(
            "scheduler_preemption_victims",
            "Number of selected preemption victims",
            buckets=exponential_buckets(1, 2, 7),
        )
        self.e2e_scheduling_duration = r.histogram(
            "scheduler_e2e_scheduling_duration_seconds",
            "Per-pod staged scheduling latency: where each pod's "
            "end-to-end time went, by attribution stage "
            "(sched.flightrecorder; stages: " + ", ".join(E2E_STAGES) + ").",
            labels=("stage",),
            buckets=exponential_buckets(0.0001, 2, 20),
            declared={"stage": E2E_STAGES},
        )
        self.explain_kernel_failures = r.counter(
            "scheduler_explain_kernel_failures_total",
            "Flight-recorder explain-kernel failures (compile, run or "
            "fetch): the cycle goes on without its score/filter breakdown, "
            "and after three the breakdown is switched off. Non-zero means "
            "a shape or backend the kernel cannot handle.",
        )
        self.pending_pods = r.gauge(
            "scheduler_pending_pods",
            "Number of pending pods, by the queue type.",
            labels=("queue",),
        )
        self.queue_incoming_pods = r.counter(
            "scheduler_queue_incoming_pods_total",
            "Number of pods added to scheduling queues by event and queue type.",
            labels=("queue", "event"),
        )
        # --- active-active federation (sched.federation) ------------------
        # conflicts: CAS-bind 409 losses + epoch-fenced stale-owner binds,
        # labeled by partition mode and replica id — the numerator of the
        # conflict/throughput curve ("none"/"r0" in single-scheduler mode)
        self.federation_conflicts = r.counter(
            "scheduler_federation_conflicts_total",
            "CAS-bind conflicts lost to another scheduler replica "
            "(409 losers and epoch-fenced stale-owner binds), by "
            "federation partition mode and replica id.",
            labels=("mode", "replica"),
        )
        self.federation_lease_transitions = r.counter(
            "scheduler_federation_lease_transitions_total",
            "Partition-lease ownership changes (acquisitions + losses) "
            "observed by this replica's lease manager.",
            labels=("mode", "replica"),
        )
        self.federation_partitions_owned = r.gauge(
            "scheduler_federation_partitions_owned",
            "Partition leases currently owned by this replica "
            "(lease mode; the ownership rebalance evidence).",
            labels=("mode", "replica"),
        )
        # --- packing engine (assign.packing) ------------------------------
        # cluster-objective telemetry, labeled by the engine that produced
        # it (today only "packing" reports; greedy/batched leave the whole
        # family unobserved, which keeps the sentinel's solver-iteration
        # rule dormant for them — an absent series extracts to None)
        self.packing_objective = r.gauge(
            "scheduler_packing_objective",
            "Last cycle's packing objective value: priority-weighted "
            "admission minus the alpha*nodes-opened and beta*fragmentation "
            "penalties (assign.packing), by engine.",
            labels=("engine",),
            declared={"engine": ENGINES},
        )
        self.nodes_used = r.gauge(
            "scheduler_nodes_used",
            "Nodes carrying at least one pod after the last scheduling "
            "cycle, as seen by the device solver, by engine.",
            labels=("engine",),
            declared={"engine": ENGINES},
        )
        self.packing_solver_iters = r.histogram(
            "scheduler_packing_solver_iters",
            "Solver iterations (projection-loop rounds) per scheduling "
            "cycle — the warm-start evidence: steady-state cycles should "
            "sit in the low buckets, spikes feed the sentinel's "
            "PackingSolverIterationSpike rule.",
            labels=("engine",),
            buckets=exponential_buckets(1, 2, 12),
            declared={"engine": ENGINES},
        )
        # --- gang admission (sched.podgroup) ------------------------------
        # quorum-met → fully-admitted latency, observed ONCE per group at
        # first admission. Labeled by engine like the packing family so a
        # run with no pod groups never creates the series — the sentinel's
        # gang-admission-stall rule stays dormant on gang-free clusters
        # (absent series extracts to None, same shape as
        # packing-solver-iteration-spike).
        self.gang_admission_duration = r.histogram(
            "scheduler_gang_admission_duration_seconds",
            "Latency from a pod group reaching quorum to its first full "
            "admission (all members of the winning attempt assumed), by "
            "engine. Observed once per group.",
            labels=("engine",),
            buckets=exponential_buckets(0.001, 2, 16),
            declared={"engine": ENGINES},
        )
        # API dispatcher lifetime counts, set at scrape time from
        # APIDispatcher.stats() (a gauge because the dispatcher owns the
        # monotonic counters; "errors" is the satellite's failed-API-write
        # signal, "batches"/"batched_calls" size the bulk micro-batches)
        self.api_dispatcher_calls = r.gauge(
            "scheduler_api_dispatcher_calls",
            "API dispatcher lifetime call counts by event: added, executed, "
            "errors, batches (bulk RPCs issued), batched_calls (calls that "
            "rode a bulk RPC).",
            labels=("event",),
        )

        # --- the served loop's phase clock (tracing.PhaseClock) -----------
        # set at scrape time from PhaseClock.snapshot(): the loop thread
        # owns the totals and takes no lock. Every phase is a series from
        # the first scrape, at zero, so a delta never meets a missing one.
        self.loop_phase_seconds = r.counter(
            "scheduler_loop_phase_seconds_total",
            "Wall seconds of the scheduler's loop thread by phase; the "
            "phases are self times and sum to the elapsed time (the "
            "running phase's elapsed part is included at scrape time). "
            "Phases: " + ", ".join(LOOP_PHASES) + ".",
            labels=("phase",),
            declared={"phase": LOOP_PHASES},
        )
        self.loop_phase_entries = r.counter(
            "scheduler_loop_phase_entries_total",
            "Times the loop thread entered each phase: Event writes for "
            "events, watch polls for pump_rpc.",
            labels=("phase",),
            declared={"phase": LOOP_PHASES},
        )
        self.loop_iterations = r.counter(
            "scheduler_loop_iterations_total",
            "Completed iterations of the served loop (pump, "
            "schedule_batch, drain), idle ones included.",
        )
        for phase in LOOP_PHASES:
            self.loop_phase_seconds.labels(phase)
            self.loop_phase_entries.labels(phase)
        # --- who ran: the CPU clocks beside the wall clocks ---------------
        # one thread's CPU time is what it spent ON a core; its wall less
        # its CPU it spent waiting: for the GIL, for a core, for a socket,
        # for the chip. Series appear with their first reading (the loop's
        # nine at the first scrape); a platform with no per-thread CPU
        # clock leaves the CPU families without series
        self.loop_phase_cpu_seconds = r.counter(
            "scheduler_loop_phase_cpu_seconds_total",
            "CPU seconds of the scheduler's loop thread by phase, beside "
            "scheduler_loop_phase_seconds_total: at most the wall in every "
            "phase, next to nothing in sleep, and the phases sum to the "
            "thread's CPU time.",
            labels=("phase",),
            declared={"phase": LOOP_PHASES},
        )
        self.api_dispatcher_worker_seconds = r.counter(
            "scheduler_api_dispatcher_worker_seconds_total",
            "Wall seconds the API dispatcher's worker threads spent "
            "executing what they took from the queue, by call type; with "
            "several workers it can pass the elapsed time. Calls executed "
            "inline on the loop thread are in the loop's phases instead.",
            labels=("call_type",),
        )
        self.api_dispatcher_worker_cpu_seconds = r.counter(
            "scheduler_api_dispatcher_worker_cpu_seconds_total",
            "CPU seconds of the same threads over the same work: Python "
            "run under the loop's GIL.",
            labels=("call_type",),
        )
        self.process_cpu_seconds = r.counter(
            "process_cpu_seconds_total",
            "Total user and system CPU time spent in seconds, every thread "
            "of the process: beyond the loop, the dispatcher's workers and "
            "the diagnostics requests it holds the threads no Python clock "
            "reaches (XLA's and the accelerator runtime's pools).",
        )

    def set_dispatcher_stats(self, stats: dict, worker_clock=None) -> None:
        for event, value in stats.items():
            self.api_dispatcher_calls.labels(event).set(value)
        for call_type, (wall, cpu) in (worker_clock or {}).items():
            self.api_dispatcher_worker_seconds.labels(call_type).set_total(
                wall)
            if cpu is not None:
                self.api_dispatcher_worker_cpu_seconds.labels(
                    call_type).set_total(cpu)

    def set_loop_clock(self, snapshot: tuple, cpu_seconds=None) -> None:
        seconds, entries, iterations = snapshot
        for phase, value in seconds.items():
            self.loop_phase_seconds.labels(phase).set_total(value)
        for phase, value in (cpu_seconds or {}).items():
            self.loop_phase_cpu_seconds.labels(phase).set_total(value)
        for phase, value in entries.items():
            self.loop_phase_entries.labels(phase).set_total(value)
        self.loop_iterations.set_total(iterations)

    def expose(self) -> str:
        self.process_cpu_seconds.set_total(time.process_time())
        return self.registry.expose()

    # --- convenience for the perf harness ---------------------------------
    def p99_attempt_latency_s(self) -> float:
        """p99 of pod_scheduling_sli_duration_seconds across attempt labels
        (histogram_quantile over the summed buckets)."""
        return self.pod_scheduling_sli_duration.quantile(0.99)

    def _attempts_by_result(self) -> dict:
        attempts: dict[str, int] = {}
        for key, child in self.schedule_attempts._children_snapshot():
            result = key[0] if key else "unknown"
            attempts[result] = attempts.get(result, 0) + int(child.value)
        return attempts

    def snapshot_baseline(self) -> dict:
        """Capture the current histogram/counter state; pass to
        ``snapshot(baseline=...)`` so the summary covers only the window
        since (the perf harness scopes to its measured phase — embedded
        numbers must describe the same population as the measurement
        fields beside them)."""
        return {
            "attempt_duration": self.scheduling_attempt_duration.merged(),
            "sli_duration": self.pod_scheduling_sli_duration.merged(),
            "algorithm_duration": self.scheduling_algorithm_duration.merged(),
            "schedule_attempts": self._attempts_by_result(),
            "e2e_stages": self._staged_children(),
        }

    def _staged_children(self) -> dict:
        """{stage: merged Histogram} for every stage observed so far."""
        return {
            key[0]: child.merged()
            for key, child in (
                self.e2e_scheduling_duration._children_snapshot()
            )
        }

    def staged_percentiles(self, baseline: dict | None = None) -> dict | None:
        """Per-stage p50/p99 (ms) of the staged latency vector, scoped to
        the window since ``baseline`` (a ``snapshot_baseline``) — the
        ``staged_latency_ms`` block every fullstack perf result carries.
        None when no stage observed anything in the window."""
        base = (baseline or {}).get("e2e_stages", {})
        out = {}
        for stage, child in self._staged_children().items():
            p50 = window_quantile_ms(child, base.get(stage), 0.50)
            p99 = window_quantile_ms(child, base.get(stage), 0.99)
            if p99 is None:
                continue
            out[stage] = {"p50": round(p50, 3), "p99": round(p99, 3)}
        return out or None

    def snapshot(self, baseline: dict | None = None) -> dict:
        """Post-run summary embedded in perf results: p50/p99 from the
        histograms plus schedule_attempts by result — the numbers a
        dashboard would derive from a scrape, pre-derived so every result
        JSON is self-describing. With ``baseline`` (a
        ``snapshot_baseline``), everything is the DELTA since it."""

        def q(hist, quantile: float) -> float | None:
            v = hist.quantile(quantile)
            return None if math.isnan(v) else round(float(v), 6)

        attempt_h = self.scheduling_attempt_duration
        sli_h = self.pod_scheduling_sli_duration
        algo_h = self.scheduling_algorithm_duration
        attempts = self._attempts_by_result()
        if baseline is not None:
            attempt_h = attempt_h.since(baseline["attempt_duration"])
            sli_h = sli_h.since(baseline["sli_duration"])
            algo_h = algo_h.since(baseline["algorithm_duration"])
            base_attempts = baseline["schedule_attempts"]
            attempts = {
                k: v - base_attempts.get(k, 0)
                for k, v in attempts.items()
                if v - base_attempts.get(k, 0)
            }
        return {
            "schedule_attempts": attempts,
            # lifetime, warm-up included: any failure is a finding
            "explain_kernel_failures": int(
                self.explain_kernel_failures.value
            ),
            "attempt_duration_s": {
                "p50": q(attempt_h, 0.50),
                "p99": q(attempt_h, 0.99),
            },
            "sli_duration_s": {
                "p50": q(sli_h, 0.50),
                "p99": q(sli_h, 0.99),
            },
            "algorithm_duration_s": {
                "p50": q(algo_h, 0.50),
                "p99": q(algo_h, 0.99),
            },
        }
