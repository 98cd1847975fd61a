"""The batched scheduler loop.

Analog of ``pkg/scheduler/scheduler.go`` (struct Scheduler :68, Run :524) +
``schedule_one.go``, re-proportioned for device batches:

- the reference pops ONE pod per cycle (``ScheduleOne`` :67) and runs
  parallel-for Filter/Score over nodes; we pop a BATCH (``pop_batch``) and
  run the whole Filter+Score+greedy-assign composition as one XLA program
  (``assign.greedy.greedy_assign_device``) — sequential assume semantics are
  preserved *inside* the program by the lax.scan carry, so binding parity
  with the per-pod loop holds even on saturated clusters.
- the scheduling cycle is serialized; binding is async per pod through the
  API dispatcher (the reference's ``go sched.runBindingCycle``,
  schedule_one.go:141).
- informer deliveries go through ``on_*`` handlers that update cache + queue
  (eventhandlers.go:455 ``addAllEventHandlers``).

Failure handling mirrors ``handleSchedulingFailure``: unschedulable pods go
back to the queue with their rejector plugins recorded (driving the queueing
hints); bind errors forget the assumed pod and requeue as error-status.
"""

from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from ..api import types as t
from ..framework import config as C
from ..framework import runtime as rt
from ..assign.greedy import greedy_assign_device
from ..state.snapshot import Cache, Snapshot
from ..queue import PriorityQueue, QueuedPodInfo
from ..queue.priority_queue import pod_key
from ..queue.events import (
    ActionType,
    ClusterEvent,
    EventResource,
    default_queueing_hints,
    node_update_event,
)
from .. import names as N
from .api_dispatcher import (
    APIDispatcher,
    BindCall,
    CallSkipped,
    StatusPatchCall,
    is_bind_conflict,
)

import jax
import numpy as np


@dataclass
class _InflightCycle:
    """A dispatched-but-unsynced scheduling cycle (pipeline mode): the device
    program is running; the host holds everything needed to sync, apply and
    — if cluster state changed underneath — replay it."""

    profile: C.Profile
    batch_infos: list
    batch: "rt.EncodedBatch"
    device_batch: "rt.DeviceBatch"
    params: "rt.ScoreParams"
    assignments: Any                 # device array, fetched at sync
    final_state: Any
    cycle_id: int
    t_start: float                   # perf_counter at launch (cycle span)
    t0: float                        # clock() at launch (duration metrics)
    t_dev: float                     # perf_counter at device dispatch
    cache0: int | None               # assign-program compile-cache size
    nominator_version: int
    vol_gen: int
    ns_gen: int
    # (DraIndex.generation, DraIndex.claims_version) at dispatch — slice/
    # class/claim churn under an in-flight cycle forces a replay
    dra_gen: tuple = (0, 0)
    # clock() spent in the launch half (host encode + dispatch); the finish
    # half adds its own span so pipelined cycle durations never include the
    # idle gap between loop ticks
    launch_s: float = 0.0
    pipelined: bool = False
    # the encode span's wall — the staged latency vector's "encode" stage
    # for every pod of this cycle (sched.flightrecorder)
    encode_s: float = 0.0


@dataclass
class SchedulerMetrics:
    """Plain counters (hot-loop cheap) + the Prometheus-shaped registry
    (kubetpu.metrics) holding the reference-named histograms
    (pkg/scheduler/metrics/metrics.go)."""

    schedule_attempts: int = 0          # scheduling_attempts_total
    scheduled: int = 0                  # result "scheduled"
    unschedulable: int = 0              # result "unschedulable"
    errors: int = 0                     # result "error"
    bind_errors: int = 0
    # bind errors that were CAS-bind conflicts (another scheduler replica
    # won the pod, or a partition-lease fence rejected a stale owner) —
    # the federation conflict/throughput curve's numerator; also counted
    # in bind_errors (a conflict IS a failed bind)
    bind_conflicts: int = 0
    cycles: int = 0
    # node rows the host encode wrote (scheduler_encode_node_rows_total)
    encode_node_rows: int = 0
    # pipelined cycles whose dispatched device result had to be discarded
    # and recomputed because cluster state changed under them (node update /
    # foreign pod event between dispatch and sync) — replay preserves exact
    # serial parity; a high rate means the cluster churns faster than the
    # pipeline can exploit
    pipeline_replays: int = 0
    preemption_attempts: int = 0        # preemption_attempts_total
    preemption_victims: int = 0         # preemption_victims histogram feed
    scheduling_seconds: float = 0.0     # scheduling_algorithm_duration sum
    # bounded reservoir of recent e2e attempt latencies (debugging aid);
    # the real p99 source is the prom SLI histogram
    attempt_latencies: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=10000)
    )
    prom: "object" = None               # SchedulerMetricsRegistry
    tpu: "object" = None                # TPUBackendMetrics (device counters)

    def __post_init__(self) -> None:
        if self.prom is None:
            from ..metrics import SchedulerMetricsRegistry

            self.prom = SchedulerMetricsRegistry()
        if self.tpu is None:
            from ..metrics import TPUBackendMetrics

            # same Registry: one /metrics exposition carries host histograms
            # and device counters together, joined per cycle by cycle id
            self.tpu = TPUBackendMetrics(registry=self.prom.registry)

    # The plain counters are mutated ONLY through these methods (analysis
    # LD003: a counter bumped from a foreign module has no single place to
    # audit or serialize; attempt_latencies is a deque — appends are
    # atomic and not RMW, so it stays a plain field). Callers all run on
    # the scheduler loop thread — the Scheduler's single-owner contract —
    # so the bodies stay bare adds.
    def note_attempts(self, n: int = 1) -> None:
        self.schedule_attempts += n

    def note_scheduled(self, n: int = 1) -> None:
        self.scheduled += n

    def note_unschedulable(self, n: int = 1) -> None:
        self.unschedulable += n

    def note_preemption_attempt(self) -> None:
        self.preemption_attempts += 1

    def note_preemption_victims(self, n: int) -> None:
        self.preemption_victims += n

    def note_bind_conflict(self) -> None:
        self.bind_conflicts += 1

    def note_encode_node_rows(self, n: int) -> None:
        self.encode_node_rows += n


class Scheduler:
    """See module docstring. Single-owner object: informer callbacks and the
    scheduling loop run on the owner's thread (the reference serializes the
    scheduling cycle the same way); only API-dispatcher completions hop
    threads, and they re-enter through a completion queue drained by the
    loop."""

    def __init__(
        self,
        client: Any,
        profile: C.Profile | None = None,
        cfg: C.SchedulerConfiguration | None = None,
        max_batch: int = 1024,
        dispatcher_workers: int = 2,
        clock: Callable[[], float] = time.monotonic,
        engine: str = "greedy",
        registry=None,
        feature_gates=None,
        recorder=None,
        pipeline: bool = False,
        encode_cache: bool = True,
        bulk: bool = True,
        mesh=None,
        flight_recorder: bool = True,
        replica_id: str = "",
        federation_mode: str = "",
        sentinel: "bool | Any" = False,
        topology: str = "off",
    ) -> None:
        """``engine``: "greedy" (per-pod device loop, exact reference
        semantics) or "batched" (capacity-coupled rounds,
        assign.batched — one big device program per round; wins when
        batches are signature-homogeneous, the scheduler_perf shape).
        ``registry``: a lifecycle-plugin Registry (framework.lifecycle);
        defaults to the in-tree set — out-of-tree plugins register on a
        copy and pass it here (the reference's app.WithPlugin).
        ``feature_gates``: a FeatureGate or {name: bool} overrides
        (pkg/features defaults apply; unknown names fail loudly).
        ``recorder``: an EventRecorder (client.events) — the scheduler
        emits the reference's canonical Events (``Scheduled`` on a
        successful bind, ``FailedScheduling`` on an unschedulable
        attempt — schedule_one.go's recorder.Eventf calls); None = no
        events.
        ``pipeline``: which of the class's two kinds of caller this is, not
        a tuning knob. ``True`` is for a LOOP that calls ``schedule_batch``
        again and can take a cycle's counts one call late (the served
        ``kubetpu scheduler`` always; ``run_until_idle``): the two-stage
        cycle dispatches batch N+1's device program and returns, so the
        caller's bind flush, Event write, drain and pump for batch N run
        while the chip works, and the next call syncs (JAX's asynchronous
        dispatch is the only concurrency; the loop stays one thread). A
        cycle whose cluster state moved under it is thrown away and
        replayed serially, so assignments are pod for pod the serial
        loop's (``scheduler_pipeline_cycles_total{result}`` counts both
        outcomes); a batch with nothing queued behind it is answered in
        the call that popped it. ``False`` (the default) is for a ONE-SHOT
        caller that needs the batch it handed in answered before the call
        returns: the tests, ``chip_smoke.py``, the perf drivers,
        placement. It is also what a replay and a mixed-profile pop run.
        Both ride the same ``_launch_cycle`` / ``_finish_cycle`` and the
        same device-resident node block with dirty-row delta uploads.
        ``encode_cache``: event-time incremental pod encoding — static
        tensor rows are template-keyed, built when the informer delivers
        the pod, and gathered (not rebuilt) at cycle time; node events
        invalidate by epoch. Cached encodes are bit-identical to fresh
        ones, so ``encode_cache=False`` is purely a debugging escape
        hatch.
        ``bulk``: opportunistic API-plane micro-batching — the dispatcher
        accumulates a cycle's API writes and flushes them at the cycle
        boundary as per-call-type bulk RPCs (a cycle's binds become one
        request); partial failures fall back to per-call execution, so
        every pod's bind-error path is unchanged and ``bulk=False``
        (``--bulk off``) is pod-for-pod identical.
        ``mesh``: shard the node axis of every device tensor over a TPU
        mesh (``parallel.mesh`` rules): a ``jax.sharding.Mesh``, ``"auto"``
        (mesh when >1 device is visible), ``"on"`` (require one) or
        None/``"off"``. The resident node block becomes a SHARDED resident
        block (per-shard routed delta uploads, incremental reshard on node
        add/delete) and both engines run SPMD with XLA-inserted collectives
        for the cross-shard argmax/sort — assignments are bit-identical to
        single-device, so ``mesh=None`` is a capacity choice, not a
        semantics one.
        ``flight_recorder``: the scheduling flight recorder + per-pod
        staged latency attribution (sched.flightrecorder): bounded ring of
        per-pod decision records (win margin, top-k scores, per-plugin
        filter rejections, requeue history) served at
        /debug/flightrecorder and rendered by ``kubetpu explain``, plus
        the scheduler_e2e_scheduling_duration_seconds{stage} histograms.
        ``False`` (``--flight-recorder off``) is the overhead escape
        hatch — decisions are unchanged either way.
        ``replica_id``/``federation_mode``: active-active federation
        stamps (sched.federation) — the replica id rides every cycle
        record and flight-recorder entry so multi-replica bind histories
        stay attributable, and the pair labels
        ``scheduler_federation_conflicts_total{mode,replica}``. Empty in
        single-scheduler mode.
        ``sentinel``: the anomaly sentinel (telemetry.sentinel) — ``True``
        builds one over the default rule table, or pass a pre-built
        ``Sentinel`` (the perf runner does, carrying the run's declared
        ``slo_budget_ms``); either way it is BOUND to this scheduler's
        metrics text, tracer, queue and cycle records, evaluated at the
        cycle boundary (``maybe_evaluate`` — no extra thread), and served
        at /debug/alerts + /debug/bundle. ``False`` (default) runs zero
        extra work.
        ``topology``: topology-aware scoring over rack/TPU-slice node
        labels (state.topology) — ``"on"``, ``"off"`` or ``"auto"``
        (active only when some node carries a topology label). Active
        topology attaches the dense coordinate block to every encoded
        batch: gang placement scores slice alignment, the packing
        objective prices slice fragmentation, and preemption can evict
        one whole low-priority gang to admit an aligned one. ``"off"`` —
        and ``"auto"`` on an unlabeled cluster — is bit-identical to a
        build without the feature (the block is an absent pytree leaf)."""
        from ..framework.featuregate import FeatureGate

        self.recorder = recorder
        # Events recorded and not yet written (_record_event), as
        # client.events.Occurrence tuples: emptied before the drain or the
        # cycle that recorded them returns
        self._pending_events: list[tuple] = []
        # profile name -> the pod-axis buckets its programs have run at
        # (_pod_bucket)
        self._pod_buckets: dict[str, set[int]] = {}
        self.replica_id = replica_id
        self.federation_mode = federation_mode

        self.cfg = cfg or C.SchedulerConfiguration()
        self.profile = profile or self.cfg.profile()
        # the profile Map (profile.go:46): pods select by spec.schedulerName.
        # A single explicit ``profile`` also answers for the default name so
        # plain pods keep scheduling under it (test/one-profile usage).
        if profile is not None:
            self.profiles: dict[str, C.Profile] = {profile.name: profile}
            self.profiles.setdefault("default-scheduler", profile)
        else:
            self.profiles = {p.name: p for p in self.cfg.profiles}
        if feature_gates is None or isinstance(feature_gates, dict):
            feature_gates = FeatureGate(feature_gates)
        self.feature_gates = feature_gates
        if engine == "batched":
            from ..assign.batched import batched_assign_device

            self._assign_device = batched_assign_device
        elif engine == "greedy":
            self._assign_device = greedy_assign_device
        elif engine == "packing":
            from ..assign.packing import PackingEngine

            # stateful engine instance: carries the warm-start dual block
            # and the objective-weight tensor across cycles; the mesh is
            # bound after resolution below (bind_mesh)
            self._assign_device = PackingEngine()
        else:
            raise ValueError(f"unknown engine {engine!r}")
        self.engine = engine
        if topology not in ("on", "off", "auto"):
            raise ValueError(f"unknown topology mode {topology!r}")
        self.topology = topology
        self.cache = Cache(clock=clock)
        self.clock = clock
        self.max_batch = max_batch
        filters = sorted({
            n for prof in self.profiles.values() for n in prof.filters.names()
        })
        # the DRA PreEnqueue gate only applies when some profile runs the
        # plugin — otherwise the gating rejector would have no registered
        # queueing hints and a gated pod could never wake
        self._dra_enabled = N.DYNAMIC_RESOURCES in filters
        if (
            N.NODE_DECLARED_FEATURES in filters
            and not self.feature_gates.enabled("NodeDeclaredFeatures")
        ):
            # the reference only registers the plugin when its gate is on
            # (default_plugins.go:60-73), so gate-off + plugin-enabled is a
            # configuration error, not a silent no-op
            raise ValueError(
                "profile enables NodeDeclaredFeatures but the "
                "NodeDeclaredFeatures feature gate is off"
            )
        self.queue = PriorityQueue(
            hints=default_queueing_hints(filters),
            pre_enqueue=[self._scheduling_gates, self._dra_pre_enqueue],
            clock=clock,
            initial_backoff_seconds=self.cfg.pod_initial_backoff_seconds,
            max_backoff_seconds=self.cfg.pod_max_backoff_seconds,
        )
        from ..tracing import PhaseClock, Tracer

        # cycle tracing (utiltrace analog): top-level span per profile
        # cycle; >100ms cycles log their step breakdown
        # (schedule_one.go:566-567's LogIfLong). Created BEFORE the
        # dispatcher so its call-type spans land in the same buffer
        self.tracer = Tracer()
        # where the loop thread's time goes, by phase (always on; read at
        # scrape time into scheduler_loop_phase_*_total). Whoever drives
        # this scheduler's loop switches it: schedule_batch and the drain
        # here, the informers' pump, cli.py's sleep
        self.loop_clock = PhaseClock()
        # pods popped plus bind completions taken, ever: the served loop
        # reads the difference to tell an iteration that did something
        # (and earns a span) from an idle one
        self.loop_work = 0
        self.dispatcher = APIDispatcher(
            client, workers=dispatcher_workers, bulk=bulk,
            tracer=self.tracer,
        )
        self.metrics = SchedulerMetrics()
        # event-time incremental pod encoding (state.encode_cache): static
        # rows pre-built at informer delivery, template-shared across pods
        # and cycles; None = rebuild-per-batch (the escape hatch)
        if encode_cache:
            from ..state.encode_cache import EncodeCache

            self.encode_cache = EncodeCache(metrics=self.metrics.tpu)
        else:
            self.encode_cache = None
        # per-profile (filter-set, score-set) frozensets for the per-event
        # pre-encode hook (rebuilt-per-event frozensets were informer-path
        # allocation churn)
        self._prof_sets: dict[int, tuple] = {}
        # scheduling flight recorder + staged latency attribution (see the
        # flight_recorder docstring above); None = off
        if flight_recorder:
            from .flightrecorder import FlightRecorder

            self.flight_recorder: "FlightRecorder | None" = FlightRecorder(
                replica=replica_id,
                failure_counter=self.metrics.prom.explain_kernel_failures,
            )
        else:
            self.flight_recorder = None
        # per-stage histogram children cached once: labels() takes the
        # metric lock per call, and the bind-ack path observes 8 stages
        # per pod — measured at ~14ms/1000 pods saved (overhead budget)
        self._stage_children: dict[str, object] = {}
        self._snapshot = Snapshot()
        # previous cycle's NodeTensors — encode_snapshot refreshes only the
        # rows whose generation moved (O(Δ) per-cycle host encode)
        self._prev_nt = None
        # --- mesh sharding (parallel.mesh) -------------------------------
        from ..parallel.mesh import resolve_mesh

        self.mesh = resolve_mesh(mesh)
        # mesh shape attribute stamped on cycle spans/records so MULTICHIP
        # numbers are attributable ("2x4" style, "" when single-device)
        self.mesh_shape: tuple = (
            tuple(self.mesh.devices.shape) if self.mesh is not None else ()
        )
        # padded node capacity must divide the shard count or the sharded
        # resident block degrades to replication (encode_batch_static)
        self._pad_multiple = 1
        if self.mesh is not None:
            from ..parallel.mesh import node_pad_multiple

            self._pad_multiple = node_pad_multiple(self.mesh)
        self._collective_wall_s: float | None = None
        if self.mesh is not None:
            from ..parallel.mesh import measure_collective_wall

            # one-shot cross-shard reduction probe: the collective tax this
            # mesh pays per argmax, exposed as a gauge next to the per-cycle
            # kernel walls (MULTICHIP evidence carries its own context). A
            # mesh that cannot run one argmax cannot run the engines: raise
            self._collective_wall_s = measure_collective_wall(self.mesh)
        # --- pipeline state (see class docstring of _InflightCycle) ------
        self.pipeline = bool(pipeline)
        # the device-resident node block serves the SERIAL loop too (PR 2
        # introduced it for pipeline mode): every cycle completes before
        # the next encode's dirty-row scatter donates the old buffers, so
        # the donation contract holds in both modes — steady-state
        # host→device traffic is O(Δ·R) regardless of pipelining. Under a
        # mesh it is the SHARDED resident block (per-shard routed deltas).
        self._resident = rt.ResidentNodeState(mesh=self.mesh)
        if self.engine == "packing":
            # the packing engine's dual-price block shards its (NC,) λ
            # along the same node axis as the resident block
            self._assign_device.bind_mesh(self.mesh)
        self._inflight: _InflightCycle | None = None
        # sticky: any host-state refresh between dispatch and sync that
        # found the cluster materially changed flips this; sync replays
        self._inflight_stale = False
        # deque: append/popleft are atomic, so dispatcher worker threads can
        # complete into it while the loop thread drains
        self._bind_completions: collections.deque = collections.deque()
        self._post_filter: Callable[..., Any] | None = None  # set by preemption
        self._last_flush = 0.0
        self.pdbs: dict[str, t.PodDisruptionBudget] = {}  # "ns/name" -> PDB
        # per-cycle context the PostFilter consumes: (batch, params,
        # final_state, key->batch-index). None outside a cycle.
        self._cycle_ctx: tuple | None = None
        # preemptor key -> victim uids awaiting their informer delete; while
        # any victim is still in the cache the pod is not eligible to
        # preempt again (PodEligibleToPreemptOthers' terminating-victims
        # check, default_preemption.go:364)
        self._preempting: dict[str, set[str]] = {}
        # nominated pods' reservations, fed into the fit filter so lower-
        # priority pods can't steal the room the victims freed
        from ..queue.nominator import Nominator

        self.nominator = Nominator()
        from .extender import HTTPExtender

        self.extenders = [HTTPExtender(c) for c in self.cfg.extenders]
        self._extender_pool = None
        if self.extenders:
            from concurrent.futures import ThreadPoolExecutor

            # one long-lived worker pool for the per-cycle extender fan-out
            # (per-cycle executor construction was hot-path thread churn)
            self._extender_pool = ThreadPoolExecutor(
                max_workers=max(1, self.cfg.parallelism)
            )
        from .podgroup import PodGroupManager

        self.podgroups = PodGroupManager(
            clock,
            initial_backoff=self.cfg.pod_initial_backoff_seconds,
            max_backoff=self.cfg.pod_max_backoff_seconds,
        )
        from ..framework import lifecycle as lc

        self.registry = registry if registry is not None else lc.default_registry()
        # loud config validation (apis/config/validation analog): a
        # malformed profile must never reach the hot loop
        from ..framework.validation import must_validate

        self._lifecycles: dict[str, lc.LifecycleRunner] = {}
        built: dict[int, lc.LifecycleRunner] = {}
        for pname, prof in self.profiles.items():
            if id(prof) not in built:
                must_validate(prof, self.registry)
                built[id(prof)] = self.registry.build(
                    prof.lifecycle.names(), prof, metrics=self.metrics.prom
                )
            self._lifecycles[pname] = built[id(prof)]
        # the default profile's runner (single-profile back-compat surface)
        self.lifecycle = self._lifecycles.get(
            "default-scheduler",
            next(iter(self._lifecycles.values())),
        )
        # permitted-with-Wait pods parked before binding (waitingPodsMap)
        self.waiting_pods: dict[str, lc.WaitingPod] = {}
        # --- anomaly sentinel (telemetry.sentinel) -----------------------
        self.sentinel = None
        if sentinel:
            from ..telemetry.sentinel import Sentinel

            self.sentinel = (
                sentinel if isinstance(sentinel, Sentinel) else Sentinel()
            )
            self.sentinel.bind(
                metrics_fn=self.metrics_text,
                tracer=self.tracer,
                bundle_sources={
                    "queue": self.queue.debug_json,
                    "cycle_records": self.metrics.tpu.records_json,
                    "dispatcher": self.dispatcher.stats,
                },
                process=(
                    f"scheduler-{replica_id}" if replica_id else "scheduler"
                ),
                component="scheduler",
            )

    def enable_preemption(self) -> None:
        """Wire the DefaultPreemption PostFilter
        (plugins/defaultpreemption/default_preemption.go:136)."""
        from .preemption import DefaultPreemptionPostFilter

        self._post_filter = DefaultPreemptionPostFilter()

    # ------------------------------------------------------- PDB informers
    def on_pdb_add(self, pdb: t.PodDisruptionBudget) -> None:
        self.pdbs[f"{pdb.namespace}/{pdb.name}"] = pdb

    on_pdb_update = on_pdb_add

    def on_pdb_delete(self, pdb: t.PodDisruptionBudget) -> None:
        self.pdbs.pop(f"{pdb.namespace}/{pdb.name}", None)

    # ------------------------------------------------------ event handlers
    # The informer seam (eventhandlers.go:455): assigned pods maintain the
    # cache; unscheduled pods maintain the queue; every event also feeds the
    # queueing hints so parked pods wake up.

    def _profile_for(self, pod: t.Pod) -> C.Profile | None:
        """frameworkForPod (schedule_one.go:532): None = not our pod."""
        return self.profiles.get(pod.scheduler_name)

    def _lifecycle_for(self, pod: t.Pod):
        return self._lifecycles.get(pod.scheduler_name, self.lifecycle)

    def _gang_member(self, pod: t.Pod) -> bool:
        """Is this pod routed through the gang lane? One predicate for
        EVERY routing decision (add/update/reject/bind-failure) — a pod
        must never be gang-routed on one path and queue-routed on another."""
        return bool(pod.scheduling_group) and self.feature_gates.enabled(
            "GangScheduling"
        )

    @staticmethod
    def _scheduling_gates(pod: t.Pod) -> str | None:
        """SchedulingGates PreEnqueue (plugins/schedulinggates): any
        non-empty spec.schedulingGates holds the pod out of the queue."""
        return N.SCHEDULING_GATES if pod.scheduling_gates else None

    def _dra_pre_enqueue(self, pod: t.Pod) -> str | None:
        """DynamicResources PreEnqueue (dynamicresources.go:270): every
        referenced ResourceClaim must exist before the pod may enter the
        active queue (template instances are created by the resourceclaim
        controller); a claim Add event re-runs this gate."""
        if not pod.resource_claims or not self._dra_enabled:
            return None
        claims = self.cache.dra.claims
        for rc in pod.resource_claims:
            if not rc.claim_name or f"{pod.namespace}/{rc.claim_name}" not in claims:
                return N.DYNAMIC_RESOURCES
        return None

    def on_node_add(self, node: t.Node) -> None:
        known = self.cache.has_node(node.name)
        self.cache.add_node(node)
        if self.encode_cache is not None:
            if known:
                # resync-duplicate Add REPLACES the node object (labels /
                # taints may differ at an interior index): full-epoch seam
                self.encode_cache.invalidate_nodes()
            else:
                # SCOPED invalidation: a genuine add appends to the node
                # axis, so the cache extends its rows with the new node's
                # columns at the next sync instead of flushing every
                # node-dependent store (at 100k nodes an add-wave flush
                # was a re-encode storm)
                self.encode_cache.invalidate_nodes(added=node)
        self.queue.on_event(
            ClusterEvent(EventResource.NODE, ActionType.ADD), None, node
        )
        self.podgroups.wake_all()   # new capacity may fit a parked gang

    def on_node_update(self, old: t.Node | None, new: t.Node) -> None:
        self.cache.update_node(new)
        if self.encode_cache is not None:
            self.encode_cache.invalidate_nodes()
        ev = node_update_event(old, new)
        if ev.action:
            self.queue.on_event(ev, old, new)

    def on_node_delete(self, node: t.Node) -> None:
        self.cache.remove_node(node.name)
        if self.encode_cache is not None:
            # SCOPED invalidation: a drain-wave delete compacts cached
            # rows down to the surviving nodes' columns at the next sync
            # (an old-index gather, bit-identical to fresh) instead of
            # flushing every node-dependent store — the removal twin of
            # the add-wave extension
            self.encode_cache.invalidate_nodes(removed=node)
        self.queue.on_event(
            ClusterEvent(EventResource.NODE, ActionType.DELETE), node, None
        )

    def on_pod_add(self, pod: t.Pod) -> None:
        if not pod.node_name and self._profile_for(pod) is None:
            # a pod naming an unknown profile is another scheduler's
            # responsibility (the reference's informer filters it out)
            return
        if pod.node_name:
            self.cache.add_pod(pod)
            if self._gang_member(pod):
                # a pre-bound member counts toward the gang quorum
                # (gangscheduling.go:82 AssignedPod/Add hint)
                self.podgroups.mark_scheduled(pod, pod.node_name)
            self.queue.on_event(
                ClusterEvent(EventResource.ASSIGNED_POD, ActionType.ADD),
                None, pod,
            )
        elif self._gang_member(pod):
            # gang member: held by the manager until quorum (the
            # GangScheduling PreEnqueue, gangscheduling.go:130). With the
            # gate off, group members schedule individually (the plugin is
            # simply not registered in the reference).
            from ..queue.priority_queue import QueuedPodInfo

            info = QueuedPodInfo(pod=pod, timestamp=self.clock())
            self.podgroups.add_pod(info)
        else:
            fr = self.flight_recorder
            t_deliver = time.perf_counter() if fr is not None else 0.0
            self.queue.add(pod)
            self._pre_encode_pod(pod)
            if fr is not None:
                # the informer stage: delivery wall incl. the event-time
                # pre-encode (the e2e base in direct mode, where no
                # apiserver ingest stamp exists)
                fr.note_delivery(
                    pod, t_deliver, time.perf_counter() - t_deliver
                )

    def on_pod_update(self, old: t.Pod | None, new: t.Pod) -> None:
        if not new.node_name and self._profile_for(new) is None:
            # foreign-scheduler pod (see on_pod_add): never ours to queue
            return
        if new.node_name:
            if old is not None and old.node_name:
                self.cache.update_pod(old, new)
                from ..queue.events import pod_update_event

                ev = pod_update_event(old, new)
                if ev.action:
                    self.queue.on_event(
                        ClusterEvent(EventResource.ASSIGNED_POD, ev.action),
                        old, new,
                    )
            else:
                # pending → assigned transition (bind confirmation, possibly
                # by another actor): drop any unscheduled queue incarnation
                # and fire AssignedPod/Add — the wake-up parked affinity/
                # spread pods registered for (the reference's filtered
                # informers deliver exactly this Delete+Add pair)
                self.cache.add_pod(new)
                self.queue.delete(new)
                if self._gang_member(new):
                    self.podgroups.mark_scheduled(new, new.node_name)
                self.queue.on_event(
                    ClusterEvent(EventResource.ASSIGNED_POD, ActionType.ADD),
                    None, new,
                )
        elif self._gang_member(new):
            # unbound gang member: refresh the manager's copy — routing it
            # into the per-pod queue would bypass quorum gating and let the
            # pod double-schedule against its own group lane
            self.podgroups.update_pod(new)
        else:
            fr = self.flight_recorder
            t_deliver = time.perf_counter() if fr is not None else 0.0
            self.queue.update(old, new)
            # a mutated pod hashes to NEW signature keys — pre-build its
            # rows now; the per-uid signature memo is identity-checked, so
            # the old object's entries can never answer for the new one
            self._pre_encode_pod(new)
            if fr is not None:
                # a pod FIRST seen through an update (informer replayed a
                # mutation before its add) still opens a flight; for a
                # known pod this only accrues informer-handling wall
                fr.note_delivery(
                    new, t_deliver, time.perf_counter() - t_deliver
                )

    def on_pod_delete(self, pod: t.Pod) -> None:
        if self.flight_recorder is not None:
            self.flight_recorder.drop(pod_key(pod))
        self.nominator.remove(pod.uid)
        if self.encode_cache is not None:
            self.encode_cache.drop_pod(pod.uid)
        # a preemptor deleted while awaiting victim deletes must not leave a
        # stale pending-victims record for a later same-ns/name pod
        self._preempting.pop(pod_key(pod), None)
        if pod.scheduling_group:
            self.podgroups.remove_pod(pod)
        wp = self.waiting_pods.pop(pod_key(pod), None)
        if wp is not None:
            # a deleted waiting pod unreserves; its assume drops below
            self._lifecycle_for(wp.pod).run_unreserve(self, wp.pod, wp.node_name)
        # has_pod covers BOUND pods too: a Delete event may carry a stale
        # object with node_name unset (the informer's last-known view from
        # before the bind) and must still drop the cached accounting and
        # fire AssignedPod/Delete (cache.go:583 RemovePod's contract)
        if pod.node_name or self.cache.has_pod(pod.uid):
            self.cache.remove_pod(pod)
            # an assumed pod also lives in the queue's in-flight set until
            # its bind completes — drop it so a failing bind cannot
            # resurrect a deleted pod
            self.queue.delete(pod)
            self.queue.on_event(
                ClusterEvent(EventResource.ASSIGNED_POD, ActionType.DELETE),
                pod, None,
            )
            self.podgroups.wake_all()   # freed capacity may fit a gang
        else:
            self.queue.delete(pod)

    def _pre_encode_pod(self, pod: t.Pod) -> None:
        """Event-time tensorization (the informer half of the encode
        cache): build the pod's static rows while the delivery is being
        handled — OFF the scheduling cycle's critical path — so cycle-time
        ``encode_batch_static`` gathers instead of rebuilding. No-op when
        the cache is off, no cycle has established node tensors yet, or a
        node event invalidated them (the next cycle re-adopts)."""
        cache = self.encode_cache
        if cache is None or self._prev_nt is None:
            return
        prof = self._profile_for(pod)
        if prof is None:
            return
        sets = self._prof_sets.get(id(prof))
        if sets is None:
            sets = (
                frozenset(prof.filters.names()),
                frozenset(prof.scores.names()),
            )
            self._prof_sets[id(prof)] = sets
        try:
            cache.precompute_pod(self._prev_nt, pod, sets[0], sets[1])
        except Exception:
            # pre-encoding is an optimization; the cycle-time encode is the
            # correctness path and surfaces real bugs loudly
            pass

    # ----------------------------------------------------- service informers
    def on_service_add(self, svc: t.Service) -> None:
        """Service selectors feed the DEFAULT PodTopologySpread constraints
        (component-helpers DefaultSelector)."""
        self.cache.add_service(svc)

    def on_service_update(self, old, new: t.Service) -> None:
        self.cache.update_service(new)

    def on_service_delete(self, svc: t.Service) -> None:
        self.cache.remove_service(svc.key)

    # --------------------------------------------------- namespace informers
    def on_namespace_add(self, ns: t.Namespace) -> None:
        """nsLister feed — namespace labels drive affinity-term
        namespaceSelectors (AffinityTerm.Matches nsLabels)."""
        self.cache.add_namespace(ns)

    on_namespace_update = on_namespace_add

    def on_namespace_delete(self, ns: t.Namespace) -> None:
        self.cache.remove_namespace(ns.name)

    # ------------------------------------------------------ volume informers
    def on_pv_add(self, pv: t.PersistentVolume) -> None:
        self.cache.add_pv(pv)
        self.queue.on_event(
            ClusterEvent(EventResource.PERSISTENT_VOLUME, ActionType.ADD),
            None, pv,
        )

    def on_pv_update(self, old, new: t.PersistentVolume) -> None:
        self.cache.update_pv(new)
        self.queue.on_event(
            ClusterEvent(EventResource.PERSISTENT_VOLUME, ActionType.UPDATE),
            old, new,
        )

    def on_pv_delete(self, pv: t.PersistentVolume) -> None:
        self.cache.remove_pv(pv.name)

    def on_pvc_add(self, pvc: t.PersistentVolumeClaim) -> None:
        self.cache.add_pvc(pvc)
        self.queue.on_event(
            ClusterEvent(EventResource.PERSISTENT_VOLUME_CLAIM, ActionType.ADD),
            None, pvc,
        )

    def on_pvc_update(self, old, new: t.PersistentVolumeClaim) -> None:
        self.cache.update_pvc(new)
        self.queue.on_event(
            ClusterEvent(EventResource.PERSISTENT_VOLUME_CLAIM, ActionType.UPDATE),
            old, new,
        )

    def on_pvc_delete(self, pvc: t.PersistentVolumeClaim) -> None:
        self.cache.remove_pvc(pvc.key)

    def on_storage_class_add(self, sc: t.StorageClass) -> None:
        self.cache.add_storage_class(sc)
        self.queue.on_event(
            ClusterEvent(EventResource.STORAGE_CLASS, ActionType.ADD),
            None, sc,
        )

    def on_storage_class_update(self, old, new: t.StorageClass) -> None:
        self.cache.update_storage_class(new)
        self.queue.on_event(
            ClusterEvent(EventResource.STORAGE_CLASS, ActionType.ADD),
            old, new,
        )

    def on_storage_class_delete(self, sc: t.StorageClass) -> None:
        self.cache.remove_storage_class(sc.name)

    # ------------------------------------------------------- DRA informers
    def on_resource_claim_add(self, claim: t.ResourceClaim) -> None:
        self.cache.dra.add_claim(claim)
        self.queue.on_event(
            ClusterEvent(EventResource.RESOURCE_CLAIM, ActionType.ADD),
            None, claim,
        )

    def on_resource_claim_update(self, old, new: t.ResourceClaim) -> None:
        self.cache.dra.add_claim(new)
        self.queue.on_event(
            ClusterEvent(EventResource.RESOURCE_CLAIM, ActionType.UPDATE),
            old, new,
        )

    def on_resource_claim_delete(self, claim: t.ResourceClaim) -> None:
        self.cache.dra.remove_claim(claim.key)
        self.queue.on_event(
            ClusterEvent(EventResource.RESOURCE_CLAIM, ActionType.DELETE),
            claim, None,
        )

    def on_resource_slice_add(self, sl: t.ResourceSlice) -> None:
        self.cache.dra.add_slice(sl)
        self.queue.on_event(
            ClusterEvent(EventResource.RESOURCE_SLICE, ActionType.ADD),
            None, sl,
        )

    def on_resource_slice_update(self, old, new: t.ResourceSlice) -> None:
        self.cache.dra.add_slice(new)
        self.queue.on_event(
            ClusterEvent(EventResource.RESOURCE_SLICE, ActionType.UPDATE),
            old, new,
        )

    def on_resource_slice_delete(self, sl: t.ResourceSlice) -> None:
        self.cache.dra.remove_slice(sl.name)

    def on_device_class_add(self, dc: t.DeviceClass) -> None:
        self.cache.dra.add_class(dc)
        self.queue.on_event(
            ClusterEvent(EventResource.DEVICE_CLASS, ActionType.ADD),
            None, dc,
        )

    def on_device_class_update(self, old, new: t.DeviceClass) -> None:
        self.cache.dra.add_class(new)
        self.queue.on_event(
            ClusterEvent(EventResource.DEVICE_CLASS, ActionType.UPDATE),
            old, new,
        )

    def on_device_class_delete(self, dc: t.DeviceClass) -> None:
        self.cache.dra.remove_class(dc.name)

    # ---------------------------------------------------- PodGroup informers
    def on_pod_group_add(self, group: t.PodGroup) -> None:
        """scheduling/v1alpha3 PodGroup informer (gangscheduling.go:109:
        a PodGroup add can complete a waiting gang's quorum)."""
        self.podgroups.add_group(group)
        self.queue.on_event(
            ClusterEvent(EventResource.WORKLOAD, ActionType.ADD), None, group
        )

    on_pod_group_update = on_pod_group_add

    def on_pod_group_delete(self, group: t.PodGroup) -> None:
        self.podgroups.remove_group(group)

    # --------------------------------------------------------- batch cycle

    def warmup(self, pods: list[t.Pod], ladder: bool = True) -> None:
        """Compile the cycle's device program ahead of the hot loop, for the
        FULL compile-cache bucket ladder up to this pod count (``ladder=
        False``: just this batch's shape). A long-lived scheduler pays XLA
        compilation once at startup; perf harnesses call this so measured
        phases see steady-state latency, matching how the reference's
        precompiled binary is measured.

        Scheduling state is untouched — no assume, no queue or nominator
        traffic, no informer effects. What warmup DOES intentionally seed
        are the pure caches of informer-fed state: the incremental snapshot
        (``_snapshot``), the host node tensors (``_prev_nt``) and, in
        pipeline mode, the device-resident node block — all derived views of
        the cache that the first measured cycle would otherwise rebuild from
        scratch. Seeding them is the point: steady state starts at cycle 1.
        The rungs warmed are remembered, so a later batch pads to its own
        (``_pod_bucket``).
        """
        if not pods:
            return
        if self._inflight is not None:
            # never warm while a cycle is on the wing: warmup may rebuild
            # the node tensors / donate resident buffers under it
            self._complete_inflight()
        self._snapshot = self.cache.update_snapshot(self._snapshot)
        from ..state.encoder import bucket_ladder, round_up

        sizes = bucket_ladder(len(pods)) if ladder else [len(pods)]
        for size in sizes:
            if round_up(size) > round_up(self.max_batch):
                break
            warm = list(pods)
            while len(warm) < size:   # replicate up the ladder rung
                warm.extend(pods[: size - len(warm)])
            self._pod_buckets.setdefault(self.profile.name, set()).add(
                round_up(size)
            )
            batch = rt.encode_batch(
                self._snapshot, warm[:size], self.profile,
                nominated=self.nominator.entries(),
                prev_nt=self._prev_nt,
                resident=self._resident,
                cache=self.encode_cache,
                track_changes=self.pipeline,
                mesh=self.mesh,
                topology=self.topology,
            )
            self._adopt_node_tensors(batch.node_tensors)
            params = rt.score_params(self.profile, batch.resource_names)
            a, _ = self._assign_device(batch.device, params)
            jax.device_get(a)  # block until compiled + executed
            if self.flight_recorder is not None and self.mesh is None:
                # warm the recorder's explain kernel for the same shape —
                # the first measured cycle must not pay its compile
                self.flight_recorder.warm(batch.device, params, a)

    def prewarm(self, max_pods: int | None = None) -> None:
        """Warm the bucket ladder with synthetic constraint-free pods (the
        CLI's ``--prewarm``): for a scheduler that boots before any real pod
        arrives, this compiles the assign program for every padded batch
        size up to ``max_pods`` (default: ``max_batch``) against the current
        node set, so the first real cycles never stall on XLA."""
        from ..api.wrappers import make_pod

        self._snapshot = self.cache.update_snapshot(self._snapshot)
        if not self._snapshot.nodes:
            # every program is shaped by the node axis: against a cluster
            # with no nodes yet there is nothing to warm that a real cycle
            # would reuse (on the v5e those sixteen throwaway programs cost
            # minutes cold and `kubetpu up --prewarm` missed its readiness
            # timeout); the first real cycles compile, as without the flag
            return
        n = min(max_pods or self.max_batch, self.max_batch)
        pods = [
            make_pod(f"prewarm-{i}", namespace="kubetpu-prewarm",
                     cpu_milli=100, memory=100 * 1024**2)
            for i in range(min(n, 64))
        ]
        self.warmup(pods + pods * ((n - 1) // max(len(pods), 1)), ladder=True)

    def _pod_bucket(self, profile: C.Profile, pods: int) -> int:
        """The padded pod count for a batch of ``pods``: the smallest bucket
        this profile has already run that holds it — its programs (assign,
        explain) are compiled, and the longer program costs tenths of a
        second at most where a compile in the serving loop costs seconds
        from a warm cache and minutes cold — else ``round_up``'s, compiled
        now and remembered. So the loop compiles only for a batch larger
        than any before it; after ``warmup``/``--prewarm`` every rung is
        known and each batch gets its own."""
        from ..state.encoder import round_up

        known = self._pod_buckets.setdefault(profile.name, set())
        bucket = min((b for b in known if b >= pods), default=0)
        if not bucket:
            bucket = round_up(pods)
            known.add(bucket)
        return bucket

    def schedule_batch(self, max_batch: int | None = None) -> dict[str, int]:
        """One scheduling cycle over up to ``max_batch`` pods. Returns result
        counts. The serial cycle: drain bind completions → pop batch →
        snapshot → encode → device assign → assume + dispatch binds →
        requeue failures. A mixed-profile batch runs one sub-cycle per
        profile (each profile is its own tensor program, frameworkForPod
        semantics).

        Pipeline mode returns the counts of the cycle that COMPLETED during
        this call (usually the batch dispatched by the previous call): pop
        the next batch → host-encode its assume-independent half while the
        in-flight device program runs → sync + apply the in-flight cycle →
        patch the assume-dependent slice → dispatch. The trailing call (pop
        empty, one cycle still in flight) drains the pipeline.

        The cycle boundary is the dispatcher's micro-batch window: every
        API write the cycle enqueued (binds, status patches, victim
        deletes) is flushed as per-call-type bulk RPCs on the way out."""
        with self.loop_clock.phase("cycle"):
            try:
                return self._schedule_batch_inner(max_batch)
            finally:
                self.dispatcher.flush()
                # the cycle's FailedScheduling Events, in one request
                self._write_events()
                if self.sentinel is not None:
                    # the sentinel rides the cycle boundary: at most one
                    # rule evaluation per interval, on the owner's thread
                    self.sentinel.maybe_evaluate()

    def _schedule_batch_inner(
        self, max_batch: int | None = None
    ) -> dict[str, int]:
        self._drain_bind_completions()
        self._flush_timers()
        limit = max_batch or self.max_batch
        # cycle-id propagation starts here: the pop span, the cycle's
        # score/assign spans, and the async bind spans all carry the same
        # cycle id, which also keys the device-side counter records. An
        # EMPTY pop records no span — an idle 20 Hz loop would otherwise
        # evict every real cycle from the bounded buffer within minutes
        batch_infos = self._pop_cycle(limit)
        if not batch_infos:
            if self._inflight is not None:
                # pipeline drain: the queue emptied with one cycle on the
                # wing — sync it and report its results
                return self._complete_inflight()
            # group lane: ready gangs run when the per-pod lane is drained
            # (the reference interleaves group entities through the same
            # queue; the batch loop gives per-pod work priority per cycle)
            from .podgroup import schedule_pod_groups

            res = schedule_pod_groups(self, budget=limit)
            self.loop_work += res["scheduled"] + res["unschedulable"]
            self.metrics.note_unschedulable(res["unschedulable"])
            return res
        if self.pipeline:
            return self._schedule_batch_pipelined(batch_infos, limit)
        return self._schedule_batch_serial(batch_infos)

    def _requeue_error(self, infos: list[QueuedPodInfo]) -> None:
        """handleSchedulingFailure for a whole batch: a cycle-level failure
        must never strand popped pods in the queue's in-flight set — requeue
        them as error status, then let the bug surface."""
        self.metrics.errors += len(infos)
        for info in infos:
            self.queue.add_unschedulable(info, error=True)

    def _pop_cycle(self, limit: int) -> list[QueuedPodInfo]:
        """Pop the next cycle's batch, stamping the cycle id + pop span."""
        cycle_id = self.metrics.cycles + 1
        t_pop = time.perf_counter()
        batch_infos = self.queue.pop_batch(limit)
        self.loop_work += len(batch_infos)
        if batch_infos:
            self.tracer.record(
                "queue-pop", start=t_pop, end=time.perf_counter(),
                cycle=cycle_id, pods=len(batch_infos),
            )
        self.metrics.cycles += 1
        return batch_infos

    def _schedule_batch_serial(
        self, batch_infos: list[QueuedPodInfo]
    ) -> dict[str, int]:
        # partition by profile, preserving queue order within each group
        by_profile: dict[str, list[QueuedPodInfo]] = {}
        for info in batch_infos:
            by_profile.setdefault(info.pod.scheduler_name, []).append(info)
        scheduled = unschedulable = 0
        groups = list(by_profile.items())
        for g_i, (pname, infos) in enumerate(groups):
            try:
                res = self._profile_cycle(self.profiles[pname], infos)
            except Exception:
                # an earlier profile's failure must not strand the LATER
                # profiles' popped pods in the in-flight set
                for _, rest in groups[g_i + 1:]:
                    self._requeue_error(rest)
                raise
            scheduled += res["scheduled"]
            unschedulable += res["unschedulable"]
        return {"scheduled": scheduled, "unschedulable": unschedulable}

    def _schedule_batch_pipelined(
        self, batch_infos: list[QueuedPodInfo], limit: int
    ) -> dict[str, int]:
        """Advance the two-stage pipeline by one cycle (see schedule_batch).
        A mixed-profile pop falls back to the serial path for that call
        (after draining the pipeline) — profile partitions are rare and not
        worth a multi-way pipeline."""
        if self._inflight is None:
            # cold start: dispatch this batch, then pull the NEXT batch
            # forward so the pipeline is primed before this call returns —
            # the pulled batch falls through to the steady-state advance
            by_profile: dict[str, list[QueuedPodInfo]] = {}
            for info in batch_infos:
                by_profile.setdefault(info.pod.scheduler_name, []).append(info)
            if len(by_profile) > 1:
                return self._schedule_batch_serial(batch_infos)
            pname, infos = next(iter(by_profile.items()))
            self._inflight = self._launch_cycle(
                self.profiles[pname], infos, self.metrics.cycles
            )
            batch_infos = self._pop_cycle(limit)
            if not batch_infos:
                return self._complete_inflight()
        # steady-state advance: one cycle in flight, ``batch_infos`` next.
        by_profile = {}
        for info in batch_infos:
            by_profile.setdefault(info.pod.scheduler_name, []).append(info)
        if len(by_profile) > 1:
            res0 = self._complete_guarding(batch_infos)
            res = self._schedule_batch_serial(batch_infos)
            return {
                "scheduled": res0["scheduled"] + res["scheduled"],
                "unschedulable": res0["unschedulable"] + res["unschedulable"],
            }
        pname, infos = next(iter(by_profile.items()))
        profile = self.profiles[pname]
        cycle_id = self.metrics.cycles
        try:
            # pre-encode this batch while the in-flight cycle runs on
            # device, then sync it, then patch + dispatch this one
            t_pre = time.perf_counter()
            with self.tracer.span("encode", cycle=cycle_id, stage="static"):
                static = self._pre_encode(profile, infos)
            pre_encode_s = time.perf_counter() - t_pre
            res = self._complete_inflight()
        except Exception:
            # a failure completing the PREVIOUS cycle must not strand the
            # freshly popped batch in the queue's in-flight set
            self._requeue_error(infos)
            raise
        # if this launch raises, its batch is requeued inside _launch_cycle
        # and the exception propagates — the completed cycle's counts (res)
        # are then unreportable, but its metrics/binds were already applied
        # (same reporting shape as the serial loop's multi-profile error
        # path: state consistent, counts lost to the raise)
        self._inflight = self._launch_cycle(
            profile, infos, cycle_id, static=static, pipelined=True,
            pre_encode_s=pre_encode_s,
        )
        return res

    def _complete_guarding(
        self, pending: list[QueuedPodInfo]
    ) -> dict[str, int]:
        """_complete_inflight, requeueing ``pending`` (a popped-but-not-yet-
        dispatched batch) as error status if the completion raises."""
        try:
            return self._complete_inflight()
        except Exception:
            self._requeue_error(pending)
            raise

    def _pre_encode(
        self, profile: C.Profile, batch_infos: list[QueuedPodInfo]
    ) -> "rt.StaticBatch | None":
        """Pipeline stage 1 for the NEXT batch, overlapping the in-flight
        device program: refresh host state (which also diffs any informer
        deltas against the in-flight encode — see _refresh_host_state) and
        build the assume-independent half of the encode. Returns None when
        the batch's encode is assume-coupled (volumes / DRA claims /
        nominations in play) — the dispatch will re-encode from scratch."""
        self._refresh_host_state()
        pods = [info.pod for info in batch_infos]
        if self.nominator.entries() or any(
            p.volumes or p.resource_claims for p in pods
        ):
            return None
        try:
            sb = rt.encode_batch_static(
                self._snapshot, pods, profile,
                nominated=(), prev_nt=self._prev_nt,
                cache=self.encode_cache,
                pad_multiple=self._pad_multiple,
                topology=self.topology,
                pad_pods=self._pod_bucket(profile, len(pods)),
            )
        except Exception:
            # stage 1 is an optimization: any failure falls back to the
            # launch-time full encode (which surfaces real bugs loudly)
            return None
        self._adopt_node_tensors(sb.nt)
        if sb.assume_coupled:
            return None
        return sb

    def _refresh_host_state(self) -> None:
        """Refresh snapshot + host node tensors and flag the in-flight cycle
        stale when the cluster MATERIALLY changed since its dispatch: a
        re-encoded row whose values differ (foreign pod add/delete), a
        pod-set content change (label/hostPort mutation feeding affinity/
        spread/port tensors without moving the rows), a replaced node
        object (labels/taints/images may differ), or a node set/order
        change (tensor rebuild). Bind confirmations of our own assumed
        pods move no node (``Cache.add_pod`` keeps an equal one), and one
        that differs only where no row or content reads it does NOT flag."""
        from ..state.encoder import encode_snapshot

        self._snapshot = self.cache.update_snapshot(self._snapshot)
        nt = self._prev_nt
        if nt is None:
            return
        new_nt = encode_snapshot(
            self._snapshot, resource_names=nt.resource_names, pods=(),
            pad_nodes=nt.alloc.shape[0], prev=nt,
        )
        if (
            new_nt is not nt
            or new_nt.last_values_changed
            or new_nt.last_nodes_replaced
            or new_nt.last_pods_mutated
        ):
            self._inflight_stale = True
        self._adopt_node_tensors(new_nt)

    def _adopt_node_tensors(self, nt) -> None:
        """Hold ``nt``, just encoded, as the next encode's ``prev`` and
        count the node rows that encode wrote."""
        self.metrics.note_encode_node_rows(len(nt.last_dirty_rows))
        self._prev_nt = nt

    def _complete_inflight(self) -> dict[str, int]:
        """Sync the in-flight cycle and apply its results — or, when host
        state moved under it, discard the device result and replay the batch
        serially against fresh state (exactly what the serial loop would
        have computed), preserving pod-for-pod parity."""
        inflight = self._inflight
        self._inflight = None
        assert inflight is not None
        try:
            self._refresh_host_state()
        except Exception:
            # the in-flight batch must not be stranded by a refresh failure
            self._requeue_error(inflight.batch_infos)
            raise
        dra = self.cache.dra
        stale = (
            self._inflight_stale
            or self.nominator.version != inflight.nominator_version
            or self._snapshot.volumes_generation != inflight.vol_gen
            or self._snapshot.namespaces_generation != inflight.ns_gen
            or (dra.generation, dra.claims_version) != inflight.dra_gen
        )
        if inflight.pipelined:
            # a cycle dispatched ahead: applied as it stood, or replayed
            self.metrics.prom.pipeline_cycles.labels(
                "replayed" if stale else "applied"
            ).inc()
        if stale:
            self.metrics.pipeline_replays += 1
            # let the stale program finish before its input buffers can be
            # donated by the replay's resident refresh
            try:
                jax.block_until_ready(inflight.assignments)
            except Exception:
                pass
            replay = self._launch_cycle(
                inflight.profile, inflight.batch_infos, inflight.cycle_id
            )
            return self._finish_cycle(replay)
        return self._finish_cycle(inflight)

    def abandon_inflight(self) -> None:
        """Give up the cycle in flight, if there is one, binding nothing:
        wait for its device program (its input buffers may be donated by
        the next encode), drop the result and requeue its pods with error
        status, so none stays in the queue's in-flight set. For a loop that
        may no longer bind — the served loop calls it in every iteration it
        is not the leader."""
        inflight, self._inflight = self._inflight, None
        if inflight is None:
            return
        try:
            jax.block_until_ready(inflight.assignments)
        except Exception:
            pass    # nothing of the result is used
        for info in inflight.batch_infos:
            self.queue.add_unschedulable(info, error=True)

    def _profile_cycle(
        self, profile: C.Profile, batch_infos: list[QueuedPodInfo]
    ) -> dict[str, int]:
        """Serial cycle: launch + sync back-to-back (the reference's fully
        serialized scheduling cycle)."""
        return self._finish_cycle(
            self._launch_cycle(profile, batch_infos, self.metrics.cycles)
        )

    def _launch_cycle(
        self,
        profile: C.Profile,
        batch_infos: list[QueuedPodInfo],
        cycle_id: int,
        static: "rt.StaticBatch | None" = None,
        pipelined: bool = False,
        pre_encode_s: float = 0.0,
    ) -> _InflightCycle:
        """Snapshot → encode (or finalize a pre-encoded StaticBatch) →
        dispatch the assign program. Does NOT block on the device: JAX async
        dispatch returns immediately; ``_finish_cycle`` syncs.
        ``pre_encode_s``: what ``_pre_encode`` already spent on this batch,
        so that the ``PreFilter`` point holds the batch's whole host encode
        in a two-stage cycle as in a serial one."""
        from ..metrics.tpu import jit_cache_size

        t0 = self.clock()
        t_start = time.perf_counter()
        prom = self.metrics.prom
        try:
            with self.tracer.span("snapshot", cycle=cycle_id):
                self._snapshot = self.cache.update_snapshot(self._snapshot)
            pods = [info.pod for info in batch_infos]
            t_enc = time.perf_counter()
            with self.tracer.span("encode", cycle=cycle_id) as enc_sp:
                batch = None
                if static is not None:
                    batch = self._finalize_static(static)
                if batch is None:
                    batch = rt.encode_batch(
                        self._snapshot, pods, profile,
                        nominated=self.nominator.entries(),
                        prev_nt=self._prev_nt,
                        resident=self._resident,
                        cache=self.encode_cache,
                        track_changes=self.pipeline,
                        mesh=self.mesh,
                        topology=self.topology,
                        pad_pods=self._pod_bucket(profile, len(pods)),
                    )
                if self.encode_cache is not None and enc_sp is not None:
                    # gather-vs-fresh-vs-invalidate: how this cycle's rows
                    # were obtained, joined to the device counters by cycle
                    delta = self.encode_cache.flush_metrics()
                    enc_sp.attrs["gather_rows"] = delta.get("hits", 0)
                    enc_sp.attrs["fresh_rows"] = delta.get("misses", 0)
                    if delta.get("invalidations"):
                        enc_sp.attrs["invalidated"] = True
                        self.tracer.instant(
                            "encode-cache-invalidate", cycle=cycle_id,
                            count=delta["invalidations"],
                        )
                stamp = batch.spread_encode
                if stamp is not None:
                    # the spread path's host time, named: a cycle with no
                    # constrained pod observes nothing
                    prom.plugin_execution_duration.labels(
                        C.POD_TOPOLOGY_SPREAD, "PreFilter", "Success"
                    ).observe(stamp.end - stamp.start)
                    self.tracer.record(
                        "encode-spread", stamp.start, stamp.end,
                        parent_id=self.tracer.current_id, off_stack=False,
                        cycle=cycle_id, signatures=stamp.signatures,
                        domains=stamp.domains,
                        constrained_pods=stamp.constrained_pods,
                        soft_pods=stamp.soft_pods,
                        counted_domains=stamp.counted_domains,
                        taints_excluded_nodes=stamp.excluded_nodes["taints"],
                        affinity_excluded_nodes=stamp.excluded_nodes[
                            "affinity"],
                    )
                stamp = batch.podaffinity_encode
                if stamp is not None:
                    # the affinity path's host time, named: a cycle without
                    # an affinity term observes nothing
                    prom.plugin_execution_duration.labels(
                        C.INTER_POD_AFFINITY, "PreFilter", "Success"
                    ).observe(stamp.end - stamp.start)
                    self.tracer.record(
                        "encode-podaffinity", stamp.start, stamp.end,
                        parent_id=self.tracer.current_id, off_stack=False,
                        cycle=cycle_id, rows=stamp.rows,
                        domains=stamp.domains, slots=stamp.slots,
                        filter_pods=stamp.filter_pods,
                        score_pods=stamp.score_pods,
                        existing_anti_pods=stamp.filter_terms[
                            "existing_anti_affinity"],
                        existing_anti_nodes=stamp.existing_anti_nodes,
                    )
            # the host encode builds per-pod state ahead of filtering —
            # the PreFilter role in the reference's extension-point map
            encode_s = time.perf_counter() - t_enc + pre_encode_s
            prom.framework_extension_point_duration.labels(
                "PreFilter", "Success", profile.name
            ).observe(encode_s)
            self._adopt_node_tensors(batch.node_tensors)
            with self.tracer.span("extenders", cycle=cycle_id):
                device_batch = self._apply_extenders(batch, pods)
            params = rt.score_params(profile, batch.resource_names)
            cache0 = jit_cache_size(self._assign_device)
            t_dev = time.perf_counter()
            assignments, final_state = self._assign_device(
                device_batch, params
            )
            # everything the dispatched program saw is now folded in; any
            # LATER host-state refresh that finds changes flips this
            self._inflight_stale = False
            return _InflightCycle(
                profile=profile, batch_infos=batch_infos, batch=batch,
                device_batch=device_batch, params=params,
                assignments=assignments, final_state=final_state,
                cycle_id=cycle_id, t_start=t_start, t0=t0, t_dev=t_dev,
                cache0=cache0,
                nominator_version=self.nominator.version,
                vol_gen=self._snapshot.volumes_generation,
                ns_gen=self._snapshot.namespaces_generation,
                dra_gen=(
                    self.cache.dra.generation,
                    self.cache.dra.claims_version,
                ),
                launch_s=self.clock() - t0,
                pipelined=pipelined,
                encode_s=encode_s,
            )
        except Exception:
            self._requeue_error(batch_infos)
            raise

    def _finalize_static(
        self, static: "rt.StaticBatch"
    ) -> "rt.EncodedBatch | None":
        """Pipeline stage 2: patch a pre-encoded StaticBatch against the
        post-assume cluster state. None = unusable (fall back to a full
        encode)."""
        if self.nominator.entries():
            # nominations appeared after stage 1: the port vocabulary /
            # folded charges may not cover them — re-encode
            return None
        if not rt.refresh_static(static, self._snapshot):
            return None
        try:
            return rt.finalize_batch(
                static, self._snapshot, nominated=(),
                resident=self._resident, mesh=self.mesh,
            )
        except rt.StaleStaticEncode:
            return None

    def _finish_cycle(self, inflight: _InflightCycle) -> dict[str, int]:
        """Sync the device result and run the host half of the cycle:
        metrics, assume + bind dispatch, failure handling."""
        from ..metrics.tpu import batch_nbytes, jit_cache_size

        profile = inflight.profile
        batch_infos = inflight.batch_infos
        batch = inflight.batch
        cycle_id = inflight.cycle_id
        prom = self.metrics.prom
        t_finish0 = self.clock()
        try:
            t_sync = time.perf_counter()
            idx = np.asarray(jax.device_get(inflight.assignments))
            t_done = time.perf_counter()
            # serial: dispatch→fetch is the device program's wall. Pipelined:
            # the program overlapped host work across loop ticks, so
            # dispatch→fetch would count the inter-tick idle gap — the
            # honest device cost there is the residual sync wait (what the
            # loop actually stalled for)
            wall_start = t_sync if inflight.pipelined else inflight.t_dev
            kernel_wall_s = t_done - wall_start
            cache1 = jit_cache_size(self._assign_device)
            assign_attrs = dict(
                cycle=cycle_id, sync_wait_s=round(t_done - t_sync, 6),
                kernel_wall_s=round(kernel_wall_s, 6),
            )
            if self.mesh_shape:
                # mesh shape + shard count on every device span: MULTICHIP
                # traces stay attributable per chip
                assign_attrs["mesh"] = "x".join(map(str, self.mesh_shape))
                assign_attrs["shards"] = self._resident._n_shards
            self.tracer.record("assign", start=wall_start, end=t_done,
                               **assign_attrs)
            # device-side counters, joined to the spans by cycle id
            compile_miss = (
                None if inflight.cache0 is None or cache1 is None
                else cache1 > inflight.cache0
            )
            full_bytes = batch_nbytes(inflight.device_batch)
            transfer_bytes = batch.upload_bytes or full_bytes
            if inflight.device_batch is not batch.device:
                # extender verdict tensors were attached post-encode: count
                # their upload too
                transfer_bytes += full_bytes - batch_nbytes(batch.device)
            # packing-engine solve diagnostics: the device scalars were
            # produced by the same program as the assignments, so fetching
            # them here adds no extra sync point
            objective_value = solver_iters = nodes_used = None
            if self.engine == "packing":
                try:
                    eng = self._assign_device
                    if eng.last_iters is not None:
                        objective_value = float(
                            jax.device_get(eng.last_objective)
                        )
                        solver_iters = int(jax.device_get(eng.last_iters))
                        nodes_used = int(
                            jax.device_get(eng.last_nodes_used)
                        )
                except Exception:
                    pass    # diagnostics must never fail the cycle
            if objective_value is not None:
                prom.packing_objective.labels(self.engine).set(
                    objective_value
                )
                prom.nodes_used.labels(self.engine).set(nodes_used)
                prom.packing_solver_iters.labels(self.engine).observe(
                    solver_iters
                )
            self.metrics.tpu.record_cycle(
                cycle=cycle_id, engine=self.engine,
                batch_size=len(batch_infos), transfer_bytes=transfer_bytes,
                kernel_wall_s=kernel_wall_s, compile_miss=compile_miss,
                profile=profile.name,
                batch_bytes=full_bytes,
                resident_bytes=batch.resident_bytes,
                pipelined=inflight.pipelined,
                mesh_shape=self.mesh_shape,
                shard_transfer_bytes=(
                    list(self._resident.last_upload_bytes_per_shard)
                    if self.mesh_shape else None
                ),
                shard_resident_bytes=(
                    self._resident.nbytes_per_shard
                    if self.mesh_shape else None
                ),
                collective_wall_s=self._collective_wall_s,
                replica=self.replica_id,
                objective_value=objective_value,
                solver_iters=solver_iters,
            )
            if self.mesh_shape:
                # per-shard routed-delta attribution, joined by cycle id
                for s_i, (b_s, r_s) in enumerate(zip(
                    self._resident.last_upload_bytes_per_shard,
                    self._resident.last_rows_per_shard,
                )):
                    if r_s:
                        self.tracer.instant(
                            "shard-upload", cycle=cycle_id, shard=s_i,
                            bytes=b_s, rows=r_s,
                        )
            # the fused device program IS Filter+Score (one XLA
            # program — per-plugin splits don't exist on device)
            prom.framework_extension_point_duration.labels(
                "Filter+Score", "Success", profile.name
            ).observe(kernel_wall_s)
            self._cycle_ctx = (
                batch, inflight.params, inflight.final_state,
                {info.key: k for k, info in enumerate(batch_infos)},
            )
            if self.flight_recorder is not None:
                try:
                    # one decision record per pod, with the cycle-start
                    # score/filter breakdown (skipped under a mesh: the
                    # sharded batch is not re-evaluated for diagnostics)
                    with self.loop_clock.phase("explain"), \
                            self.tracer.span("explain", cycle=cycle_id):
                        self.flight_recorder.note_cycle(
                            batch=batch,
                            device_batch=inflight.device_batch,
                            params=inflight.params,
                            batch_infos=batch_infos,
                            idx=idx,
                            cycle_id=cycle_id,
                            profile=profile.name,
                            encode_s=inflight.encode_s,
                            kernel_s=kernel_wall_s,
                            breakdown=self.mesh is None,
                            engine=self.engine,
                            objective_value=objective_value,
                            solver_iters=solver_iters,
                            skipped_reason=(
                                None if self.mesh is None else "mesh"
                            ),
                        )
                except Exception:
                    pass    # diagnostics must never fail the cycle
        except Exception:
            self._requeue_error(batch_infos)
            raise

        scheduled = 0
        failed: list[QueuedPodInfo] = []
        with self.loop_clock.phase("bind_dispatch"), self.tracer.span(
            "bind-dispatch", cycle=cycle_id, pods=len(batch_infos)
        ):
            for k, info in enumerate(batch_infos):
                j = int(idx[k])
                self.metrics.note_attempts()
                if 0 <= j < len(batch.node_names):
                    if self._assume_and_bind(info, batch.node_names[j]):
                        scheduled += 1
                    # a Reserve/Permit rejection already requeued the pod
                else:
                    failed.append(info)
        # the cycle's span ends where its histogram's observation does:
        # it covers explain and bind-dispatch, not the failure handling
        cycle_attrs = dict(
            cycle=cycle_id, profile=profile.name,
            pods=len(batch_infos), pipelined=inflight.pipelined,
        )
        if self.mesh_shape:
            cycle_attrs["mesh"] = "x".join(map(str, self.mesh_shape))
        self.tracer.record(
            "scheduling-cycle", start=inflight.t_start,
            end=time.perf_counter(), parent_id=self.tracer.current_id,
            # a pipelined cycle straddles two loop iterations: it cannot
            # nest on the loop's lane and rides one of its own
            off_stack=inflight.pipelined, **cycle_attrs,
        )
        self.metrics.note_scheduled(scheduled)
        self.metrics.note_unschedulable(len(failed))
        # active cycle time = launch half + finish half: in pipeline mode
        # the two halves run in different loop ticks, and the idle gap
        # between them must not inflate the duration histograms
        cycle_s = inflight.launch_s + (self.clock() - t_finish0)
        self.metrics.scheduling_seconds += cycle_s
        prom.scheduling_algorithm_duration.observe(cycle_s)
        # per-attempt duration: each pod's attempt spans the batch cycle
        # (the reference's per-pod loop measures its own span; the batch is
        # the attempt for every pod in it)
        if scheduled:
            prom.schedule_attempts.labels("scheduled", profile.name).inc(scheduled)
            prom.scheduling_attempt_duration.labels(
                "scheduled", profile.name
            ).observe_n(cycle_s, scheduled)
        if failed:
            prom.schedule_attempts.labels("unschedulable", profile.name).inc(len(failed))
            prom.scheduling_attempt_duration.labels(
                "unschedulable", profile.name
            ).observe_n(cycle_s, len(failed))
        if batch.spread_encode is not None:
            # beside the attempts it is read as a share of
            prom.spread_constrained_pods.inc(
                batch.spread_encode.constrained_pods
            )
            prom.spread_soft_constrained_pods.inc(
                batch.spread_encode.soft_pods
            )
            policy_pods = batch.spread_encode.policy_pods
            prom.spread_policy_pods.labels("taints").inc(policy_pods["taints"])
            prom.spread_policy_pods.labels("affinity").inc(
                policy_pods["affinity"]
            )
        if batch.podaffinity_encode is not None:
            prom.podaffinity_pods.labels("filter").inc(
                batch.podaffinity_encode.filter_pods
            )
            prom.podaffinity_pods.labels("score").inc(
                batch.podaffinity_encode.score_pods
            )
            for term, pods in batch.podaffinity_encode.filter_terms.items():
                prom.podaffinity_filter_pods.labels(term).inc(pods)
            prom.podaffinity_existing_anti_nodes.inc(
                batch.podaffinity_encode.existing_anti_nodes
            )

        try:
            for info in failed:
                self._handle_unschedulable(info, profile)
        finally:
            # drop the cycle's batch (device tensors + host snapshot
            # encoding) so it doesn't pin memory across cycles
            self._cycle_ctx = None
            if self._post_filter is not None:
                reset = getattr(self._post_filter, "reset", None)
                if reset is not None:
                    reset()
        return {"scheduled": scheduled, "unschedulable": len(failed)}

    def _apply_extenders(self, batch, pods):
        """Run the configured extender webhooks for the batch and attach
        their (P, N) mask/score to the device pytree (findNodesThatPass
        Extenders + extender Prioritize — sched/extender.py). Shared by the
        per-pod lane and the pod-group lane."""
        device_batch = batch.device
        if not self.extenders:
            return device_batch
        from dataclasses import replace as _dc_replace

        import jax.numpy as jnp

        from .extender import run_extenders

        ext_mask, ext_score = run_extenders(
            self.extenders, pods, batch.node_names,
            batch.num_nodes,
            pad_pods=device_batch.requests.shape[0],
            pad_nodes=device_batch.alloc.shape[0],
            parallelism=self.cfg.parallelism,
            executor=self._extender_pool,
        )
        if ext_mask is not None:
            device_batch = _dc_replace(
                device_batch,
                extender_mask=jnp.asarray(ext_mask),
                extender_score=jnp.asarray(ext_score),
            )
        return device_batch

    def _assume_and_bind(self, info: QueuedPodInfo, node_name: str) -> bool:
        """assumeAndReserve + Permit + async binding cycle
        (schedule_one.go:307 assumeAndReserve, :211 RunPermitPlugins, :391
        bindingCycle). Returns False when a Reserve/Permit plugin rejected
        the pod (it was forgotten and requeued)."""
        assumed = info.pod.with_node(node_name)
        self.cache.assume_pod(assumed)
        info.cycle_id = self.metrics.cycles
        # a scheduled pod's nomination (if any) is spent
        self.nominator.remove(info.pod.uid)
        self._preempting.pop(info.key, None)
        # the pod stays in flight through the binding cycle — queue.done only
        # after the bind lands, so events during binding replay on failure
        if info.initial_attempt_timestamp is not None:
            sli = self.clock() - info.initial_attempt_timestamp
            self.metrics.attempt_latencies.append(sli)
            self.metrics.prom.pod_scheduling_sli_duration.labels(
                str(info.attempts)
            ).observe(sli)
            self.metrics.prom.pod_scheduling_attempts.observe(info.attempts)
        return self._begin_binding(info, assumed)

    def _begin_binding(self, info: QueuedPodInfo, assumed: t.Pod) -> bool:
        """Reserve → Permit → dispatch (or park as a waiting pod). Shared by
        the per-pod batch and the pod-group lane."""
        from ..framework import lifecycle as lc

        node_name = assumed.node_name
        lifecycle = self._lifecycle_for(info.pod)
        if lifecycle:
            st = lifecycle.run_reserve(self, info.pod, node_name)
            if not st.ok:
                lifecycle.run_unreserve(self, info.pod, node_name)
                self._reject_assumed(info, assumed, st)
                return False
            st, pending, deadline = lifecycle.run_permit(
                self, info.pod, node_name, self.clock()
            )
            if st.code == lc.WAIT:
                self.waiting_pods[info.key] = lc.WaitingPod(
                    pod=info.pod, node_name=node_name, info=info,
                    pending=pending, deadline=deadline,
                )
                return True
            if not st.ok:
                lifecycle.run_unreserve(self, info.pod, node_name)
                self._reject_assumed(info, assumed, st)
                return False
        self._dispatch_bind(info, assumed)
        return True

    def _dispatch_bind(self, info: QueuedPodInfo, assumed: t.Pod) -> None:
        node_name = assumed.node_name
        t_dispatch = time.perf_counter()
        # the BindCall stamps its own API-phase start (t_exec) on the
        # worker thread; on_done reads it back through this cell so the
        # staged vector can split dispatch-wait from the bind round trip
        call_cell: list = []

        def on_done(
            err: Exception | None, info=info, assumed=assumed,
            t_dispatch=t_dispatch, call_cell=call_cell,
        ) -> None:
            # completion time stamped HERE on the dispatcher thread — the
            # loop drains later, and drain time would inflate the bind span
            # by up to a whole loop interval
            t_exec = call_cell[0].t_exec if call_cell else 0.0
            self._bind_completions.append(
                (info, assumed, err, t_dispatch, t_exec,
                 time.perf_counter())
            )

        lifecycle = self._lifecycle_for(info.pod)
        pre = post = None
        if lifecycle.pre_bind_plugins:
            def pre(info=info, node_name=node_name, lifecycle=lifecycle):
                st = lifecycle.run_pre_bind(self, info.pod, node_name)
                if not st.ok:
                    raise RuntimeError(
                        f"PreBind {st.plugin}: {st.reason or st.code}"
                    )
        if lifecycle.post_bind_plugins:
            def post(info=info, node_name=node_name, lifecycle=lifecycle):
                lifecycle.run_post_bind(self, info.pod, node_name)
        # an interested binder extender owns the bind API call
        # (schedule_one.go:1142 bind → extendersBinding)
        bind_fn = None
        for e in self.extenders:
            if e.is_binder() and e.is_interested(info.pod):
                bind_fn = e.bind
                break
        call = BindCall(info.pod, node_name, on_done=on_done, pre=pre,
                        post=post, bind_fn=bind_fn)
        call_cell.append(call)
        self.dispatcher.add(call)

    def _reject_assumed(self, info: QueuedPodInfo, assumed: t.Pod, st) -> None:
        """A Reserve/Permit rejection (or permit timeout): forget the assume
        and requeue — handleSchedulingFailure for the binding-path statuses."""
        self.cache.forget_pod(assumed)
        self.metrics.note_unschedulable()
        if self._gang_member(info.pod):
            self.podgroups.unmark_scheduled(info.pod)
            self.podgroups.requeue_member(info)
        else:
            where = self.queue.add_unschedulable(
                info, [st.plugin] if st.plugin else ()
            )
            if self.flight_recorder is not None:
                self.flight_recorder.note_requeue(
                    info.key, where, [st.plugin] if st.plugin else (),
                )

    # ---------------------------------------------------------- waiting pods
    def get_waiting_pod(self, key: str):
        """fwk.Handle.GetWaitingPod — Permit plugins allow/reject through
        the returned WaitingPod; verdicts take effect next cycle."""
        return self.waiting_pods.get(key)

    def iterate_waiting_pods(self):
        return list(self.waiting_pods.values())

    def _drain_waiting_pods(self) -> None:
        """Move decided waiting pods onward; time out the overdue (the
        reference rejects on permit timeout, frameworkImpl.WaitOnPermit)."""
        from ..framework import lifecycle as lc

        now = self.clock()
        for key in list(self.waiting_pods):
            wp = self.waiting_pods[key]
            if wp.rejected is None and wp.pending and now >= wp.deadline:
                wp.rejected = lc.Status(
                    lc.UNSCHEDULABLE, "permit wait timed out",
                    next(iter(sorted(wp.pending))),
                )
            if not wp.decided:
                continue
            del self.waiting_pods[key]
            assumed = wp.pod.with_node(wp.node_name)
            if wp.rejected is not None:
                self._lifecycle_for(wp.pod).run_unreserve(self, wp.pod, wp.node_name)
                self._reject_assumed(wp.info, assumed, wp.rejected)
            else:
                self._dispatch_bind(wp.info, assumed)

    def _drain_bind_completions(self) -> int:
        """Bind results re-enter the loop thread here (the reference handles
        this in the per-pod binding goroutine; we serialize into the cycle).
        Returns how many completions it took; a drain that found none
        records no span. The Events recorded on the way (one ``Scheduled``
        per bound pod) are written in one bulk request before it returns."""
        clock = self.loop_clock
        with clock.phase("drain"), self.tracer.span("drain") as sp:
            events0 = clock.entries["events"]
            events_s0 = clock.seconds["events"]
            completions = self._apply_bind_completions()
            requests = self._write_events()
            self.loop_work += completions
            if sp is not None:
                sp.discard = not completions
                sp.attrs.update(
                    completions=completions,
                    events=clock.entries["events"] - events0,
                    events_s=round(clock.seconds["events"] - events_s0, 6),
                    event_requests=requests,
                )
        return completions

    def _record_event(
        self, pod: t.Pod, reason: str, note: str, type: str = "Normal"
    ) -> None:
        """Every Event the scheduler records goes through here, stamped
        now and NOT yet written: ``_write_events`` sends what has gathered,
        at the end of ``_drain_bind_completions`` and of ``schedule_batch``.
        When either returns, every Event recorded so far is in the store or
        counted as dropped (an Event recorded outside both — ``warmup``
        completing an in-flight cycle — goes with the next; ``close`` ends
        in a drain)."""
        if self.recorder is None:
            return
        self._pending_events.append((
            f"Pod/{pod.namespace}/{pod.name}", reason, note, type,
            self.recorder.clock(),
        ))

    def _write_events(self) -> int:
        """Write the recorded Events — ``EventRecorder.events``: one bulk
        request, two when a repeat is read first — and empty the list. The
        write is a phase of its own (``events``), whatever phase it
        interrupts, and its entries count the Events. Returns the store
        round trips it took."""
        pending = self._pending_events
        if not pending:
            return 0
        self._pending_events = []
        recorder = self.recorder
        requests0 = recorder.requests
        with self.loop_clock.phase("events", entries=len(pending)):
            recorder.events(pending)
        return recorder.requests - requests0

    def _apply_bind_completions(self) -> int:
        completions = 0
        while True:
            try:
                info, assumed, err, t_dispatch, t_exec, t_done = (
                    self._bind_completions.popleft()
                )
            except IndexError:
                break
            completions += 1
            if isinstance(err, CallSkipped):
                continue  # superseded bind: the newer call's completion rules
            # the bind ran off-thread: record its dispatch→completion span
            # here on the loop thread, joined to the cycle by cycle id (one
            # per POD: the per-item ring)
            self.tracer.record(
                "bind", start=t_dispatch, end=t_done, per_item=True,
                cycle=getattr(info, "cycle_id", 0), pod=info.key,
                status="error" if err is not None else "bound",
                # the cross-process join key: the collector stitches this
                # span to the apiserver's ingest/bind-subresource spans
                # (and the other replicas' attempts) by the pod's id
                pod_trace=getattr(info.pod, "trace_id", "") or "",
            )
            fr = self.flight_recorder
            if fr is not None:
                stages = fr.note_bind(info, err, t_dispatch, t_exec, t_done)
                if stages:
                    # the per-pod staged latency vector lands in the
                    # {stage} histograms at bind ack — the staged p50/p99
                    # every fullstack perf result carries
                    children = self._stage_children
                    for stage, seconds in stages.items():
                        child = children.get(stage)
                        if child is None:
                            child = children[stage] = (
                                self.metrics.prom.e2e_scheduling_duration
                                .labels(stage)
                            )
                        child.observe(seconds)
            if err is None:
                self.cache.finish_binding(assumed.uid)
                self.queue.done(info.key)
                self._record_event(
                    info.pod, "Scheduled",
                    f"Successfully assigned {info.key} to "
                    f"{assumed.node_name}",
                )
            else:
                # bind failed: roll back the assume and retry as error status
                # (handleSchedulingFailure, schedule_one.go:1190 analog)
                self.metrics.bind_errors += 1
                self.metrics.errors += 1
                if is_bind_conflict(err):
                    # a CAS-bind race lost to another scheduler replica
                    # (or a fenced stale-owner bind): the federation
                    # arbitration path, distinct from a transport error.
                    # The error-status requeue below IS the conflict
                    # backoff — the loser won't re-fight the pod before
                    # the winner's bind echoes through the informer and
                    # deletes the queue entry.
                    self.metrics.note_bind_conflict()
                    self.metrics.prom.federation_conflicts.labels(
                        self.federation_mode or "none",
                        self.replica_id or "r0",
                    ).inc()
                self.cache.forget_pod(assumed)
                # binding-cycle failure runs Unreserve (schedule_one.go:391
                # bindingCycle's deferred unreserve-on-failure)
                self._lifecycle_for(info.pod).run_unreserve(
                    self, info.pod, assumed.node_name
                )
                if self._gang_member(info.pod):
                    # gang member: hand back to the group manager (it never
                    # lived in the per-pod queue)
                    self.podgroups.unmark_scheduled(info.pod)
                    self.podgroups.requeue_member(info)
                else:
                    where = self.queue.add_unschedulable(info, error=True)
                    if fr is not None:
                        fr.note_requeue(info.key, where, error=True)
        return completions

    def _handle_unschedulable(
        self, info: QueuedPodInfo, profile: C.Profile | None = None
    ) -> None:
        """No feasible node. Run PostFilter (preemption) if wired, then
        requeue with rejector plugins for the queueing hints.

        Rejector attribution is conservative: every enabled Filter plugin is
        recorded (the reference records the plugins that actually rejected
        per node, schedule_one.go FitError) — over-eager wake-ups are safe;
        the leftover flush bounds staleness either way."""
        profile = profile or self._profile_for(info.pod) or self.profile
        fr = self.flight_recorder
        if self._post_filter is not None:
            nominated = self._post_filter(self, info)
            if nominated is not None:
                # preemption nominated a node: victims' deletes will fire
                # hints; pod waits in backoff for the room to open
                info.nominated_node_name = nominated
                where = self.queue.add_unschedulable(
                    info, profile.filters.names()
                )
                if fr is not None:
                    fr.note_requeue(
                        info.key, where, profile.filters.names(),
                        nominated=nominated,
                    )
                    fr.note_preemption(
                        info.key, nominated,
                        self._preempting.get(info.key, ()),
                    )
                return
        where = self.queue.add_unschedulable(
            info, profile.filters.names()
        )
        if fr is not None:
            fr.note_requeue(info.key, where, profile.filters.names())
        if where not in ("deleted", "already-queued"):
            # only patch status for pods that still exist and we own
            self.dispatcher.add(
                StatusPatchCall(info.pod, reason="Unschedulable")
            )
            self._record_event(
                info.pod, "FailedScheduling",
                "0 nodes are available for the pod's constraints",
                type="Warning",
            )

    # ------------------------------------------------------------- running

    def _flush_timers(self) -> None:
        """The reference's flush goroutines (scheduling_queue.go:442: backoff
        every 1 s, unschedulable leftover every 30 s) folded into the loop."""
        now = self.clock()
        if now - self._last_flush >= 30.0:
            self.queue.flush_unschedulable_leftover()
            self.cache.cleanup_expired()
            self._last_flush = now
        self.queue.flush_backoff_completed()
        if self.waiting_pods:
            self._drain_waiting_pods()
        for queue_name, count in self.queue.stats().items():
            self.metrics.prom.pending_pods.labels(queue_name).set(count)

    def metrics_text(self) -> str:
        """Prometheus text exposition of the scheduler metric set (the
        /metrics endpoint body). Dispatcher lifetime counters (added/
        executed/errors + bulk batch counts) are folded in at scrape time
        so the DiagnosticsServer surfaces API-write failures."""
        self.metrics.prom.set_dispatcher_stats(
            self.dispatcher.stats(), self.dispatcher.worker_clock())
        self.metrics.prom.set_loop_clock(
            self.loop_clock.snapshot(), self.loop_clock.cpu_snapshot())
        self.metrics.tpu.set_host_encode_totals(
            self.metrics.encode_node_rows, self.cache.bind_confirmations)
        text = self.metrics.prom.expose()
        if self.recorder is not None and hasattr(
            self.recorder, "metrics_text"
        ):
            # the owning component exposes its recorder's counters
            # (kubetpu_events_dropped_total, ..._written_total, the write
            # requests) — the best-effort event contract made
            # scrape-visible
            text += self.recorder.metrics_text()
        if self.sentinel is not None:
            text += self.sentinel.metrics_text()
        return text

    def run_until_idle(self, max_cycles: int = 10000) -> int:
        """Drive cycles until no pod is ready (harness/test mode). Returns
        total scheduled."""
        total = 0
        for _ in range(max_cycles):
            res = self.schedule_batch()
            total += res["scheduled"]
            if res["scheduled"] == 0 and res["unschedulable"] == 0:
                break
        if self._inflight is not None:
            # a batch whose pods all Reserve-rejected reports zeros while a
            # cycle is still on the wing — drain it before declaring idle
            total += self._complete_inflight()["scheduled"]
        self.dispatcher.sync()
        self._drain_bind_completions()
        return total

    def close(self) -> None:
        if self._inflight is not None:
            # drain the pipeline so no device work (or its binds) dangles
            try:
                self._complete_inflight()
            except Exception:
                self._inflight = None
        self.dispatcher.close()
        self._drain_bind_completions()
        if self._extender_pool is not None:
            self._extender_pool.shutdown(wait=False)
        if self.sentinel is not None:
            self.sentinel.close()
