"""Active-active scheduler federation — N full replicas, one cluster.

One scheduler process is a throughput ceiling no kernel or API-plane work
can lift (ROADMAP item 3). This module runs N complete ``Scheduler``
instances — each with its own informer bundle, queue, encode cache and
dispatcher — against ONE apiserver/store, and lets the already-exact
CAS-bind/409 fallback path arbitrate whatever overlap the chosen partition
mode leaves. The TPU-batched engines are untouched: federation is pure
coordination, threaded through the informers (per-replica filtered pumps),
the dispatcher (per-replica conflict accounting), the lease machinery
(K-of-N partition leases with epoch fencing) and the metrics plane
(``scheduler_federation_*``).

Partition modes (``SchedulerFederation(partition=…)``):

- ``hash`` — pending pods are partitioned by a stable hash of their key
  (``crc32(ns/name) % n_live``): no overlap by construction. On membership
  change (replica death) the hash ranks recompute over the survivors and
  each survivor re-adopts the pending pods that now fall to it.
- ``race`` — every replica sees every pending pod; overlap is resolved by
  the CAS bind: the first replica's bind lands, the rest get 409, forget
  the assume, and requeue with the error backoff (the *conflict backoff* —
  the loser does not re-fight the same pod before the winner's bind echoes
  through its informer and deletes the queue entry).
- ``lease`` — the pod keyspace is split into K partitions, each owned via
  a renewable partition lease (``PartitionLeaseManager``, built on
  ``LeaderElector``): no overlap while leases are stable, rebalanced on
  membership change with a bounded handover window (the lease duration),
  and EPOCH-FENCED — a bind from a replica whose partition lease was
  stolen is rejected at dispatch (``StaleOwnerError``, counted as a
  conflict) because the shared lease record's ``leader_transitions`` no
  longer matches the epoch the owner captured at acquisition.

  Deviation note (documented): the ISSUE sketch says "node shard"; leases
  here partition the POD keyspace instead. Sharding nodes while every
  replica races on every pod would make N-1 of N bind attempts conflict by
  construction and break placement parity with the singleton (each replica
  would score against a partial cluster). Pod-keyspace leases keep the
  node set whole — placement quality and binding parity match the single
  scheduler — while still giving lease-granted exclusive ownership,
  rebalance-on-membership-change and epoch fencing their testable surface.

Threading: each replica stays a single-owner object. ``step()`` drives all
replicas in deterministic lockstep on the caller's thread (tests; the
pump-all-then-schedule-all order is what injects overlap in race mode —
every replica sees the same store instant before any of them binds).
``run_threads()`` gives each replica its own loop thread for wall-clock
measurement (the perf runner's ``--replicas N``); replicas only share the
store, whose CAS semantics are the arbitration point either way.
"""

from __future__ import annotations

import threading
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable

from .leaderelection import (
    LeaderElector,
    StoreLeaseClient,
    default_clock,
)

PARTITION_MODES = ("hash", "race", "lease")

#: store bucket + namespace the partition leases live in
LEASE_NAMESPACE = "kube-system"
LEASE_PREFIX = "kubetpu-partition"


class StaleOwnerError(RuntimeError):
    """A bind was attempted by a replica whose partition lease is no longer
    its own (stolen, expired, or re-acquired at a later epoch): the fence
    rejects the write before it reaches the store. Classified as a bind
    conflict by the dispatcher/scheduler — forget-assumed → requeue; the
    current owner schedules the pod."""


def pod_partition(key: str, partitions: int) -> int:
    """Stable partition of a pod key (``ns/name``): crc32, not ``hash()``
    — Python's string hash is salted per process, and replicas in
    DIFFERENT processes must agree on ownership."""
    return zlib.crc32(key.encode("utf-8")) % max(partitions, 1)


class PartitionLeaseManager:
    """K renewable partition leases for one replica, built on the singleton
    ``LeaderElector`` primitive (one elector per partition — the K-of-N
    generalization the ISSUE names).

    ``tick(target)`` renews owned partitions, acquires unheld/expired ones
    while under ``target`` (the federation's fair share for this replica),
    and releases the excess above it (released leases are immediately
    acquirable — the bounded handover window on scale-out). Epochs: at
    every acquisition the lease record's ``leader_transitions`` is
    captured; ``check_fence`` re-reads the SHARED lease record and rejects
    when the holder or epoch moved — a stale owner cannot bind even if its
    local state still says "mine"."""

    def __init__(
        self,
        client: Any,
        identity: str,
        partitions: int,
        clock: Callable[[], float] = default_clock,
        lease_duration_s: float = 2.0,
        renew_deadline_s: float = 1.5,
        retry_period_s: float = 0.05,
        start: int = 0,
        namespace: str = LEASE_NAMESPACE,
        prefix: str = LEASE_PREFIX,
    ) -> None:
        self.client = client
        self.identity = identity
        self.partitions = partitions
        self.namespace = namespace
        self.prefix = prefix
        # acquisition scan starts at a per-replica offset so N fresh
        # replicas fan out over the keyspace instead of all CASing lease 0
        self._start = start % max(partitions, 1)
        self.electors = [
            LeaderElector(
                client=client,
                identity=identity,
                name=f"{prefix}-{p}",
                namespace=namespace,
                lease_duration_s=lease_duration_s,
                renew_deadline_s=renew_deadline_s,
                retry_period_s=retry_period_s,
                clock=clock,
            )
            for p in range(partitions)
        ]
        # partition -> fencing epoch captured at acquisition
        self._owned_epoch: dict[int, int] = {}
        self.transitions = 0        # acquisitions + losses, for the metric

    def owned(self) -> frozenset[int]:
        return frozenset(self._owned_epoch)

    def owns(self, partition: int) -> bool:
        return partition in self._owned_epoch

    def tick(self, target: int) -> bool:
        """One renew/acquire/release round. Returns True when the owned
        set changed (the federation re-adopts pending pods then)."""
        before = frozenset(self._owned_epoch)
        # renew what we hold; a failed renew is a loss. A successful tick
        # may also be a RE-acquisition (the lease was stolen and then
        # released between our ticks — the usurp branch bumps the epoch
        # even for a released lease), so the fencing epoch is re-synced
        # from the observed record, never assumed stable
        for p in list(self._owned_epoch):
            if self.electors[p].tick():
                self._owned_epoch[p] = self.electors[p].observed_epoch()
            else:
                del self._owned_epoch[p]
        # acquire while under the fair share, scanning from our offset
        for i in range(self.partitions):
            if len(self._owned_epoch) >= target:
                break
            p = (self._start + i) % self.partitions
            if p in self._owned_epoch:
                continue
            if self.electors[p].tick():
                self._owned_epoch[p] = self.electors[p].observed_epoch()
        # release the excess (scale-out handover: a released lease is
        # acquirable immediately, no expiry wait)
        while len(self._owned_epoch) > target:
            p = max(self._owned_epoch)
            self.electors[p].release()
            del self._owned_epoch[p]
        after = frozenset(self._owned_epoch)
        if after != before:
            self.transitions += len(after ^ before)
            return True
        return False

    def check_fence(self, partition: int) -> None:
        """Raise ``StaleOwnerError`` unless the SHARED lease record for
        ``partition`` still names this replica at the epoch it captured.
        Called on the bind path — the authority is the store's record, not
        this replica's belief."""
        epoch = self._owned_epoch.get(partition)
        if epoch is None:
            raise StaleOwnerError(
                f"{self.identity} does not own partition {partition}"
            )
        record, _rv = self.client.get_lease(
            self.namespace, f"{self.prefix}-{partition}"
        )
        if record is None or record.holder_identity != self.identity:
            holder = record.holder_identity if record is not None else ""
            raise StaleOwnerError(
                f"partition {partition} lease is held by "
                f"{holder or '<nobody>'}, not {self.identity}"
            )
        if record.leader_transitions != epoch:
            raise StaleOwnerError(
                f"partition {partition} epoch moved "
                f"({epoch} -> {record.leader_transitions}): "
                f"{self.identity} was fenced"
            )

    def release_all(self) -> None:
        for p in list(self._owned_epoch):
            self.electors[p].release()
        self.transitions += len(self._owned_epoch)
        self._owned_epoch.clear()


@dataclass
class ReplicaHandle:
    """One federated scheduler replica: the scheduler, its informers, and
    (in lease mode) its partition-lease manager."""

    index: int
    replica_id: str
    sched: Any
    informers: Any
    client: Any
    store: Any
    leases: PartitionLeaseManager | None = None
    alive: bool = True
    # membership generation this replica last reconciled ownership against
    seen_membership: int = -1
    # lockstep bookkeeping: last round's informer deliveries + cycle counts
    last_moved: int = 0
    last_result: dict = field(default_factory=dict)


class SchedulerFederation:
    """See module docstring.

    ``store``: the shared store (MemStore) every replica binds through, OR
    a callable ``(replica_index) -> store`` building one connection per
    replica (RemoteStore against one apiserver — the fullstack shape).
    ``scheduler_kwargs`` are forwarded to every ``Scheduler`` (engine,
    max_batch, bulk, …); each replica additionally gets its
    ``replica_id``/``federation_mode`` stamps and the shared ``clock``.
    ``client_factory`` (optional) builds the API client from a store —
    defaults to ``StoreClient``; the perf runner injects a counting one.
    """

    def __init__(
        self,
        store: Any,
        replicas: int = 2,
        partition: str = "race",
        partitions: int | None = None,
        scheduler_kwargs: dict | None = None,
        client_factory: Callable[[Any], Any] | None = None,
        clock: Callable[[], float] = default_clock,
        lease_duration_s: float = 2.0,
        informer_bulk: bool = True,
    ) -> None:
        if partition not in PARTITION_MODES:
            raise ValueError(
                f"unknown partition mode {partition!r} "
                f"(one of {PARTITION_MODES})"
            )
        if replicas < 1:
            raise ValueError("federation needs at least one replica")
        from ..client import SchedulerInformers, StoreClient
        from .scheduler import Scheduler

        self.mode = partition
        self.clock = clock
        # lease-mode keyspace: 2 partitions per replica by default, so a
        # dead replica's load spreads over SEVERAL survivors instead of
        # doubling exactly one
        self.partitions = partitions or (
            2 * replicas if partition == "lease" else replicas
        )
        self._membership_gen = 0
        kwargs = dict(scheduler_kwargs or {})
        kwargs.setdefault("clock", clock)
        make_client = client_factory or (lambda s: StoreClient(s))
        self.handles: list[ReplicaHandle] = []
        for i in range(replicas):
            rstore = store(i) if callable(store) else store
            rid = f"r{i}"
            client = make_client(rstore)
            leases = None
            if partition == "lease":
                leases = PartitionLeaseManager(
                    StoreLeaseClient(rstore),
                    identity=rid,
                    partitions=self.partitions,
                    clock=clock,
                    lease_duration_s=lease_duration_s,
                    renew_deadline_s=0.75 * lease_duration_s,
                    start=i * self.partitions // replicas,
                )
                client = _fenced_client(client, leases, self.partitions)
            sched = Scheduler(
                client,
                replica_id=rid,
                federation_mode=partition,
                **kwargs,
            )
            sched.enable_preemption()
            handle = ReplicaHandle(
                index=i, replica_id=rid, sched=sched, informers=None,
                client=client, store=rstore, leases=leases,
            )
            handle.informers = SchedulerInformers(
                rstore, sched, bulk=informer_bulk,
                pod_filter=self._make_pod_filter(handle),
            )
            self.handles.append(handle)

    # ---------------------------------------------------------- membership
    def live(self) -> list[ReplicaHandle]:
        return [h for h in self.handles if h.alive]

    def _make_pod_filter(self, handle: ReplicaHandle):
        """The per-replica informer filter: deliver a PENDING pod only to
        its owner (assigned pods always flow — every replica's cache must
        account every node's load). Race mode owns everything."""
        if self.mode == "race":
            return None

        def owns(pod) -> bool:
            return self._owns(handle, f"{pod.namespace}/{pod.name}")

        return owns

    def _owns(self, handle: ReplicaHandle, key: str) -> bool:
        if not handle.alive:
            return False
        if self.mode == "race":
            return True
        if self.mode == "lease":
            assert handle.leases is not None
            return handle.leases.owns(pod_partition(key, self.partitions))
        # hash: rank among the LIVE replicas, so membership changes
        # rebalance by construction
        live = self.live()
        try:
            rank = live.index(handle)
        except ValueError:
            return False
        return pod_partition(key, len(live)) == rank

    def _target_share(self) -> int:
        live = len(self.live())
        if live == 0:
            return 0
        return -(-self.partitions // live)        # ceil

    def kill(self, index: int, close: bool = True) -> None:
        """Stop a replica mid-run (the replica-kill recovery scenario).
        Its partition (hash rank / owned leases) is re-absorbed by the
        survivors: immediately in hash mode (ranks recompute), after lease
        expiry in lease mode (the bounded handover window). The dead
        replica's leases are deliberately NOT released — a crash wouldn't
        release them either; recovery time includes the expiry wait.
        ``close=False`` defers the scheduler teardown (threaded mode: the
        caller joins the replica's loop thread first, then closes — a
        close racing the owner thread is not a crash we want to model)."""
        handle = self.handles[index]
        if not handle.alive:
            return
        handle.alive = False
        self._membership_gen += 1
        if close:
            try:
                handle.sched.close()
            except Exception:
                pass

    def close_replica(self, index: int) -> None:
        """Finish a ``kill(close=False)`` after its loop thread exited."""
        try:
            self.handles[index].sched.close()
        except Exception:
            pass

    def close(self) -> None:
        for h in self.handles:
            if h.alive:
                if h.leases is not None:
                    h.leases.release_all()
                h.sched.close()
                h.alive = False

    # ------------------------------------------------------------ stepping
    def start(self) -> None:
        """Initial list+watch for every replica (WaitForCacheSync)."""
        for h in self.live():
            h.informers.start()
        if self.mode == "lease":
            # settle initial ownership before the first scheduling round so
            # round 1 already has every partition owned somewhere
            for h in self.live():
                h.leases.tick(self._target_share())
            for h in self.live():
                self._reconcile_ownership(h, force=True)

    def step(self) -> dict[str, int]:
        """One deterministic lockstep round: every live replica pumps
        (same store instant — race-mode overlap is injected HERE), leases
        tick and ownership reconciles, then every replica runs one
        scheduling cycle and drains its dispatcher. Returns aggregate
        counts for the round."""
        live = self.live()
        for h in live:
            h.last_moved = h.informers.pump()
        for h in live:
            self._tick_replica(h)
        total = {"scheduled": 0, "unschedulable": 0, "moved": 0}
        for h in live:
            res = h.sched.schedule_batch()
            h.sched.dispatcher.sync()
            h.sched._drain_bind_completions()
            h.last_result = res
            total["scheduled"] += res["scheduled"]
            total["unschedulable"] += res["unschedulable"]
            total["moved"] += h.last_moved
        return total

    def _tick_replica(self, handle: ReplicaHandle) -> None:
        """Lease renewal + ownership reconciliation for one replica (runs
        on the replica's own thread in threaded mode — the scheduler stays
        single-owner)."""
        changed = False
        if handle.leases is not None:
            t0 = handle.leases.transitions
            changed = handle.leases.tick(self._target_share())
            prom = handle.sched.metrics.prom
            moved = handle.leases.transitions - t0
            if moved:
                prom.federation_lease_transitions.labels(
                    self.mode, handle.replica_id
                ).inc(moved)
            prom.federation_partitions_owned.labels(
                self.mode, handle.replica_id
            ).set(len(handle.leases.owned()))
        self._reconcile_ownership(handle, force=changed)

    def _reconcile_ownership(
        self, handle: ReplicaHandle, force: bool = False
    ) -> None:
        """After a membership or lease change, re-adopt the pending pods
        that now fall to this replica: pods its filter used to drop were
        never enqueued here, and no further informer event is coming for
        them. Lists the store's unbound pods and re-delivers the owned
        ones (``queue.add`` de-duplicates re-deliveries)."""
        if not force and handle.seen_membership == self._membership_gen:
            return
        if self.mode == "race":
            handle.seen_membership = self._membership_gen
            return
        from ..client.informers import PODS

        try:
            items, _rv = handle.store.list(PODS)
        except Exception:
            # transient list failure: do NOT mark this generation seen —
            # the next tick retries, otherwise a dead replica's backlog
            # would be skipped forever on one dropped RPC
            return
        handle.seen_membership = self._membership_gen
        for key, pod in items:
            if getattr(pod, "node_name", ""):
                continue
            if self._owns(handle, key):
                handle.sched.on_pod_add(pod)

    # ---------------------------------------------------------- convenience
    def run_until_idle(
        self,
        max_rounds: int = 1000,
        advance_clock: Callable[[float], None] | None = None,
        idle_rounds: int = 3,
    ) -> int:
        """Lockstep rounds until the whole federation is quiescent.
        ``advance_clock`` steps an injectable clock when a round made no
        progress (conflict losers sit in the error backoff; pods parked
        behind an expired lease wait for the handover window) — tests pass
        their fake clock's advance, real deployments pass None. Returns
        total pods scheduled."""
        total = 0
        idle = 0
        for _ in range(max_rounds):
            res = self.step()
            total += res["scheduled"]
            if res["scheduled"] or res["unschedulable"] or res["moved"]:
                idle = 0
                continue
            idle += 1
            if idle >= idle_rounds:
                break
            if advance_clock is not None:
                # past the max error backoff AND the lease handover window
                advance_clock(1.0)
        return total

    def run_threads(
        self, stop: threading.Event, period_s: float = 0.0
    ) -> list[threading.Thread]:
        """Wall-clock mode: one loop thread per live replica (pump → lease
        tick → cycle → drain), until ``stop`` is set. The caller owns
        progress monitoring and the stop signal (perf runner)."""
        import time as _time

        def loop(handle: ReplicaHandle) -> None:
            while not stop.is_set() and handle.alive:
                try:
                    moved = handle.informers.pump()
                    self._tick_replica(handle)
                    res = handle.sched.schedule_batch()
                    handle.sched.dispatcher.sync()
                    handle.sched._drain_bind_completions()
                except Exception:
                    if not handle.alive:
                        return      # killed mid-cycle: expected teardown
                    raise
                if not moved and not res["scheduled"]:
                    _time.sleep(period_s or 0.002)

        threads = []
        for h in self.live():
            th = threading.Thread(
                target=loop, args=(h,),
                name=f"federated-sched-{h.replica_id}", daemon=True,
            )
            th.start()
            threads.append(th)
        return threads

    # ------------------------------------------------------------- evidence
    def conflicts(self) -> int:
        """Total CAS-bind conflicts (409 losers + fenced stale-owner
        binds) across all replicas."""
        return sum(h.sched.metrics.bind_conflicts for h in self.handles)

    def bind_attempts(self) -> int:
        """Binds DISPATCHED across all replicas (``metrics.scheduled``
        counts at assume time, so a conflicted attempt and its later
        successful retry both count — that is the denominator the
        conflict rate wants)."""
        return sum(h.sched.metrics.scheduled for h in self.handles)

    def bound(self) -> int:
        """Binds that actually landed (attempts minus failed binds)."""
        return self.bind_attempts() - sum(
            h.sched.metrics.bind_errors for h in self.handles
        )

    def conflict_rate(self) -> float:
        """Conflicted bind attempts / all bind attempts (0.0 when nothing
        dispatched) — the x-axis of the conflict/throughput curve."""
        c, a = self.conflicts(), self.bind_attempts()
        return c / a if a else 0.0

    def lease_transitions(self) -> int:
        return sum(
            h.leases.transitions for h in self.handles
            if h.leases is not None
        )


class ReplicaMembership:
    """ONE process's slice of the federation — what ``SchedulerFederation``
    wires for N in-process replicas, rebuilt here for a replica that is a
    separate OS process (``kubetpu scheduler --partition hash|race|lease
    --replica-count N``, spawned by the launch supervisor).

    Cross-process membership is SUPERVISOR-driven, not gossip-driven: the
    replica count is declared at spawn, a dead replica is answered by the
    restart policy (the respawned process re-federates — hash re-adopts
    its rank's backlog through the informer's initial list, lease re-
    acquires its fair share through the shared store), and hash ranks are
    therefore STATIC (``replica_index`` of ``replica_count``), unlike the
    in-process federation's live re-ranking. Lease mode keeps its full
    dynamic behavior because the leases live in the shared store: expiry,
    fair-share rebalancing, and epoch fencing all work across processes
    exactly as they do across threads.
    """

    def __init__(
        self,
        store: Any,
        replica_id: str,
        partition: str,
        replica_count: int,
        replica_index: int | None = None,
        partitions: int | None = None,
        clock: Callable[[], float] = default_clock,
        lease_duration_s: float = 2.0,
    ) -> None:
        if partition not in PARTITION_MODES:
            raise ValueError(
                f"unknown partition mode {partition!r} "
                f"(one of {PARTITION_MODES})"
            )
        if replica_count < 1:
            raise ValueError("--partition needs --replica-count >= 1")
        if replica_index is None:
            # the launch convention: replica ids are r0..r{N-1}
            digits = "".join(c for c in replica_id if c.isdigit())
            replica_index = int(digits) if digits else 0
        if not 0 <= replica_index < replica_count:
            raise ValueError(
                f"replica index {replica_index} outside 0..{replica_count - 1}"
            )
        self.store = store
        self.replica_id = replica_id
        self.mode = partition
        self.replica_count = replica_count
        self.replica_index = replica_index
        self.partitions = partitions or (
            2 * replica_count if partition == "lease" else replica_count
        )
        self.leases: PartitionLeaseManager | None = None
        if partition == "lease":
            self.leases = PartitionLeaseManager(
                StoreLeaseClient(store),
                identity=replica_id,
                partitions=self.partitions,
                clock=clock,
                lease_duration_s=lease_duration_s,
                renew_deadline_s=0.75 * lease_duration_s,
                start=replica_index * self.partitions // replica_count,
            )

    # ----------------------------------------------------------- federation
    def _owns(self, key: str) -> bool:
        if self.mode == "race":
            return True
        if self.mode == "lease":
            assert self.leases is not None
            return self.leases.owns(pod_partition(key, self.partitions))
        return (
            pod_partition(key, self.replica_count) == self.replica_index
        )

    def pod_filter(self):
        """The per-replica informer filter (None in race mode — everyone
        sees everything and the CAS bind arbitrates)."""
        if self.mode == "race":
            return None

        def owns(pod) -> bool:
            return self._owns(f"{pod.namespace}/{pod.name}")

        return owns

    def wrap_client(self, client: Any) -> Any:
        """Lease mode's correctness backstop: every bind epoch-fenced
        against the shared lease record. Hash/race pass through (the
        strict CAS bind is their arbitration)."""
        if self.leases is None:
            return client
        return _fenced_client(client, self.leases, self.partitions)

    def _target_share(self) -> int:
        return -(-self.partitions // self.replica_count)        # ceil

    def tick(self, sched: Any) -> None:
        """One membership round, called from the scheduler's loop: renew/
        acquire/release leases at the declared fair share and — when the
        owned set changed — re-adopt the pending pods that now fall to
        this replica (their informer events were filtered away while a
        previous owner held them; ``queue.add`` dedupes re-deliveries).
        Hash mode is static: the initial informer list already delivered
        this rank's backlog, including after a supervisor respawn."""
        if self.leases is None:
            return
        t0 = self.leases.transitions
        changed = self.leases.tick(self._target_share())
        prom = sched.metrics.prom
        moved = self.leases.transitions - t0
        if moved:
            # same accounting as SchedulerFederation._tick_replica — the
            # mp handover evidence reads this counter off /metrics
            prom.federation_lease_transitions.labels(
                self.mode, self.replica_id
            ).inc(moved)
        prom.federation_partitions_owned.labels(
            self.mode, self.replica_id
        ).set(len(self.leases.owned()))
        if not changed:
            return
        from ..client.informers import PODS

        try:
            items, _rv = self.store.list(PODS)
        except Exception:
            return          # transient: the next tick retries
        for key, pod in items:
            if getattr(pod, "node_name", ""):
                continue
            if self._owns(key):
                sched.on_pod_add(pod)

    def release(self) -> None:
        if self.leases is not None:
            self.leases.release_all()


def _fenced_client(client: Any, leases: PartitionLeaseManager,
                   partitions: int):
    """Wrap a store client so every bind is epoch-fenced against the
    partition lease (lease mode's correctness backstop): the fence check
    happens at the dispatcher's API phase, after Reserve/Permit, exactly
    where the reference's 409 surfaces. Non-bind verbs pass through."""

    class _FencedClient:
        def __init__(self) -> None:
            self._inner = client

        def __getattr__(self, name: str):
            return getattr(self._inner, name)

        def bind(self, pod, node_name) -> None:
            leases.check_fence(
                pod_partition(f"{pod.namespace}/{pod.name}", partitions)
            )
            self._inner.bind(pod, node_name)

        def bulk_bind(self, pairs):
            """Fence per-op so one stale partition fails only ITS binds:
            fenced-out ops get their StaleOwnerError positionally, the
            rest ride the inner bulk verb unchanged. The fence verdict is
            cached per PARTITION within the batch — the answer is
            identical for every pod sharing one, and the uncached version
            would pay one lease read (an RPC in fullstack mode) per pod,
            undoing the one-RPC-per-cycle bulk bind path."""
            errs: list = [None] * len(pairs)
            ok_idx: list[int] = []
            ok_pairs: list = []
            verdicts: dict[int, StaleOwnerError | None] = {}
            for i, (pod, node_name) in enumerate(pairs):
                p = pod_partition(
                    f"{pod.namespace}/{pod.name}", partitions
                )
                if p not in verdicts:
                    try:
                        leases.check_fence(p)
                        verdicts[p] = None
                    except StaleOwnerError as e:
                        verdicts[p] = e
                if verdicts[p] is not None:
                    errs[i] = verdicts[p]
                    continue
                ok_idx.append(i)
                ok_pairs.append((pod, node_name))
            if ok_pairs:
                for i, err in zip(ok_idx, self._inner.bulk_bind(ok_pairs)):
                    errs[i] = err
            return errs

    return _FencedClient()
