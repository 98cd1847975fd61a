"""The scheduler's informer bundle + store-backed API client.

``addAllEventHandlers`` (pkg/scheduler/eventhandlers.go:455) registers the
scheduler's informer callbacks for every resource it watches; this module is
that wiring against the framework's own storage layer: one Reflector +
SharedInformer per resource kind, deliveries bound to the scheduler's
``on_*`` seam. ``StoreClient`` closes the loop the other way — the
dispatcher's bind/status/claim writes land in the store, whose watch events
flow back through the informers (level-triggered reconciliation, the same
all-state-through-the-API-server shape as the reference; SURVEY §1).

Pump-driven: ``pump()`` steps every reflector once; callers interleave it
with ``schedule_batch`` (the informer goroutines folded into the loop). The
pump times itself on the scheduler's phase clock (``tracing.PhaseClock``):
``pump_rpc`` while blocked on the watch poll, ``pump_apply`` while
delivering. Numbers go up to the scheduler; its tracer does not come down.
"""

from __future__ import annotations

from typing import Any

from ..api import types as t
from ..store.memstore import MemStore
from .reflector import FuncHandler, Reflector, SharedInformer

# store bucket names (the GVR path segments)
NODES = "nodes"
PODS = "pods"
RESOURCE_CLAIMS = "resourceclaims"
RESOURCE_SLICES = "resourceslices"
DEVICE_CLASSES = "deviceclasses"
PERSISTENT_VOLUMES = "persistentvolumes"
PERSISTENT_VOLUME_CLAIMS = "persistentvolumeclaims"
STORAGE_CLASSES = "storageclasses"
SERVICES = "services"
NAMESPACES = "namespaces"
POD_GROUPS = "podgroups"
PDBS = "poddisruptionbudgets"
LEASES = "leases"


def pod_store_key(pod: t.Pod) -> str:
    return f"{pod.namespace}/{pod.name}"


class StoreClient:
    """The API client the scheduler's dispatcher writes through, backed by
    the store — binds/status/claims become versioned writes whose watch
    events the informers deliver back."""

    def __init__(self, store: MemStore) -> None:
        self.store = store
        self.status_patches: list[tuple[str, str]] = []
        # False once the store answered a bind op 400: a server older than
        # the op, which binds nothing by that answer; the binds go as a get
        # and a CAS update from then on
        self._bind_op = True

    def bind(self, pod: t.Pod, node_name: str) -> None:
        """POST pods/<name>/binding: a one-op batch of the ``bind`` op
        where the store has the bulk verb, so there is one bind semantics;
        a get and a CAS update where it has not."""
        from ..store.memstore import bind_refusal, bulk_result_error

        if hasattr(self.store, "bulk"):
            (err,) = self._bind_batch([(pod, node_name)])
            if err is not None:
                raise err
            return
        key = pod_store_key(pod)
        current, rv = self.store.get(PODS, key)
        refused = bind_refusal(key, current, pod.uid)
        if refused is not None:
            raise bulk_result_error(refused)
        self.store.update(
            PODS, key, current.with_node(node_name), expect_rv=rv
        )

    def bulk_bind(
        self, pairs: "list[tuple[t.Pod, str]]"
    ) -> "list[Exception | None]":
        """One scheduling cycle's binds as ONE bulk round trip of ``bind``
        ops (key, uid, node: no pod crosses the wire either way) — the
        dispatcher's micro-batch path. Positional results: None for a
        landed bind, else the exception the single-op ``bind`` raises for
        that pod (the dispatcher falls back to per-call execution for
        those, so the bind-error → forget-assumed → requeue path is
        unchanged pod for pod)."""
        if not hasattr(self.store, "bulk"):
            raise NotImplementedError("store has no bulk verb")
        return self._bind_batch(pairs)

    def _bind_batch(self, pairs) -> "list[Exception | None]":
        from ..store.memstore import bulk_result_error

        if self._bind_op:
            res = self.store.bulk(PODS, [
                {"op": "bind", "key": pod_store_key(pod), "uid": pod.uid,
                 "node": node_name}
                for pod, node_name in pairs
            ])
            if not res or any(r.get("status") != 400 for r in res):
                return [bulk_result_error(r) for r in res]
            self._bind_op = False
        return self._bind_by_update(pairs)

    def _bind_by_update(self, pairs) -> "list[Exception | None]":
        """The binds as a bulk get and a bulk CAS update: two round trips,
        only against a store that does not know the ``bind`` op."""
        from ..store.memstore import bind_refusal, bulk_result_error

        keys = [pod_store_key(pod) for pod, _ in pairs]
        gets = self.store.bulk(PODS, [{"op": "get", "key": k} for k in keys])
        errs: "list[Exception | None]" = [None] * len(pairs)
        upd_idx: list[int] = []
        upd_ops: list[dict] = []
        for i, ((pod, node_name), res) in enumerate(zip(pairs, gets)):
            current = res.get("object")
            refused = bind_refusal(keys[i], current, pod.uid)
            if refused is not None:
                errs[i] = bulk_result_error(refused)
                continue
            upd_idx.append(i)
            upd_ops.append({
                "op": "update", "key": keys[i],
                "object": current.with_node(node_name),
                "expect_rv": res["resourceVersion"],
            })
        if upd_ops:
            for i, res in zip(upd_idx, self.store.bulk(PODS, upd_ops)):
                errs[i] = bulk_result_error(res)
        return errs

    def patch_status(self, pod: t.Pod, reason: str, message: str = "") -> None:
        # PodScheduled=False condition patch; conditions aren't part of the
        # scheduling envelope, so record without a store write
        self.status_patches.append((pod_store_key(pod), reason))

    def bulk_status_patch(
        self, items: "list[tuple[t.Pod, str, str]]"
    ) -> "list[Exception | None]":
        for pod, reason, _message in items:
            self.status_patches.append((pod_store_key(pod), reason))
        return [None] * len(items)

    def delete_pod(self, pod: t.Pod, reason: str = "") -> None:
        try:
            self.store.delete(PODS, pod_store_key(pod))
        except KeyError:
            pass  # victim already gone

    def bulk_delete_victim(
        self, items: "list[tuple[t.Pod, str]]"
    ) -> "list[Exception | None]":
        """Preemption victims deleted in one bulk round trip; a 404 is a
        victim already gone — the single-op path's pass."""
        from ..store.memstore import bulk_result_error

        store = self.store
        if not hasattr(store, "bulk"):
            raise NotImplementedError("store has no bulk verb")
        res = store.bulk(PODS, [
            {"op": "delete", "key": pod_store_key(pod)} for pod, _ in items
        ])
        return [
            None if (r.get("status") == 404) else bulk_result_error(r)
            for r in res
        ]

    def nominate(self, pod: t.Pod, node_name: str) -> None:
        # status.nominatedNodeName patch — nominations live in the
        # scheduler's nominator; the write is informational here
        pass

    def update_claim_status(self, claim: t.ResourceClaim) -> None:
        # the scheduler owns only the claim's STATUS (allocation +
        # reservedFor, bindClaim's patch) — merge it into the LIVE object so
        # a concurrent spec change is never clobbered, CAS so the write is
        # atomic, and skip a deleted claim instead of resurrecting it.
        # Conflicts past the retry budget surface (PreBind fails the bind
        # loudly rather than dropping the allocation record).
        import dataclasses

        from ..store.memstore import ConflictError

        last: Exception | None = None
        for _ in range(5):
            current, rv = self.store.get(RESOURCE_CLAIMS, claim.key)
            if current is None:
                return
            merged = dataclasses.replace(
                current,
                allocation=claim.allocation,
                reserved_for=claim.reserved_for,
            )
            try:
                self.store.update(
                    RESOURCE_CLAIMS, claim.key, merged, expect_rv=rv
                )
                return
            except ConflictError as e:
                last = e
        raise RuntimeError(
            f"claim status write for {claim.key} kept conflicting: {last}"
        )


class SchedulerInformers:
    """One informer per watched kind, bound to a Scheduler's handlers.

    ``bulk`` (default on, effective only when the store exposes
    ``watch_bulk`` — RemoteStore): ``pump()`` drains EVERY kind's watch
    cursor in one batched round trip instead of one poll per kind, each
    kind's frame delivered to its informer under a single lock acquisition.
    The poll asks for bind deltas: a pods bind op's event comes as (key,
    uid, node) and the pods informer rebuilds the pod from the one it
    holds (``SharedInformer._apply_batch``; a delta it cannot rebuild
    relists the kind). Deliveries are event-for-event identical to
    per-kind polling — the ``--bulk off`` escape hatch restores the
    per-kind path.

    ``pod_filter`` (scheduler federation's per-replica filtered pump,
    sched.federation): a predicate consulted for PENDING pods only — a
    pending pod another replica owns is dropped at delivery time, before
    it can enter this scheduler's queue. ASSIGNED pods and deletes always
    flow (every replica's cache must account every node's load, and a
    bound-elsewhere echo must still evict the loser's queue entry). The
    predicate reads live ownership state, so a membership rebalance
    changes routing without informer surgery — the federation re-delivers
    the newly-owned backlog itself."""

    def __init__(
        self, store: MemStore, sched: Any, bulk: bool = True,
        pod_filter: "Any | None" = None,
    ) -> None:
        from ..tracing import PhaseClock

        self.store = store
        self.sched = sched
        # the scheduler's own clock; a stand-in scheduler gets a private one
        self._clock = getattr(sched, "loop_clock", None) or PhaseClock()
        self._bulk = bulk and hasattr(store, "watch_bulk")
        self._reflectors: list[Reflector] = []
        s = sched
        on_pod_add: Any = s.on_pod_add
        on_pod_update: Any = lambda old, new: s.on_pod_update(old, new)
        if pod_filter is not None:
            def on_pod_add(pod, _raw=s.on_pod_add):
                if pod.node_name or pod_filter(pod):
                    _raw(pod)

            def on_pod_update(old, new, _raw=s.on_pod_update):
                if new.node_name or pod_filter(new):
                    _raw(old, new)
        self._bind(NODES, s.on_node_add,
                   lambda old, new: s.on_node_update(old, new),
                   s.on_node_delete)
        self._bind(PODS, on_pod_add, on_pod_update, s.on_pod_delete)
        # slices + classes sync BEFORE claims: a pre-allocated claim
        # consumed while the device catalog is still empty would bucket
        # network-attached devices under the claim's node (see
        # DraIndex._rebucket, which also heals any remaining interleave)
        self._bind(RESOURCE_SLICES, s.on_resource_slice_add,
                   s.on_resource_slice_update, s.on_resource_slice_delete)
        self._bind(DEVICE_CLASSES, s.on_device_class_add,
                   s.on_device_class_update, s.on_device_class_delete)
        self._bind(RESOURCE_CLAIMS, s.on_resource_claim_add,
                   s.on_resource_claim_update, s.on_resource_claim_delete)
        self._bind(PERSISTENT_VOLUMES, s.on_pv_add, s.on_pv_update,
                   s.on_pv_delete)
        self._bind(PERSISTENT_VOLUME_CLAIMS, s.on_pvc_add, s.on_pvc_update,
                   s.on_pvc_delete)
        self._bind(STORAGE_CLASSES, s.on_storage_class_add,
                   s.on_storage_class_update, s.on_storage_class_delete)
        self._bind(SERVICES, s.on_service_add, s.on_service_update,
                   s.on_service_delete)
        self._bind(NAMESPACES, s.on_namespace_add,
                   lambda old, new: s.on_namespace_update(new),
                   s.on_namespace_delete)
        self._bind(POD_GROUPS, s.on_pod_group_add,
                   lambda old, new: s.on_pod_group_update(new),
                   s.on_pod_group_delete)
        self._bind(PDBS, s.on_pdb_add,
                   lambda old, new: s.on_pdb_update(new),
                   s.on_pdb_delete)

    def _bind(self, kind: str, on_add, on_update, on_delete) -> None:
        informer = SharedInformer(kind)
        informer.add_handler(FuncHandler(
            on_add=on_add, on_update=on_update, on_delete=on_delete,
        ))
        self._reflectors.append(Reflector(self.store, informer))

    def start(self) -> None:
        """Initial list+watch for every kind (WaitForCacheSync analog —
        after this the scheduler's cache reflects the store)."""
        for r in self._reflectors:
            r.sync()

    def pump(self) -> int:
        """Drain pending watch events into the scheduler. Returns the
        number of deliveries. With ``bulk`` on, all kinds ride one batched
        poll; any reflector the batched path cannot serve (not yet synced,
        scoped, or pull-only watcher) falls the whole pump back to
        per-kind stepping."""
        with self._clock.phase("pump_apply"):
            if self._bulk:
                pumped = self._pump_bulk()
                if pumped is not None:
                    return pumped
            total = 0
            for r in self._reflectors:
                total += r.step(polling=self._polling)
            return total

    def _polling(self):
        return self._clock.phase("pump_rpc")

    @property
    def watch_decode_s(self) -> float:
        """Seconds of ``pump_rpc`` so far that decoded ``watch_bulk``
        replies (``RemoteStore``'s decode clock); 0 over a store in this
        process, which has no wire."""
        return getattr(self.store, "watch_decode_s", 0.0)

    def _pump_bulk(self) -> int | None:
        """One batched watch poll for every reflector's cursor. None =
        ineligible (caller falls back to per-kind steps)."""
        from ..store.memstore import CompactedError

        cursors: dict[str, int] = {}
        for r in self._reflectors:
            w = r._watcher
            if w is None or not getattr(w, "bulk_pollable", False):
                return None
            cursors[r.informer.kind] = w.resource_version
        try:
            with self._polling():
                # the informers hold every pod, so a bind comes as its
                # delta and is rebuilt here, not decoded
                buckets = self.store.watch_bulk(cursors, bind_deltas=True)
        except ConnectionError:
            # transient transport failure: same retry-next-pump shape as
            # Reflector.step's
            return 0
        total = 0
        for r in self._reflectors:
            res = buckets.get(r.informer.kind)
            if res is None:
                continue
            if not isinstance(res, CompactedError):
                events, cursor = res
                r._watcher.advance(cursor)
                if r.informer._apply_batch(events):
                    total += len(events)
                    continue
            # compacted (reflector.go's too-old handling), or a bind delta
            # with no pod to rebuild: only this kind relists
            r.note_relist()
            r.sync()
            total += len(r.informer.store)
        return total

    def bind_delta_metrics_text(self) -> str:
        """Prometheus text for the bind deltas the informers took, a
        diagnostics metrics source: ``applied`` rebuilt from the pod held,
        ``relisted`` refused for want of it."""
        applied = relisted = 0
        for r in self._reflectors:
            a, rl = r.informer.bind_delta_counts()
            applied += a
            relisted += rl
        return (
            "# HELP scheduler_watch_bind_deltas_total Bind deltas of the "
            "batched watch poll, by result: applied (the pod held, its "
            "node set) or relisted (no such pod held: the kind relisted).\n"
            "# TYPE scheduler_watch_bind_deltas_total counter\n"
            f'scheduler_watch_bind_deltas_total{{result="applied"}} '
            f"{applied}\n"
            f'scheduler_watch_bind_deltas_total{{result="relisted"}} '
            f"{relisted}\n"
        )

    @property
    def synced(self) -> bool:
        return all(r.informer.synced for r in self._reflectors)


def run_scheduler_from_store(
    store: MemStore, sched: Any, max_cycles: int = 10000
) -> int:
    """Convenience loop: informers → batch cycles → dispatcher writes →
    informer echoes, until quiescent. Returns pods scheduled."""
    informers = SchedulerInformers(store, sched)
    informers.start()
    total = 0
    idle = 0
    for _ in range(max_cycles):
        moved = informers.pump()
        res = sched.schedule_batch()
        sched.dispatcher.sync()
        sched._drain_bind_completions()
        total += res["scheduled"]
        if not moved and not res["scheduled"] and not res["unschedulable"]:
            idle += 1
            if idle >= 2:   # one extra spin to drain bind echoes
                break
        else:
            idle = 0
    informers.pump()
    return total
