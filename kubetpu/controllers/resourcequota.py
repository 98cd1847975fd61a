"""ResourceQuota controller + quota admission.

Reference: ``pkg/controller/resourcequota`` (resource_quota_controller.go
recomputes ``status.used`` from the live objects) and the apiserver's
quota admission (``plugin/pkg/admission/resourcequota``): a write that
would push usage past ``hard`` is rejected with 403.

Tracked resources (the scheduling envelope's slice): ``pods`` (active pod
count), ``requests.cpu`` (milli), ``requests.memory`` (bytes) — aggregated
over non-terminal pods in the quota's namespace.

``quota_admission(store)`` builds the validating hook for
``apiserver.Registry``: on pod CREATE it recomputes usage live (the
admission plugin's quota check is synchronous, not informer-lagged) and
vetoes overflow. ``install_quota_admission`` registers it, with its write
lock, so that it ENGAGES only where a quota exists (``quota_engages``).
"""

from __future__ import annotations

import dataclasses

from ..api import types as t
from ..client.informers import PODS
from ..store.memstore import ConflictError, MemStore
from .workqueue import QueueController

RESOURCE_QUOTAS = "resourcequotas"

_TERMINAL = ("Succeeded", "Failed")


def _usage(pods: list[t.Pod]) -> dict[str, int]:
    used = {"pods": 0, "requests.cpu": 0, "requests.memory": 0}
    for p in pods:
        if p.phase in _TERMINAL:
            continue
        used["pods"] += 1
        req = p.requests_dict()
        used["requests.cpu"] += req.get(t.CPU, 0)
        used["requests.memory"] += req.get(t.MEMORY, 0)
    return used


class ResourceQuotaController(QueueController):
    """Keeps every quota's ``status.used`` current: pod events dirty the
    namespace's quotas; sync recomputes from the informer cache."""

    def __init__(self, store: MemStore, clock=None) -> None:
        super().__init__(store, clock=clock)
        self._quotas = self.watch(RESOURCE_QUOTAS, lambda q: [q.key])
        self._pods = self.watch(PODS, self._pod_keys)
        self.writes = 0

    def _pod_keys(self, pod: t.Pod) -> list[str]:
        return [
            key for key, q in self._quotas.store.items()
            if q.namespace == pod.namespace
        ]

    def sync(self, key: str) -> None:
        q = self._quotas.store.get(key)
        if q is None:
            return
        used = _usage([
            p for p in self._pods.store.values()
            if p.namespace == q.namespace
        ])
        tracked = tuple(
            (name, used.get(name, 0)) for name, _ in q.hard
        )
        if tracked == q.used:
            return
        live, rv = self.store.get(RESOURCE_QUOTAS, key)
        if live is None:
            return
        try:
            self.store.update(
                RESOURCE_QUOTAS, key,
                dataclasses.replace(live, used=tracked),
                expect_rv=rv,
            )
            self.writes += 1
        except ConflictError:
            pass   # re-synced on the echo


def _hard_quotas(store: MemStore) -> list:
    """Every ResourceQuota with ``hard`` set. No quota in the store (a
    cluster that never made one) costs the O(1) ``store.count`` and no
    list: a list of ANY kind walks every object of the store under its
    lock."""
    if not store.count(RESOURCE_QUOTAS):
        return []
    return [q for _k, q in store.list(RESOURCE_QUOTAS)[0] if q.hard]


def quota_engages(store: MemStore):
    """The ``engages`` predicate of quota admission's hook and write lock
    (``Registry.has_dynamic_admission``): True when a namespace of the
    batch's objects holds a quota with ``hard`` set — read from the store
    as it stands, so a quota created later engages from its commit on and
    a deleted one lets go, with no restart. Where it answers False the
    hook would return at its first line for every object of the batch,
    and there is nothing for the lock to make atomic."""

    def engages(kind: str, objs) -> bool:
        if kind != PODS:
            return False
        quota_ns = {q.namespace for q in _hard_quotas(store)}
        return bool(quota_ns) and any(
            getattr(o, "namespace", "") in quota_ns for o in objs
        )

    return engages


def quota_admission(store: MemStore):
    """Validating-hook factory for apiserver.Registry: reject pod creates
    that would exceed any ResourceQuota in the namespace (admission is
    synchronous against the LIVE store, like the reference's quota
    evaluator — informer lag cannot let a burst slip past hard). With no
    ResourceQuota in the store it returns after one O(1) count, before any
    list; with one somewhere it lists quotas, then the pods, as ever.

    The check alone is NOT race-free: two concurrent POSTs can both read
    usage below ``hard`` and both create. Install via
    ``install_quota_admission`` so the registry also holds a per-namespace
    write lock across check+create (the reference quota admission
    serializes through its locked quota accessor the same way)."""
    from ..apiserver.admission import AdmissionDenied

    def hook(kind: str, key: str, obj, old) -> None:
        if kind != PODS or old is not None:
            return    # creates only (updates don't add pods)
        quotas = [
            q for q in _hard_quotas(store) if q.namespace == obj.namespace
        ]
        if not quotas:
            return
        pods = [
            p for _k, p in store.list(PODS)[0]
            if p.namespace == obj.namespace
        ]
        used = _usage(pods + [obj])
        for q in quotas:
            for name, limit in q.hard:
                if used.get(name, 0) > limit:
                    raise AdmissionDenied(
                        f"exceeded quota {q.name}: {name} "
                        f"{used.get(name, 0)} > hard {limit}"
                    )

    return hook


def quota_write_lock():
    """Per-namespace write-lock provider for apiserver.Registry: serializes
    the quota check with the create it gates, so concurrent POSTs in one
    namespace cannot both pass the usage check and overflow ``hard``."""
    import threading

    # one entry per namespace ever seen, retained for the process lifetime:
    # eviction cannot be made safe without reopening the race (a thread
    # holding an evicted lock no longer excludes a thread that minted a
    # fresh one), and a Lock is ~100 bytes — bounded by distinct
    # namespaces, not by request volume
    locks: dict[str, threading.Lock] = {}
    meta = threading.Lock()

    def provider(kind: str, key: str, obj, verb: str):
        if kind != PODS or verb != "create":
            return None
        ns = getattr(obj, "namespace", "") or ""
        with meta:
            lock = locks.get(ns)
            if lock is None:
                lock = locks[ns] = threading.Lock()
        return lock

    return provider


def install_quota_admission(registry, store: MemStore) -> None:
    """Wire quota enforcement onto an apiserver admission registry: the
    live-usage validating hook plus the per-namespace write lock that makes
    check+create atomic under concurrency. Both are registered with
    ``quota_engages``: a pods ``:bulk`` batch none of whose namespaces
    holds a quota takes the verb's one-lock pass, any other the sequential
    chain; the single verbs run the chain always."""
    engages = quota_engages(store)
    registry.add_validating_hook(
        quota_admission(store), kinds=(PODS,), engages=engages,
    )
    registry.add_write_lock(
        quota_write_lock(), kinds=(PODS,), engages=engages,
    )
