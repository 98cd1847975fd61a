"""Admission + per-kind validation — the apiserver's write-path gate.

Reference: a write is decode → admission (mutating → validating chain) →
strategy validation → storage (`DefaultBuildHandlerChain` +
``registerResourceHandlers`` feeding the generic registry Store, whose
``Create``/``Update`` run the per-resource strategy —
staging/src/k8s.io/apiserver/pkg/registry/generic/registry/store.go:514;
strategies under the reference's ``pkg/registry/<group>/<kind>/strategy.go``
with validation in ``pkg/apis/<group>/validation``). Here:

- ``Registry.admit(kind, key, obj, old, verb)`` runs the MUTATING hooks
  (each may return a replacement object — the MutatingAdmissionWebhook /
  defaulting seam), then the kind's validation strategy (invalid object →
  ``ValidationError`` → HTTP 422, the reference's Unprocessable Entity for
  field validation failures), then the VALIDATING hooks (policy veto →
  ``AdmissionDenied`` → HTTP 403, the ValidatingAdmissionWebhook shape).
- Strategies are per-KIND functions over the typed envelope; the default
  registry covers every bucket the framework serves, with the reference's
  load-bearing field rules (a name is required and must agree with the
  URL key; resource quantities are non-negative; replicas/parallelism
  bounds; maxSurge+maxUnavailable not both zero; PDB minAvailable XOR
  maxUnavailable; topology-spread maxSkew ≥ 1 — pkg/apis/core/validation,
  pkg/apis/apps/validation, pkg/apis/policy/validation).

The in-process ``MemStore`` API deliberately bypasses this (that path is
the reference's "write to etcd directly"); everything arriving over REST —
every separate-process component — is gated.
"""

from __future__ import annotations

from contextlib import ExitStack, contextmanager
from typing import Any, Callable, Iterable, Sequence

from ..api import types as t

POD_PHASES = {"", "Pending", "Running", "Succeeded", "Failed", "Unknown"}


class ValidationError(ValueError):
    """Strategy validation failure → 422 Unprocessable Entity."""

    status = 422

    def __init__(self, kind: str, key: str, errors: list[str]) -> None:
        self.errors = errors
        super().__init__(f"{kind}/{key} invalid: " + "; ".join(errors))


class AdmissionDenied(Exception):
    """Validating-hook veto → 403 Forbidden (admission webhook deny)."""

    status = 403


def _name_key_agree(obj: Any, key: str, errs: list[str]) -> None:
    name = getattr(obj, "name", None)
    if name is not None:
        if not name:
            errs.append("metadata.name is required")
            return
        namespace = getattr(obj, "namespace", None)
        natural = f"{namespace}/{name}" if namespace is not None else name
        if key != natural:
            errs.append(
                f"the name in the URL ({key!r}) does not match the "
                f"object ({natural!r})"
            )


def _non_negative(pairs: Iterable[tuple[str, int]], what: str,
                  errs: list[str]) -> None:
    for k, v in pairs:
        if v < 0:
            errs.append(f"{what}[{k}]: must be non-negative, got {v}")


def validate_pod(pod: t.Pod, errs: list[str]) -> None:
    _non_negative(pod.requests, "spec.requests", errs)
    if pod.phase not in POD_PHASES:
        errs.append(f"status.phase: unknown phase {pod.phase!r}")
    for c in pod.topology_spread_constraints:
        if c.max_skew < 1:
            errs.append("topologySpreadConstraints.maxSkew: must be >= 1")
        if not c.topology_key:
            errs.append("topologySpreadConstraints.topologyKey is required")
    for port in pod.ports:
        if not (0 < port.host_port <= 65535):
            errs.append(f"hostPort {port.host_port}: out of range")
    if pod.priority < -(2**31) or pod.priority >= 2**31:
        errs.append("spec.priority: out of int32 range")


def validate_node(node: t.Node, errs: list[str]) -> None:
    _non_negative(node.allocatable, "status.allocatable", errs)


def _validate_workload(obj: Any, errs: list[str]) -> None:
    if getattr(obj, "replicas", 0) < 0:
        errs.append("spec.replicas: must be non-negative")
    sel = getattr(obj, "selector", None)
    tpl = getattr(obj, "template", None)
    if sel is not None and tpl is not None:
        from ..api.selectors import label_selector_matches

        if not label_selector_matches(sel, tpl.labels_dict()):
            # apps validation: template labels must satisfy the selector,
            # or the controller could never claim its own pods
            errs.append("spec.template.metadata.labels: must match selector")


def validate_deployment(dep: t.Deployment, errs: list[str]) -> None:
    _validate_workload(dep, errs)
    if dep.strategy not in ("RollingUpdate", "Recreate"):
        errs.append(f"spec.strategy: unknown strategy {dep.strategy!r}")
    if dep.max_surge < 0 or dep.max_unavailable < 0:
        errs.append("maxSurge/maxUnavailable: must be non-negative")
    elif (dep.strategy == "RollingUpdate"
          and dep.max_surge == 0 and dep.max_unavailable == 0):
        errs.append("maxSurge and maxUnavailable may not both be zero")


def validate_job(job: t.Job, errs: list[str]) -> None:
    if job.completions < 0:
        errs.append("spec.completions: must be non-negative")
    if job.parallelism < 0:
        errs.append("spec.parallelism: must be non-negative")
    if job.backoff_limit < 0:
        errs.append("spec.backoffLimit: must be non-negative")
    if job.succeeded < 0 or job.failed < 0:
        errs.append("status counts must be non-negative")


def validate_statefulset(ss: t.StatefulSet, errs: list[str]) -> None:
    _validate_workload(ss, errs)
    if ss.pod_management_policy not in ("OrderedReady", "Parallel"):
        errs.append(
            f"spec.podManagementPolicy: unknown {ss.pod_management_policy!r}"
        )


def validate_pdb(pdb: t.PodDisruptionBudget, errs: list[str]) -> None:
    if pdb.min_available is not None and pdb.max_unavailable is not None:
        errs.append("minAvailable and maxUnavailable are mutually exclusive")
    for v in (pdb.min_available, pdb.max_unavailable):
        if v is not None and v < 0:
            errs.append("PDB thresholds must be non-negative")


def validate_resource_claim(claim: t.ResourceClaim, errs: list[str]) -> None:
    for req in claim.requests:
        if not req.name:
            errs.append("spec.devices.requests[].name is required")
        if req.count < 1:
            errs.append(
                f"request {req.name!r}: count must be >= 1, got {req.count}"
            )


def validate_resource_slice(sl: t.ResourceSlice, errs: list[str]) -> None:
    if not sl.driver:
        errs.append("spec.driver is required")
    modes = sum((bool(sl.node_name), sl.all_nodes, sl.node_selector is not None))
    if modes > 1:
        errs.append(
            "nodeName / allNodes / nodeSelector are mutually exclusive"
        )


_VALIDATORS: dict[type, Callable[[Any, list[str]], None]] = {
    t.Pod: validate_pod,
    t.Node: validate_node,
    t.ReplicaSet: _validate_workload,
    t.Deployment: validate_deployment,
    t.Job: validate_job,
    t.StatefulSet: validate_statefulset,
    t.DaemonSet: _validate_workload,
    t.PodDisruptionBudget: validate_pdb,
    t.ResourceClaim: validate_resource_claim,
    t.ResourceSlice: validate_resource_slice,
}


#: (fn, kinds or None for every kind, ``engages`` predicate or None)
_Registered = tuple[Callable, "set[str] | None", "Callable | None"]


class Registry:
    """The admission chain + strategy dispatcher for one server."""

    def __init__(self) -> None:
        # hook: fn(kind, key, obj, old) — mutating returns obj|None,
        # validating raises AdmissionDenied; ``kinds=None`` = every kind.
        # The third slot is the hook's ``engages`` predicate (None = it
        # engages for every write of its kinds), see has_dynamic_admission
        self._mutating: list[_Registered] = []
        self._validating: list[_Registered] = []
        # locker: fn(kind, key, obj, verb) -> context manager | None; the
        # apiserver holds every matching lock across admit AND the storage
        # write, so a usage-counting validator (quota) sees check+create as
        # one atomic step (the reference's locked quota reservation)
        self._lockers: list[_Registered] = []

    def add_mutating_hook(
        self, fn: Callable, kinds: Iterable[str] | None = None,
        engages: Callable[[str, Sequence], bool] | None = None,
    ) -> None:
        self._mutating.append((fn, set(kinds) if kinds else None, engages))

    def add_validating_hook(
        self, fn: Callable, kinds: Iterable[str] | None = None,
        engages: Callable[[str, Sequence], bool] | None = None,
    ) -> None:
        """``engages(kind, objs)`` is the hook's own promise about a batch
        of objects it would be handed: False means it would pass every one
        of them untouched, so the bulk verb may skip it for that batch.
        A hook registered without one engages for every write. The verb
        asks with the store lock held: a predicate may read the store, and
        takes no lock that a write-lock provider holds across a write."""
        self._validating.append((fn, set(kinds) if kinds else None, engages))

    def add_write_lock(
        self, fn: Callable, kinds: Iterable[str] | None = None,
        engages: Callable[[str, Sequence], bool] | None = None,
    ) -> None:
        """Register a write-lock provider: ``fn(kind, key, obj, verb)``
        returns a context manager (a ``threading.Lock`` works) scoping the
        write, or None to pass. ``engages`` as for a hook: False means no
        write of the batch needs the lock."""
        self._lockers.append((fn, set(kinds) if kinds else None, engages))

    def has_dynamic_admission(self, kind: str, objs: Sequence = ()) -> bool:
        """True when any mutating/validating hook or write-lock provider
        matching ``kind`` ENGAGES for the batch ``objs`` (the objects of a
        bulk request's create/update ops). One registered without an
        ``engages`` predicate always does, for the empty batch too, which
        is how a webhook keeps the sequential chain; one registered with a
        predicate is asked, and what it answers it reads from the store as
        it stands (quota admission: does a namespace of the batch hold a
        quota), so the answer moves with the store and needs no restart.

        The bulk verb's one-lock storage pass is only sound for a batch NO
        hook engages for (a usage-counting validator like quota must see
        each admit+write as one atomic step, and an update hook's ``old``
        must reflect earlier ops in the same batch); an engaged batch runs
        whole through the sequential single-verb chain instead. The verb
        asks under the store lock that applies the batch (``MemStore.bulk``
        ``guard``), so the answer cannot go stale before the first write."""
        # a predicate that hook and lock share (quota's) is asked once
        passed: list[Callable] = []
        for _fn, kinds, engages in (
            *self._mutating, *self._validating, *self._lockers,
        ):
            if kinds is None or kind in kinds:
                if engages is None:
                    return True
                if objs and engages not in passed:
                    if engages(kind, objs):
                        return True
                    passed.append(engages)
        return False

    @contextmanager
    def locked(self, kind: str, key: str, obj: Any, verb: str = "create"):
        """Every matching write lock held, in registration order, for the
        duration of the admit + store write."""
        with ExitStack() as stack:
            for fn, kinds, _engages in self._lockers:
                if kinds is None or kind in kinds:
                    cm = fn(kind, key, obj, verb)
                    if cm is not None:
                        stack.enter_context(cm)
            yield

    def admit(
        self, kind: str, key: str, obj: Any, old: Any = None,
        verb: str = "create",
    ) -> Any:
        """Mutate → validate strategy → validating hooks. Returns the
        (possibly mutated) object to store, or raises."""
        for fn, kinds, _engages in self._mutating:
            if kinds is None or kind in kinds:
                replacement = fn(kind, key, obj, old)
                if replacement is not None:
                    obj = replacement
        self.validate(kind, key, obj)
        for fn, kinds, _engages in self._validating:
            if kinds is None or kind in kinds:
                fn(kind, key, obj, old)
        return obj

    def validate(self, kind: str, key: str, obj: Any) -> None:
        """The kind's validation strategy alone (``admit`` less its hooks):
        what the bulk verb runs per op on its one-lock pass, where no hook
        engages."""
        errs: list[str] = []
        _name_key_agree(obj, key, errs)
        validator = _VALIDATORS.get(type(obj))
        if validator is not None:
            validator(obj, errs)
        if errs:
            raise ValidationError(kind, key, errs)
