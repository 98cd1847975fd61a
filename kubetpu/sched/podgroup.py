"""Pod-group (gang) scheduling: state tracking + the group scheduling cycle.

Reference surfaces mirrored:

- ``PodGroupManager`` tracks member pods per group the way the reference's
  pod-group state + queue-side pending pool do
  (backend/queue/pending_pod_group_pods.go, fwk.PodGroupManager): pending
  (unscheduled) members, scheduled (assumed/assigned) members, attempt
  bookkeeping.
- Quorum gating = the GangScheduling plugin's PreEnqueue
  (plugins/gangscheduling/gangscheduling.go:130): a gang pod waits outside
  the active lane until its PodGroup object exists and
  AllPodsCount >= minCount.
- The group cycle = scheduleOnePodGroup → podGroupCycle → the placement /
  default algorithms (schedule_one_podgroup.go:43,:172,:319,:632), with the
  all-or-nothing acceptance of the GangScheduling PlacementFeasible plugin
  (gangscheduling.go:248: scheduled >= minCount, or UnschedulableAndUnresolvable
  when remaining + scheduled < minCount).

Batch-native re-shapes (documented deviations, same observable outcomes):

- The reference fans gang pods one-at-a-time through Permit, where they WAIT
  until minCount pods are assumed (gangscheduling.go Permit). Here the whole
  group is decided atomically inside one device cycle, so there is nothing
  to wait on: accepted groups go straight to binding, rejected groups roll
  back in-cycle (the revertFn stack in podGroupSchedulingDefaultAlgorithm
  becomes "never assume"). Permit-style waiting still exists for
  out-of-tree plugins via the framework's extension points.
- Topology-constrained groups run the device-parallel placement search
  (assign/placement.py) instead of the sequential simulate/revert loop.
- Unconstrained groups are BATCHED: many ready groups join one device
  assignment; per-group all-or-nothing acceptance is applied to the result.
  A rejected group's pods are never assumed, so later groups saw a
  conservatively fuller cluster — they can only have been denied nodes, not
  handed infeasible ones.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import TYPE_CHECKING

import numpy as np

from ..api import types as t
from ..queue.priority_queue import QueuedPodInfo, pod_key

if TYPE_CHECKING:
    from .scheduler import Scheduler


@dataclass
class GroupEntry:
    """Queue + state bookkeeping for one pod group (QueuedPodGroupInfo)."""

    group: t.PodGroup | None = None           # None until informer delivers it
    pending: dict[str, QueuedPodInfo] = field(default_factory=dict)  # key -> info
    scheduled: dict[str, str] = field(default_factory=dict)  # pod key -> node
    attempts: int = 0
    unschedulable_count: int = 0
    timestamp: float = 0.0
    backoff_until: float = 0.0
    parked: bool = False                      # unschedulable pool (event-woken)
    admitted: bool = False                    # gang admission latency observed

    def all_count(self) -> int:
        return len(self.pending) + len(self.scheduled)

    def min_count(self) -> int:
        g = self.group
        if g is None or g.gang is None:
            return 1
        return g.gang.min_count

    def quorum_met(self) -> bool:
        return self.group is not None and self.all_count() >= self.min_count()


class PodGroupManager:
    """Tracks pod groups and their member pods; owns the group-side queue
    states (pending-quorum / active / backoff / parked)."""

    def __init__(self, clock, initial_backoff: float = 1.0,
                 max_backoff: float = 10.0) -> None:
        self._clock = clock
        self._initial_backoff = initial_backoff
        self._max_backoff = max_backoff
        self.entries: dict[str, GroupEntry] = {}   # "ns/name" -> entry

    def _entry(self, namespace: str, name: str) -> GroupEntry:
        key = f"{namespace}/{name}"
        e = self.entries.get(key)
        if e is None:
            e = GroupEntry(timestamp=self._clock())
            self.entries[key] = e
        return e

    def entry_for_pod(self, pod: t.Pod) -> GroupEntry:
        return self._entry(pod.namespace, pod.scheduling_group)

    # ---- informer surface ----------------------------------------------

    def add_group(self, group: t.PodGroup) -> None:
        e = self._entry(group.namespace, group.name)
        e.group = group
        # the gangscheduling PodGroup/Add hint (gangscheduling.go:109): a
        # group add/update (e.g. lowered minCount) can revive a parked gang
        e.parked = False

    update_group = add_group

    def remove_group(self, group: t.PodGroup) -> None:
        e = self.entries.get(group.key)
        if e is not None:
            e.group = None

    def add_pod(self, info: QueuedPodInfo) -> None:
        """An unscheduled gang pod arrived (PreEnqueue holds it here until
        quorum). A new member also un-parks the group — the GangScheduling
        queueing hint for UnscheduledPod/Add (gangscheduling.go:95)."""
        e = self.entry_for_pod(info.pod)
        e.pending[info.key] = info
        e.parked = False

    def remove_pod(self, pod: t.Pod) -> None:
        e = self.entries.get(f"{pod.namespace}/{pod.scheduling_group}")
        if e is None:
            return
        e.pending.pop(pod_key(pod), None)
        e.scheduled.pop(pod_key(pod), None)

    def update_pod(self, pod: t.Pod) -> None:
        """Informer update for an unbound member: refresh the stored object
        (spec changes like priority/requests take effect next attempt)."""
        e = self.entry_for_pod(pod)
        info = e.pending.get(pod_key(pod))
        if info is not None:
            info.pod = pod
        else:
            self.add_pod(QueuedPodInfo(pod=pod, timestamp=self._clock()))

    def mark_scheduled(self, pod: t.Pod, node_name: str) -> None:
        e = self._entry(pod.namespace, pod.scheduling_group)
        e.pending.pop(pod_key(pod), None)
        e.scheduled[pod_key(pod)] = node_name
        e.parked = False   # AssignedPod/Add hint (gangscheduling.go:82)

    def unmark_scheduled(self, pod: t.Pod) -> None:
        """Bind failed / assumed pod forgotten: the member is pending again."""
        e = self._entry(pod.namespace, pod.scheduling_group)
        e.scheduled.pop(pod_key(pod), None)

    def requeue_member(self, info: QueuedPodInfo) -> None:
        e = self.entry_for_pod(info.pod)
        e.pending[info.key] = info

    def wake_all(self) -> None:
        """Cluster event that may free capacity (node add / assigned-pod
        delete): un-park every parked group. Conservative analog of the
        hint-driven moveAllToActiveOrBackoffQueue for group entities."""
        for e in self.entries.values():
            e.parked = False

    # ---- queue-side ------------------------------------------------------

    def _backoff_duration(self, e: GroupEntry) -> float:
        """Group-level backoff caps at plain max_backoff. The reference's
        sqrt(entity_size) cap scaling (backoff_queue.go:247) applies to the
        per-pod queue's entity requeues and is kept there
        (priority_queue._backoff_duration); a sqrt-scaled cap here (316 s
        for a 1000-pod gang) would outlast every stall detector while the
        reference's own leftover flush bounds staleness at 30 s anyway."""
        if e.unschedulable_count == 0:
            return 0.0
        return min(
            self._initial_backoff * (2.0 ** (e.unschedulable_count - 1)),
            self._max_backoff,
        )

    def ready_groups(self) -> list[tuple[str, GroupEntry]]:
        """Groups with quorum met, not parked, past backoff, with pending
        pods — the pop-side of the group lane."""
        now = self._clock()
        out = []
        for key, e in self.entries.items():
            if not e.pending or e.parked or not e.quorum_met():
                continue
            if e.backoff_until > now:
                continue
            out.append((key, e))
        # PrioritySort analog at group granularity: highest member priority
        # first, then oldest
        out.sort(key=lambda kv: (
            -max((i.pod.priority for i in kv[1].pending.values()), default=0),
            kv[1].timestamp,
        ))
        return out

    def group_failed(self, e: GroupEntry) -> None:
        e.unschedulable_count += 1
        e.attempts += 1
        e.backoff_until = self._clock() + self._backoff_duration(e)
        e.parked = True

    def group_attempted(self, e: GroupEntry) -> None:
        e.attempts += 1
        e.unschedulable_count = 0
        e.backoff_until = 0.0


# --------------------------------------------------------------------------
# placement generation (TopologyPlacementGenerator analog)
# --------------------------------------------------------------------------


def _topology_labeled(sched: "Scheduler") -> bool:
    """Whether the topology axis is ACTIVE for gang routing: mode is not
    ``off`` AND at least one node carries a slice/rack label. ``auto``
    (and even ``on``) on an unlabeled cluster resolves to inactive, so
    unlabeled runs stay bit-identical with ``--topology off``."""
    if getattr(sched, "topology", "off") == "off":
        return False
    from ..state.topology import RACK_KEY, SLICE_KEY, topology_tensors

    nt = sched._prev_nt
    if nt is not None:
        return topology_tensors(nt).labeled
    for info in sched._snapshot.nodes.values():
        labels = info.node.labels_dict()
        if SLICE_KEY in labels or RACK_KEY in labels:
            return True
    return False


def generate_placements(
    sched: "Scheduler", e: GroupEntry, node_names: list[str], num_nodes: int,
    node_capacity: int,
) -> tuple[np.ndarray, list[str]] | None:
    """Candidate placements as a (D, NC) node-mask stack.

    topology_placement.go:61 GeneratePlacements: group nodes by the
    constraint key's label value; when some member pods are already
    scheduled, only their domain qualifies (getScheduledPodsTopologyDomain —
    pods split across domains is an error → no placements). Without
    topology constraints there is ONE placement spanning all nodes.
    Returns (masks, placement_names) or None when no placement exists.
    """
    group = e.group
    keys = group.topology_keys if group is not None else ()
    if not keys:
        if _topology_labeled(sched):
            from ..state.topology import SLICE_KEY

            snapshot = sched._snapshot
            slices: dict[str, list[int]] = {}
            for i, name in enumerate(node_names):
                info = snapshot.nodes.get(name)
                if info is None:
                    continue
                val = info.node.labels_dict().get(SLICE_KEY)
                if val is not None:
                    slices.setdefault(val, []).append(i)
            if slices:
                # one candidate per TPU slice (alignment-first), PLUS the
                # all-nodes fallback so a gang too large for any single
                # slice still admits; the count-then-alignment selection
                # in _placement_group_cycle prefers a single-slice fit
                # (ties on count, wins on alignment)
                ordered = sorted(slices)
                names = [f"slice:{v}" for v in ordered] + ["<all>"]
                masks = np.zeros((len(names), node_capacity), dtype=bool)
                for d, v in enumerate(ordered):
                    masks[d, slices[v]] = True
                masks[-1, :num_nodes] = True
                return masks, names
        mask = np.zeros((1, node_capacity), dtype=bool)
        mask[0, :num_nodes] = True
        return mask, ["<all>"]
    key = keys[0]   # single constraint, like the reference (maxItems=1)
    domains: dict[str, list[int]] = {}
    snapshot = sched._snapshot
    for i, name in enumerate(node_names):
        info = snapshot.nodes.get(name)
        if info is None:
            continue
        val = info.node.labels_dict().get(key)
        if val is not None:
            domains.setdefault(val, []).append(i)
    required: str | None = None
    for pk, node in e.scheduled.items():
        info = snapshot.nodes.get(node)
        val = info.node.labels_dict().get(key) if info is not None else None
        if val is None:
            return None    # scheduled pod on an unlabeled node: no domain
        if required is not None and required != val:
            return None    # members split across domains (reference errors)
        required = val
    names = sorted(domains)
    if required is not None:
        names = [d for d in names if d == required]
    if not names:
        return None
    masks = np.zeros((len(names), node_capacity), dtype=bool)
    for d, dom in enumerate(names):
        masks[d, domains[dom]] = True
    return masks, names


# --------------------------------------------------------------------------
# the group cycles (called from Scheduler.schedule_batch)
# --------------------------------------------------------------------------


def schedule_pod_groups(sched: "Scheduler", budget: int) -> dict[str, int]:
    """Run group cycles for ready groups, up to ``budget`` pods total.

    Unconstrained groups are coalesced into one multi-group device cycle;
    topology-constrained groups each run the placement search. Returns
    result counts {"scheduled": n, "unschedulable": m}.
    """
    mgr = sched.podgroups
    ready = mgr.ready_groups()
    if not ready:
        return {"scheduled": 0, "unschedulable": 0}

    # routing reads node labels, so it needs a CURRENT snapshot (the
    # group lane can run before any per-pod cycle refreshed it);
    # incremental update_snapshot makes the refresh O(Δ)
    sched._snapshot = sched.cache.update_snapshot(sched._snapshot)
    scheduled = unschedulable = 0
    plain: list[tuple[str, GroupEntry]] = []
    constrained: list[tuple[str, GroupEntry]] = []
    total = 0
    # placement search rides the TopologyAwareWorkloadScheduling gate
    # (schedule_one_podgroup.go:759: non-TAS falls back to the default
    # algorithm, which ignores topology constraints)
    tas = sched.feature_gates.enabled("TopologyAwareWorkloadScheduling")
    # the node-topology axis routes EVERY gang through the placement
    # search on labeled clusters: per-slice candidate masks give the
    # alignment-first landing + the slice-eviction preemption mode
    topo = _topology_labeled(sched)
    for key, e in ready:
        if total + len(e.pending) > budget and (plain or constrained):
            break
        total += len(e.pending)
        if (tas and e.group is not None and e.group.topology_keys) or topo:
            constrained.append((key, e))
        else:
            plain.append((key, e))

    if plain:
        # one coalesced device cycle per PROFILE (frameworkForPodGroup: all
        # members share a scheduler name; groups of different profiles are
        # different tensor programs)
        by_prof: dict[str, list[GroupEntry]] = {}
        for _, e in plain:
            first = next(iter(e.pending.values()))
            by_prof.setdefault(first.pod.scheduler_name, []).append(e)
        for pname, entries_ in by_prof.items():
            s, u = _coalesced_group_cycle(sched, entries_)
            scheduled += s
            unschedulable += u
    for _, e in constrained:
        s, u = _placement_group_cycle(sched, e)
        scheduled += s
        unschedulable += u
    return {"scheduled": scheduled, "unschedulable": unschedulable}


def _pop_members(e: GroupEntry, clock) -> list[QueuedPodInfo]:
    """Take the group's pending members for one attempt (queue-sort order).
    Clears the pending pool — failure paths re-add."""
    infos = sorted(e.pending.values(), key=lambda i: i.sort_key())
    e.pending.clear()
    now = clock()
    for i in infos:
        i.attempts += 1
        if i.initial_attempt_timestamp is None:
            i.initial_attempt_timestamp = now
    return infos


def _coalesced_group_cycle(
    sched: "Scheduler", entries: list[GroupEntry]
) -> tuple[int, int]:
    """One device assignment over the concatenated members of many
    unconstrained groups, then per-group all-or-nothing acceptance.

    Greedy parity note: the engine sees groups in queue order, exactly like
    back-to-back scheduleOnePodGroup cycles — except a REJECTED group's pods
    were visible (as in-batch assignments) to later groups' scoring. The
    rejection rolls them back (never assumed), so later groups only saw a
    fuller cluster: conservative, never over-committing.
    """
    from ..framework import runtime as rt

    import jax

    sched._snapshot = sched.cache.update_snapshot(sched._snapshot)
    groups_infos = [_pop_members(e, sched.clock) for e in entries]
    pods: list[t.Pod] = []
    spans: list[tuple[int, int]] = []
    for infos in groups_infos:
        start = len(pods)
        pods.extend(i.pod for i in infos)
        spans.append((start, len(pods)))
    profile = sched._profile_for(pods[0]) or sched.profile
    batch = rt.encode_batch(
        sched._snapshot, pods, profile,
        nominated=sched.nominator.entries(), prev_nt=sched._prev_nt,
        topology=sched.topology,
    )
    sched._adopt_node_tensors(batch.node_tensors)
    params = rt.score_params(profile, batch.resource_names)
    device_batch = sched._apply_extenders(batch, pods)
    assignments, _ = sched._assign_device(device_batch, params)
    idx = np.asarray(jax.device_get(assignments))

    scheduled = unschedulable = 0
    for e, infos, (start, end) in zip(entries, groups_infos, spans):
        rows = idx[start:end]
        sched.metrics.note_attempts(len(infos))
        fitted = int((rows >= 0).sum())
        # PlacementFeasible (gang): scheduled members + this attempt's fits
        if fitted + len(e.scheduled) >= e.min_count():
            mgr_scheduled = 0
            for k, info in enumerate(infos):
                j = int(rows[k])
                if 0 <= j < len(batch.node_names):
                    if _bind_member(sched, e, info, batch.node_names[j]):
                        mgr_scheduled += 1
                else:
                    # group admitted; this member retries after capacity
                    # changes (leftovers park with backoff, or they would
                    # re-run a full device cycle every schedule_batch)
                    e.pending[info.key] = info
            if mgr_scheduled == len(infos):
                sched.podgroups.group_attempted(e)
            else:
                sched.podgroups.group_failed(e)
            scheduled += mgr_scheduled
            unschedulable += len(infos) - mgr_scheduled
            if mgr_scheduled:
                _note_gang_admitted(sched, e)
                if sched.flight_recorder is not None:
                    sched.flight_recorder.note_gang(
                        _group_key(e, infos), "placed",
                        engine=sched.engine, placement="<coalesced>",
                        members=len(infos), need=e.min_count(),
                    )
        else:
            # all-or-nothing rollback: nothing was assumed; park the group
            for info in infos:
                e.pending[info.key] = info
            sched.podgroups.group_failed(e)
            unschedulable += len(infos)
    return scheduled, unschedulable


def _placement_group_cycle(sched: "Scheduler", e: GroupEntry) -> tuple[int, int]:
    """Placement search for one topology-constrained group: generate domain
    placements, simulate ALL of them in one vmapped device program, pick the
    best feasible one (PodGroupPodsCount score = scheduled + proposed)."""
    from ..assign.placement import placement_assign_device
    from ..framework import runtime as rt

    import jax
    import jax.numpy as jnp

    sched._snapshot = sched.cache.update_snapshot(sched._snapshot)
    infos = _pop_members(e, sched.clock)
    pods = [i.pod for i in infos]
    profile = sched._profile_for(pods[0]) or sched.profile
    batch = rt.encode_batch(
        sched._snapshot, pods, profile,
        nominated=sched.nominator.entries(), prev_nt=sched._prev_nt,
        topology=sched.topology,
    )
    sched._adopt_node_tensors(batch.node_tensors)
    gen = generate_placements(
        sched, e, batch.node_names, batch.num_nodes,
        batch.device.alloc.shape[0],
    )
    if gen is None:
        for info in infos:
            e.pending[info.key] = info
        sched.podgroups.group_failed(e)
        return 0, len(infos)
    masks, names = gen
    params = rt.score_params(profile, batch.resource_names)
    device_batch = sched._apply_extenders(batch, pods)
    assignments, counts, alignment = placement_assign_device(
        device_batch, params, jnp.asarray(masks), engine=sched.engine
    )
    counts = np.asarray(jax.device_get(counts))
    alignment = np.asarray(jax.device_get(alignment))
    assignments = np.asarray(jax.device_get(assignments))
    sched.metrics.note_attempts(len(infos))

    need = e.min_count() - len(e.scheduled)
    feasible = counts >= need
    if not feasible.any():
        if _try_gang_preemption(sched, e, infos, batch, device_batch,
                                params, need):
            return 0, len(infos)
        for info in infos:
            e.pending[info.key] = info
        sched.podgroups.group_failed(e)
        return 0, len(infos)
    # PodGroupPodsCount: maximize scheduled + proposed, then slice
    # alignment (same-slice concentration), keeping np.argmax's
    # first-best tie-break. alignment ≤ members² < 2^32 always, so one
    # int64 lexicographic key is exact.
    score = np.where(
        feasible,
        counts.astype(np.int64) * (np.int64(1) << 32)
        + alignment.astype(np.int64),
        np.int64(-1),
    )
    best = int(np.argmax(score))
    rows = assignments[best]
    scheduled = 0
    for k, info in enumerate(infos):
        j = int(rows[k])
        if 0 <= j < len(batch.node_names):
            if _bind_member(sched, e, info, batch.node_names[j]):
                scheduled += 1
        else:
            e.pending[info.key] = info
    if scheduled == len(infos):
        sched.podgroups.group_attempted(e)
    else:
        sched.podgroups.group_failed(e)   # leftovers park with backoff
    if scheduled:
        _note_gang_admitted(sched, e)
        if sched.flight_recorder is not None:
            sched.flight_recorder.note_gang(
                _group_key(e, infos), "placed", engine=sched.engine,
                placement=names[best], members=len(infos), need=need,
                alignment=int(alignment[best]),
                slices_considered=tuple(names),
                fragmentation_delta=_frag_delta(
                    batch.node_tensors, rows, len(batch.node_names)),
            )
    return scheduled, len(infos) - scheduled


def _group_key(e: GroupEntry, infos: list[QueuedPodInfo]) -> str:
    if e.group is not None:
        return e.group.key
    p = infos[0].pod
    return f"{p.namespace}/{p.scheduling_group}"


def _note_gang_admitted(sched: "Scheduler", e: GroupEntry) -> None:
    """First full admission of a group: observe the quorum→admitted
    latency ONCE. The series stays absent on gang-free runs — that
    absence keeps the sentinel's gang-admission-stall rule dormant."""
    if e.admitted:
        return
    e.admitted = True
    sched.metrics.prom.gang_admission_duration.labels(sched.engine).observe(
        max(sched.clock() - e.timestamp, 0.0)
    )


def _frag_delta(nt, rows, num_nodes: int) -> int | None:
    """How many fully-free slices this placement newly opens — the
    fragmentation cost of the landing, rendered by ``kubetpu explain``.
    None when the cluster carries no slice labels."""
    from ..state.topology import topology_tensors

    tt = topology_tensors(nt)
    if not tt.num_slices:
        return None
    sid = np.asarray(tt.slice_id)[:num_nodes]
    busy = np.zeros(tt.num_slices + 1, dtype=bool)
    pc = np.asarray(nt.pod_count)[:num_nodes]
    np.logical_or.at(busy, sid, pc > 0)
    opened: set[int] = set()
    for j in rows:
        j = int(j)
        if 0 <= j < num_nodes:
            s = int(sid[j])
            if s < tt.num_slices and not busy[s]:
                opened.add(s)
    return len(opened)


def _try_gang_preemption(
    sched: "Scheduler", e: GroupEntry, infos: list[QueuedPodInfo],
    batch, device_batch, params, need: int,
) -> bool:
    """Topology-aware gang preemption: no placement fits, so offer each
    low-priority victim GANG's slice as a contiguous candidate set and
    dry-run the preemptor's whole engine under every "that gang evicted"
    hypothesis on device (ops.preemption.dry_run_gang_preemption). A
    feasible hypothesis evicts exactly ONE victim gang — every member via
    DeleteVictimCall — and parks the preemptor until the deletes land
    (assigned-pod deletes fire wake_all, which un-parks it).

    Victim choice among feasible hypotheses: lowest victim priority,
    then fewest victim pods, then highest slice alignment of the
    resulting proposal. Returns True when victims were dispatched."""
    import jax
    import jax.numpy as jnp

    if sched._post_filter is None or device_batch.topology is None:
        return False
    from ..ops.preemption import dry_run_gang_preemption
    from ..state.topology import SLICE_KEY
    from .api_dispatcher import DeleteVictimCall

    gkey = _group_key(e, infos)
    prior = sched._preempting.get(gkey)
    if prior:
        live = {u for u in prior if sched.cache.has_pod(u)}
        if live:
            sched._preempting[gkey] = live
            return False          # earlier eviction still in flight
        sched._preempting.pop(gkey, None)

    pprio = max((i.pod.priority for i in infos), default=0)
    node_index = {name: i for i, name in enumerate(batch.node_names)}
    snapshot = sched._snapshot
    nc, r = device_batch.nodes.requested.shape
    ridx = {name: j for j, name in enumerate(batch.resource_names) if j < r}

    cands = []   # (victim_key, victim_prio, [pods], slice_val, slice_rows)
    for vkey, ve in sched.podgroups.entries.items():
        if ve is e or not ve.scheduled:
            continue
        vpods: list[t.Pod] = []
        vnodes: list[str] = []
        for pk, node in ve.scheduled.items():
            ninfo = snapshot.nodes.get(node)
            if ninfo is None:
                continue
            for p in ninfo.pods.values():
                if pod_key(p) == pk:
                    vpods.append(p)
                    vnodes.append(node)
                    break
        if not vpods:
            continue
        vprio = max(p.priority for p in vpods)
        if vprio >= pprio:
            continue              # only strictly lower-priority gangs
        slice_vals = set()
        for node in vnodes:
            ninfo = snapshot.nodes.get(node)
            val = (ninfo.node.labels_dict().get(SLICE_KEY)
                   if ninfo is not None else None)
            slice_vals.add(val)
        if len(slice_vals) != 1 or None in slice_vals:
            continue              # victims must sit on ONE labeled slice
        sval = next(iter(slice_vals))
        srows = [
            i for i, name in enumerate(batch.node_names)
            if (ni := snapshot.nodes.get(name)) is not None
            and ni.node.labels_dict().get(SLICE_KEY) == sval
        ]
        if srows:
            cands.append((vkey, vprio, vpods, sval, srows))
    if not cands:
        return False

    c = len(cands)
    masks = np.zeros((c, nc), dtype=bool)
    freed_req = np.zeros((c, nc, r), dtype=np.int64)
    freed_count = np.zeros((c, nc), dtype=np.int32)
    for ci, (_, _, vpods, _, srows) in enumerate(cands):
        masks[ci, srows] = True
        for p in vpods:
            j = node_index.get(p.node_name)
            if j is None:
                continue
            freed_count[ci, j] += 1
            for k, v in p.requests:
                col = ridx.get(k)
                if col is not None:
                    freed_req[ci, j, col] += v
    counts, alignment = dry_run_gang_preemption(
        device_batch, params, jnp.asarray(masks), jnp.asarray(freed_req),
        jnp.asarray(freed_count),
        engine="batched" if sched.engine == "batched" else "greedy",
    )
    counts = np.asarray(jax.device_get(counts))
    alignment = np.asarray(jax.device_get(alignment))

    best = None
    for ci, (vkey, vprio, vpods, sval, _) in enumerate(cands):
        if int(counts[ci]) < need:
            continue
        key = (vprio, len(vpods), -int(alignment[ci]))
        if best is None or key < best[0]:
            best = (key, ci, vkey, vpods, sval)
    if best is None:
        return False

    _, ci, vkey, vpods, sval = best
    for p in vpods:
        sched.dispatcher.add(DeleteVictimCall(p, preemptor_key=gkey))
    sched._preempting[gkey] = {p.uid for p in vpods}
    sched.metrics.prom.preemption_victims.observe(len(vpods))
    if sched.flight_recorder is not None:
        sched.flight_recorder.note_gang(
            gkey, "preempting", engine=sched.engine,
            placement=f"slice:{sval}", members=len(infos), need=need,
            alignment=int(alignment[ci]),
            slices_considered=tuple(f"slice:{v}" for _, _, _, v, _ in cands),
            victims=tuple(pod_key(p) for p in vpods), victim_group=vkey,
        )
    # not unschedulable — WAITING on the dispatched evictions: park
    # without backoff (the victims' assigned-pod deletes wake_all)
    for info in infos:
        e.pending[info.key] = info
    e.attempts += 1
    e.parked = True
    return True


def _bind_member(
    sched: "Scheduler", e: GroupEntry, info: QueuedPodInfo, node_name: str
) -> bool:
    """Assume + Reserve/Permit + async-bind one accepted member
    (prepareForBindingCycle + runBindingCycle,
    submitPodGroupAlgorithmResult success arm). Returns False when a
    Reserve/Permit plugin rejected the member — _reject_assumed's group
    branch already handed it back to the manager's pending pool."""
    e.pending.pop(info.key, None)
    e.scheduled[info.key] = node_name
    assumed = info.pod.with_node(node_name)
    sched.cache.assume_pod(assumed)
    if info.initial_attempt_timestamp is not None:
        sli = sched.clock() - info.initial_attempt_timestamp
        sched.metrics.attempt_latencies.append(sli)
        sched.metrics.prom.pod_scheduling_sli_duration.labels(
            str(info.attempts)
        ).observe(sli)
        sched.metrics.prom.pod_scheduling_attempts.observe(info.attempts)
    if not sched._begin_binding(info, assumed):
        return False
    sched.metrics.note_scheduled()
    return True
