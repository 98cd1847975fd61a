"""Framework runtime — compose filter/score kernels per profile.

The analog of ``pkg/scheduler/framework/runtime/framework.go``: the reference
runs, per pod, PreFilter → parallel per-node Filter → PreScore → parallel
per-node Score → NormalizeScore → weight multiply → sum
(``RunScorePlugins``, framework.go:1351). Here the whole batch is one tensor
program: every enabled plugin contributes a ``(P, N)`` raw score tensor, the
runtime applies each plugin's NormalizeScore rule (masked to feasible nodes —
the reference only ever scores nodes that passed Filter), multiplies by the
profile weight, and sums into the total ``(P, N)`` score used for selection.

The encoded, padded device batch is a pytree (``DeviceBatch``) so it can flow
through jit/scan/shard_map unchanged.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..api import types as t
from ..ops import filters as F
from ..ops import scores as S
from ..ops import podaffinity as PA
from ..ops import spread as SP
from ..state import podaffinity as enc_podaffinity
from ..state import spread as enc_spread
from ..state import encoder as enc
from ..state.snapshot import Snapshot
from . import config as C


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class DeviceNodeState:
    """The persistent node-state block of a scheduling problem: everything
    on the node axis that survives from cycle to cycle. In pipeline mode
    these arrays LIVE on device across cycles (``ResidentNodeState``) and
    only dirty rows are re-uploaded; a ``DeviceBatch`` composes this block
    with the per-batch pod block."""

    alloc: jnp.ndarray              # (N, R) int64
    requested: jnp.ndarray          # (N, R) int64 exact
    nonzero_requested: jnp.ndarray  # (N, R) int64 scoring view
    pod_count: jnp.ndarray          # (N,) int32
    allowed_pods: jnp.ndarray       # (N,) int32
    node_valid: jnp.ndarray         # (N,) bool


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class DeviceBatch:
    """Padded device-resident scheduling problem: P pods × N nodes × R
    resources. Padding rows/cols are masked out (``node_valid``/``pod_valid``
    False, ``static_mask`` False on pads) so kernels need no special cases.

    Split into the persistent ``nodes`` block (device-resident across cycles
    in pipeline mode) and the per-batch pod block; the node-field properties
    keep every kernel reading ``b.alloc`` etc. unchanged."""

    # persistent node-state block
    nodes: DeviceNodeState
    # pods
    requests: jnp.ndarray           # (P, R) int64 exact
    nonzero_requests: jnp.ndarray   # (P, R) int64
    pod_valid: jnp.ndarray          # (P,) bool
    # static per-(pod,node) facts from the encoder, SIGNATURE-compressed:
    # (S, N) rows for S distinct pod signatures plus a per-pod (P,) row
    # index; kernels gather rows on device (the host→device transfer and
    # host encode are O(S·N), not O(P·N) — S=1 for replicated workloads).
    # None (an empty pytree leaf) when the profile does not score that
    # plugin / no pod has a static constraint.
    static_mask: jnp.ndarray | None        # (S, N) bool
    node_affinity_raw: jnp.ndarray | None  # (S2, N) int64
    taint_prefer_raw: jnp.ndarray | None   # (S2, N) int64
    image_sum_scores: jnp.ndarray | None   # (S3, N) int64
    image_count: jnp.ndarray | None        # (P,) int32
    # NodePorts dynamic filter (interned triples, see encoder._encode_ports)
    pod_ports: jnp.ndarray          # (P, K) bool
    node_ports: jnp.ndarray         # (N, K) bool
    port_conflict: jnp.ndarray      # (K, K) bool
    # Nominator reservations (queue/nominator.py) — None when no nominations
    nominated_node: jnp.ndarray | None = None  # (G,) int32 node idx (-1 none)
    nominated_req: jnp.ndarray | None = None   # (G, R) int64
    nominated_gate: jnp.ndarray | None = None  # (P, G) bool
    nominated_ports: jnp.ndarray | None = None  # (G, K) bool port triples
    # batch index of each nomination's own pod (-1 if not in this batch):
    # once the scan assigns that pod, its nomination stops being charged
    # (the reference deletes nominations at assume, schedule_one.go:307)
    nominated_pod_idx: jnp.ndarray | None = None  # (G,) int32
    # PodTopologySpread (None when no pod has constraints)
    spread: "SpreadDevice | None" = None
    # InterPodAffinity (None when no pod carries (anti)affinity)
    podaffinity: "PodAffinityDevice | None" = None
    # per-pod signature row indices for the (S, N) arrays above (None when
    # the matching array is None)
    static_sig: jnp.ndarray | None = None  # (P,) int32 row into static_mask
    score_sig: jnp.ndarray | None = None   # (P,) int32 row into na/tt raws
    image_sig: jnp.ndarray | None = None   # (P,) int32 row into image sums
    # extender webhook verdicts for this cycle (sched/extender.py):
    # candidates may only SHRINK; scores arrive pre-weighted/scaled
    extender_mask: jnp.ndarray | None = None   # (P, N) bool
    extender_score: jnp.ndarray | None = None  # (P, N) int64
    # DynamicResources prioritized-list raw score (dynamicresources.go:1059
    # computeScore), signature-compressed like the other static raws
    dra_score_raw: jnp.ndarray | None = None   # (S5, N) int64
    dra_score_sig: jnp.ndarray | None = None   # (P,) int32
    # per-pod priority column (assign.packing admission order + objective;
    # None only for hand-built batches — finalize_batch always sets it)
    pod_priority: jnp.ndarray | None = None     # (P,) int32
    # dense node-topology coordinates (state.topology) — present only when
    # topology scoring is ACTIVE (--topology on, or auto with labeled
    # nodes). None keeps the pytree — and therefore every compiled kernel
    # and its outputs — bit-identical to a build without the feature.
    topology: "TopologyDevice | None" = None

    # node-block accessors (kernels read b.alloc etc. — the split into a
    # persistent node block is invisible to them)
    @property
    def alloc(self) -> jnp.ndarray:
        return self.nodes.alloc

    @property
    def requested(self) -> jnp.ndarray:
        return self.nodes.requested

    @property
    def nonzero_requested(self) -> jnp.ndarray:
        return self.nodes.nonzero_requested

    @property
    def pod_count(self) -> jnp.ndarray:
        return self.nodes.pod_count

    @property
    def allowed_pods(self) -> jnp.ndarray:
        return self.nodes.allowed_pods

    @property
    def node_valid(self) -> jnp.ndarray:
        return self.nodes.node_valid


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PodAffinityDevice:
    """Device-side InterPodAffinity rows (see state.podaffinity)."""

    node_domain: jnp.ndarray  # (R, N) int32
    has_key: jnp.ndarray      # (R, N) bool
    base_sums: jnp.ndarray    # (R, D) int64 — scan state init
    update: jnp.ndarray       # (P, R) int64
    fa_rows: jnp.ndarray      # (P, CA) int32
    fa_self: jnp.ndarray      # (P,) bool
    ra_rows: jnp.ndarray      # (P, CR) int32
    ea_rows: jnp.ndarray      # (P, CE) int32
    score_rows: jnp.ndarray   # (P, CS) int32
    score_vals: jnp.ndarray   # (P, CS) int64
    has_filter_work: bool = field(metadata=dict(static=True), default=False)
    has_score_work: bool = field(metadata=dict(static=True), default=False)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class SpreadDevice:
    """Device-side spread tensors (see state.spread.SpreadTensors)."""

    eligible: jnp.ndarray        # (S, N) bool
    node_domain: jnp.ndarray     # (S, N) int32
    node_count: jnp.ndarray      # (S, N) int32 — base counts (scan state init)
    has_key: jnp.ndarray         # (S, N) bool
    domain_present: jnp.ndarray  # (S, D) bool
    num_domains: jnp.ndarray     # (S,) int32
    is_hostname: jnp.ndarray     # (S,) bool
    sig_idx: jnp.ndarray         # (P, C) int32
    action: jnp.ndarray          # (P, C) int8
    max_skew: jnp.ndarray        # (P, C) int32
    min_domains: jnp.ndarray     # (P, C) int32
    self_match: jnp.ndarray      # (P, C) int32
    pod_match_sig: jnp.ndarray   # (P, S) bool
    ignored: jnp.ndarray         # (P, N) bool
    has_hard: bool = field(metadata=dict(static=True), default=False)
    has_soft: bool = field(metadata=dict(static=True), default=False)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class TopologyDevice:
    """Device-side dense topology coordinates (see state.topology).

    Domain counts are STATIC so alignment/fragmentation segment-sums get
    a fixed ``num_segments`` — a new slice label retraces, exactly like a
    spread constraint growing a domain axis."""

    slice_id: jnp.ndarray  # (N,) int32; value == num_slices ⇒ unlabeled
    rack_id: jnp.ndarray   # (N,) int32; value == num_racks ⇒ unlabeled
    num_slices: int = field(metadata=dict(static=True), default=0)
    num_racks: int = field(metadata=dict(static=True), default=0)


@dataclass
class EncodedBatch:
    """Host-side handle pairing the device pytree with name lookups."""

    device: DeviceBatch
    node_names: list[str]
    pods: list[t.Pod]
    resource_names: list[str]
    num_nodes: int                  # real (unpadded) N
    num_pods: int                   # real (unpadded) P
    # host-side references preemption/extender paths reuse (not device data)
    node_tensors: "enc.NodeTensors | None" = None
    port_vocab: object | None = None
    # actual host→device bytes this encode shipped (pod block + node delta;
    # equals the full pytree bytes when no resident node state was used)
    upload_bytes: int = 0
    # bytes of the device-resident node block backing this batch (0 when the
    # node block was a one-shot upload, i.e. no residency)
    resident_bytes: int = 0
    # the spread encode of this batch, when some pod carries or inherits a
    # topology spread constraint (else None): what the scheduler observes
    # and records as the ``encode-spread`` span
    spread_encode: "SpreadEncodeStamp | None" = None
    # the inter-pod affinity encode of this batch, when the encoder ran and
    # found a term (else None): observed and recorded as the
    # ``encode-podaffinity`` span
    podaffinity_encode: "PodAffinityEncodeStamp | None" = None


@dataclass(frozen=True)
class PodAffinityEncodeStamp:
    """``encode_pod_affinity`` as ``finalize_batch`` ran it: start and end
    on ``time.perf_counter`` (the tracer's clock), and what it built."""

    start: float
    end: float
    rows: int
    domains: int
    #: CA + CR + CE + CS, the row-id slots a pod hands the kernels: with
    #: ``rows`` and the batch's bucket, what ``ops.podaffinity.table_pays``
    #: decides from
    slots: int
    #: the batch's real pods with at least one filter slot (incoming
    #: required affinity or anti-affinity, an existing pod's anti-affinity)
    filter_pods: int
    #: the batch's real pods with at least one weighted score slot
    score_pods: int
    #: of ``filter_pods``, by the kind of slot (``affinity``,
    #: ``anti_affinity``, ``existing_anti_affinity``): the pods with at
    #: least one slot of that kind; a pod may count under several
    filter_terms: dict
    #: summed over the real pods, the nodes their existing pods'
    #: anti-affinity refuses at the batch's start counts
    existing_anti_nodes: int


@dataclass(frozen=True)
class SpreadEncodeStamp:
    """``encode_spread`` as ``finalize_batch`` ran it: start and end on
    ``time.perf_counter`` (the tracer's clock), and what it built."""

    start: float
    end: float
    signatures: int
    domains: int
    constrained_pods: int
    #: of those, the pods with at least one ScheduleAnyway constraint: the
    #: ones the soft spread score runs for
    soft_pods: int
    #: the most domains any signature counts (``domains`` also holds the
    #: values of nodes left out of counting, interned after them)
    counted_domains: int
    #: by Honor policy ("taints", "affinity"): the pods with a signature
    #: whose policy left at least one node out of counting, and the most
    #: nodes it left out for any signature
    policy_pods: dict
    excluded_nodes: dict


class StaleStaticEncode(Exception):
    """A pre-encoded StaticBatch can no longer be finalized against the
    current cluster state (e.g. an assumed pod introduced a host-port triple
    outside the batch's interned vocabulary, or the nomination set changed).
    Callers fall back to a full re-encode."""


def _node_block_nbytes(nodes: DeviceNodeState) -> int:
    return sum(
        int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(nodes)
    )


@partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
def _scatter_node_rows(
    alloc, requested, nonzero, pod_count, allowed, valid,
    idx, u_alloc, u_req, u_nz, u_pc, u_al, u_vd,
):
    """Write the dirty node rows into the device-resident block. The six
    state buffers are DONATED: each output aliases its input (same
    shape/dtype), so the update is in-place on device and the old buffers
    are invalidated — the ResidentNodeState owner is the only holder by
    contract. ``idx`` is padded to the chunk length (ONE per cluster size,
    ``ResidentNodeState._scatter_single``) with out-of-range indices;
    mode="drop" discards those writes. ``valid`` rides along so an
    incremental reshard (node add/delete within the same padded capacity)
    can flip validity rows without a full re-upload."""
    return (
        alloc.at[idx].set(u_alloc, mode="drop"),
        requested.at[idx].set(u_req, mode="drop"),
        nonzero.at[idx].set(u_nz, mode="drop"),
        pod_count.at[idx].set(u_pc, mode="drop"),
        allowed.at[idx].set(u_al, mode="drop"),
        valid.at[idx].set(u_vd, mode="drop"),
    )


def _make_routed_scatter(mesh, axis: str):
    """Build the per-shard routed twin of ``_scatter_node_rows`` for a
    sharded resident block: every input is sharded on its leading (shard)
    axis, so each device receives ONLY its own update block — the
    host→device routing happened at ``device_put`` — and the scatter body
    runs shard-local (indices are shard-local; no collectives). Donation
    aliases each state buffer in place, like the single-device scatter."""
    spec = jax.sharding.PartitionSpec(axis)

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec,) * 13, out_specs=(spec,) * 6,
    )
    def scatter(alloc, requested, nonzero, pod_count, allowed, valid,
                idx, u_alloc, u_req, u_nz, u_pc, u_al, u_vd):
        i = idx[0]
        return (
            alloc.at[i].set(u_alloc[0], mode="drop"),
            requested.at[i].set(u_req[0], mode="drop"),
            nonzero.at[i].set(u_nz[0], mode="drop"),
            pod_count.at[i].set(u_pc[0], mode="drop"),
            allowed.at[i].set(u_al[0], mode="drop"),
            valid.at[i].set(u_vd[0], mode="drop"),
        )

    return scatter


class ResidentNodeState:
    """Owner of the persistent device-resident node block (pipeline mode).

    ``refresh(nt, num_nodes)`` brings the device block up to date with the
    host ``NodeTensors``: a full upload when the block doesn't exist yet or
    is not comparable (resource axis / padded capacity change), a dirty-row
    scatter consuming ``nt.pending_device_rows`` in steady state — host→
    device traffic O(Δ rows · R), not O(N · R) — and, when the encode was
    REBUILT but kept the same shape (node add/delete within a padding
    bucket), an *incremental reshard*: the old and new NodeTensors are
    diffed row-wise and only the rows that actually changed (plus the
    validity boundary) are scattered. The scatter donates the old buffers
    (see ``_scatter_node_rows``), so after a refresh any previously
    returned DeviceNodeState is dead; callers must not hold device batches
    across a refresh (the scheduler refreshes only between completed
    cycles).

    ``mesh``: a 1-D node-axis ``jax.sharding.Mesh`` — the block then lives
    SHARDED across the mesh (each device owns ``NC / n_shards`` contiguous
    node rows), full uploads place each shard's rows on its owner only, and
    delta uploads are ROUTED: dirty rows are grouped by owning shard on the
    host, shipped as a shard-axis-sharded update block (each device
    receives only its own rows), and scattered shard-locally via shard_map
    — no collectives on the upload path."""

    def __init__(self, mesh=None, axis=None) -> None:
        self.device: DeviceNodeState | None = None
        self._nt_token: object | None = None
        self._num_nodes = -1
        self.last_upload_bytes = 0
        self.mesh = mesh
        self.axis = axis if axis is not None else "nodes"
        self._n_shards = 1
        self._shardings = None
        self._routed_scatter = None
        self._block_sharded = False
        if mesh is not None:
            from ..parallel.mesh import (
                _axis_size,
                node_axes_of,
                node_state_shardings,
            )

            if axis is None:
                self.axis, _ = node_axes_of(mesh)
            self._n_shards = _axis_size(mesh, self.axis)
            self._shardings = node_state_shardings(mesh, self.axis)
            self._routed_scatter = _make_routed_scatter(mesh, self.axis)
        # per-shard view of the LAST refresh (length n_shards): bytes each
        # shard received and how many real dirty rows were routed to it —
        # the feed for the shard-labeled transfer metrics / trace instants
        self.last_upload_bytes_per_shard: list[int] = [0] * self._n_shards
        self.last_rows_per_shard: list[int] = [0] * self._n_shards

    @property
    def nbytes(self) -> int:
        return _node_block_nbytes(self.device) if self.device is not None else 0

    @property
    def nbytes_per_shard(self) -> list[int]:
        """Per-shard resident bytes, honest about placement: an even split
        when the block really is sharded, everything on shard 0 when the
        single-device fallback placed it there."""
        total = self.nbytes
        if total and self._block_sharded and self._n_shards > 1:
            return [total // self._n_shards] * self._n_shards
        return [total] + [0] * (self._n_shards - 1)

    def _full_upload(self, nt: "enc.NodeTensors", num_nodes: int) -> DeviceNodeState:
        NC = nt.alloc.shape[0]
        node_valid = np.zeros(NC, dtype=bool)
        node_valid[:num_nodes] = True
        dev = DeviceNodeState(
            alloc=nt.alloc,
            requested=nt.requested,
            nonzero_requested=nt.nonzero_requested,
            pod_count=nt.pod_count,
            allowed_pods=nt.allowed_pods,
            node_valid=node_valid,
        )
        sharded = self._shardings is not None and NC % self._n_shards == 0
        if sharded:
            dev = jax.device_put(dev, self._shardings)
        else:
            dev = jax.device_put(dev)
        self._block_sharded = sharded
        self.device = dev
        self._nt_token = nt
        self._num_nodes = num_nodes
        nt.pending_device_rows = set()   # start delta accumulation
        self.last_upload_bytes = _node_block_nbytes(dev)
        if sharded:
            per = self.last_upload_bytes // self._n_shards
            self.last_upload_bytes_per_shard = [per] * self._n_shards
            self.last_rows_per_shard = [NC // self._n_shards] * self._n_shards
        else:
            # single-device fallback (shard count does not divide NC):
            # everything landed on one device — attribute it there, like
            # _scatter_single, so per-chip metrics never claim an even
            # split that didn't happen
            self.last_upload_bytes_per_shard = (
                [self.last_upload_bytes] + [0] * (self._n_shards - 1)
            )
            self.last_rows_per_shard = [NC] + [0] * (self._n_shards - 1)
        return dev

    def _reshard_rows(
        self, nt: "enc.NodeTensors", num_nodes: int
    ) -> "list[int] | None":
        """Dirty rows for an incremental reshard: the encode was rebuilt
        (new NodeTensors object — node add/delete/reorder) but padded
        capacity and resource axis still match the resident block. Diff the
        old tensors (what the device holds, modulo their un-flushed pending
        rows) against the new ones and return the union of value-changed
        rows, the old pending set, and the validity boundary. None = not
        comparable (full upload)."""
        old = self._nt_token
        if old is None or getattr(old, "alloc", None) is None:
            return None
        diff = nt.diff_rows(old)
        if diff is None:
            return None
        rows = set(diff)
        if old.pending_device_rows:
            # rows dirty on the OLD tensors but never shipped: the device
            # copy differs from old AND possibly from new — re-send them
            rows.update(old.pending_device_rows)
        lo, hi = sorted((self._num_nodes, num_nodes))
        rows.update(range(lo, hi))   # validity flips on the boundary
        return sorted(rows)

    def refresh(self, nt: "enc.NodeTensors", num_nodes: int) -> DeviceNodeState:
        pending = nt.pending_device_rows
        if self.device is None or self._nt_token is None:
            return self._full_upload(nt, num_nodes)
        if self._nt_token is not nt:
            # the encode was REBUILT (node add/delete/reorder): incremental
            # reshard when the block is still comparable, else full upload
            rows = self._reshard_rows(nt, num_nodes)
            if rows is None:
                return self._full_upload(nt, num_nodes)
        elif pending is None:
            # same tensors object but no delta bookkeeping: be safe
            return self._full_upload(nt, num_nodes)
        else:
            rows_set = set(pending)
            if self._num_nodes != num_nodes:
                # the append-incremental encode grew the node count IN
                # PLACE (same tensors object): the boundary rows flip
                # validity and ride the same delta scatter as any dirty
                # row — an add-wave must not force a full re-upload
                lo, hi = sorted((self._num_nodes, num_nodes))
                rows_set.update(range(lo, hi))
            if not rows_set:
                self.last_upload_bytes = 0
                self.last_upload_bytes_per_shard = [0] * self._n_shards
                self.last_rows_per_shard = [0] * self._n_shards
                return self.device
            rows = sorted(rows_set)
        nt.pending_device_rows = set()
        self._nt_token = nt
        if not rows:
            # reshard diff found nothing to ship (values identical)
            self.last_upload_bytes = 0
            self.last_upload_bytes_per_shard = [0] * self._n_shards
            self.last_rows_per_shard = [0] * self._n_shards
            self._num_nodes = num_nodes
            return self.device
        if 2 * len(rows) >= num_nodes:
            # dense update: a full contiguous upload beats a scatter
            return self._full_upload(nt, num_nodes)
        NC = nt.alloc.shape[0]
        valid_of = np.asarray(rows, dtype=np.int64) < num_nodes
        self._num_nodes = num_nodes
        if self._shardings is not None and NC % self._n_shards == 0:
            dev = self._scatter_routed(nt, rows, valid_of, NC)
            if dev is None:
                # routing would ship >= the full block (dirty rows
                # clustered in few shards → every shard bucket-padded to
                # the max): a contiguous full upload is strictly smaller
                return self._full_upload(nt, num_nodes)
        else:
            dev = self._scatter_single(nt, rows, valid_of, NC)
        self.device = dev
        return dev

    def _scatter_single(
        self, nt: "enc.NodeTensors", rows: list, valid_of: np.ndarray, NC: int
    ) -> DeviceNodeState:
        # ONE scatter program a cluster size: the dirty rows travel in
        # chunks of a fixed length (a bucket per row count would compile a
        # new program in the serving loop whenever a drain dirties a count
        # not met before; at 1024 rows a chunk is ~140 KB, beside a block
        # of megabytes). refresh() sends 2 * len(rows) >= num_nodes whole,
        # so a cluster under 2048 nodes needs one chunk
        pad = max(min(1024, NC // 2), 1)
        dev = self.device
        state = (
            dev.alloc, dev.requested, dev.nonzero_requested,
            dev.pod_count, dev.allowed_pods, dev.node_valid,
        )
        sources = (
            nt.alloc, nt.requested, nt.nonzero_requested, nt.pod_count,
            nt.allowed_pods,
        )
        self.last_upload_bytes = 0
        for lo in range(0, len(rows), pad):
            chunk = rows[lo: lo + pad]
            idx = np.full(pad, NC, dtype=np.int32)   # pad rows → dropped writes
            idx[: len(chunk)] = chunk
            updates = []
            for a in sources:
                u = np.zeros((pad,) + a.shape[1:], dtype=a.dtype)
                u[: len(chunk)] = a[chunk]
                updates.append(u)
            u_vd = np.zeros(pad, dtype=bool)
            u_vd[: len(chunk)] = valid_of[lo: lo + pad]
            updates.append(u_vd)
            state = _scatter_node_rows(
                *state, jnp.asarray(idx), *(jnp.asarray(u) for u in updates)
            )
            self.last_upload_bytes += int(
                idx.nbytes + sum(u.nbytes for u in updates)
            )
        # keep the per-shard arrays n_shards long even on the (shouldn't-
        # happen: encode pads NC to a shard multiple) unsharded fallback,
        # so shard-labeled metrics never disagree with mesh_shape
        self.last_upload_bytes_per_shard = (
            [self.last_upload_bytes] + [0] * (self._n_shards - 1)
        )
        self.last_rows_per_shard = [len(rows)] + [0] * (self._n_shards - 1)
        alloc, req, nz, pc, al, vd = state
        return DeviceNodeState(
            alloc=alloc, requested=req, nonzero_requested=nz,
            pod_count=pc, allowed_pods=al, node_valid=vd,
        )

    def _scatter_routed(
        self, nt: "enc.NodeTensors", rows: list, valid_of: np.ndarray, NC: int
    ) -> "DeviceNodeState | None":
        """Per-shard routed delta upload (see class docstring): group dirty
        rows by owning shard, pad each shard's group to a common bucket,
        ship the blocks shard-axis-sharded (each device receives only its
        rows) and scatter shard-locally with LOCAL indices. Returns None
        when the bucket-padded slot count reaches the full row count (the
        caller full-uploads instead — routing would not ship less)."""
        n_sh = self._n_shards
        rows_per_shard = NC // n_sh
        rows_arr = np.asarray(rows, dtype=np.int64)   # sorted ascending
        shard_of = rows_arr // rows_per_shard
        counts = np.bincount(shard_of, minlength=n_sh)
        bucket = enc.round_up(int(counts.max()), minimum=1)
        if n_sh * bucket >= NC:
            return None
        # rows are sorted, so each shard's rows are contiguous: the flat
        # slot of row j inside the (n_sh, bucket) block is
        # shard * bucket + (j - first index of its shard)
        starts = np.zeros(n_sh + 1, dtype=np.int64)
        starts[1:] = np.cumsum(counts)
        flat = shard_of * bucket + (np.arange(len(rows_arr)) - starts[shard_of])
        # local out-of-range sentinel → shard-local mode="drop"
        idx = np.full(n_sh * bucket, rows_per_shard, dtype=np.int32)
        idx[flat] = rows_arr - shard_of * rows_per_shard

        def blocks(a: np.ndarray) -> np.ndarray:
            u = np.zeros((n_sh * bucket,) + a.shape[1:], dtype=a.dtype)
            u[flat] = a[rows_arr]
            return u.reshape((n_sh, bucket) + a.shape[1:])

        u_alloc = blocks(nt.alloc)
        u_req = blocks(nt.requested)
        u_nz = blocks(nt.nonzero_requested)
        u_pc = blocks(nt.pod_count)
        u_al = blocks(nt.allowed_pods)
        u_vd = np.zeros(n_sh * bucket, dtype=bool)
        u_vd[flat] = valid_of
        u_vd = u_vd.reshape(n_sh, bucket)
        idx = idx.reshape(n_sh, bucket)
        from jax.sharding import NamedSharding, PartitionSpec as P

        row_sh = NamedSharding(self.mesh, P(self.axis))
        put = partial(jax.device_put, device=row_sh)
        dev = self.device
        alloc, req, nz, pc, al, vd = self._routed_scatter(
            dev.alloc, dev.requested, dev.nonzero_requested,
            dev.pod_count, dev.allowed_pods, dev.node_valid,
            put(idx), put(u_alloc), put(u_req), put(u_nz), put(u_pc),
            put(u_al), put(u_vd),
        )
        per_row_bytes = (
            u_alloc.nbytes + u_req.nbytes + u_nz.nbytes + u_pc.nbytes
            + u_al.nbytes + u_vd.nbytes + idx.nbytes
        ) // (n_sh * bucket)
        self.last_upload_bytes = per_row_bytes * n_sh * bucket
        self.last_upload_bytes_per_shard = [per_row_bytes * bucket] * n_sh
        self.last_rows_per_shard = counts.tolist()
        return DeviceNodeState(
            alloc=alloc, requested=req, nonzero_requested=nz,
            pod_count=pc, allowed_pods=al, node_valid=vd,
        )


class PackingSolverState:
    """Device-resident dual-variable block for the packing engine — the
    warm-start twin of :class:`ResidentNodeState`.

    Holds one ``(NC,)`` float32 dual-price vector λ per padded node
    capacity (the scheduler's warmup ladder touches several bucket sizes;
    each keeps its own prices). ``duals(n)`` hands the current vector to
    the solver — zeros on first sight of a capacity (a cold start,
    counted in ``resets``) — and the solver DONATES it
    (``packing_assign_device`` donate_argnums), so the caller must
    ``store(n, …)`` the returned vector back; this class is the only
    holder by contract, mirroring the resident node block's donation
    discipline. ``carries`` counts warm handoffs — the warm-start
    evidence rides ``solver_iters_per_cycle``, these counters attribute
    it.

    ``mesh``: when the scheduler runs node-axis sharded, λ is placed
    sharded along the same node axis so the solver's per-node penalty
    row stays shard-local (``bind_mesh`` — the engine is constructed
    before the scheduler resolves its mesh, so binding is late)."""

    def __init__(self, mesh=None, axis=None) -> None:
        self._lam: dict[int, jnp.ndarray] = {}
        self.resets = 0
        self.carries = 0
        self.mesh = None
        self._sharding = None
        self.bind_mesh(mesh, axis)

    def bind_mesh(self, mesh, axis=None) -> None:
        if mesh is self.mesh:
            return
        self.mesh = mesh
        self._sharding = None
        if mesh is not None:
            from ..parallel.mesh import node_axes_of

            if axis is None:
                axis, _ = node_axes_of(mesh)
            self._sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(axis)
            )
        # duals placed under the old layout are stale; drop them
        self._lam.clear()

    def duals(self, n: int) -> jnp.ndarray:
        lam = self._lam.pop(n, None)
        if lam is None:
            self.resets += 1
            lam = jnp.zeros(n, dtype=jnp.float32)
            if self._sharding is not None:
                lam = jax.device_put(lam, self._sharding)
        else:
            self.carries += 1
        return lam

    def store(self, n: int, lam: jnp.ndarray) -> None:
        self._lam[n] = lam

    def reset(self) -> None:
        """Drop every price vector (cold-start escape hatch)."""
        self._lam.clear()

    @property
    def nbytes(self) -> int:
        return sum(int(v.nbytes) for v in self._lam.values())


def _resource_weights(
    resource_names: Sequence[str], spec: Sequence[tuple[str, int]]
) -> np.ndarray:
    w = np.zeros(len(resource_names), dtype=np.int64)
    idx = {r: i for i, r in enumerate(resource_names)}
    for name, weight in spec:
        j = idx.get(name)
        if j is not None:
            w[j] = weight
    return w


def _is_scalar(resource_names: Sequence[str]) -> np.ndarray:
    return np.array(
        [r not in enc.BASE_RESOURCES for r in resource_names], dtype=bool
    )


def _image_tensors(
    nt: enc.NodeTensors, pods: Sequence[t.Pod], pad_pods: int | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """ImageLocality host encoding (imagelocality/image_locality.go:60
    sumImageScores + :118 scaledImageScore): per (pod, node) the sum over the
    pod's container images present on the node of
    ``size * numNodesWithImage // totalNumNodes``. Signature-compressed: one
    (N,) row per distinct image set, pods carry the row index."""
    N = nt.num_nodes
    NC = nt.alloc.shape[0]
    P = len(pods)
    PP = max(pad_pods or P, P)
    total = max(N, 1)
    if not any(p.images for p in pods):
        # no image anywhere → the raw score is identically zero; skip the
        # three device leaves entirely (feasible_and_scores None-guards)
        return None, None, None
    counts = np.zeros(PP, dtype=np.int32)
    sig = np.zeros(PP, dtype=np.int32)
    node_images: list[dict[str, t.ImageState]] = [
        dict(info.node.images) for info in nt.infos
    ]
    ids: dict[tuple[str, ...], int] = {(): 0}
    rows: list[np.ndarray] = [np.zeros(N, dtype=np.int64)]
    for i, p in enumerate(pods):
        counts[i] = len(p.images)
        key = p.images
        sid = ids.get(key)
        if sid is None:
            v = np.zeros(N, dtype=np.int64)
            for n_i, imgs in enumerate(node_images):
                s = 0
                for name in key:
                    st = imgs.get(name)
                    if st is not None:
                        s += st.size_bytes * st.num_nodes // total
                v[n_i] = s
            sid = len(rows)
            ids[key] = sid
            rows.append(v)
        sig[i] = sid
    sums = np.zeros((len(rows), NC), dtype=np.int64)
    for s, v in enumerate(rows):
        sums[s, :N] = v
    return sums, sig, counts


@dataclass
class StaticBatch:
    """The assume-independent half of an encoded batch (pipeline stage 1).

    Everything here is a function of the node set's static facts (labels,
    taints, images, ports vocabulary) and the pending pods — NOT of which
    pods are assigned where. The pipelined scheduler builds this while the
    previous cycle's device program runs, then ``finalize_batch`` patches in
    the assume-dependent slice (node resource rows via delta upload, spread
    counts, affinity sums, nominations, in-use ports) after that cycle's
    assumes land."""

    pods: list
    profile: "C.Profile | None"
    nt: "enc.NodeTensors"
    pb: "enc.PodBatch"
    resource_names: list[str]
    num_nodes: int
    num_pods: int
    pad_nodes: int
    pad_pods: int
    folded: frozenset
    want_na: bool
    want_tt: bool
    want_img: bool
    want_spread: bool
    want_interpod: bool
    dra_score_raw: "np.ndarray | None"
    dra_score_sig: "np.ndarray | None"
    img_sums: "np.ndarray | None"
    img_sig: "np.ndarray | None"
    img_counts: "np.ndarray | None"
    node_valid: np.ndarray
    pod_valid: np.ndarray
    nominated_key: tuple
    # True when the static encode itself already depends on assignment state
    # (folded singleton scalars, volumes, DRA) — a pre-encoded StaticBatch
    # with this set must not be reused across an assume boundary
    assume_coupled: bool = False
    # set by refresh_static when node rows moved since stage 1: the in-use
    # port rows baked into ``pb`` are then stale and finalize re-derives
    # them from the current NodeInfos (the one-shot encode path keeps
    # pb.node_ports as-is — nothing ran in between)
    ports_stale: bool = False
    # the EncodeCache (state.encode_cache) stage 1 encoded against; stage 2
    # reuses its persistent affinity/spread term caches
    cache: object | None = None
    # topology mode ("off"|"auto"|"on") — finalize_batch attaches the dense
    # coordinate block when the mode is active AND any node carries a
    # topology label; coordinates are read fresh from the NodeTensors memo
    # at stage 2 so a label change between stages is never baked stale
    topology: str = "off"


def encode_batch(
    snapshot: Snapshot,
    pods: Sequence[t.Pod],
    profile: C.Profile | None = None,
    pad: bool = True,
    resource_names: Sequence[str] | None = None,
    nominated: Sequence = (),
    prev_nt: "enc.NodeTensors | None" = None,
    resident: "ResidentNodeState | None" = None,
    cache=None,
    track_changes: bool = True,
    mesh=None,
    topology: str = "off",
    pad_pods: int = 0,
) -> EncodedBatch:
    """Snapshot + pending pods → padded device batch.

    Padding buckets P and N to powers of two so churning clusters reuse the
    XLA compile cache (SURVEY §7 'dynamic shapes'): padded nodes have zero
    allocatable and ``allowed_pods``=0 (infeasible for every pod), padded pods
    have an all-False static mask.

    ``prev_nt``: the previous cycle's ``EncodedBatch.node_tensors`` — lets
    ``encode_snapshot`` refresh only the node rows whose generation moved
    (the loop's per-cycle host encode becomes O(Δ + batch)).

    ``resident``: a ResidentNodeState — the node block is delta-uploaded
    into the device-resident buffers instead of shipped whole.

    ``cache``: an ``encode_cache.EncodeCache`` — static pod rows become
    gathers over template-keyed rows shared across pods and cycles (the
    host-side O(Δ) twin of ``prev_nt``/``resident``).

    ``mesh``: a node-axis ``jax.sharding.Mesh`` — the device pytree is
    placed with the parallel.mesh sharding rules (node-axis leaves sharded,
    pod leaves replicated) in the same single ``device_put``, so the
    assignment engines run SPMD with XLA-inserted collectives.
    """
    if mesh is None and resident is not None:
        mesh = resident.mesh
    pad_multiple = 1
    if mesh is not None:
        from ..parallel.mesh import node_pad_multiple

        pad_multiple = node_pad_multiple(mesh)
    sb = encode_batch_static(
        snapshot, pods, profile, pad=pad, resource_names=resource_names,
        nominated=nominated, prev_nt=prev_nt, cache=cache,
        track_changes=track_changes, pad_multiple=pad_multiple,
        topology=topology, pad_pods=pad_pods,
    )
    return finalize_batch(
        sb, snapshot, nominated=nominated, resident=resident, mesh=mesh
    )


def encode_batch_static(
    snapshot: Snapshot,
    pods: Sequence[t.Pod],
    profile: C.Profile | None = None,
    pad: bool = True,
    resource_names: Sequence[str] | None = None,
    nominated: Sequence = (),
    prev_nt: "enc.NodeTensors | None" = None,
    cache=None,
    track_changes: bool = True,
    pad_multiple: int = 1,
    topology: str = "off",
    pad_pods: int = 0,
) -> StaticBatch:
    """Stage 1: the assume-independent host encode (see StaticBatch).
    ``track_changes=False`` (serial loop) skips the pipeline-only
    staleness diff in the incremental snapshot encode. ``pad_multiple``:
    round the padded NODE capacity up to this multiple — a mesh of
    n_shards devices needs NC % n_shards == 0 or the sharded resident
    block degrades to per-cycle replication (round_up's buckets are
    multiples of 8, so this only bites past 8 shards on tiny clusters).
    ``pad_pods``: pad the POD axis to this bucket (at least ``len(pods)``)
    instead of ``round_up``'s — the scheduler names one whose programs it
    has already compiled (``Scheduler._pod_bucket``)."""
    N, P = snapshot.num_nodes(), len(pods)
    NP = enc.round_up(N) if pad else N
    if pad:
        NP = enc.shard_aligned(NP, pad_multiple)
    PP = P
    if pad:
        PP = max(pad_pods, P) if pad_pods else enc.round_up(P)
    folded: frozenset = frozenset()
    if resource_names is None:
        resource_names, folded = enc.batch_resource_axis(snapshot, pods)
    # DRA (state.dra): pre-analyze the batch's claims so dense pool columns
    # join the resource axis BEFORE the node tensors are built; pool ids are
    # interned on the cache's index, keeping the axis cycle-stable for the
    # incremental encode
    dra_state = None
    want_dra_plugin = profile is None or (
        profile.has_filter(C.DYNAMIC_RESOURCES)
    )
    if (
        want_dra_plugin
        and getattr(snapshot, "dra", None) is not None
        and any(p_.resource_claims for p_ in pods)
    ):
        from ..state.dra import DraState

        dra_state = DraState(snapshot)
        for p_ in pods:
            dra_state.analyze(p_)
        pool_names = dra_state.pool_resource_names()
        if pool_names:
            resource_names = list(resource_names) + pool_names
    nt = enc.encode_snapshot(
        snapshot, resource_names=resource_names, pods=pods, pad_nodes=NP,
        prev=prev_nt, track_changes=track_changes,
    )
    if dra_state is not None and dra_state.used_pools:
        dra_state.fill_node_columns(
            nt, len(nt.resource_names) - len(dra_state.used_pools)
        )
    enabled = (
        frozenset(profile.filters.names()) if profile is not None else None
    )
    enabled_sc = (
        frozenset(profile.scores.names()) if profile is not None else None
    )
    nominated_triples: list[tuple[int, str, str]] = []
    for e in nominated:
        nominated_triples.extend(getattr(e, "ports", ()))
    vol_state = None
    if any(v.pvc_name for p_ in pods for v in p_.volumes):
        # a pod referencing a PVC engages the volume plugins even when the
        # listers are empty (a MISSING claim is what rejects it)
        from ..state.volumes import VolumeState

        vol_state = VolumeState(snapshot)
    # a nomination whose own pod sits in THIS batch is excluded: the folded
    # resource is a batch singleton, so the nominee is its only requester —
    # charging would block the nominee from its own nominated node (the
    # dense path's self-exclusion is the per-pod gate, e.uid != p.uid)
    batch_uids = {p_.uid for p_ in pods}
    folded_nominated = (
        [
            (e.node_name, tuple(e.requests))
            for e in nominated
            if getattr(e, "node_name", "") and e.uid not in batch_uids
        ]
        if folded else ()
    )
    pb = enc.encode_pod_batch(
        nt, pods, enabled_filters=enabled, pad_pods=PP,
        enabled_scores=enabled_sc, extra_port_triples=nominated_triples,
        volume_state=vol_state,
        folded_resources=folded,
        folded_nominated=folded_nominated,
        dra_state=dra_state,
        cache=cache,
    )
    # DRA prioritized-list score rows (per distinct host-spec set)
    dra_score_raw = dra_score_sig = None
    want_dra_score = profile is None or profile.has_score(C.DYNAMIC_RESOURCES)
    if dra_state is not None and want_dra_score:
        NC = nt.alloc.shape[0]
        row_ids: dict[tuple, int] = {}
        rows: list[np.ndarray] = []
        sig_arr = np.zeros(PP, dtype=np.int32)
        any_score = False
        for i, p_ in enumerate(pods):
            d = dra_state.analyze(p_)
            specs = tuple(
                s for s in d.host_specs
                if dra_state.spec_score(s, nt) is not None
            )
            sid = row_ids.get(specs)
            if sid is None:
                v = np.zeros(N, dtype=np.int64)
                for s in specs:
                    v = v + dra_state.spec_score(s, nt)
                sid = len(rows)
                row_ids[specs] = sid
                rows.append(v)
            sig_arr[i] = sid
            if specs:
                any_score = True
        if any_score:
            dra_score_raw = np.zeros((len(rows), NC), dtype=np.int64)
            for s_i, v in enumerate(rows):
                dra_score_raw[s_i, :N] = v
            dra_score_sig = sig_arr
    want_na = profile is None or profile.has_score(C.NODE_AFFINITY)
    want_tt = profile is None or profile.has_score(C.TAINT_TOLERATION)
    want_img = profile is None or profile.has_score(C.IMAGE_LOCALITY)
    want_spread = profile is None or (
        profile.has_filter(C.POD_TOPOLOGY_SPREAD)
        or profile.has_score(C.POD_TOPOLOGY_SPREAD)
    )
    want_interpod = profile is None or (
        profile.has_filter(C.INTER_POD_AFFINITY)
        or profile.has_score(C.INTER_POD_AFFINITY)
    )
    img_sums, img_sig, img_counts = (
        _image_tensors(nt, pods, pad_pods=PP)
        if want_img else (None, None, None)
    )
    node_valid = np.zeros(nt.alloc.shape[0], dtype=bool)
    node_valid[:N] = True
    pod_valid = np.zeros(PP, dtype=bool)
    pod_valid[:P] = True
    return StaticBatch(
        pods=list(pods),
        profile=profile,
        nt=nt,
        pb=pb,
        resource_names=nt.resource_names,
        num_nodes=N,
        num_pods=P,
        pad_nodes=nt.alloc.shape[0],
        pad_pods=PP,
        folded=folded,
        want_na=want_na,
        want_tt=want_tt,
        want_img=want_img,
        want_spread=want_spread,
        want_interpod=want_interpod,
        dra_score_raw=dra_score_raw,
        dra_score_sig=dra_score_sig,
        img_sums=img_sums,
        img_sig=img_sig,
        img_counts=img_counts,
        node_valid=node_valid,
        pod_valid=pod_valid,
        nominated_key=tuple(id(e) for e in nominated),
        assume_coupled=bool(folded) or dra_state is not None
        or vol_state is not None,
        cache=cache,
        topology=topology,
    )


def refresh_static(sb: StaticBatch, snapshot: Snapshot) -> bool:
    """Re-encode the node resource rows of a pre-encoded StaticBatch on its
    own axis (stage-2 entry: fold in the assumes that landed since stage 1).
    Returns False when the node SET changed since stage 1 — the StaticBatch
    is then unusable (its num_nodes/node_valid/static_mask are pinned at
    the stage-1 node count) and the caller must re-encode from scratch.
    Object identity alone no longer detects that: the append-incremental
    encoder extends the SAME NodeTensors in place on a pure node add, so
    the node count is checked explicitly."""
    nt = enc.encode_snapshot(
        snapshot, resource_names=sb.resource_names, pods=(),
        pad_nodes=sb.pad_nodes, prev=sb.nt,
    )
    if nt is not sb.nt or nt.num_nodes != sb.num_nodes:
        return False
    if nt.last_dirty_rows:
        # node accounting moved (the assumes this refresh folds in) — the
        # stage-1 port rows no longer reflect in-use triples
        sb.ports_stale = True
    return True


def _node_port_rows(
    nt: "enc.NodeTensors", vocab, NC: int, K: int
) -> np.ndarray:
    """(NC, K) in-use port-triple rows from the CURRENT NodeInfo state —
    the assume-dependent half of the NodePorts tensors. Raises
    StaleStaticEncode when a node holds a triple outside the batch's
    interned vocabulary (an assume introduced a new triple; the conflict
    matrix can't express it)."""
    rows = np.zeros((NC, K), dtype=bool)
    for i, info in enumerate(nt.infos):
        for tr in info.port_triples:
            tid = vocab.get(tr)
            if tid < 0:
                raise StaleStaticEncode(f"port triple {tr} not in batch vocab")
            rows[i, tid] = True
    return rows


def finalize_batch(
    sb: StaticBatch,
    snapshot: Snapshot,
    nominated: Sequence = (),
    resident: "ResidentNodeState | None" = None,
    mesh=None,
) -> EncodedBatch:
    """Stage 2: patch the assume-dependent slice onto a StaticBatch and
    build the device pytree — spread counts and affinity sums re-derived
    from the CURRENT NodeInfo state, nominations re-encoded, in-use ports
    recomputed, and the node block delta-uploaded when ``resident`` is
    given. Raises StaleStaticEncode when the StaticBatch can't be patched
    (nomination set changed since stage 1, or an unknown port triple)."""
    if tuple(id(e) for e in nominated) != sb.nominated_key:
        raise StaleStaticEncode("nomination set changed since static encode")
    profile, pods, nt, pb = sb.profile, sb.pods, sb.nt, sb.pb
    N, P, PP = sb.num_nodes, sb.num_pods, sb.pad_pods
    NC = sb.pad_nodes
    cache = sb.cache
    if cache is not None:
        # namespace labels feed affinity namespaceSelectors: a moved
        # generation clears the cache's persistent match verdicts
        cache.sync_namespaces(snapshot.namespaces_generation)
    # template groups of the existing pods, shared by the spread and
    # affinity encoders (one O(pods) pass, built only if either needs it)
    _groups_memo: list = []

    def groups_of():
        if not _groups_memo:
            from ..state.encode_cache import groups_for

            _groups_memo.append(groups_for(nt, cache))
        return _groups_memo[0]

    pa_dev = None
    # affinity-free cluster fast path: the cache maintains a count of
    # assigned pods carrying any (anti)affinity, so a SchedulingBasic-shaped
    # steady state skips the template-group pass AND the affinity encoder
    # in O(pending) attribute checks
    want_pa = sb.want_interpod and not (
        snapshot.pods_with_affinity == 0
        and not any(enc_podaffinity.has_any_affinity(p) for p in pods)
    )
    pa_stamp = None
    if want_pa:
        t_pa = time.perf_counter()
        pa = enc_podaffinity.encode_pod_affinity(
            nt, pods,
            hard_pod_affinity_weight=(
                profile.hard_pod_affinity_weight if profile is not None else 1
            ),
            pad_pods=PP,
            namespaces=snapshot.namespaces,
            cache=cache,
            groups=groups_of(),
        )
        if pa is not None:
            t_end = time.perf_counter()
            by_term = {
                "affinity": (pa.fa_rows[:P] >= 0).any(axis=1),
                "anti_affinity": (pa.ra_rows[:P] >= 0).any(axis=1),
                "existing_anti_affinity": (pa.ea_rows[:P] >= 0).any(axis=1),
            }
            pa_stamp = PodAffinityEncodeStamp(
                start=t_pa, end=t_end,
                rows=pa.num_rows, domains=pa.max_domains,
                slots=PA.kernel_slots(pa),
                filter_pods=int(np.logical_or.reduce(
                    list(by_term.values())).sum()),
                score_pods=int((pa.score_rows[:P] >= 0).any(axis=1).sum()),
                filter_terms={term: int(used.sum())
                              for term, used in by_term.items()},
                existing_anti_nodes=pa.existing_anti_nodes(P),
            )
            # host numpy leaves — the single batched device_put below ships
            # the whole pytree in one dispatch instead of ~30
            pa_dev = PodAffinityDevice(
                node_domain=pa.node_domain,
                has_key=pa.has_key,
                base_sums=pa.base_sums,
                update=pa.update,
                fa_rows=pa.fa_rows,
                fa_self=pa.fa_self,
                ra_rows=pa.ra_rows,
                ea_rows=pa.ea_rows,
                score_rows=pa.score_rows,
                score_vals=pa.score_vals,
                has_filter_work=pa.has_filter_work,
                has_score_work=pa.has_score_work,
            )
    spread_dev = None
    spread_stamp = None
    if sb.want_spread:
        defaults = (
            profile.default_spread_constraints if profile is not None else ()
        )
        t_spread = time.perf_counter()
        sp = enc_spread.encode_spread(
            nt, pods, pad_pods=PP,
            default_constraints=defaults,
            default_selector_of=(
                enc_spread.default_selector_from_services(snapshot)
                if defaults and snapshot.services else None
            ),
            cache=cache,
            # reuse the affinity encoder's group pass when it ran; spread
            # builds its own only past its cheap no-constraints early-out
            groups=_groups_memo[0] if _groups_memo else None,
        )
        if sp is not None:
            used = sp.sig_idx[:P] >= 0              # (P, C) constraint slots
            spread_stamp = SpreadEncodeStamp(
                start=t_spread, end=time.perf_counter(),
                signatures=sp.num_sigs, domains=sp.max_domains,
                constrained_pods=int(used.any(axis=1).sum()),
                soft_pods=int(
                    (used & (sp.action[:P] == enc_spread.SOFT))
                    .any(axis=1).sum()
                ),
                counted_domains=int(sp.num_domains.max()),
                policy_pods=sp.policy_pods,
                excluded_nodes=sp.excluded_nodes,
            )
            spread_dev = SpreadDevice(
                eligible=sp.eligible,
                node_domain=sp.node_domain,
                node_count=sp.node_count,
                has_key=sp.has_key,
                domain_present=sp.domain_present,
                num_domains=sp.num_domains,
                is_hostname=sp.is_hostname,
                sig_idx=sp.sig_idx,
                action=sp.action,
                max_skew=sp.max_skew,
                min_domains=sp.min_domains,
                self_match=sp.self_match,
                pod_match_sig=sp.pod_match_sig,
                ignored=sp.ignored,
                has_hard=sp.has_hard,
                has_soft=sp.has_soft,
            )
    img_sums, img_sig, img_counts = sb.img_sums, sb.img_sig, sb.img_counts
    node_valid, pod_valid = sb.node_valid, sb.pod_valid

    # in-use ports: the stage-1 rows are reused verbatim unless node state
    # moved since (refresh_static flags it) — then they are re-derived from
    # the current NodeInfos (assumes occupy ports)
    K = pb.port_conflict.shape[0]
    node_ports = (
        _node_port_rows(nt, pb.port_vocab, NC, K)
        if sb.ports_stale else pb.node_ports
    )

    # Nominator reservations (queue/nominator.py): the gate row for pod p
    # enables nomination g iff g's priority >= p's and g is not p itself
    # (framework/runtime's RunFilterPluginsWithNominatedPods rule).
    nom_node = nom_req = nom_gate = nom_ports = nom_pod_idx = None
    if nominated:
        name_to_idx = {n: j for j, n in enumerate(nt.node_names)}
        uid_to_idx = {p_.uid: i for i, p_ in enumerate(pods)}
        G = len(nominated)
        nom_node = np.full(G, -1, dtype=np.int32)
        nom_req = np.zeros((G, len(nt.resource_names)), dtype=np.int64)
        nom_gate = np.zeros((PP, G), dtype=bool)
        nom_ports = np.zeros((G, K), dtype=bool)
        nom_pod_idx = np.full(G, -1, dtype=np.int32)
        ridx = {r: j for j, r in enumerate(nt.resource_names)}
        for g, e in enumerate(nominated):
            nom_node[g] = name_to_idx.get(e.node_name, -1)
            nom_pod_idx[g] = uid_to_idx.get(e.uid, -1)
            for k, val in e.requests:
                j = ridx.get(k)
                if j is not None:
                    nom_req[g, j] = val
            for tr in getattr(e, "ports", ()):
                tid = pb.port_vocab.get(tr)
                if tid >= 0:
                    nom_ports[g, tid] = True
            for i, p_ in enumerate(pods):
                nom_gate[i, g] = e.priority >= p_.priority and e.uid != p_.uid

    # topology coordinates: attached ONLY when the mode is active and some
    # node actually carries a slice/rack label ("auto" on an unlabeled
    # cluster leaves the leaf absent → the pytree, the compiled kernels and
    # their outputs are bit-identical to topology-off)
    topo_dev = None
    if sb.topology != "off":
        from ..state.topology import topology_tensors

        tt = topology_tensors(nt)
        if tt.labeled:
            topo_dev = TopologyDevice(
                slice_id=tt.slice_id,
                rack_id=tt.rack_id,
                num_slices=tt.num_slices,
                num_racks=tt.num_racks,
            )

    if resident is not None:
        nodes_block = resident.refresh(nt, N)
        node_upload = resident.last_upload_bytes
        resident_bytes = resident.nbytes
    else:
        nodes_block = DeviceNodeState(
            alloc=nt.alloc,
            requested=nt.requested,
            nonzero_requested=nt.nonzero_requested,
            pod_count=nt.pod_count,
            allowed_pods=nt.allowed_pods,
            node_valid=node_valid,
        )
        node_upload = _node_block_nbytes(nodes_block)
        resident_bytes = 0

    if mesh is None and resident is not None:
        mesh = resident.mesh
    # host numpy leaves throughout; ONE batched device_put ships the whole
    # pytree (leaf-by-leaf jnp.asarray was ~30 separate dispatches per
    # cycle). Resident-path node buffers are already on device — and, under
    # a mesh, already sharded with the same rules — device_put passes them
    # through untouched.
    dev = DeviceBatch(
        nodes=nodes_block,
        requests=pb.requests,
        nonzero_requests=pb.nonzero_requests,
        pod_valid=pod_valid,
        static_mask=pb.static_mask,
        static_sig=(
            pb.static_sig if pb.static_mask is not None else None
        ),
        node_affinity_raw=(
            pb.node_affinity_raw
            if sb.want_na and pb.node_affinity_raw is not None else None
        ),
        taint_prefer_raw=(
            pb.taint_prefer_raw
            if sb.want_tt and pb.taint_prefer_raw is not None else None
        ),
        score_sig=(
            pb.score_sig
            if pb.score_sig is not None
            and ((sb.want_na and pb.node_affinity_raw is not None)
                 or (sb.want_tt and pb.taint_prefer_raw is not None))
            else None
        ),
        image_sum_scores=img_sums if sb.want_img else None,
        image_sig=img_sig if sb.want_img else None,
        image_count=img_counts if sb.want_img else None,
        pod_ports=pb.pod_ports,
        node_ports=node_ports,
        port_conflict=pb.port_conflict,
        nominated_node=nom_node,
        nominated_req=nom_req,
        nominated_gate=nom_gate,
        nominated_ports=nom_ports,
        nominated_pod_idx=nom_pod_idx,
        spread=spread_dev,
        podaffinity=pa_dev,
        dra_score_raw=sb.dra_score_raw,
        dra_score_sig=(
            sb.dra_score_sig if sb.dra_score_raw is not None else None
        ),
        pod_priority=pb.priority,
        topology=topo_dev,
    )
    if mesh is not None:
        from ..parallel.mesh import batch_shardings, node_axes_of

        axis, pod_axis = node_axes_of(mesh)
        dev = jax.device_put(
            dev, batch_shardings(dev, mesh, axis, pod_axis, guard=True)
        )
    else:
        dev = jax.device_put(dev)
    from ..metrics.tpu import batch_nbytes

    total_bytes = batch_nbytes(dev)
    pod_block_bytes = total_bytes - _node_block_nbytes(nodes_block)
    return EncodedBatch(
        device=dev,
        node_names=nt.node_names,
        pods=list(pods),
        resource_names=nt.resource_names,
        num_nodes=N,
        num_pods=P,
        node_tensors=nt,
        port_vocab=pb.port_vocab,
        upload_bytes=pod_block_bytes + node_upload,
        resident_bytes=resident_bytes,
        spread_encode=spread_stamp,
        podaffinity_encode=pa_stamp,
    )


@dataclass(frozen=True)
class ScoreParams:
    """Static numeric config handed to the jitted program (weights aligned to
    the batch's resource axis)."""

    fit_weights: tuple[int, ...]
    balanced_weights: tuple[int, ...]
    is_scalar: tuple[bool, ...]
    strategy: str
    shape_x: tuple[int, ...]
    shape_y: tuple[int, ...]          # pre-scaled ×10 (MaxNodeScore/MaxCustomPriorityScore)
    w_fit: int
    w_balanced: int
    w_node_affinity: int
    w_taint: int
    w_image: int
    w_spread: int
    w_interpod: int
    w_dra: int
    filter_fit: bool
    filter_ports: bool
    filter_spread: bool
    filter_interpod: bool


def score_params(profile: C.Profile, resource_names: Sequence[str]) -> ScoreParams:
    ss = profile.scoring_strategy
    shape = ss.shape or ((0, 0), (100, 10))
    return ScoreParams(
        fit_weights=tuple(_resource_weights(resource_names, ss.resources).tolist()),
        balanced_weights=tuple(
            _resource_weights(resource_names, profile.balanced_resources).tolist()
        ),
        is_scalar=tuple(_is_scalar(resource_names).tolist()),
        strategy=ss.type,
        shape_x=tuple(x for x, _ in shape),
        shape_y=tuple(y * 10 for _, y in shape),
        w_fit=profile.score_weight(C.NODE_RESOURCES_FIT),
        w_balanced=profile.score_weight(C.NODE_RESOURCES_BALANCED),
        w_node_affinity=profile.score_weight(C.NODE_AFFINITY),
        w_taint=profile.score_weight(C.TAINT_TOLERATION),
        w_image=profile.score_weight(C.IMAGE_LOCALITY),
        w_spread=profile.score_weight(C.POD_TOPOLOGY_SPREAD),
        w_interpod=profile.score_weight(C.INTER_POD_AFFINITY),
        w_dra=profile.score_weight(C.DYNAMIC_RESOURCES),
        filter_fit=profile.has_filter(C.NODE_RESOURCES_FIT),
        filter_ports=profile.has_filter(C.NODE_PORTS),
        filter_spread=profile.has_filter(C.POD_TOPOLOGY_SPREAD),
        filter_interpod=profile.has_filter(C.INTER_POD_AFFINITY),
    )


def masked_normalize(raw: jnp.ndarray, mask: jnp.ndarray, reverse: bool = False) -> jnp.ndarray:
    """DefaultNormalizeScore over feasible nodes only (the reference's
    nodeScoreList contains only nodes that passed Filter)."""
    masked = jnp.where(mask, raw, 0)
    return S.default_normalize(masked, reverse=reverse)


def filter_components(
    b: DeviceBatch,
    p: ScoreParams,
    requested: jnp.ndarray | None = None,
    pod_count: jnp.ndarray | None = None,
    node_ports: jnp.ndarray | None = None,
    spread_counts: jnp.ndarray | None = None,
    pa_sums: jnp.ndarray | None = None,
    nominated_active: jnp.ndarray | None = None,
    pa_counts: "PA.NodeCounts | None" = None,
):
    """Per-plugin Filter masks, un-ANDed — the split preemption needs:
    failures of ``static`` / ``spread_ok`` / ``pa_ok`` are
    UnschedulableAndUnresolvable for the victim-search (removing pods can't
    fix node labels; spread/affinity removal effects are conservatively out
    of kernel scope, ops/preemption.py docstring), while ``fit``/``ports_ok``
    failures are the resolvable kind (preemption.go:180 NodesForStatusCode).

    Returns ``(static, fit, ports_ok, spread_ok, pa_ok, sp_counts,
    pa_state)``; mask entries are None when the plugin is disabled or has no
    work, and ``pa_state`` is what the affinity masks were read from, for
    the score: the (R, D) sums or, where ``ops.podaffinity.table_pays`` or
    the caller handed one, their ``NodeCounts``.
    """
    req = b.requested if requested is None else requested
    pc = b.pod_count if pod_count is None else pod_count
    ports = b.node_ports if node_ports is None else node_ports

    static = b.node_valid[None, :] & b.pod_valid[:, None]
    if b.static_mask is not None:
        # (S, N) rows gathered per pod on device (fused into consumers)
        sm = (
            b.static_mask[b.static_sig]
            if b.static_sig is not None else b.static_mask
        )
        static = static & sm
    fit = None
    if p.filter_fit:
        if b.nominated_node is not None:
            gate = b.nominated_gate
            if nominated_active is not None:
                # a nomination stops charging once its own pod was assigned
                # earlier in this batch (assume deletes the nomination)
                gate = gate & nominated_active[None, :]
            fit = F.resource_fit_mask_nominated(
                b.requests, b.alloc, req, pc, b.allowed_pods,
                gate, b.nominated_node, b.nominated_req,
            )
        else:
            fit = F.resource_fit_mask(
                b.requests, b.alloc, req, pc, b.allowed_pods
            )
    ports_ok = None
    if p.filter_ports:
        # conflict[p, n] = any pod triple k conflicting with in-use triple l
        wants_conf = jnp.einsum(
            "pk,kl->pl", b.pod_ports.astype(jnp.int32),
            b.port_conflict.astype(jnp.int32),
        )                                                     # (P, K)
        conflict = jnp.einsum(
            "pl,nl->pn", wants_conf, ports.astype(jnp.int32)
        ) > 0                                                 # (P, N)
        if b.nominated_ports is not None and b.nominated_node is not None:
            # nominated pods' host ports are reserved on their nominated
            # node for >=-priority-gated pods, like their resources
            # (RunFilterPluginsWithNominatedPods adds the whole pod)
            gate = b.nominated_gate
            if nominated_active is not None:
                gate = gate & nominated_active[None, :]
            nom_conf = jnp.einsum(
                "pl,gl->pg", wants_conf,
                b.nominated_ports.astype(jnp.int32),
            )                                                 # (P, G)
            n_nodes = ports.shape[0]
            at_node = (
                b.nominated_node[:, None]
                == jnp.arange(n_nodes, dtype=b.nominated_node.dtype)[None, :]
            )                                                 # (G, N)
            conflict = conflict | (
                jnp.einsum(
                    "pg,gn->pn",
                    (gate & (nom_conf > 0)).astype(jnp.int32),
                    at_node.astype(jnp.int32),
                ) > 0
            )
        ports_ok = ~conflict
    sp = b.spread
    sp_counts = None
    spread_ok = None
    if sp is not None:
        sp_counts = sp.node_count if spread_counts is None else spread_counts
        if p.filter_spread and sp.has_hard:
            with jax.named_scope("spread_filter"):
                spread_ok = jax.vmap(
                    lambda si, ac, ms, md, sm: SP.spread_filter_pod(
                        sp, sp_counts, si, ac, ms, md, sm
                    )
                )(sp.sig_idx, sp.action, sp.max_skew, sp.min_domains,
                  sp.self_match)
    pa = b.podaffinity
    pa_state = None
    pa_ok = None
    if pa is not None:
        pa_state = pa.base_sums if pa_sums is None else pa_sums
        if pa_counts is not None:
            pa_state = pa_counts
        elif PA.table_pays(pa):
            # built here ONCE for the filter and the score of every pod
            pa_state = PA.node_counts(pa, pa_state)
        if p.filter_interpod and pa.has_filter_work:
            with jax.named_scope("interpod_filter"):
                pa_ok = jax.vmap(
                    lambda fr, fs, rr, er: PA.affinity_filter_pod(
                        pa, pa_state, fr, fs, rr, er
                    )
                )(pa.fa_rows, pa.fa_self, pa.ra_rows, pa.ea_rows)
    return static, fit, ports_ok, spread_ok, pa_ok, sp_counts, pa_state


def feasible_and_scores(
    b: DeviceBatch,
    p: ScoreParams,
    requested: jnp.ndarray | None = None,
    nonzero_requested: jnp.ndarray | None = None,
    pod_count: jnp.ndarray | None = None,
    node_ports: jnp.ndarray | None = None,
    spread_counts: jnp.ndarray | None = None,
    pa_sums: jnp.ndarray | None = None,
    nominated_active: jnp.ndarray | None = None,
    pa_counts: "PA.NodeCounts | None" = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The full Filter + Score composition for a batch against ONE snapshot
    state (no inter-pod capacity coupling — that is the assignment engine's
    job). Returns ``(mask (P,N) bool, total (P,N) int64)``.

    Optional ``requested``/``nonzero_requested``/``pod_count`` override the
    batch's node usage — the greedy scan threads its running state through
    here so this one function is both the one-shot and the stepped semantics.
    ``pa_counts`` is ``pa_sums`` as ``ops.podaffinity.NodeCounts``, from a
    caller that keeps one (the scan); without it ``filter_components``
    builds one where the batch is wide enough to pay for it.
    """
    req = b.requested if requested is None else requested
    nz = b.nonzero_requested if nonzero_requested is None else nonzero_requested

    w_fit = jnp.asarray(p.fit_weights, dtype=jnp.int64)
    w_bal = jnp.asarray(p.balanced_weights, dtype=jnp.int64)
    scal = jnp.asarray(p.is_scalar, dtype=bool)

    # --- Filter ----------------------------------------------------------
    static, fit, ports_ok, spread_ok, pa_ok, sp_counts, pa_state = (
        filter_components(
            b, p, requested=requested, pod_count=pod_count,
            node_ports=node_ports, spread_counts=spread_counts,
            pa_sums=pa_sums, nominated_active=nominated_active,
            pa_counts=pa_counts,
        )
    )
    mask = static
    for part in (fit, ports_ok, spread_ok, pa_ok):
        if part is not None:
            mask = mask & part
    if b.extender_mask is not None:
        # findNodesThatPassExtenders (schedule_one.go:886): extenders only
        # shrink the feasible set
        mask = mask & b.extender_mask
    sp = b.spread
    pa = b.podaffinity

    # --- Score -----------------------------------------------------------
    total = jnp.zeros(mask.shape, dtype=jnp.int64)
    if p.w_fit:
        if p.strategy == C.LEAST_ALLOCATED:
            raw = S.least_allocated_score(b.nonzero_requests, nz, b.alloc, w_fit, scal)
        elif p.strategy == C.MOST_ALLOCATED:
            raw = S.most_allocated_score(b.nonzero_requests, nz, b.alloc, w_fit, scal)
        else:
            raw = S.requested_to_capacity_ratio_score(
                b.nonzero_requests, nz, b.alloc, w_fit, scal,
                jnp.asarray(p.shape_x, dtype=jnp.int64),
                jnp.asarray(p.shape_y, dtype=jnp.int64),
            )
        total = total + p.w_fit * raw          # no NormalizeScore (already 0..100)
    if p.w_balanced:
        raw = S.balanced_allocation_score(b.requests, req, b.alloc, w_bal, scal)
        total = total + p.w_balanced * raw
    if p.w_node_affinity and b.node_affinity_raw is not None:
        na_raw = (
            b.node_affinity_raw[b.score_sig]
            if b.score_sig is not None else b.node_affinity_raw
        )
        total = total + p.w_node_affinity * masked_normalize(na_raw, mask)
    if p.w_taint and b.taint_prefer_raw is not None:
        tt_raw = (
            b.taint_prefer_raw[b.score_sig]
            if b.score_sig is not None else b.taint_prefer_raw
        )
        total = total + p.w_taint * masked_normalize(tt_raw, mask, reverse=True)
    if p.w_image and b.image_sum_scores is not None:
        img = (
            b.image_sum_scores[b.image_sig]
            if b.image_sig is not None else b.image_sum_scores
        )
        total = total + p.w_image * S.image_locality_score(img, b.image_count)
    if sp is not None and p.w_spread and sp.has_soft:
        with jax.named_scope("spread_score"):
            spread_sc = jax.vmap(
                lambda si, ac, ms, ig, m: SP.spread_score_pod(
                    sp, sp_counts, si, ac, ms, ig, m
                )
            )(sp.sig_idx, sp.action, sp.max_skew, sp.ignored, mask)
        total = total + p.w_spread * spread_sc
    if pa is not None and p.w_interpod and pa.has_score_work:
        with jax.named_scope("interpod_score"):
            pa_sc = jax.vmap(
                lambda sr, sv, m: PA.affinity_score_pod(
                    pa, pa_state, sr, sv, m
                )
            )(pa.score_rows, pa.score_vals, mask)
        total = total + p.w_interpod * pa_sc
    if p.w_dra and b.dra_score_raw is not None:
        # DynamicResources prioritized-list score + DefaultNormalizeScore
        # (dynamicresources.go:1059 Score, :1138 NormalizeScore)
        dra_raw = (
            b.dra_score_raw[b.dra_score_sig]
            if b.dra_score_sig is not None else b.dra_score_raw
        )
        total = total + p.w_dra * masked_normalize(dra_raw, mask)
    if b.extender_score is not None:
        # extender Prioritize, pre-scaled weight*MaxNodeScore/MaxExtenderPriority
        # (schedule_one.go:1015) — added after plugin normalization
        total = total + b.extender_score
    return mask, total


@partial(jax.jit, static_argnames=("params",))
def filter_score_batch(b: DeviceBatch, params: ScoreParams):
    """One-shot batch Filter+Score (all pods vs. the same snapshot) — the
    extender Prioritize path and the first half of batched assignment."""
    return feasible_and_scores(b, params)
