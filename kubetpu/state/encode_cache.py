"""Cross-cycle encode cache — event-time, template-keyed pod tensorization.

The r05 fullstack trace showed host encode eating 86% of the scheduling
cycle (116ms of 134.4ms per 128-pod cycle) while the device assign took
15.7ms: the tensorization layer rebuilt every static per-pod row from
scratch each cycle even though PR 2 already made the *device* side O(Δ).
This module closes the host side of that gap, in three layers:

1. **Event-time pre-encoding** — the scheduler's informer handlers
   (``on_pod_add``/``on_pod_update``) call ``precompute_pod`` when a pending
   pod is delivered, so its static rows (filter mask, NodeAffinity /
   TaintToleration score rows, request row) are built OFF the cycle
   critical path. Cycle-time ``encode_pod_batch`` then *gathers* rows out
   of this cache instead of rebuilding them.
2. **Template-keyed row sharing** — rows are keyed by the pod's *static
   signatures* (``_static_filter_signature`` / ``_static_score_signature``
   / the request tuple), not by pod identity: pods stamped from one
   Deployment/Job template are spec-identical, so a 1000-pod burst from 3
   templates encodes ~3 rows — shared across pods AND across cycles, with
   an LRU bound and hit/miss counters surfaced through
   ``TPUBackendMetrics``.
3. **Invalidation by construction** — a row is a pure function of its
   signature key plus the node static facts, so pod mutation can never
   leave a stale row behind (a mutated pod hashes to a *different* key);
   node-side staleness is handled by an epoch the scheduler bumps on every
   node add/update/delete (``invalidate_nodes``), which clears the
   node-dependent caches wholesale. Rows involving per-batch coupled state
   (volumes, DRA, folded singleton scalars, in-batch RWOP duplicates) are
   never cached here — the batch encoder layers those onto a *copy* of the
   cached base row.

The persistent inter-pod-affinity / topology-spread term caches
(``aff_row_specs``, ``match``, ``sel_counts``) live here too: they memoize
the per-*template* term→row specs and selector-match verdicts that
``state.podaffinity`` / ``state.spread`` previously recomputed per existing
pod per cycle (the other 60% of the r05 encode wall). Namespace-label
changes clear the match caches (affinity namespaceSelectors match against
namespace labels).
"""

from __future__ import annotations

import collections
from dataclasses import dataclass
from operator import is_
from typing import Callable

import numpy as np


_MISSING = object()


class _LRU:
    """Tiny bounded mapping: least-recently-USED eviction via OrderedDict
    (get refreshes recency). Eviction is always safe — every entry can be
    rebuilt from its key."""

    __slots__ = ("_d", "maxlen")

    def __init__(self, maxlen: int) -> None:
        self._d: "collections.OrderedDict" = collections.OrderedDict()
        self.maxlen = maxlen

    def get(self, key, default=None):
        d = self._d
        got = d.get(key, _MISSING)
        if got is _MISSING:
            return default
        d.move_to_end(key)
        return got

    def put(self, key, value) -> None:
        d = self._d
        d[key] = value
        d.move_to_end(key)
        if len(d) > self.maxlen:
            d.popitem(last=False)

    def pop(self, key) -> None:
        self._d.pop(key, None)

    def clear(self) -> None:
        self._d.clear()

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d


def template_key(pod) -> tuple:
    """The pod's TEMPLATE identity: every spec fact the per-pod halves of
    the spread/affinity encoders read. Pods stamped from one controller
    template share it, so per-pod work collapses to per-template work.
    Index [0:3] — (labels, namespace, affinity) — is what the existing-pod
    group consumers (base sums, selector counts) key on."""
    return (
        pod.labels, pod.namespace, pod.affinity,
        pod.topology_spread_constraints, pod.tolerations, pod.node_selector,
    )


def _same_template(a, b) -> bool:
    """Do two objects of one pod carry the very same ``template_key``
    fields? A copy that changed only other fields (``Pod.with_node``)
    does, so its template needs no deep hash."""
    return all(map(is_, template_key(a), template_key(b)))


class _BoundedMemo(dict):
    """Plain-dict memo with a size bound enforced by wholesale clear —
    for per-POD hot paths (uid → memo) where an OrderedDict's per-get
    recency bookkeeping costs more than the occasional full recompute."""

    __slots__ = ("maxlen",)

    def __init__(self, maxlen: int) -> None:
        super().__init__()
        self.maxlen = maxlen

    def put(self, key, value) -> None:
        if len(self) >= self.maxlen:
            self.clear()
        self[key] = value


@dataclass
class NodeCtx:
    """Node-side facts the static row builders consume, hoisted once per
    node epoch (they only change when a node is added/updated/removed —
    exactly the events that bump the epoch): taint tuples, the
    unschedulable mask, and declared-feature sets."""

    node_taints: list               # per node: tuple of taints
    tainted_nodes: list             # [(node_idx, taints)] for tainted only
    node_unsched: np.ndarray        # (N,) bool
    any_unsched: bool
    node_feature_sets: list | None  # per node set() or None when none declare


def build_node_ctx(nt) -> NodeCtx:
    node_taints = [info.node.taints for info in nt.infos]
    tainted = [(i, tt) for i, tt in enumerate(node_taints) if tt]
    unsched = np.array(
        [info.node.unschedulable for info in nt.infos], dtype=bool
    )
    feature_sets = (
        [set(info.node.declared_features) for info in nt.infos]
        if any(info.node.declared_features for info in nt.infos) else None
    )
    return NodeCtx(
        node_taints=node_taints,
        tainted_nodes=tainted,
        node_unsched=unsched,
        any_unsched=bool(unsched.any()),
        node_feature_sets=feature_sets,
    )


#: past this many cached rows a scoped extension costs more python than the
#: full-miss storm it avoids — wholesale clear instead (templates in real
#: workloads number in the dozens, so the cap only bites pathological keys)
EXTEND_MAX_ENTRIES = 1024


class EncodeCache:
    """See module docstring. Single-owner like the scheduler loop: informer
    callbacks and the encode path run on the loop thread.

    ``scoped=True`` (default) keeps node-epoch invalidation SCOPED: a node
    ADD (``invalidate_nodes(added=...)``) extends every cached row with the
    appended nodes' columns at the next sync — O(templates × Δnodes) —
    instead of clearing all node-dependent stores (at 100k nodes under an
    autoscaler add-wave the wholesale clear was a full re-encode storm per
    event). A node DELETE (``invalidate_nodes(removed=...)``) is scoped
    too: the next sync maps the rebuilt tensors' node names back to the
    old indices and COMPACTS every cached row by gathering the survivor
    columns — rows are pure per-node functions, so the gather is
    bit-identical to a fresh build (the drain-wave twin of the add-wave
    extension; ROADMAP 5b). Only updates (facts change at an interior
    index) and mixed add+remove waves still pay the full-epoch flush
    through the bare ``invalidate_nodes()`` seam. ``scoped=False`` is the
    escape hatch / A-B control: every epoch bump clears wholesale, the
    pre-PR-14 behavior."""

    def __init__(
        self, max_entries: int = 8192, metrics=None, scoped: bool = True,
    ) -> None:
        self.max_entries = max_entries
        self.scoped = scoped
        self.extend_max_entries = EXTEND_MAX_ENTRIES
        # --- node-fact versioning ---------------------------------------
        # bumped by the scheduler on EVERY node add/update/delete; rows are
        # valid only while built against (this epoch, this NodeTensors)
        self.node_epoch = 0
        # bumped only on FULL flushes (bare invalidate_nodes, or scoped
        # off): the template-group index keys on this, so an add-wave
        # extends its count vectors instead of rebuilding them wholesale
        self._full_epoch = 0
        self._pending_adds = 0        # scoped adds since the last sync
        self._pending_removes = 0     # scoped removals since the last sync
        self._pending_full = False    # a full flush is owed at next sync
        self._nt_len = -1             # node count rows were built against
        self._nt_token: object | None = None   # adopted NodeTensors
        self._nt_epoch = -1                    # epoch rows were built at
        self._ctx: NodeCtx | None = None
        # --- template-keyed row stores ----------------------------------
        self._filter_rows = _LRU(max_entries)  # key -> (row (N,) bool, trivial)
        self._score_rows = _LRU(max_entries)   # key -> (na_vec, tt_vec)
        self._request_rows = _LRU(max_entries)
        self._req_token: tuple | None = None   # (axis tuple, folded frozenset)
        # per-pod signature memo: uid -> (pod object, filter_sig, score_sig)
        # — identity-checked so a replaced (mutated) pod can NEVER reuse the
        # previous object's signatures
        self._pod_sigs = _BoundedMemo(max_entries * 8)
        # --- incremental template-group index ---------------------------
        # per node, the pods it counted, their gids and the node's
        # {gid: count}; the node generation folded in; the aggregated (N,)
        # count vectors — pod_groups() diffs only nodes whose generation
        # moved, and keys only the pods it has not counted
        self._groups_nt: object | None = None
        self._groups_epoch = -1
        self._group_vecs: dict = {}    # gid -> (N,) int64
        # node name -> ({uid: pod}, {uid: gid}, {gid: count})
        self._group_node: dict = {}
        self._group_gens: dict = {}
        # template keys interned to small ints, shared by the bound-pod
        # index and the pending pods' ids
        self._group_ids: dict = {}     # template_key -> gid
        self._group_keys: list = []    # gid -> key
        # pending pods' ids (pod_gids_for): uid-memoized, identity-checked
        self._pod_group_ids = _BoundedMemo(max_entries * 8)
        # bound pods the index walks kept (same object, or a copy with the
        # same template fields) and had to key: plain ints, mirrored into
        # the prom registry by flush_metrics
        self.index_pods = {"kept": 0, "keyed": 0}
        self._flushed_index_pods = {"kept": 0, "keyed": 0}
        # --- persistent affinity / spread term caches -------------------
        self._ns_gen: int | None = None
        # (affinity, ns) -> tuple of source-row specs (state.podaffinity)
        self.aff_row_specs = _LRU(max_entries)
        # (row_key, labels, ns) -> bool — does a pod shaped (labels, ns)
        # drive / match this affinity row
        self.match = _LRU(max_entries)
        # (selector, labels) -> bool — countPodsMatchSelector verdict
        self.sel_counts = _LRU(max_entries)
        # --- counters (plain ints: hot-loop cheap; mirrored into the
        # prom registry per cycle by flush_metrics) ----------------------
        self.hits: collections.Counter = collections.Counter()
        self.misses: collections.Counter = collections.Counter()
        self.invalidations = 0
        # re-encode work accounting (the node-wave evidence): bytes of rows
        # built from scratch on a miss vs bytes of delta columns appended
        # by scoped extensions, and how many syncs extended vs flushed
        self.rebuilt_bytes = 0
        self.extended_bytes = 0
        self.scoped_extensions = 0
        self.scoped_removals = 0
        self.compacted_bytes = 0      # row bytes dropped by removal gathers
        self._flushed_hits: collections.Counter = collections.Counter()
        self._flushed_misses: collections.Counter = collections.Counter()
        self._flushed_invalidations = 0
        self.metrics = metrics   # TPUBackendMetrics | None

    # ------------------------------------------------------------ epochs
    def invalidate_nodes(self, added=None, removed=None) -> None:
        """A node event landed. Bare call — the BLESSED full-epoch seam
        for updates: every node-dependent row is suspect and the next
        sync clears wholesale. ``added=<node>`` — a scoped node ADD: the
        next sync EXTENDS cached rows with the appended nodes' columns
        instead of clearing. ``removed=<node>`` — a scoped node DELETE
        (the drain wave): the next sync COMPACTS cached rows down to the
        surviving nodes' columns by an old-index gather, falling back to
        the wholesale clear when the wave turns out to be mixed
        (graftcheck EC001 pins bare calls to the scheduler's node event
        handlers so this scoping can't silently regress to a
        flush-per-event storm). O(1) every way — all real work is
        deferred to the next sync."""
        self.node_epoch += 1
        if removed is not None and self.scoped:
            self._pending_removes += 1
        elif added is not None and self.scoped:
            self._pending_adds += 1
        else:
            self._pending_full = True
            self._full_epoch += 1

    def sync_nodes(self, nt) -> bool:
        """Adopt ``nt`` (the NodeTensors the current encode runs against).
        When every epoch bump since the last sync was a scoped ADD and the
        encoder extended the SAME tensors object in place, cached rows are
        extended with the appended nodes' columns (O(templates × Δ));
        otherwise the node-dependent stores clear wholesale. Returns True
        when a wholesale invalidation happened (for the encode span's
        trace attrs)."""
        if (
            self._nt_token is nt
            and self._nt_epoch == self.node_epoch
            and self._nt_len == nt.num_nodes
        ):
            return False
        # same-object growth is append-only BY CONSTRUCTION: the encoder
        # mutates tensors in place only when the old rows are a preserved
        # prefix. Gating on observed growth (not just the pending-add
        # counter) also covers appends that bypass the node informer —
        # e.g. a placeholder node born from an assigned pod on an
        # unknown node.
        if (
            self.scoped
            and not self._pending_full
            and not self._pending_removes
            and self._nt_token is nt
            and 0 <= self._nt_len < nt.num_nodes
            and (len(self._filter_rows) + len(self._score_rows))
            <= self.extend_max_entries
        ):
            self._extend_rows(nt, self._nt_len)
            self._nt_epoch = self.node_epoch
            self._nt_len = nt.num_nodes
            self._pending_adds = 0
            self.scoped_extensions += 1
            return False    # rows stayed valid — not an invalidation
        # removal-only wave: deletes rebuild the tensors, so the NEW
        # object's node names are mapped back to old indices and every
        # cached row is compacted by a survivor gather — bit-identical to
        # a fresh build (rows are pure per-node functions and no
        # survivor's facts changed). Any name the old axis doesn't know
        # (a mixed wave) falls through to the wholesale clear.
        if (
            self.scoped
            and not self._pending_full
            and self._pending_removes
            and not self._pending_adds
            and nt is not None
            and self._nt_token is not None
            and self._nt_token is not nt
            and (len(self._filter_rows) + len(self._score_rows))
            <= self.extend_max_entries
        ):
            keep = self._removal_keep(nt)
            if keep is not None:
                self._compact_rows(nt, keep)
                self._nt_token = nt
                self._nt_epoch = self.node_epoch
                self._nt_len = nt.num_nodes
                self._pending_removes = 0
                self.scoped_removals += 1
                return False    # rows stayed valid — not an invalidation
        self._filter_rows.clear()
        self._score_rows.clear()
        self._ctx = None
        invalidated = self._nt_token is not None
        self._nt_token = nt
        self._nt_epoch = self.node_epoch
        self._nt_len = nt.num_nodes if nt is not None else -1
        self._pending_adds = 0
        self._pending_removes = 0
        self._pending_full = False
        if invalidated:
            self.invalidations += 1
        return invalidated

    def _extend_rows(self, nt, start: int) -> None:
        """Append the columns for nodes [start:) to every cached filter /
        score row: each row is a pure function of (node facts, stored
        pod's signature), so the delta columns are built against a VIEW of
        only the appended nodes and concatenated — bit-identical to a
        fresh full-width build, at O(templates × Δnodes) cost."""
        from . import encoder as enc

        d_nt = _delta_tensors(nt, start)
        d_ctx = build_node_ctx(d_nt)
        ctx = self._ctx
        if ctx is not None:
            ctx.node_taints.extend(d_ctx.node_taints)
            ctx.tainted_nodes.extend(
                (start + i, tt) for i, tt in d_ctx.tainted_nodes
            )
            ctx.node_unsched = np.concatenate(
                [ctx.node_unsched, d_ctx.node_unsched]
            )
            ctx.any_unsched = bool(ctx.any_unsched or d_ctx.any_unsched)
            if d_ctx.node_feature_sets is not None and (
                ctx.node_feature_sets is None
            ):
                # first declaring node arrived in the delta: the hoist
                # needs per-node sets for the OLD nodes too — rebuild
                self._ctx = build_node_ctx(nt)
            elif ctx.node_feature_sets is not None:
                ctx.node_feature_sets.extend(
                    d_ctx.node_feature_sets
                    if d_ctx.node_feature_sets is not None
                    else [set() for _ in range(nt.num_nodes - start)]
                )
        fd = self._filter_rows._d
        for key in list(fd.keys()):
            row, trivial, pod = fd[key]
            _fsig, feat_req, _nn, unknown, f = key
            delta = enc.build_static_filter_row(
                d_nt, d_ctx, pod, f, feat_req, unknown
            )
            fd[key] = (
                np.concatenate([row, delta]),
                bool(trivial and delta.all()),
                pod,
            )
            self.extended_bytes += delta.nbytes
        sd = self._score_rows._d
        for key in list(sd.keys()):
            na, tt, pod = sd[key]
            _ssig, want_na, want_tt = key
            dna, dtt = enc.build_static_score_rows(
                d_nt, d_ctx, pod, want_na, want_tt
            )
            sd[key] = (
                np.concatenate([na, dna]), np.concatenate([tt, dtt]), pod,
            )
            self.extended_bytes += dna.nbytes + dtt.nbytes

    def _removal_keep(self, nt) -> "np.ndarray | None":
        """Map the rebuilt tensors' node names back to old row indices:
        ``keep[j]`` = the old index of new node j. None when the mapping
        is not a pure survivor gather — an unknown name means the wave
        also ADDED a node (mixed: wholesale), and a stale old token
        (mutated past the rows' length) can't be trusted as the source
        axis."""
        old_names = getattr(self._nt_token, "node_names", None)
        if old_names is None or len(old_names) != self._nt_len:
            return None
        if nt.num_nodes >= len(old_names):
            return None     # nothing was removed — not a drain wave
        pos = {name: i for i, name in enumerate(old_names)}
        keep = np.empty(nt.num_nodes, dtype=np.int64)
        for j, name in enumerate(nt.node_names):
            i = pos.get(name)
            if i is None:
                return None
            keep[j] = i
        return keep

    def _compact_rows(self, nt, keep: np.ndarray) -> None:
        """Gather the survivor columns out of every cached row (and the
        hoisted node ctx / group count vectors): ``row[keep]`` reorders
        old columns into the new axis order, which is bit-identical to
        rebuilding each row against the new tensors because rows are
        pure per-node functions and a removal-only wave changes no
        survivor's facts."""
        old_n = self._nt_len
        ctx = self._ctx
        if ctx is not None:
            ctx.node_taints = [ctx.node_taints[i] for i in keep]
            ctx.tainted_nodes = [
                (j, tt) for j, tt in enumerate(ctx.node_taints) if tt
            ]
            ctx.node_unsched = ctx.node_unsched[keep]
            ctx.any_unsched = bool(ctx.node_unsched.any())
            if ctx.node_feature_sets is not None:
                nfs = [ctx.node_feature_sets[i] for i in keep]
                # fresh build_node_ctx collapses to None when no node
                # declares features — match it so downstream branches
                # (feature filter on/off) stay identical
                ctx.node_feature_sets = nfs if any(nfs) else None
        fd = self._filter_rows._d
        for key in list(fd.keys()):
            row, _trivial, pod = fd[key]
            row2 = row[keep]
            fd[key] = (row2, bool(row2.all()), pod)
            self.compacted_bytes += max(row.nbytes - row2.nbytes, 0)
        sd = self._score_rows._d
        for key in list(sd.keys()):
            na, tt, pod = sd[key]
            na2, tt2 = na[keep], tt[keep]
            sd[key] = (na2, tt2, pod)
            self.compacted_bytes += max(
                na.nbytes + tt.nbytes - na2.nbytes - tt2.nbytes, 0
            )
        # the incremental template-group index rides along: gather its
        # count vectors and drop the removed nodes' per-node entries, so
        # the next pod_groups() stays O(Δ) instead of re-deriving every
        # node after the drain wave
        if (
            self._groups_nt is self._nt_token
            and self._groups_epoch == self._full_epoch
        ):
            vecs = self._group_vecs
            for gid, vec in list(vecs.items()):
                if len(vec) < old_n:
                    vec = np.concatenate(
                        [vec, np.zeros(old_n - len(vec), dtype=np.int64)]
                    )
                vecs[gid] = vec[keep]
            gone = set(getattr(self._nt_token, "node_names", ())) - set(
                nt.node_names
            )
            for name in gone:
                self._group_node.pop(name, None)
                self._group_gens.pop(name, None)
            self._groups_nt = nt

    def fresh_for(self, nt) -> bool:
        """May event-time precompute build rows against ``nt`` right now?
        Only when ``nt`` is the adopted tensors AND no node event landed
        since they were encoded (a bumped epoch means ``nt`` no longer
        reflects the node set — rows built from it would be stale)."""
        return (
            nt is not None
            and self._nt_token is nt
            and self._nt_epoch == self.node_epoch
            and self._nt_len == nt.num_nodes
        )

    def node_ctx(self, nt) -> NodeCtx:
        ctx = self._ctx
        if ctx is None or self._nt_token is not nt:
            ctx = build_node_ctx(nt)
            if self._nt_token is nt:
                self._ctx = ctx
        return ctx

    def sync_namespaces(self, ns_gen: int) -> None:
        """Namespace labels feed affinity-term namespaceSelectors — any
        namespace change invalidates the persistent match verdicts."""
        if self._ns_gen != ns_gen:
            if self._ns_gen is not None:
                self.match.clear()
                self.invalidations += 1
            self._ns_gen = ns_gen

    def sync_request_axis(self, axis: tuple, folded: frozenset) -> None:
        """Request rows are laid out on the batch's resource axis; the
        ``unknown`` flag additionally depends on the folded set. A changed
        (axis, folded) token clears the request-row store."""
        token = (axis, folded)
        if self._req_token != token:
            self._request_rows.clear()
            self._req_token = token

    # ----------------------------------------------------- row accessors
    # Entries carry a representative POD alongside the row: any pod whose
    # signature hashes to the key rebuilds the identical row (rows are pure
    # functions of the key + node facts), which is what lets a scoped node
    # ADD extend cached rows with freshly built delta columns.
    def filter_row(self, key, build: Callable[[], np.ndarray], pod=None):
        """(row, trivial) for a pure-static filter signature key."""
        got = self._filter_rows.get(key)
        if got is not None:
            self.hits["filter"] += 1
            return got[0], got[1]
        self.misses["filter"] += 1
        row = build()
        self.rebuilt_bytes += row.nbytes
        entry = (row, bool(row.all()))
        self._filter_rows.put(key, entry + (pod,))
        return entry

    def score_row(self, key, build: Callable[[], tuple], pod=None):
        got = self._score_rows.get(key)
        if got is not None:
            self.hits["score"] += 1
            return got[0], got[1]
        self.misses["score"] += 1
        entry = build()
        self.rebuilt_bytes += entry[0].nbytes + entry[1].nbytes
        self._score_rows.put(key, entry + (pod,))
        return entry

    def request_row(self, key, build: Callable[[], tuple]):
        got = self._request_rows.get(key)
        if got is not None:
            self.hits["request"] += 1
            return got
        self.misses["request"] += 1
        entry = build()
        self._request_rows.put(key, entry)
        return entry

    # ------------------------------------------------- per-pod signatures
    def pod_sigs(self, pod) -> tuple:
        """(filter_sig, score_sig) for ``pod``, memoized by uid and
        verified by OBJECT IDENTITY — an informer update replaces the pod
        object, so a stale memo can never answer for a mutated pod."""
        from .encoder import _static_filter_signature, _static_score_signature

        got = self._pod_sigs.get(pod.uid)
        if got is not None and got[0] is pod:
            self.hits["pod_sig"] += 1
            return got[1], got[2]
        self.misses["pod_sig"] += 1
        fsig = _static_filter_signature(pod)
        ssig = _static_score_signature(pod)
        self._pod_sigs.put(pod.uid, (pod, fsig, ssig))
        return fsig, ssig

    def drop_pod(self, uid: str) -> None:
        self._pod_sigs.pop(uid, None)
        self._pod_group_ids.pop(uid, None)

    # ------------------------------------------------ event-time pre-encode
    def precompute_pod(self, nt, pod, enabled_filters, enabled_scores) -> bool:
        """Event-time hook: build (or touch) the pod's static rows NOW, off
        the cycle critical path. No-op unless ``fresh_for(nt)`` — after a
        node event the rows must wait for the next cycle's re-adopt.
        Returns True when the rows are present afterwards."""
        from . import encoder as enc

        if not self.fresh_for(nt):
            return False
        fsig, ssig = self.pod_sigs(pod)
        ctx = self.node_ctx(nt)
        f = enc.names.ALL_FILTERS if enabled_filters is None else enabled_filters
        # request row first: its ``unknown`` verdict is part of the filter
        # key (only possible once a batch has established the axis token)
        unknown = False
        if self._req_token is not None:
            axis, folded = self._req_token
            ridx = {r: i for i, r in enumerate(axis)}
            key = (pod.requests, pod.nonzero, ())
            entry = self.request_row(
                key,
                lambda: enc.build_request_row(pod, ridx, len(axis), folded, ()),
            )
            unknown = entry[2]
        feat_req = (
            pod.required_node_features
            if enc.names.NODE_DECLARED_FEATURES in f else ()
        )
        fkey = (
            fsig, feat_req,
            pod.node_name if enc.names.NODE_NAME in f else "",
            bool(unknown) and enc.names.NODE_RESOURCES_FIT in f,
            f,   # the RESOLVED set — must match the batch encoder's key
        )
        self.filter_row(
            fkey,
            lambda: enc.build_static_filter_row(
                nt, ctx, pod, f, feat_req, fkey[3]
            ),
            pod,
        )
        sc = (
            enc.DEFAULT_SCORES if enabled_scores is None else enabled_scores
        )
        want_na = enc.names.NODE_AFFINITY in sc
        want_tt = enc.names.TAINT_TOLERATION in sc
        if want_na or want_tt:
            skey = (ssig, want_na, want_tt)
            self.score_row(
                skey,
                lambda: enc.build_static_score_rows(
                    nt, ctx, pod, want_na, want_tt
                ),
                pod,
            )
        return True

    # ------------------------------------------------ template-group index
    def _gid_of_key(self, key) -> int:
        gid = self._group_ids.get(key)
        if gid is None:
            gid = len(self._group_keys)
            self._group_ids[key] = gid
            self._group_keys.append(key)
        return gid

    def group_id_of(self, pod) -> int:
        """Small-int id of a PENDING pod's template — the deep key hash is
        paid once per pod OBJECT (uid-memoized, identity-checked), after
        which template membership is an int. Bound pods are keyed by
        ``pod_groups``' own per-node record instead."""
        got = self._pod_group_ids.get(pod.uid)
        if got is not None and got[0] is pod:
            return got[1]
        gid = self._gid_of_key(template_key(pod))
        self._pod_group_ids.put(pod.uid, (pod, gid))
        return gid

    def pod_groups(self, nt) -> dict:
        """``collect_pod_groups(nt)``, maintained by pod deltas: each node
        whose generation moved diffs its pods against the ones it counted,
        by uid. The same object keeps its gid; a new object with the same
        template fields (the assumed copy, the informer's ``with_node``
        rebuild) keeps it too; only a pod not seen before, or one whose
        template fields changed, is keyed; a uid that left is subtracted —
        O(Δ pods) keys a cycle, whatever the pods per node. The count
        vectors are rebuilt from the per-node counts when the tensors were
        replaced or a FULL-epoch flush landed (update/delete), without
        re-keying a pod; scoped node ADDS just grow them in place.
        Returned vectors are LIVE index state — callers must not mutate
        them."""
        if len(self._group_keys) > (1 << 16):
            # template-id interning ran away (per-pod-unique labels): reset
            # the whole index — gids are invalidated with it
            self._group_ids = {}
            self._group_keys = []
            self._pod_group_ids.clear()
            self._group_node = {}
            self._groups_nt = None
        N = nt.num_nodes
        rebuild = (
            self._groups_nt is not nt or self._groups_epoch != self._full_epoch
        )
        if rebuild:
            # the node axis may have moved: zero the vectors and revisit
            # every node, but keep what each node NAME counted — a pod's
            # gid does not depend on the node axis
            self._group_vecs = {}
            self._group_gens = {}
            names = set(nt.node_names)
            for name in [n for n in self._group_node if n not in names]:
                del self._group_node[name]
            self._groups_nt = nt
            self._groups_epoch = self._full_epoch
        gens = nt.node_gens
        vecs = self._group_vecs
        seen_gens = self._group_gens
        group_node = self._group_node
        # scoped node ADDS grow the node axis in place: extend the count
        # vectors with zeros (appended nodes' pods fold in via the gens
        # loop below — their generations are unseen)
        for gid, vec in list(vecs.items()):
            if len(vec) < N:
                vecs[gid] = np.concatenate(
                    [vec, np.zeros(N - len(vec), dtype=np.int64)]
                )
        walked = keyed = 0
        for i, info in enumerate(nt.infos):
            name = nt.node_names[i]
            g = gens.get(name)
            if seen_gens.get(name) == g:
                continue
            seen_gens[name] = g
            pods = info.pods
            entry = group_node.get(name)
            if entry is None:
                if not pods:
                    continue
                entry = group_node[name] = ({}, {}, {})
            seen, gids, counts = entry
            delta: dict = {}
            # a node the cycle touched holds its other pods as the very
            # objects it counted: only the rest are looked at
            get = seen.get
            for uid, q in [
                (u, q) for u, q in pods.items() if get(u) is not q
            ]:
                p = get(uid)
                seen[uid] = q
                if p is not None and _same_template(p, q):
                    continue
                gid = self._gid_of_key(template_key(q))
                keyed += 1
                old = gids.get(uid)
                gids[uid] = gid
                if gid != old:
                    if old is not None:
                        delta[old] = delta.get(old, 0) - 1
                    delta[gid] = delta.get(gid, 0) + 1
            walked += len(pods)
            if len(seen) > len(pods):
                # some counted uid left the node: subtract it
                for uid in [u for u in seen if u not in pods]:
                    del seen[uid]
                    gid = gids.pop(uid)
                    delta[gid] = delta.get(gid, 0) - 1
            for gid, d in delta.items():
                if d:
                    c = counts.get(gid, 0) + d
                    if c:
                        counts[gid] = c
                    else:
                        del counts[gid]
            # a rebuild's vectors start at zero: fold the node's whole
            # counts in, else only what changed
            for gid, c in (counts if rebuild else delta).items():
                if c:
                    vec = vecs.get(gid)
                    if vec is None:
                        vec = np.zeros(N, dtype=np.int64)
                        vecs[gid] = vec
                    vec[i] += c
            if not seen:
                del group_node[name]
        self.index_pods["kept"] += walked - keyed
        self.index_pods["keyed"] += keyed
        return {
            self._group_keys[gid]: v for gid, v in vecs.items() if v.any()
        }

    # ----------------------------------------------------------- metrics
    def stats(self) -> dict:
        h, m = sum(self.hits.values()), sum(self.misses.values())
        return {
            "hits": h,
            "misses": m,
            "hit_rate": (h / (h + m)) if (h + m) else None,
            "entries": len(self._filter_rows) + len(self._score_rows)
            + len(self._request_rows),
            "invalidations": self.invalidations,
            # re-encode work: bytes built from scratch on misses vs bytes
            # appended by scoped extensions (the node-wave evidence the
            # tier-1 scoped-vs-flush test and trace records assert on)
            "rebuilt_bytes": self.rebuilt_bytes,
            "extended_bytes": self.extended_bytes,
            "scoped_extensions": self.scoped_extensions,
            "scoped_removals": self.scoped_removals,
            "compacted_bytes": self.compacted_bytes,
        }

    def hit_rate(self, kinds=("filter", "score", "request")) -> float | None:
        h = sum(self.hits[k] for k in kinds)
        m = sum(self.misses[k] for k in kinds)
        return (h / (h + m)) if (h + m) else None

    def flush_metrics(self) -> dict:
        """Mirror the counter deltas since the last flush into the prom
        registry (TPUBackendMetrics) and return them — the scheduler calls
        this once per cycle and attaches the deltas to the encode span."""
        delta = {"hits": 0, "misses": 0}
        for kind in set(self.hits) | set(self._flushed_hits):
            d = self.hits[kind] - self._flushed_hits[kind]
            if d:
                delta["hits"] += d
                self._flushed_hits[kind] = self.hits[kind]
                if self.metrics is not None:
                    self.metrics.encode_cache_hits.labels(kind).inc(d)
        for kind in set(self.misses) | set(self._flushed_misses):
            d = self.misses[kind] - self._flushed_misses[kind]
            if d:
                delta["misses"] += d
                self._flushed_misses[kind] = self.misses[kind]
                if self.metrics is not None:
                    self.metrics.encode_cache_misses.labels(kind).inc(d)
        for result, n in self.index_pods.items():
            d = n - self._flushed_index_pods[result]
            if d:
                self._flushed_index_pods[result] = n
                if self.metrics is not None:
                    self.metrics.template_index_pods.labels(result).inc(d)
        inv = self.invalidations - self._flushed_invalidations
        if inv:
            delta["invalidations"] = inv
            self._flushed_invalidations = self.invalidations
        if self.metrics is not None:
            self.metrics.encode_cache_entries.set(self.stats()["entries"])
        return delta


def _delta_tensors(nt, start: int):
    """A minimal NodeTensors VIEW over only the appended nodes
    [start:num_nodes) — just what the static row builders consume (names,
    infos, label machinery; resource arrays are not read by them). Fresh
    vocabs: the view is self-contained, ids never leak into ``nt``."""
    from .encoder import NodeTensors

    d = nt.num_nodes - start
    z2 = np.zeros((d, 0), dtype=np.int64)
    sub = NodeTensors(
        resource_names=[],
        node_names=list(nt.node_names[start:]),
        alloc=z2,
        requested=z2,
        nonzero_requested=z2,
        pod_count=np.zeros(d, dtype=np.int32),
        allowed_pods=np.zeros(d, dtype=np.int32),
        infos=list(nt.infos[start:]),
    )
    # intern the appended nodes' labels (the full build does this too) —
    # requirement_mask treats an un-interned key as absent-on-every-node,
    # which would extend selector/affinity rows with all-False columns
    for info in sub.infos:
        for k, v in info.node.labels:
            sub.key_vocab.intern(k)
            sub.val_vocab.intern(v)
    return sub


def groups_for(nt, cache, groups: dict | None = None) -> dict:
    """The template-group view for an encode: the precomputed ``groups``
    when the caller already built them, else the cache's incremental index,
    else a from-scratch pass. The single place that decides."""
    if groups is not None:
        return groups
    if cache is not None:
        return cache.pod_groups(nt)
    return collect_pod_groups(nt)


def pod_gids_for(pods, cache) -> list:
    """Per-pod template ids for a pending batch: the cache's uid-memoized
    global ids, or call-local first-seen ids when no cache is wired."""
    if cache is not None:
        return [cache.group_id_of(p) for p in pods]
    local: dict = {}
    return [
        local.setdefault(template_key(p), len(local)) for p in pods
    ]


def collapse_label_groups(groups: dict) -> dict:
    """Collapse template groups to ``{(labels, ns): [counts, labels
    dict]}`` — the view selector matching consumes (selectors never look
    past the counted pod's labels and namespace)."""
    out: dict = {}
    for key, vec in groups.items():
        got = out.get(key[:2])
        if got is None:
            out[key[:2]] = [vec.copy(), dict(key[0])]
        else:
            got[0] += vec
    return out


def collect_pod_groups(nt) -> dict:
    """One pass over the snapshot's assigned pods, grouped by TEMPLATE:
    ``{template_key(pod): (N,) int64 per-node counts}``.

    Pods stamped from one controller template share the key, so the group
    count is tiny regardless of pod count — the per-(existing pod × row)
    Python loops in ``state.podaffinity`` / ``state.spread`` collapse to
    per-(template × row) numpy segment sums over these vectors. O(total
    assigned pods) dict work, no row logic per pod. (``EncodeCache.
    pod_groups`` is the incremental O(Δ) twin of this function.)"""
    N = nt.num_nodes
    groups: dict = {}
    for n_i, info in enumerate(nt.infos):
        for q in info.pods.values():
            key = template_key(q)
            vec = groups.get(key)
            if vec is None:
                vec = np.zeros(N, dtype=np.int64)
                groups[key] = vec
            vec[n_i] += 1
    return groups


