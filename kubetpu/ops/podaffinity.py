"""InterPodAffinity device kernels.

Reference checks (pkg/scheduler/framework/plugins/interpodaffinity/):
- Filter (filtering.go:364-419): existing-pods anti-affinity (any node label
  pair with count > 0 → infeasible), incoming anti-affinity (count > 0 at the
  node's domain for any term → infeasible), incoming affinity (every term's
  count > 0 where all term keys exist; self-affinity escape when the global
  map is empty and the pod matches its own terms, filtering.go:414).
- Score (scoring.go:240): Σ over topology maps at the node's values, then
  min-max normalize over filtered nodes (scoring.go:258), 0 when max == min.
  As the code computes it: ``int64(float64(100 · (s − min)) / float64(max −
  min))``, the multiplication FIRST and in integers, then one float64
  division, then truncation. ``benchmark/reference/oracle.py``
  ``interpod_scores`` computes the same (``int(MAX * (raw − mn) / (mx −
  mn))``), and the two are compared exactly
  (``tests/test_preferredaffinity_served.py``, on the CPU in tier-1 and on
  the chip by hand). Upstream's order is an open question until
  ``/root/reference`` is on a machine: if scoring.go divides first
  (``float64(MaxNodeScore) * (float64(s − min) / float64(max − min))``) the
  two orders differ by one at 8 of the 20,300 pairs 0 ≤ s ≤ d ≤ 200 (29/50,
  29/100, 57/100, 58/100, 87/150, 58/200, 114/200, 116/200; PERF.md 7).

All counts live in the carried ``sums (R, D)`` state (interned count rows ×
topology domains — see state.podaffinity). The count a kernel reads at a node
depends on the ROW and not on the pod, and an element gather costs the chip
10–27 ns (PERF.md 6, PR 37). So where the batch is wide (``table_pays``: R ≤
P × slots, static shapes) the counts are read from ``NodeCounts``, the per-row
node table ``at_node[r, n] = sums[r, node_domain[r, n]]``: R × N element
gathers build it once per evaluation (``node_counts``), the greedy scan
carries it and keeps it true with a compare and an add (``node_counts_add``),
and a pod's slot takes the table's row ``rid``. A narrow caller (one pod
against many rows: preemption's re-check) gathers its own slots × N.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

MAX_NODE_SCORE = 100


class NodeCounts(NamedTuple):
    """``sums (R, D)`` as the kernels read it."""

    at_node: jnp.ndarray     # (R, N) int64, 0 where the node lacks the key
    row_total: jnp.ndarray   # (R,) int64 — sums.sum(axis=1)


def kernel_slots(pa) -> int:
    """CA + CR + CE + CS: the row-id slots one pod of the batch hands the
    two kernels (a static shape)."""
    return sum(rows.shape[-1] for rows in (
        pa.fa_rows, pa.ra_rows, pa.ea_rows, pa.score_rows))


def table_pays(pa) -> bool:
    """Whether ``node_counts`` gathers no more elements (R × N) than the
    kernels would without it (P × slots × N). Static shapes only. On the
    chip (PERF.md 6, PR 37): 1024 pods × 5–6 slots over 3 rows, the table 9
    to 12 times faster; one pod over 64 and 512 rows, the gather 17 and 160
    times faster."""
    return pa.node_domain.shape[0] <= pa.fa_rows.shape[0] * kernel_slots(pa)


def node_counts(pa, sums) -> NodeCounts:
    with jax.named_scope("interpod_node_counts"):
        dom = pa.node_domain
        at = jnp.take_along_axis(sums, jnp.maximum(dom, 0), axis=1)
        return NodeCounts(jnp.where(dom >= 0, at, 0), sums.sum(axis=1))


def node_counts_add(pa, counts: NodeCounts, dcol, inc) -> NodeCounts:
    """``counts`` after ``inc[r]`` went into ``sums[r, dcol[r]]`` for every
    row (``inc[r]`` is 0 where ``dcol[r] < 0``, so the nodes that lack the
    key stay 0): every node of that domain reads the new sum."""
    with jax.named_scope("interpod_node_counts"):
        same = pa.node_domain == dcol[:, None]
        return NodeCounts(
            counts.at_node + jnp.where(same, inc[:, None], 0),
            counts.row_total + inc,
        )


def _slot_counts(pa, sums, rid):
    """(N,) count at each node's domain for one row id (0 where key absent;
    garbage-safe for rid < 0 — callers gate on validity). ``sums`` is the
    (R, D) state, gathered here, or its ``NodeCounts``, read."""
    r = jnp.maximum(rid, 0)
    if isinstance(sums, NodeCounts):
        return sums.at_node[r]
    dom = pa.node_domain[r]
    return jnp.where(dom >= 0, sums[r][jnp.maximum(dom, 0)], 0)


def _row_total(sums, r):
    if isinstance(sums, NodeCounts):
        return sums.row_total[r]
    return jnp.sum(sums[r])


def affinity_filter_pod(pa, sums, fa_rows, fa_self, ra_rows, ea_rows):
    """(N,) bool for ONE pod. ``sums`` is the (R, D) state or its
    ``NodeCounts``. ``fa_rows (CA,)``, ``ra_rows (CR,)``, ``ea_rows (CE,)``
    are the pod's row-id slots (−1 unused); every kernel cost is O(slots ×
    N), independent of the global row count."""
    n = pa.node_domain.shape[1]

    # incoming required affinity (satisfyPodAffinity)
    keys_ok = jnp.ones(n, dtype=bool)
    pods_exist = jnp.ones(n, dtype=bool)
    set_total = jnp.int64(0)
    any_fa = jnp.any(fa_rows >= 0)
    for c in range(fa_rows.shape[0]):
        rid = fa_rows[c]
        valid = rid >= 0
        r = jnp.maximum(rid, 0)
        cnt = _slot_counts(pa, sums, rid)
        keys_ok = keys_ok & jnp.where(valid, pa.has_key[r], True)
        pods_exist = pods_exist & jnp.where(valid, cnt > 0, True)
        set_total = set_total + jnp.where(valid, _row_total(sums, r), 0)
    escape = (set_total == 0) & fa_self
    fa_ok = jnp.where(any_fa, keys_ok & (pods_exist | escape), True)

    # incoming required anti-affinity (satisfyPodAntiAffinity)
    ra_ok = jnp.ones(n, dtype=bool)
    for c in range(ra_rows.shape[0]):
        rid = ra_rows[c]
        valid = rid >= 0
        r = jnp.maximum(rid, 0)
        cnt = _slot_counts(pa, sums, rid)
        ra_ok = ra_ok & jnp.where(valid, ~(pa.has_key[r] & (cnt > 0)), True)

    # existing pods' anti-affinity (satisfyExistingPodsAntiAffinity): only
    # rows whose term matches this pod are in its ea slots
    affected = jnp.zeros(n, dtype=bool)
    for c in range(ea_rows.shape[0]):
        rid = ea_rows[c]
        valid = rid >= 0
        cnt = _slot_counts(pa, sums, rid)
        affected = affected | jnp.where(valid, cnt > 0, False)

    return fa_ok & ra_ok & ~affected


def affinity_score_pod(pa, sums, score_rows, score_vals, mask):
    """(N,) int64 normalized InterPodAffinity score for ONE pod given its
    feasibility row. ``sums`` is the (R, D) state or its ``NodeCounts``;
    ``score_rows/score_vals (CS,)`` are the pod's weighted row slots."""
    n = pa.node_domain.shape[1]
    raw = jnp.zeros(n, dtype=jnp.int64)
    for c in range(score_rows.shape[0]):
        rid = score_rows[c]
        valid = rid >= 0
        cnt = _slot_counts(pa, sums, rid)
        raw = raw + jnp.where(valid, score_vals[c] * cnt, 0)
    big = jnp.iinfo(jnp.int64).max
    mn = jnp.min(jnp.where(mask, raw, big))
    mx = jnp.max(jnp.where(mask, raw, -big))
    diff = mx - mn
    f = (
        MAX_NODE_SCORE
        * (raw - mn).astype(jnp.float64)
        / jnp.maximum(diff, 1).astype(jnp.float64)
    )
    out = jnp.where(diff > 0, f.astype(jnp.int64), 0)
    return jnp.where(mask, out, 0)
