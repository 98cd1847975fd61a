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
topology domains — see state.podaffinity); the kernels are pure gathers.
"""

from __future__ import annotations

import jax.numpy as jnp

MAX_NODE_SCORE = 100


def _slot_counts(pa, sums, rid):
    """(N,) count at each node's domain for one row id (0 where key absent;
    garbage-safe for rid < 0 — callers gate on validity)."""
    r = jnp.maximum(rid, 0)
    dom = pa.node_domain[r]
    return jnp.where(dom >= 0, sums[r][jnp.maximum(dom, 0)], 0)


def affinity_filter_pod(pa, sums, fa_rows, fa_self, ra_rows, ea_rows):
    """(N,) bool for ONE pod. ``fa_rows (CA,)``, ``ra_rows (CR,)``,
    ``ea_rows (CE,)`` are the pod's row-id slots (−1 unused); every kernel
    cost is O(slots × N), independent of the global row count."""
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
        set_total = set_total + jnp.where(valid, jnp.sum(sums[r]), 0)
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
    feasibility row. ``score_rows/score_vals (CS,)`` are the pod's weighted
    row slots."""
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
