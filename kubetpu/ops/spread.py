"""PodTopologySpread device kernels.

Reference semantics (pkg/scheduler/framework/plugins/podtopologyspread/):
- Filter (filtering.go:314): per DoNotSchedule constraint,
  ``matchNum + selfMatch − minMatch > maxSkew`` → infeasible; nodes missing
  the topology key are infeasible outright. ``minMatch`` is the minimum
  per-domain match count over counted domains, treated as 0 when
  ``len(domains) < minDomains`` (filtering.go:55 minMatchNum).
- Score (scoring.go:199): per ScheduleAnyway constraint,
  ``cnt·log(size+2) + (maxSkew−1)`` summed over constraints, rounded; then
  the plugin's own NormalizeScore (scoring.go:229):
  ``MaxNodeScore·(max+min−s)/max`` over scored nodes, ignored → 0,
  max==0 → MaxNodeScore.

All kernels take the carried per-(signature, node) match-count state
(``counts``) so in-batch assignments (greedy scan) reproduce the reference's
updateWithPod (filtering.go:181) exactly. Per-domain sums are segment-sums of
``counts`` over the interned domain ids; domain id −1 (node ineligible /
value not counted) routes to a scratch segment and reads back matchNum 0 via
the Go-map-miss convention.

Where the soft score runs: ``framework.runtime.feasible_and_scores`` calls
``spread_score_pod`` wherever the batch's static ``has_soft`` is set, under
``jax.named_scope("spread_score")``: once per STEP of the greedy scan
(``assign/greedy.py``, over the counts the step before left) and once per
cycle at (P, N) in the flight recorder's explain program
(``sched/flightrecorder.py``, ``jit_explain_kernel`` in a trace). The
reference's arithmetic is float64 (``log``, multiply-add, ``round``) and the
benchmark's cell ``preferredspread-5k.saturate`` holds it to the scalar oracle
ON THE CHIP, pod for pod, at some 24,000 matching pods a zone;
``tests/test_preferredspread_served.py`` compares the normalised scores
exactly at such counts. A TPU has no float64, and a v5e's emulation lost the
fraction before ``round``: ``round(count * log(size + 2) + 4)`` came out a
unit off for one count in 70 at size 1 and one in 9 at size 5000, a whole
zone scored wrong at (23872, 29489, 35107) matching pods a zone, and the
cell's parity failed in one run of 26 (PR 34). So no float reaches the device:
``log(size + 2)`` is the host's own ``math.log`` as 58-bit fixed point
(``_log_size_table``; the size is an integer in 0..N, the device gathers) and
the multiply-add and the rounding are int64, exact for counts under 2**29.
"""

from __future__ import annotations

import fractions
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MAX_NODE_SCORE = 100
_BIG = jnp.iinfo(jnp.int32).max


#: fixed point of ``log(size + 2)``: 58 fraction bits, held as a high word and
#: a low word of 29 bits so that count * word stays inside int64
_FRAC = 58
_SPLIT = 29


def _fixed_point(weights) -> np.ndarray:
    """(len, 2) int64: ``floor(w * 2**58)`` of each double's exact value, as
    ``(w >> 29, w & (2**29 - 1))``."""
    words = [int(fractions.Fraction(w) * (1 << _FRAC)) for w in weights]
    return np.array([(w >> _SPLIT, w & ((1 << _SPLIT) - 1)) for w in words],
                    dtype=np.int64)


@functools.lru_cache(maxsize=None)
def _log_size_table(sizes: int) -> np.ndarray:
    """``log(size + 2)`` for every topology size 0..sizes-1 (scoring.go
    topologyNormalizingWeight) by the host's ``math.log``, in fixed point: a
    constant of the compiled program."""
    return _fixed_point(math.log(size + 2.0) for size in range(sizes))


def _times_log_size(cnt, weight):
    """``cnt * log(size + 2)`` as ``(whole, fraction in units of 2**-58)``,
    both int64, exact in the table's 58 bits for 0 <= cnt < 2**29."""
    high = cnt * weight[0]
    low = ((high & ((1 << _SPLIT) - 1)) << _SPLIT) + cnt * weight[1]
    return (high >> _SPLIT) + (low >> _FRAC), low & ((1 << _FRAC) - 1)


def _rounded(whole, frac):
    """``round(whole + frac * 2**-58)``. A fraction of exactly one half, which
    no count above 0 gives, would round up where Python rounds to even."""
    return whole + ((frac + (1 << (_FRAC - 1))) >> _FRAC)


def _domain_sums(counts_s, eligible_s, node_domain_s, num_domains_total):
    """(D+1,) per-domain match sums for one signature; slot D is the −1
    scratch bucket."""
    seg = jnp.where(node_domain_s >= 0, node_domain_s, num_domains_total)
    vals = jnp.where(eligible_s, counts_s, 0)
    return jax.ops.segment_sum(vals, seg, num_segments=num_domains_total + 1)


def spread_filter_pod(st, counts, sig_idx, action, max_skew, min_domains, self_match):
    """(N,) bool feasibility for ONE pod's hard constraints.

    ``st`` is the device SpreadTensors pytree; ``counts`` the (S, N) carried
    state; the remaining args are the pod's (C,) constraint-slot rows.
    """
    n = st.eligible.shape[1]
    d = st.domain_present.shape[1]
    ok = jnp.ones(n, dtype=bool)
    C = sig_idx.shape[0]
    for c in range(C):  # C is a small static bound; unrolled
        sid = sig_idx[c]
        valid = (sid >= 0) & (action[c] == 0)
        s = jnp.maximum(sid, 0)
        elig = st.eligible[s]
        dom = st.node_domain[s]
        sums = _domain_sums(counts[s], elig, dom, d)          # (D+1,)
        present = st.domain_present[s]
        min_match = jnp.min(jnp.where(present, sums[:d], _BIG))
        min_match = jnp.where(
            st.num_domains[s] < min_domains[c], 0, min_match
        )
        match_num = jnp.where(dom >= 0, sums[jnp.where(dom >= 0, dom, d)], 0)
        skew_ok = (match_num + self_match[c] - min_match) <= max_skew[c]
        ok_c = st.has_key[s] & skew_ok
        ok = ok & jnp.where(valid, ok_c, True)
    return ok


def spread_score_pod(
    st, counts, sig_idx, action, max_skew, ignored, mask
):
    """(N,) int64 normalized spread score for ONE pod.

    ``mask`` is the pod's final feasibility row (the reference scores only
    nodes that passed Filter); ``ignored`` its soft-ignored row.
    """
    n = st.eligible.shape[1]
    d = st.domain_present.shape[1]
    scored = mask & ~ignored
    whole = jnp.zeros(n, dtype=jnp.int64)
    frac = jnp.zeros(n, dtype=jnp.int64)
    C = sig_idx.shape[0]
    for c in range(C):
        sid = sig_idx[c]
        valid = (sid >= 0) & (action[c] == 1)
        s = jnp.maximum(sid, 0)
        elig = st.eligible[s]
        dom = st.node_domain[s]
        sums = _domain_sums(counts[s], elig, dom, d)
        # per-node count: hostname constraints read the node's own count
        # (scoring.go:217), others the node's domain sum
        cnt_node = jnp.where(
            st.is_hostname[s],
            counts[s].astype(jnp.int64),
            jnp.where(dom >= 0, sums[jnp.where(dom >= 0, dom, d)], 0),
        )
        # topology size over *scored* nodes (initPreScoreState topoSize /
        # filteredNodes−ignored for hostname)
        seg = jnp.where(dom >= 0, dom, d)
        present_scored = (
            jax.ops.segment_max(
                scored.astype(jnp.int32), seg, num_segments=d + 1
            )[:d]
            > 0
        )
        size = jnp.where(
            st.is_hostname[s],
            jnp.sum(scored),
            jnp.sum(present_scored),
        )
        # size <= the scored nodes or the domains present: a row of the table
        weight = jnp.asarray(_log_size_table(max(n, d) + 1))[size]
        cnt_whole, cnt_frac = _times_log_size(cnt_node, weight)
        counted = valid & st.has_key[s]
        whole = whole + jnp.where(
            counted, cnt_whole + (max_skew[c].astype(jnp.int64) - 1), 0)
        frac = frac + jnp.where(counted, cnt_frac, 0)
    # a fraction is under 2**58, so up to 31 constraints sum inside int64
    score = _rounded(whole, frac)                             # (N,)

    # NormalizeScore (scoring.go:229) over scored nodes
    min_s = jnp.min(jnp.where(scored, score, jnp.iinfo(jnp.int64).max))
    max_s = jnp.max(jnp.where(scored, score, 0))
    normalized = jnp.where(
        max_s == 0,
        jnp.int64(MAX_NODE_SCORE),
        MAX_NODE_SCORE * (max_s + min_s - score) // jnp.maximum(max_s, 1),
    )
    # A pod with no soft constraints Skips the plugin entirely
    # (scoring.go:149 PreScore returns Skip) — 0, not the max==0 branch.
    any_soft = jnp.any((sig_idx >= 0) & (action == 1))
    return jnp.where(any_soft & scored, normalized, 0)
