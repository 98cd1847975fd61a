"""Greedy assignment as one device-resident loop over the pods.

The reference schedules pods strictly one at a time: ``scheduleOne`` pops a
pod, filters + scores all nodes against the *current* cache (which includes
all previously assumed pods), picks the best node (``selectHost``,
schedule_one.go:605), and assumes the pod onto it (cache.AssumePod,
backend/cache/cache.go:397) before the next pod starts. That serialization is
what makes greedy results well-defined on saturated clusters.

Here the same semantics run as a single XLA program: a loop over the pod
axis, carrying ``(requested, nonzero_requested, pod_count)`` node-state
tensors; each step re-runs the full Filter+Score composition for one pod
against the running state and updates it with a one-hot scatter. No
host↔device round-trips inside the batch. The loop ends at the last REAL
pod, so a batch padded to a larger compile bucket (``Scheduler._pod_bucket``)
pays for its own pods' steps and not for the bucket's.

Tie-breaking: the reference picks uniformly at random among max-score nodes
(schedule_one.go:1037 reservoir sample). We take the FIRST max-score node in
snapshot order — deterministic, replayable, and within the documented parity
budget (ties are score-equivalent by definition).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..framework import runtime as rt
from ..ops import podaffinity as PA


def _pod_view(b: rt.DeviceBatch, i) -> rt.DeviceBatch:
    """P=1 view of pod ``i`` (traced index) over the same nodes."""

    def row(a):
        return None if a is None else a[i][None]

    return rt.DeviceBatch(
        # the persistent node block passes through whole (the scan threads
        # its own running node state via the feasible_and_scores overrides)
        nodes=b.nodes,
        requests=b.requests[i][None],
        nonzero_requests=b.nonzero_requests[i][None],
        pod_valid=b.pod_valid[i][None],
        # (S, N) signature arrays pass through whole; the view narrows only
        # the per-pod row indices (device gathers the row inside the kernel)
        static_mask=b.static_mask,
        static_sig=row(b.static_sig),
        node_affinity_raw=b.node_affinity_raw,
        taint_prefer_raw=b.taint_prefer_raw,
        score_sig=row(b.score_sig),
        image_sum_scores=b.image_sum_scores,
        image_sig=row(b.image_sig),
        image_count=row(b.image_count),
        extender_mask=row(b.extender_mask),
        extender_score=row(b.extender_score),
        dra_score_raw=b.dra_score_raw,
        dra_score_sig=row(b.dra_score_sig),
        pod_ports=b.pod_ports[i][None],
        node_ports=b.node_ports,
        port_conflict=b.port_conflict,
        nominated_node=b.nominated_node,
        nominated_req=b.nominated_req,
        nominated_gate=row(b.nominated_gate),
        nominated_ports=b.nominated_ports,
        nominated_pod_idx=b.nominated_pod_idx,
        spread=_spread_view(b.spread, i),
        podaffinity=_pa_view(b.podaffinity, i),
    )


def _pa_view(pa, i):
    if pa is None:
        return None
    import dataclasses

    return dataclasses.replace(
        pa,
        update=pa.update[i][None],
        fa_rows=pa.fa_rows[i][None],
        fa_self=pa.fa_self[i][None],
        ra_rows=pa.ra_rows[i][None],
        ea_rows=pa.ea_rows[i][None],
        score_rows=pa.score_rows[i][None],
        score_vals=pa.score_vals[i][None],
    )


def _spread_view(sp, i):
    if sp is None:
        return None
    import dataclasses

    return dataclasses.replace(
        sp,
        sig_idx=sp.sig_idx[i][None],
        action=sp.action[i][None],
        max_skew=sp.max_skew[i][None],
        min_domains=sp.min_domains[i][None],
        self_match=sp.self_match[i][None],
        pod_match_sig=sp.pod_match_sig[i][None],
        ignored=sp.ignored[i][None],
    )


def greedy_scan(b: rt.DeviceBatch, params: rt.ScoreParams):
    """The scan, not jitted: ``(assignments, final_state, pa_counts)`` where
    ``pa_counts`` is the carried ``ops.podaffinity.NodeCounts`` (None without
    inter-pod affinity): ``final_state[5]`` as the steps read it. See
    ``greedy_assign_device``."""

    n = b.alloc.shape[0]
    node_iota = jnp.arange(n, dtype=jnp.int32)

    def step(state, pa_counts, i):
        (requested, nonzero, pod_count, node_ports, spread_counts, pa_sums,
         nom_active) = state
        view = _pod_view(b, i)
        mask, score = rt.feasible_and_scores(
            view, params,
            requested=requested, nonzero_requested=nonzero,
            pod_count=pod_count, node_ports=node_ports,
            spread_counts=spread_counts, pa_sums=pa_sums,
            nominated_active=nom_active, pa_counts=pa_counts,
        )
        mask, score = mask[0], score[0]
        feasible = jnp.any(mask)
        best = jnp.argmax(jnp.where(mask, score, -1)).astype(jnp.int32)
        chosen = jnp.where(feasible, best, jnp.int32(-1))
        onehot = (node_iota == chosen) & feasible           # (N,) bool
        oh64 = onehot.astype(jnp.int64)[:, None]
        requested = requested + oh64 * view.requests[0][None, :]
        nonzero = nonzero + oh64 * view.nonzero_requests[0][None, :]
        pod_count = pod_count + onehot.astype(pod_count.dtype)
        node_ports = node_ports | (onehot[:, None] & view.pod_ports[0][None, :])
        if spread_counts is not None:
            # updateWithPod (podtopologyspread/filtering.go:181): +1 in every
            # signature whose selector+namespace the assigned pod matches, on
            # the chosen node, when that node is eligible for the signature.
            with jax.named_scope("spread_counts_update"):
                upd = (
                    b.spread.pod_match_sig[i][:, None]
                    & b.spread.eligible
                    & onehot[None, :]
                )
                spread_counts = spread_counts + upd.astype(
                    spread_counts.dtype
                )
        if pa_sums is not None:
            # interpodaffinity updateWithPod (filtering.go:75): scatter the
            # assigned pod's increments into each row at the chosen node's
            # domain (no-op when the node lacks the row's topology key).
            pa = b.podaffinity
            with jax.named_scope("interpod_counts_update"):
                r = pa_sums.shape[0]
                dcol = jnp.where(
                    chosen >= 0, pa.node_domain[:, jnp.maximum(chosen, 0)], -1
                )                                               # (R,)
                inc = jnp.where(dcol >= 0, pa.update[i], 0)
                pa_sums = pa_sums.at[
                    jnp.arange(r), jnp.maximum(dcol, 0)
                ].add(inc)
            pa_counts = PA.node_counts_add(pa, pa_counts, dcol, inc)
        if nom_active is not None:
            # assume deletes the nomination (schedule_one.go:307): once the
            # scan assigns a nomination's own pod, stop charging it
            nom_active = nom_active & ~(
                (b.nominated_pod_idx == i) & feasible
            )
        return (
            requested, nonzero, pod_count, node_ports, spread_counts, pa_sums,
            nom_active,
        ), pa_counts, chosen

    p = b.requests.shape[0]
    init = (
        b.requested, b.nonzero_requested, b.pod_count, b.node_ports,
        None if b.spread is None else b.spread.node_count,
        None if b.podaffinity is None else b.podaffinity.base_sums,
        None if b.nominated_pod_idx is None
        else jnp.ones(b.nominated_pod_idx.shape[0], dtype=bool),
    )
    # the counts as the kernels read them, beside the (R, D) sums: gathered
    # once here, then kept true step by step with no gather
    pa_counts = (
        None if b.podaffinity is None
        else PA.node_counts(b.podaffinity, b.podaffinity.base_sums)
    )
    # padded pods are infeasible on every node (``pod_valid`` masks them):
    # their steps would choose -1 and leave the state as it is
    stop = jnp.max(jnp.where(
        b.pod_valid, jnp.arange(1, p + 1, dtype=jnp.int32), 0
    ))

    def body(i, carry):
        state, pa_counts, assignments = carry
        state, pa_counts, chosen = step(state, pa_counts, i)
        return state, pa_counts, assignments.at[i].set(chosen)

    final_state, pa_counts, assignments = jax.lax.fori_loop(
        0, stop, body, (init, pa_counts, jnp.full(p, -1, dtype=jnp.int32))
    )
    return assignments, final_state, pa_counts


@partial(jax.jit, static_argnames=("params",))
def greedy_assign_device(b: rt.DeviceBatch, params: rt.ScoreParams):
    """Run the greedy scan. Returns ``(assignments (P,) int32 node index or
    -1, final_state)`` where final_state is the post-batch
    ``(requested, nonzero_requested, pod_count)`` — the cache applies it as
    the batch's assume step.

    Buffer-donation note: the scan CARRY is double-buffered by XLA itself
    (loop state aliases in place inside the compiled program), so the hot
    per-step node-state updates never copy. The INPUT node block must NOT
    be donated here: in pipeline mode those buffers are the device-resident
    cluster state (runtime.ResidentNodeState) reused by the next cycle's
    delta scatter, and the post-cycle preemption PostFilter re-reads them
    through the cycle context. Donation of the node-state buffers happens
    at the one seam where they are provably unreferenced — the resident
    scatter (runtime._scatter_node_rows)."""

    assignments, final_state, _ = greedy_scan(b, params)
    return assignments, final_state


def greedy_assign(
    batch: rt.EncodedBatch, profile=None, params: rt.ScoreParams | None = None
) -> list[str | None]:
    """Host wrapper: run the scan and map node indices back to names.
    Unschedulable (and padded) pods map to ``None``."""
    if params is None:
        from ..framework import config as C
        params = rt.score_params(profile or C.Profile(), batch.resource_names)
    assignments, _ = greedy_assign_device(batch.device, params)
    out: list[str | None] = []
    idx = jax.device_get(assignments)
    for i in range(batch.num_pods):
        j = int(idx[i])
        out.append(batch.node_names[j] if 0 <= j < len(batch.node_names) else None)
    return out
