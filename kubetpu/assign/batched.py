"""Batched assignment v2 — capacity-coupled rounds instead of a per-pod scan.

The reference schedules strictly one pod at a time; its own opportunistic
batching (pkg/scheduler/framework/runtime/batch.go:33) only reuses scores
for identical-signature pods and hits a capacity-coupling wall
(batch.go:61-64): reused placements may violate capacity, so it re-checks
serially. This module is the TPU answer to that wall: solve the whole batch
as a small number of *rounds*, each a single fixed-shape device program:

1. Score all still-unassigned pods against the CURRENT node state (the same
   ``feasible_and_scores`` composition the greedy scan steps through).
2. **Tie-spread argmax**: pods whose (max score, tie set) coincide — the
   identical-pod case that dominates scheduler_perf workloads — are fanned
   across their tie set by rank instead of all piling onto the first max.
   For a singleton group this reduces to exactly the greedy scan's
   "first max-score node" choice, and for K identical pods over an
   equal-score node set it reproduces the scan's round-robin outcome
   (each assignment drops a node's score below the others).
3. **One-per-node queue-order acceptance**: of the pods that chose a node,
   only the first in queue order is admitted this round (capacity checked);
   the rest are rescored next round against the updated state. Because a
   resource assignment only lowers the assigned node's own score, a
   non-conflicted choice is exactly what the scan would have chosen — so
   resource-monotone profiles get pod-for-pod parity with greedy, and
   capacity/ports are never violated (assume-between-pods semantics,
   schedule_one.go:1102).

Rounds run under ``lax.while_loop`` with fixed shapes (an ``active`` mask
carries the frontier) until no pod makes progress. A batch spread over many
feasible nodes converges in O(P / distinct-target-nodes) rounds — one round
for SchedulingBasic shapes; the adversarial case (every pod feasible on one
node only) degrades to the scan's O(P) — with the same result.

A batch that carries a HARD topology spread constraint
(``b.spread.has_hard``) is not solved in rounds at all but handed to the
greedy scan: acceptance projects onto capacity only, and K pods that each
pass the skew check against round-start counts can together exceed
``maxSkew`` (30 nodes in zones of 20/5/5, 64 pods at maxSkew 1: 38 of the
64 round placements were infeasible at their turn). A per-domain quota
would be correct but admits about ``maxSkew`` pods a domain a round.
Required anti-affinity inside one round is the same open question
(ROADMAP B2). Two things follow until a later PR moves the choice to where
the engine is chosen (``Scheduler.__init__``, ``assign/placement.py``,
``ops/preemption.py``, ``parallel/mesh.py``): the rounds never see
``has_hard``, so the hard-spread filter that ``feasible_and_scores`` applies
inside a round is dead code on this path (the soft score and the counts
update below are not); and such a batch runs the scan under this function's
program name, ``jit_batched_assign_device``, so a profile or a bytes model
keyed by that name reads rounds where the scan ran.

This is the LP-relaxation/Sinkhorn family member that keeps integer
semantics: the tie-spread argmax is the zero-temperature limit of a
Sinkhorn row/column balancing over score-equivalent columns, and the
acceptance step is the exact (not relaxed) capacity projection, so the
parity harness (tests/test_batched.py) can hold it to the greedy scan's
results pod-for-pod on the SchedulingBasic shapes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..framework import runtime as rt

# plain int — a module-level jnp scalar would init the backend at import
I64_MIN = -(2**62)


def _tie_spread_choice(mask, score, active):
    """Per-pod target node: rank-r pod of each (max score, tie set) group
    takes the (r mod |ties|)-th tie node. Returns (P,) int32, -1 = no
    feasible node."""
    p, n = mask.shape
    feasible = mask & active[:, None]
    any_f = jnp.any(feasible, axis=1)
    masked = jnp.where(feasible, score, I64_MIN)
    best = jnp.max(masked, axis=1)                         # (P,)
    ties = feasible & (masked == best[:, None])            # (P, N)

    # group hash: deterministic projection of the tie row + the max score.
    # A collision only merges two groups' rank counters (suboptimal
    # spreading, never incorrect — acceptance still enforces capacity).
    w = (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761) + 1).astype(
        jnp.uint64
    )
    h = jnp.sum(jnp.where(ties, w[None, :], 0), axis=1)
    h = h ^ (best.astype(jnp.uint64) << jnp.uint64(1))
    h = jnp.where(any_f & active, h, jnp.uint64(0))

    # rank of each pod within its hash group, by pod (queue) order
    iota = jnp.arange(p, dtype=jnp.int32)
    sh, si = jax.lax.sort((h, iota), num_keys=2)
    seg_start = jnp.where(
        jnp.concatenate([jnp.array([True]), sh[1:] != sh[:-1]]), iota, 0
    )
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
    rank_sorted = iota - seg_start
    rank = jnp.zeros(p, dtype=jnp.int32).at[si].set(rank_sorted)

    cnt = jnp.sum(ties, axis=1).astype(jnp.int32)          # (P,)
    r = jnp.where(cnt > 0, rank % jnp.maximum(cnt, 1), 0)
    # the (r+1)-th True column of the tie row
    csum = jnp.cumsum(ties.astype(jnp.int32), axis=1)      # (P, N)
    choice = jnp.argmax(csum == (r[:, None] + 1), axis=1).astype(jnp.int32)
    return jnp.where(any_f & active, choice, jnp.int32(-1))


def _accept(choice, requests, free, count_room, check_capacity=True):
    """Queue-order admission, at most ONE pod per node per round.

    One-per-node is the sequential-consistency key: with it, a pod's round-k
    choice diverges from the greedy scan only when its target was taken
    earlier in the round — and then it is REJECTED and rescored next round
    against the updated state, which is exactly the scan's view. Since a
    resource assignment only lowers the assigned node's own score
    (LeastAllocated/Balanced are per-node), every non-conflicting choice is
    greedy's choice, so resource-monotone profiles get pod-for-pod parity.
    (Topology-coupled scores — zone anti-affinity — can still shift OTHER
    nodes' ranking mid-round; the harness measures that residual.)

    ``choice`` (P,) target node (-1 = none); ``free`` (N, R) remaining
    resources; ``count_room`` (N,) remaining pod slots. Feasibility vs. the
    node STATE (ports included) was already enforced by the choice mask.
    ``check_capacity`` mirrors the profile's NodeResourcesFit *filter*: when
    that filter is disabled, the greedy scan happily overcommits a node
    (nothing masks it out), so the batched engine must not re-impose the
    capacity projection here or the two engines diverge.
    """
    p = requests.shape[0]
    n = free.shape[0]
    iota = jnp.arange(p, dtype=jnp.int32)
    key = jnp.where(choice >= 0, choice, jnp.int32(n))     # inactive last
    sk, si = jax.lax.sort((key, iota), num_keys=2)
    first = jnp.concatenate([jnp.array([True]), sk[1:] != sk[:-1]])
    node = jnp.minimum(sk, n - 1)
    ok = first & (sk < n)
    if check_capacity:
        s_req = requests[si]
        ok = (
            ok
            & jnp.all(s_req <= free[node], axis=1)
            & (count_room[node] >= 1)
        )
    accepted = jnp.zeros(p, dtype=bool).at[si].set(ok)
    return accepted & (choice >= 0)


@partial(jax.jit, static_argnames=("params", "max_rounds"))
def batched_assign_device(
    b: rt.DeviceBatch, params: rt.ScoreParams, max_rounds: int = 0
):
    """Run the round loop. Same contract as ``greedy_assign_device``:
    returns ``(assignments (P,) int32 node index or -1, final_state)`` with
    the identical 7-slot final-state tuple."""
    if b.spread is not None and b.spread.has_hard:
        # a round admits by capacity alone (``_accept``), so pods of one
        # round can each pass a DoNotSchedule skew check that their sum
        # breaks; the scan re-runs the filter after every placement. Decided
        # at trace time from the batch's static field: a batch without a
        # hard constraint compiles the program it always did.
        from .greedy import greedy_assign_device

        return greedy_assign_device(b, params)
    p = b.requests.shape[0]
    n = b.alloc.shape[0]
    cap = max_rounds or p
    node_iota = jnp.arange(n, dtype=jnp.int32)

    def cond(carry):
        (_, _, _, _, _, _, _, active, _, progress, rounds) = carry
        return jnp.any(active) & progress & (rounds < cap)

    def body(carry):
        (requested, nonzero, pod_count, node_ports, spread_counts, pa_sums,
         nom_active, active, assignments, _, rounds) = carry
        mask, score = rt.feasible_and_scores(
            b, params,
            requested=requested, nonzero_requested=nonzero,
            pod_count=pod_count, node_ports=node_ports,
            spread_counts=spread_counts, pa_sums=pa_sums,
            nominated_active=nom_active,
        )
        choice = _tie_spread_choice(mask, score, active)
        accepted = _accept(
            choice, b.requests,
            free=b.alloc - requested,
            count_room=b.allowed_pods - pod_count,
            check_capacity=params.filter_fit,
        )
        # Commit only the queue-order prefix before the FIRST rejection: a
        # rejected pod re-chooses next round, and anything a later pod
        # grabbed this round might be exactly what it re-chooses — greedy
        # order says the earlier pod gets it. Pods with no feasible node
        # inside the committed prefix finalize as unschedulable (each pod
        # gets exactly one attempt at its turn, like the scan). The earliest
        # active pod always commits or finalizes, so every round progresses.
        iota_p = jnp.arange(p, dtype=jnp.int32)
        rejected = active & (choice >= 0) & ~accepted
        first_rej = jnp.min(jnp.where(rejected, iota_p, jnp.int32(p)))
        commit = accepted & (iota_p < first_rej)
        finalize = active & (choice < 0) & (iota_p < first_rej)
        accepted = commit
        seg = jnp.where(accepted, choice, n)               # N = drop bucket
        a64 = accepted.astype(jnp.int64)
        requested = requested + jax.ops.segment_sum(
            b.requests * a64[:, None], seg, num_segments=n + 1
        )[:n]
        nonzero = nonzero + jax.ops.segment_sum(
            b.nonzero_requests * a64[:, None], seg, num_segments=n + 1
        )[:n]
        pod_count = pod_count + jax.ops.segment_sum(
            accepted.astype(pod_count.dtype), seg, num_segments=n + 1
        )[:n]
        node_ports = node_ports | (
            jax.ops.segment_sum(
                b.pod_ports.astype(jnp.int64) * a64[:, None],
                seg, num_segments=n + 1,
            )[:n] > 0
        )
        if spread_counts is not None:
            with jax.named_scope("spread_counts_update"):
                onehot = (
                    (choice[:, None] == node_iota[None, :])
                    & accepted[:, None]
                )
                upd = jnp.einsum(
                    "ps,pn->sn", b.spread.pod_match_sig.astype(jnp.int32),
                    onehot.astype(jnp.int32),
                ) * b.spread.eligible.astype(jnp.int32)
                spread_counts = spread_counts + upd.astype(
                    spread_counts.dtype
                )
        if pa_sums is not None:
            with jax.named_scope("interpod_counts_update"):
                pa = b.podaffinity
                r_rows, d = pa_sums.shape
                safe_choice = jnp.maximum(choice, 0)
                dcol = pa.node_domain[:, safe_choice].T       # (P, R)
                valid = (dcol >= 0) & accepted[:, None]
                inc = jnp.where(valid, pa.update, 0)          # (P, R)
                flat_ids = jnp.where(
                    valid,
                    jnp.arange(r_rows, dtype=jnp.int32)[None, :] * d
                    + jnp.maximum(dcol, 0),
                    r_rows * d,                               # drop bucket
                )
                flat = jax.ops.segment_sum(
                    inc.reshape(-1), flat_ids.reshape(-1),
                    num_segments=r_rows * d + 1,
                )[: r_rows * d]
                pa_sums = pa_sums + flat.reshape(r_rows, d)
        if nom_active is not None:
            idx = b.nominated_pod_idx
            consumed = (idx >= 0) & accepted[jnp.maximum(idx, 0)]
            nom_active = nom_active & ~consumed
        assignments = jnp.where(accepted, choice, assignments)
        active = active & ~accepted & ~finalize
        progress = jnp.any(accepted | finalize)
        return (requested, nonzero, pod_count, node_ports, spread_counts,
                pa_sums, nom_active, active, assignments, progress,
                rounds + 1)

    init = (
        b.requested, b.nonzero_requested, b.pod_count, b.node_ports,
        None if b.spread is None else b.spread.node_count,
        None if b.podaffinity is None else b.podaffinity.base_sums,
        None if b.nominated_pod_idx is None
        else jnp.ones(b.nominated_pod_idx.shape[0], dtype=bool),
        b.pod_valid,
        jnp.full(p, -1, dtype=jnp.int32),
        jnp.array(True),
        jnp.int32(0),
    )
    out = jax.lax.while_loop(cond, body, init)
    (requested, nonzero, pod_count, node_ports, spread_counts, pa_sums,
     nom_active, _active, assignments, _progress, rounds) = out
    final_state = (
        requested, nonzero, pod_count, node_ports, spread_counts, pa_sums,
        nom_active,
    )
    return assignments, final_state
