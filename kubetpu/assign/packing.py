"""Constraint-based packing engine — cluster-level objectives on device.

The greedy scan and the batched rounds both optimize *per-cycle placement*:
each pod lands on its own best-scoring node and the cluster-level outcome
(how many nodes carry the workload, which priorities got admitted) is
whatever falls out. This third engine inverts that: it solves a penalized
LP-relaxation of the bin-pack over the same device-resident
``(pods × nodes × resources)`` tensors, maximizing

    priority-weighted admission  −  α·nodes-opened  −  β·fragmentation

as a fixed-point projection loop. "Priority Matters" (arXiv:2511.08373)
poses the same objective as a constraint program solved on the host; here
the relaxation runs as rounds of a ``jax.lax.while_loop`` so one cycle is
still a single fixed-shape device program, mesh-shardable on the node axis
exactly like the other two engines.

Mechanics per round (the batched engine's skeleton, rescored):

1. ``feasible_and_scores`` gives the EXACT hard-constraint mask (fits,
   taints, affinity, ports, nominations — relaxation never touches it) and
   the profile score.
2. The **packing utility** replaces the raw score as the argmax key:
   normalized profile score (tiebreak weight) minus the α penalty for
   landing on a still-empty node, minus the β emptiness of the target (a
   best-fit pull toward already-full nodes), minus a per-node dual price
   λ_n. The weights live in ONE ``(K,)`` device tensor
   (:class:`PackingWeights`) — the future learned-scoring hook
   (arXiv:2603.10545): a tuning loop perturbs a tensor, not code.
3. **Priority-ordered acceptance**: of the pods that chose a node, the
   highest-priority (queue order within a tier) is admitted — capacity
   checked exactly, one per node per round, commit-prefix semantics like
   the batched engine so every round provably progresses. This is where
   "priority-weighted admission" is enforced, not just scored: when
   capacity is scarce the high tiers win the contested slots.
4. **Dual ascent**: λ_n rises where this round's choices collided
   (``log1p(choosers−1)`` steps, clipped below the α opening penalty so
   pricing spreads pods across OPEN nodes but never pushes them to open a
   new one). λ is the relaxation's memory of contention.

**Warm start** is the perf claim: λ persists across cycles in a
device-resident :class:`~kubetpu.framework.runtime.PackingSolverState`
block beside ``ResidentNodeState`` (donated back into the solver each
cycle, DS001-safe). On a churn-steady cluster the previous cycle's prices
already encode where contention lives, so the first rounds don't pile onto
the same nodes and the loop converges in a handful of iterations instead
of from-scratch — measured as ``solver_iters_per_cycle`` in the perf
runner, never asserted.

The engine returns the identical ``(assignments, 7-slot final_state)``
contract, so gang atomicity (podgroup machinery), preemption, nomination
and binding ride through unchanged; ``--engine greedy``/``batched`` remain
bit-identical escape hatches.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..framework import runtime as rt
from .batched import I64_MIN

# fixed-point scale for the float packing utility before it enters the
# int64 banded tie-spread argmax (20 fractional bits; utilities are O(1))
_UTIL_SCALE = float(1 << 20)


@dataclass(frozen=True)
class PackingWeights:
    """Objective weights, host-side view of the ``(K,)`` device tensor.

    ``score_weight``    — profile score (row-normalized) as tiebreak pull.
    ``priority_weight`` — per-priority-point admission bonus in the
                          OBJECTIVE (admission order uses raw priority).
    ``alpha_open``      — penalty for placing on a node with zero pods.
    ``beta_frag``       — penalty ∝ target-node emptiness (best-fit pull).
    ``dual_step``       — λ ascent step per ``log1p`` overflow unit.
    ``dual_decay``      — per-cycle multiplicative λ decay (forgets stale
                          contention; 0 disables warm-start entirely).
    ``tie_band``        — utility width within which nodes count as TIED
                          and pods fan across them by rank. The solver
                          emits EQUALIZATION prices (λ_j that level the
                          used nodes' penalized utilities, the LP-dual
                          fixed-point property), so a warm λ pulls last
                          cycle's used set into one band and the next
                          solve spreads in round one instead of replaying
                          the band-by-band descent — the warm-start lever.
    ``lam_cap_frac``    — λ clip ceiling as a fraction of ``alpha_open``
                          (bounds how much history a price can carry; set
                          above the biggest utility gap equalization must
                          bridge).
    ``slice_frag``      — penalty for landing on a node whose TPU slice is
                          currently fully free (opening it fragments a
                          slice a future aligned gang could have taken
                          whole). Inert without a topology block.
    ``slice_align``     — reward for landing in a slice that already
                          carries load (concentrates the workload into
                          fewer slices). Inert without a topology block.

    Serialized into perf results (``WorkloadResult.packing_weights``) so a
    measured frontier is reproducible from its JSON alone.
    """

    score_weight: float = 0.25
    priority_weight: float = 0.1
    alpha_open: float = 1.0
    beta_frag: float = 0.5
    dual_step: float = 0.1
    dual_decay: float = 0.9
    tie_band: float = 0.15
    lam_cap_frac: float = 2.0
    slice_frag: float = 0.5
    slice_align: float = 0.25

    def tensor(self) -> jnp.ndarray:
        """The ``(K,)`` float32 device tensor the solver consumes."""
        return jnp.asarray(
            [
                self.score_weight, self.priority_weight, self.alpha_open,
                self.beta_frag, self.dual_step, self.dual_decay,
                self.tie_band, self.lam_cap_frac,
                self.slice_frag, self.slice_align,
            ],
            dtype=jnp.float32,
        )

    def to_json(self) -> dict:
        return {
            "score_weight": self.score_weight,
            "priority_weight": self.priority_weight,
            "alpha_open": self.alpha_open,
            "beta_frag": self.beta_frag,
            "dual_step": self.dual_step,
            "dual_decay": self.dual_decay,
            "tie_band": self.tie_band,
            "lam_cap_frac": self.lam_cap_frac,
            "slice_frag": self.slice_frag,
            "slice_align": self.slice_align,
        }


def _banded_tie_choice(mask, util, active, band):
    """Per-pod target node: the batched engine's tie-spread argmax with the
    tie predicate widened from ``== best`` to ``>= best − band`` — nodes
    whose utility sits within the band of the max count as one tie class
    and the class's pods fan across it by rank. ``band == 0`` reduces to
    the exact tie-spread. Returns (P,) int32, -1 = no feasible node."""
    p, n = mask.shape
    feasible = mask & active[:, None]
    any_f = jnp.any(feasible, axis=1)
    masked = jnp.where(feasible, util, I64_MIN)
    best = jnp.max(masked, axis=1)                         # (P,)
    ties = feasible & (masked >= best[:, None] - band)     # (P, N)

    # group hash: deterministic projection of the tie row + the max
    # utility (collisions only merge rank counters — suboptimal spreading,
    # never incorrect; acceptance still enforces capacity)
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


def _priority_order(priority, pod_valid):
    """(P,) int32 rank of each pod under (priority desc, queue order asc):
    rank 0 schedules first. Invalid pods sink to the end."""
    p = priority.shape[0]
    iota = jnp.arange(p, dtype=jnp.int32)
    # single sortable key: higher priority first, queue order within a tier
    key = jnp.where(pod_valid, -priority.astype(jnp.int64), 2**40) * p + iota
    _, si = jax.lax.sort((key, iota), num_keys=1)
    return jnp.zeros(p, dtype=jnp.int32).at[si].set(iota)


def _accept_packed(choice, requests, free, count_room, order, coupled,
                   check_capacity=True):
    """Priority-ordered MULTI-admission: every pod whose prefix (by
    admission ``order``, within its target node's chooser set) still fits
    the node's free capacity and pod-count room is admitted this round —
    a whole bin fills in one iteration instead of one pod per round (the
    batched engine's one-per-node rule buys greedy parity; packing buys
    convergence speed instead). Capacity stays the exact projection: the
    prefix-sum check is cumulative, so the admitted set never overcommits
    (assume-between-pods semantics, like the scan). With ``check_capacity``
    off (NodeResourcesFit filter disabled) every chooser is admitted — the
    greedy scan happily overcommits there too.

    ``coupled`` marks pods whose landing changes constraint state other
    pods' round-start masks already read (hostPorts, spread-count
    contributions, affinity-sum updates): co-admitting two of those to one
    node could violate a constraint the mask can't see mid-round (two
    port-80 pods both admitted to the node that had the port free). At
    most ONE coupled pod is admitted per node per round — plain pods keep
    full multi-admission, which is the convergence win; constraint-heavy
    pods degrade to exactly the batched engine's within-node serialism."""
    p = requests.shape[0]
    n = free.shape[0]
    iota = jnp.arange(p, dtype=jnp.int32)
    key = jnp.where(choice >= 0, choice, jnp.int32(n))     # inactive last
    sk, _so, si = jax.lax.sort((key, order, iota), num_keys=2)
    ok = sk < n
    first = jnp.concatenate([jnp.array([True]), sk[1:] != sk[:-1]])
    # segment-start position broadcast forward (the same seg_start trick
    # as the tie-spread rank) — shared by the capacity prefix sums and the
    # one-coupled-per-segment rule
    seg_pos = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first, iota, 0)
    )
    if check_capacity:
        node = jnp.minimum(sk, n - 1)
        # segment-relative inclusive prefix sums: cum − base, where base is
        # the exclusive cumsum at the segment start
        s_req = requests[si].astype(jnp.int64)             # (P, R)
        cum = jnp.cumsum(s_req, axis=0)
        excl = cum - s_req
        base = excl[seg_pos]                                # (P, R)
        within = cum - base                                 # inclusive
        cnt = iota - seg_pos + 1                            # 1-based rank
        ok = (
            ok
            & jnp.all(within <= free[node], axis=1)
            & (cnt <= count_room[node])
        )
    # one coupled pod per segment per round (conservative: rejected-for-
    # capacity coupled choosers still count — costs a round, never safety)
    s_c = coupled[si].astype(jnp.int32)
    cum_c = jnp.cumsum(s_c)
    c_within = cum_c - (cum_c - s_c)[seg_pos]               # inclusive
    ok = ok & ((s_c == 0) | (c_within == 1))
    accepted = jnp.zeros(p, dtype=bool).at[si].set(ok)
    return accepted & (choice >= 0)


@partial(jax.jit, static_argnames=("params", "max_iters"),
         donate_argnums=(2,))
def packing_assign_device(
    b: rt.DeviceBatch, params: rt.ScoreParams, lam: jnp.ndarray,
    weights: jnp.ndarray, max_iters: int = 0,
):
    """One packing solve. ``lam`` is the (N,) float32 warm-start dual
    vector (DONATED — callers must rebind it from the result, DS001);
    ``weights`` the (K,) :class:`PackingWeights` tensor.

    Returns ``(assignments, final_state, lam, objective, iters,
    nodes_used)`` — the first two are the engine contract, the rest feed
    the solver-state block, flight recorder and telemetry.
    """
    p = b.requests.shape[0]
    n = b.alloc.shape[0]
    cap = max_iters or p
    prio = (
        b.pod_priority if b.pod_priority is not None
        else jnp.zeros(p, dtype=jnp.int32)
    )
    w_score, w_prio = weights[0], weights[1]
    alpha, beta = weights[2], weights[3]
    step, decay = weights[4], weights[5]
    band_f, cap_frac = weights[6], weights[7]
    w_sfrag, w_salign = weights[8], weights[9]
    lam = lam * decay                  # forget a fraction of stale prices
    lam_cap = alpha * cap_frac
    band = jnp.round(band_f * _UTIL_SCALE).astype(jnp.int64)
    order = _priority_order(prio, b.pod_valid)
    # pods whose landing mutates constraint state (ports taken, spread
    # counts, affinity sums) — _accept_packed serializes these within a
    # node so a round-start mask is never violated mid-round
    coupled = jnp.any(b.pod_ports != 0, axis=1)
    if b.spread is not None:
        coupled = coupled | jnp.any(b.spread.pod_match_sig != 0, axis=1)
    if b.podaffinity is not None:
        coupled = coupled | jnp.any(b.podaffinity.update != 0, axis=1)
    node_iota = jnp.arange(n, dtype=jnp.int32)
    alloc_f = jnp.maximum(b.alloc, 1).astype(jnp.float32)
    has_cap = (b.alloc > 0) & b.node_valid[:, None]
    res_n = jnp.maximum(jnp.sum(has_cap, axis=1), 1).astype(jnp.float32)

    def emptiness(requested):
        """(N,) mean free-fraction over capacity-bearing resources — the
        best-fit pull: fuller nodes read lower."""
        free_frac = jnp.where(
            has_cap, (b.alloc - requested).astype(jnp.float32) / alloc_f, 0.0
        )
        return jnp.sum(free_frac, axis=1) / res_n

    def cond(carry):
        (_, _, _, _, _, _, _, active, _, progress, _, iters) = carry
        return jnp.any(active) & progress & (iters < cap)

    def body(carry):
        (requested, nonzero, pod_count, node_ports, spread_counts, pa_sums,
         nom_active, active, assignments, _, lam, iters) = carry
        mask, score = rt.feasible_and_scores(
            b, params,
            requested=requested, nonzero_requested=nonzero,
            pod_count=pod_count, node_ports=node_ports,
            spread_counts=spread_counts, pa_sums=pa_sums,
            nominated_active=nom_active,
        )
        # packing utility: per-pod row-normalized profile score as the
        # tiebreak, node-level packing terms as the decision
        score_f = score.astype(jnp.float32)
        row_max = jnp.max(
            jnp.where(mask, jnp.abs(score_f), 0.0), axis=1, keepdims=True
        )
        norm = score_f / jnp.maximum(row_max, 1.0)          # (P, N) in [-1,1]
        closed = ((pod_count == 0) & b.node_valid).astype(jnp.float32)
        # deterministic low-index bias on CLOSED nodes only, one step per
        # index WIDER than the tie band: still-empty nodes must never form
        # a tie class (fanning pods across empty nodes is exactly
        # anti-packing — bins open one at a time, lowest index first).
        # Open nodes carry no bias, so near-equal open nodes DO tie and
        # the class fills in parallel.
        bias = closed * node_iota.astype(jnp.float32) * (2.0 * band_f)
        node_pen = alpha * closed + beta * emptiness(requested) + lam + bias
        if b.topology is not None:
            # slice terms recompute per round from the CURRENT requested
            # rows, so the first pod admitted into a free slice flips its
            # price for every later round — slices open one at a time
            from ..ops.topology import slice_occupancy

            sid, n_sl = b.topology.slice_id, b.topology.num_slices
            s_active, _ = slice_occupancy(requested, b.node_valid, sid, n_sl)
            labeled_n = sid < n_sl
            in_free = labeled_n & ~s_active[sid]
            in_active = labeled_n & s_active[sid]
            node_pen = node_pen + (
                w_sfrag * in_free.astype(jnp.float32)
                - w_salign * in_active.astype(jnp.float32)
            )
        util_f = w_score * norm - node_pen[None, :]
        util = jnp.where(
            mask, jnp.round(util_f * _UTIL_SCALE).astype(jnp.int64), I64_MIN
        )
        choice = _banded_tie_choice(mask, util, active, band)
        accepted = _accept_packed(
            choice, b.requests,
            free=b.alloc - requested,
            count_room=b.allowed_pods - pod_count,
            order=order, coupled=coupled,
            check_capacity=params.filter_fit,
        )
        # dual ascent on the OVERFLOW (choosers that did not fit this
        # round): λ prices sustained contention so the next round — and,
        # warm-started, the next cycle — spreads straight to where room is
        seg_all = jnp.where(choice >= 0, choice, n)
        choosers = jax.ops.segment_sum(
            (active & (choice >= 0)).astype(jnp.float32),
            seg_all, num_segments=n + 1,
        )[:n]
        admitted_n = jax.ops.segment_sum(
            accepted.astype(jnp.float32), seg_all, num_segments=n + 1,
        )[:n]
        lam = jnp.clip(
            lam + step * jnp.log1p(jnp.maximum(choosers - admitted_n, 0.0)),
            0.0, lam_cap,
        )
        # no commit prefix (that is the batched engine's greedy-parity
        # device; packing has its own order): every admitted pod commits.
        # A pod with no feasible node finalizes only if it precedes every
        # rejection in admission order — a later state update (affinity,
        # spread) could still open a node for it otherwise. The earliest-
        # ordered active pod always commits or finalizes, so every
        # iteration progresses and the loop terminates in ≤ P rounds.
        rejected = active & (choice >= 0) & ~accepted
        first_rej = jnp.min(jnp.where(rejected, order, jnp.int32(p)))
        finalize = active & (choice < 0) & (order < first_rej)
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
            onehot = (choice[:, None] == node_iota[None, :]) & accepted[:, None]
            upd = jnp.einsum(
                "ps,pn->sn", b.spread.pod_match_sig.astype(jnp.int32),
                onehot.astype(jnp.int32),
            ) * b.spread.eligible.astype(jnp.int32)
            spread_counts = spread_counts + upd.astype(spread_counts.dtype)
        if pa_sums is not None:
            pa = b.podaffinity
            r_rows, d = pa_sums.shape
            safe_choice = jnp.maximum(choice, 0)
            dcol = pa.node_domain[:, safe_choice].T           # (P, R)
            valid = (dcol >= 0) & accepted[:, None]
            inc = jnp.where(valid, pa.update, 0)              # (P, R)
            flat_ids = jnp.where(
                valid,
                jnp.arange(r_rows, dtype=jnp.int32)[None, :] * d
                + jnp.maximum(dcol, 0),
                r_rows * d,                                   # drop bucket
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
                pa_sums, nom_active, active, assignments, progress, lam,
                iters + 1)

    init = (
        b.requested, b.nonzero_requested, b.pod_count, b.node_ports,
        None if b.spread is None else b.spread.node_count,
        None if b.podaffinity is None else b.podaffinity.base_sums,
        None if b.nominated_pod_idx is None
        else jnp.ones(b.nominated_pod_idx.shape[0], dtype=bool),
        b.pod_valid,
        jnp.full(p, -1, dtype=jnp.int32),
        jnp.array(True),
        lam,
        jnp.int32(0),
    )
    out = jax.lax.while_loop(cond, body, init)
    (requested, nonzero, pod_count, node_ports, spread_counts, pa_sums,
     nom_active, _active, assignments, _progress, lam, iters) = out
    # warm-start output: the EQUALIZATION price at the fixed point, not the
    # loop's raw ascent residue. At an LP-bin-pack optimum the duals
    # equalize penalized utilities across the active bins; computing that
    # directly — λ_j = relu(v_j − v_marginal) over start-state node
    # utilities v, marginal = the worst node this solve actually used —
    # collapses the whole used set into ONE tie band for the next solve,
    # so an unchanged cluster fans out in round one instead of replaying
    # the band-by-band descent. Unused nodes sit strictly below the band
    # (they priced out this solve too), so warm never opens extra nodes.
    closed0 = ((b.pod_count == 0) & b.node_valid).astype(jnp.float32)
    bias0 = closed0 * node_iota.astype(jnp.float32) * (2.0 * band_f)
    v0 = -(alpha * closed0 + beta * emptiness(b.requested) + bias0)
    used = (pod_count > b.pod_count) & b.node_valid
    v_marg = jnp.min(jnp.where(used, v0, jnp.inf))
    lam_eq = jnp.clip(jnp.maximum(v0 - v_marg, 0.0), 0.0, lam_cap)
    lam = jnp.where(jnp.any(used), lam_eq, lam)
    final_state = (
        requested, nonzero, pod_count, node_ports, spread_counts, pa_sums,
        nom_active,
    )
    # cluster-level objective, the recorded "why": priority-weighted
    # admission minus what the placement spent in nodes and fragmentation
    admitted = (assignments >= 0) & b.pod_valid
    admission = jnp.sum(
        jnp.where(admitted, 1.0 + w_prio * prio.astype(jnp.float32), 0.0)
    )
    open_nodes = (pod_count > 0) & b.node_valid
    nodes_used = jnp.sum(open_nodes).astype(jnp.int32)
    frag = jnp.sum(jnp.where(open_nodes, emptiness(requested), 0.0))
    objective = admission - alpha * nodes_used.astype(jnp.float32) - beta * frag
    if b.topology is not None:
        # slice-fragmentation spend: slices this solve opened from fully
        # free (the recorded "why" mirrors the per-round utility terms)
        from ..ops.topology import slice_occupancy

        sid, n_sl = b.topology.slice_id, b.topology.num_slices
        act0, _ = slice_occupancy(b.requested, b.node_valid, sid, n_sl)
        act1, _ = slice_occupancy(requested, b.node_valid, sid, n_sl)
        newly_opened = jnp.sum(
            (act1[:n_sl] & ~act0[:n_sl]).astype(jnp.float32)
        )
        objective = objective - w_sfrag * newly_opened
    return assignments, final_state, lam, objective, iters, nodes_used


class PackingEngine:
    """The registered ``engine="packing"`` callable: the scheduler's
    ``(DeviceBatch, ScoreParams) -> (assignments, final_state)`` contract
    wrapping :func:`packing_assign_device` plus the cross-cycle solver
    state. Holds the ``PackingSolverState`` dual block (warm start), the
    ``PackingWeights`` device tensor, and the last solve's diagnostics
    (``last_objective`` / ``last_iters`` / ``last_nodes_used`` — device
    scalars; the scheduler fetches them at cycle finish alongside the
    assignments so no extra sync point is added)."""

    def __init__(self, weights: PackingWeights | None = None, mesh=None):
        self.weights = weights or PackingWeights()
        self.state = rt.PackingSolverState(mesh=mesh)
        self._w: jnp.ndarray | None = None
        self.last_objective = None
        self.last_iters = None
        self.last_nodes_used = None

    def bind_mesh(self, mesh) -> None:
        """Adopt the scheduler's resolved mesh (the seam constructs the
        engine before mesh resolution); drops any un-sharded duals."""
        self.state.bind_mesh(mesh)

    def __call__(self, b: rt.DeviceBatch, params: rt.ScoreParams):
        if self._w is None:
            self._w = self.weights.tensor()
        n = b.alloc.shape[0]
        lam = self.state.duals(n)
        assignments, final_state, lam_out, objective, iters, nodes_used = (
            packing_assign_device(b, params, lam, self._w)
        )
        self.state.store(n, lam_out)
        self.last_objective = objective
        self.last_iters = iters
        self.last_nodes_used = nodes_used
        return assignments, final_state

    @property
    def _cache_size(self):
        # compile-miss accounting (metrics.tpu.jit_cache_size) delegates
        # to the inner jit so packing cycles classify like the other two
        return getattr(packing_assign_device, "_cache_size", None)
