"""Scheduling flight recorder + per-pod lifecycle attribution.

Two coupled concerns live here, both bounded-memory and loop-thread-owned
(the Scheduler's single-owner contract):

1. **Per-pod lifecycle tracing** — a trace id + monotonic ingest timestamp
   is stamped at REST create by the apiserver (``Pod.trace_id`` /
   ``Pod.ingest_ts``, ``perf_counter`` seconds) and carried through the
   watch frame; the scheduler stamps informer delivery, the queue
   accumulates enqueue→pop wait across backoff/requeue hops
   (``QueuedPodInfo.queue_wait_s``), the cycle contributes its encode and
   kernel walls, and the dispatcher stamps micro-batch execution start
   (``BindCall.t_exec``). At bind ack the recorder folds these into one
   staged latency vector per pod — the stages of
   ``scheduler_e2e_scheduling_duration_seconds{stage}``
   (kubetpu.metrics.scheduler_metrics.E2E_STAGES):

   - ``api_ingest``  REST create → informer delivery (fullstack only)
   - ``informer``    delivery-handler wall (incl. event-time pre-encode)
   - ``queue_wait``  enqueue → pop, summed across requeue/backoff hops
   - ``encode``      the owning cycle's host-encode wall
   - ``kernel``      the owning cycle's device-program wall
   - ``dispatch``    bind enqueue → micro-batch execution start
   - ``bind_rtt``    bind execution → completion (the API round trip)
   - ``e2e``         ingest (or delivery) → bind ack

   Scope: the per-pod QUEUE lane. Gang/podgroup-lane members bypass the
   delivery stamping (their queueing lives in the group manager), so they
   get decision records but no staged vector — a delivery-less pod must
   never pollute the staged histograms with a bind-span-only "e2e".

2. **Decision records** — a ring buffer (``maxlen`` like the reference's
   bounded event buffers) of per-pod scheduling decisions: the node that
   won, its score margin and top-k breakdown, per-plugin(-group) filter
   rejection counts, requeue history, and preemption/nomination outcomes.
   Served at ``GET /debug/flightrecorder`` on the DiagnosticsServer,
   rendered by ``kubetpu explain pod/<ns>/<name>``, and dumpable to JSON
   — recorded traces double as training data for a learned scoring engine
   (ROADMAP item 5; "Learning to Score", 2603.10545, tunes weights from
   exactly these records).

Score/filter breakdown semantics: the greedy scan's carry makes pod k's
true state depend on pods 0..k-1, and the fused device program exposes no
per-step tensors. The recorder therefore evaluates ONE extra batched
filter+score kernel per cycle against the CYCLE-START state (exact for the
first pod, the "as-popped view" for later ones — flagged
``view: "cycle-start"`` on every record); the ACTUAL assignment recorded is
always the scan's. The extra kernel is a single parallel (P,N) evaluation —
a fraction of the P-step sequential scan — and the whole recorder sits
behind ``Scheduler(flight_recorder=False)`` / ``--flight-recorder off``.
"""

from __future__ import annotations

import collections
import itertools
from dataclasses import dataclass
from typing import Any

import numpy as np

from .. import klog
from .. import names as N

_log = klog.get_logger("kubetpu.sched.flightrecorder")

#: how the fused device filter decomposes for attribution: the component
#: order of ``runtime.filter_components``. The static mask fuses the
#: spec-static plugins (NodeSelector/NodeAffinity/TaintToleration/NodeName/
#: NodeUnschedulable) — they cannot be split post-encode, so they report
#: as one group.
STATIC_FILTER_GROUP = (
    f"{N.NODE_AFFINITY}+{N.TAINT_TOLERATION}+{N.NODE_NAME}"
    f"+{N.NODE_UNSCHEDULABLE}"
)
_COMPONENT_NAMES = (
    STATIC_FILTER_GROUP,
    N.NODE_RESOURCES_FIT,
    N.NODE_PORTS,
    N.POD_TOPOLOGY_SPREAD,
    N.INTER_POD_AFFINITY,
)


_EXPLAIN_JIT = None
_EXPLAIN_MASKS_JIT = None

#: score sentinel for infeasible nodes in the top-k (far below any real
#: score so a masked node can never surface)
_NEG = -(2 ** 62)


def _explain_kernel(device_batch, params, assignments):
    """One batched Filter+Score evaluation against cycle-start state,
    REDUCED ON DEVICE to the per-pod summaries the records need — feasible
    counts, per-component rejection counts, top-k (score, node-index)
    pairs, and each pod's score on its actual assignment — so the host
    fetch is a few KB per cycle, not the (P, N) mask/score tensors (the
    <5% overhead budget is won here). Jitted lazily so importing the
    recorder never touches a backend."""
    global _EXPLAIN_JIT
    if _EXPLAIN_JIT is None:
        import jax
        import jax.numpy as jnp

        from ..framework import runtime as rt

        def explain_kernel(b, p, idx):
            # filter_components is recomputed inside feasible_and_scores,
            # but the two subgraphs are identical pure computations and
            # XLA CSEs them — measured: both ≈ feasible_and_scores alone
            comps = rt.filter_components(b, p)[:5]
            mask, total = rt.feasible_and_scores(b, p)
            valid = b.node_valid[None, :]
            mask = mask & valid
            feasible = mask.sum(axis=1).astype(jnp.int32)        # (P,)
            reject = tuple(
                None if c is None
                else ((~c) & valid).sum(axis=1).astype(jnp.int32)
                for c in comps
            )
            # top-3 via repeated argmax: lax.top_k on the (P, N) int64
            # scores is ~4x this whole kernel's cost on CPU (measured
            # 8.5 ms vs 2.0 ms at 128x512) — three masked argmax passes
            # keep int64 score exactness at a fraction of the price
            masked = jnp.where(mask, total, jnp.int64(_NEG))
            k = min(3, masked.shape[1])
            vals, idxs = [], []
            rows = jnp.arange(masked.shape[0])
            for _ in range(k):
                i = jnp.argmax(masked, axis=1)
                v = jnp.take_along_axis(masked, i[:, None], axis=1)[:, 0]
                vals.append(v)
                idxs.append(i.astype(jnp.int32))
                masked = masked.at[rows, i].set(jnp.int64(_NEG))
            top_vals = jnp.stack(vals, axis=1)                   # (P, k)
            top_idx = jnp.stack(idxs, axis=1)
            win = jnp.take_along_axis(
                total, jnp.maximum(idx, 0)[:, None].astype(jnp.int32), axis=1
            )[:, 0]                                              # (P,)
            return feasible, reject, top_vals, top_idx, win

        _EXPLAIN_JIT = jax.jit(explain_kernel, static_argnames=("p",))
    return _EXPLAIN_JIT(device_batch, params, assignments)


def _explain_masks_kernel(device_batch, params):
    """The per-component (P, N) masks themselves — fetched ONLY for cycles
    with an unschedulable pod (example rejected nodes are a debugging
    detail; the steady-state all-feasible path never pays this)."""
    global _EXPLAIN_MASKS_JIT
    if _EXPLAIN_MASKS_JIT is None:
        import jax

        from ..framework import runtime as rt

        def explain_masks_kernel(b, p):
            return rt.filter_components(b, p)[:5]

        _EXPLAIN_MASKS_JIT = jax.jit(
            explain_masks_kernel, static_argnames=("p",))
    return _EXPLAIN_MASKS_JIT(device_batch, params)


@dataclass
class PodFlight:
    """Lifecycle stamps for one pending pod (perf_counter seconds)."""

    key: str
    trace_id: str = ""
    ingest_pc: float = 0.0      # apiserver REST-create stamp (0 = direct)
    deliver_pc: float = 0.0     # informer delivery into the scheduler
    informer_s: float = 0.0     # delivery-handler wall


class FlightRecorder:
    """See module docstring. Appends happen on the scheduler loop thread;
    HTTP reads snapshot the deque with the tracer's retry idiom."""

    def __init__(
        self,
        max_records: int = 4096,
        max_e2e_samples: int = 65536,
        top_k: int = 3,
        replica: str = "",
        failure_counter=None,
    ) -> None:
        """``failure_counter``: the owning scheduler's
        ``scheduler_explain_kernel_failures_total`` child — every
        explain-kernel failure is counted there, scrape-visible."""
        self.top_k = top_k
        # federation stamp: every decision record carries the scheduler
        # replica that made it ("" in single-scheduler mode) so a
        # multi-replica bind history is attributable per record
        self.replica = replica
        self._records: collections.deque[dict] = collections.deque(
            maxlen=max_records
        )
        # key -> latest record; bounded alongside the ring (an LRU twice
        # the ring keeps lookups alive slightly past eviction, never grows)
        self._by_key: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict()
        )
        self._by_key_max = 2 * max_records
        # key -> PodFlight for pods still pending (dropped at ack/delete)
        self._flights: "collections.OrderedDict[str, PodFlight]" = (
            collections.OrderedDict()
        )
        self._flights_max = 4 * max_records
        # (ack perf_counter, e2e seconds) — the soak stage's raw reservoir
        self.e2e_samples: collections.deque = collections.deque(
            maxlen=max_e2e_samples
        )
        self.breakdown_failures = 0     # explain-kernel errors (soft-off)
        self._breakdown_ok = True
        self._failure_counter = failure_counter
        self._seq = itertools.count()
        # the previous cycle's dispatched-but-unfetched explain kernel:
        # (device summary pytree, device masks or None, records, node
        # names, n_real, assignment per record). Resolved at the NEXT
        # note_cycle or on first read — the kernel overlaps host work
        # instead of stalling the loop (JAX async dispatch; outputs are
        # fresh buffers, so later donation of the inputs is safe). A
        # one-slot deque: append (loop thread) and popleft (loop OR a
        # diagnostics HTTP reader) are atomic, so concurrent resolvers
        # can never double-fetch or drop a newly-dispatched cycle
        self._pending: collections.deque = collections.deque()

    # ------------------------------------------------------------ lifecycle
    def note_delivery(self, pod, deliver_pc: float, informer_s: float) -> None:
        """Informer delivered a pending pod: open (or refresh) its flight.
        The FIRST delivery wins — a re-delivered update must not reset the
        e2e base."""
        key = f"{pod.namespace}/{pod.name}"
        fl = self._flights.get(key)
        if fl is None:
            fl = PodFlight(
                key=key,
                trace_id=getattr(pod, "trace_id", "") or "",
                ingest_pc=float(getattr(pod, "ingest_ts", 0.0) or 0.0),
                deliver_pc=deliver_pc,
                informer_s=informer_s,
            )
            self._flights[key] = fl
            while len(self._flights) > self._flights_max:
                self._flights.popitem(last=False)
        else:
            fl.informer_s += informer_s

    def drop(self, key: str) -> None:
        """Pod deleted while pending — forget its flight."""
        self._flights.pop(key, None)

    # ------------------------------------------------------------ decisions
    def note_cycle(
        self,
        batch,
        device_batch,
        params,
        batch_infos,
        idx,
        cycle_id: int,
        profile: str,
        encode_s: float,
        kernel_s: float,
        breakdown: bool = True,
        engine: str = "",
        objective_value: "float | None" = None,
        solver_iters: "int | None" = None,
        skipped_reason: str | None = None,
    ) -> None:
        """One decision record per pod of the finished cycle. ``idx`` is
        the scan's assignment vector (node index or -1). ``breakdown``
        gates the extra explain kernel (off under a mesh — the sharded
        batch is not re-evaluated here). ``objective_value`` /
        ``solver_iters`` are the packing engine's solve diagnostics
        (assign.packing; None otherwise) — stamped on every record of the
        cycle so ``kubetpu explain`` can render the packing rationale, and
        the breakdown's ``top_nodes[0]`` (the cycle-start masked argmax —
        exactly what the greedy scan would have picked first) doubles as
        the greedy counterfactual beside it. ``skipped_reason`` names WHY
        ``breakdown=False`` was passed (e.g. ``"mesh"`` — the sharded
        batch is not re-evaluated here) so explain renders "breakdown
        skipped: mesh" instead of an empty block reading as
        "no rejections"."""
        self._resolve_pending()
        summary_dev = masks_dev = None
        node_names = batch.node_names
        n_real = batch.num_nodes
        if breakdown and self._breakdown_ok:
            try:
                summary_dev = _explain_kernel(
                    device_batch, params, np.asarray(idx, dtype=np.int32)
                )
                if any(
                    not (0 <= int(idx[k]) < len(node_names))
                    for k in range(len(batch_infos))
                ):
                    # an unschedulable pod in the cycle: also compute the
                    # full per-component masks so its record can name
                    # example rejected nodes (the all-feasible steady
                    # state never pays this)
                    masks_dev = _explain_masks_kernel(device_batch, params)
            except Exception as e:
                self._note_breakdown_failure(e)
        recs: list = []
        for k, info in enumerate(batch_infos):
            j = int(idx[k])
            rec: dict[str, Any] = {
                "pod": info.key,
                "uid": info.pod.uid,
                "cycle": cycle_id,
                "profile": profile,
                "replica": self.replica,
                "attempts": info.attempts,
                "status": (
                    "scheduled" if 0 <= j < len(node_names)
                    else "unschedulable"
                ),
                "node": node_names[j] if 0 <= j < len(node_names) else None,
                "priority": info.pod.priority,
                "encode_s": encode_s,
                "kernel_s": kernel_s,
                "queue_wait_s": getattr(info, "queue_wait_s", 0.0),
            }
            if engine:
                rec["engine"] = engine
            if objective_value is not None:
                rec["objective_value"] = objective_value
            if solver_iters is not None:
                rec["solver_iters"] = solver_iters
            if skipped_reason and not breakdown:
                rec["skipped_reason"] = skipped_reason
            fl = self._flights.get(info.key)
            if fl is not None and fl.trace_id:
                rec["trace_id"] = fl.trace_id
            self._insert(rec)
            recs.append(rec)
        if summary_dev is not None:
            self._pending.append((
                summary_dev, masks_dev, recs, node_names, n_real,
                [int(idx[k]) for k in range(len(recs))],
            ))

    def _resolve_pending(self) -> None:
        """Fetch the previous cycle's dispatched explain results (tiny
        arrays; the kernel overlapped host work since) and fold the
        breakdown into its records in place — they live in the ring."""
        try:
            p = self._pending.popleft()
        except IndexError:
            return
        try:
            summary_dev, masks_dev, recs, node_names, n_real, js = p
            summary = self._fetch_summary(summary_dev)
            comp_masks = (
                None if masks_dev is None else self._fetch_masks(masks_dev)
            )
            for k, (rec, j) in enumerate(zip(recs, js)):
                rec.update(self._pod_breakdown(
                    k, j, summary, comp_masks, node_names, n_real
                ))
        except Exception as e:
            self._note_breakdown_failure(e)

    def _note_breakdown_failure(self, exc: Exception) -> None:
        """Diagnostics never break the cycle, and a kernel that keeps
        failing stops being retried after three — but a shape or backend
        the explain kernel cannot handle must be SEEN: the first failure
        is logged with its exception and every one is counted
        (``scheduler_explain_kernel_failures_total`` on /metrics)."""
        self.breakdown_failures += 1
        if self._failure_counter is not None:
            self._failure_counter.inc()
        if self.breakdown_failures == 1:
            _log.error(
                "explain kernel failed; decision records lose their "
                "score/filter breakdown",
                err=f"{type(exc).__name__}: {exc}",
            )
        if self.breakdown_failures >= 3:
            self._breakdown_ok = False

    def warm(self, device_batch, params, assignments) -> None:
        """Compile and run the explain kernel for this batch shape ahead of
        the hot loop (``Scheduler.warmup``), under the same failure
        accounting as a live cycle."""
        import jax

        try:
            jax.block_until_ready(
                _explain_kernel(device_batch, params, assignments)[0]
            )
        except Exception as e:
            self._note_breakdown_failure(e)

    @staticmethod
    def _fetch_summary(summary_dev):
        """Materialize the device-side summary reduction (a few KB) — one
        pytree device_get, not one dispatch per array."""
        import jax

        feasible, reject, top_vals, top_idx, win = jax.device_get(
            summary_dev
        )
        return (
            np.asarray(feasible),
            tuple(None if r is None else np.asarray(r) for r in reject),
            np.asarray(top_vals), np.asarray(top_idx), np.asarray(win),
        )

    @staticmethod
    def _fetch_masks(masks_dev):
        import jax

        return tuple(
            None if c is None else np.asarray(jax.device_get(c))
            for c in masks_dev
        )

    def _pod_breakdown(
        self, k: int, j: int, summary, comp_masks, node_names, n_real: int
    ) -> dict:
        """Top-k score breakdown + per-plugin-group rejection counts for
        pod ``k``, against the cycle-start view (from the device-reduced
        summary; example rejected nodes only when the cycle's masks were
        fetched)."""
        feasible, reject, top_vals, top_idx, win = summary
        rejected: dict[str, int] = {}
        for name, r in zip(_COMPONENT_NAMES, reject):
            if r is not None and r[k]:
                rejected[name] = int(r[k])
        out: dict[str, Any] = {
            "view": "cycle-start",
            "feasible_nodes": int(feasible[k]),
            "total_nodes": int(n_real),
            "rejected_by": rejected,
        }
        if comp_masks is not None and not (0 <= j < len(node_names)):
            examples: dict[str, list[str]] = {}
            for name, c in zip(_COMPONENT_NAMES, comp_masks):
                if c is None or name not in rejected:
                    continue
                ex = np.flatnonzero(~c[k][:n_real])[:3]
                examples[name] = [node_names[int(i)] for i in ex]
            out["rejected_examples"] = examples
        top = [
            {"node": node_names[int(i)], "score": int(v)}
            for v, i in zip(top_vals[k], top_idx[k])
            if v > _NEG // 2 and 0 <= int(i) < n_real
        ][: self.top_k]
        if top:
            out["top_nodes"] = top
            if 0 <= j < len(node_names):
                win_score = int(win[k]) if j < n_real else None
                runner = next(
                    (t["score"] for t in top if t["node"] != node_names[j]),
                    None,
                )
                out["win"] = {
                    "node": node_names[j],
                    "score": win_score,
                    "margin": (
                        None if win_score is None or runner is None
                        else win_score - runner
                    ),
                }
        return out

    def _insert(self, rec: dict) -> None:
        rec["seq"] = next(self._seq)
        self._records.append(rec)
        self._by_key[rec["pod"]] = rec
        self._by_key.move_to_end(rec["pod"])
        while len(self._by_key) > self._by_key_max:
            self._by_key.popitem(last=False)

    # ------------------------------------------------------------- outcomes
    def note_requeue(
        self, key: str, where: str, plugins=(), nominated: str | None = None,
        error: bool = False,
    ) -> None:
        """The unschedulable/bind-failure epilogue: where the pod was
        requeued, which plugins rejected it, and any preemption
        nomination."""
        rec = self._by_key.get(key)
        if rec is None:
            return
        hop = {"queue": where, "plugins": sorted(plugins)}
        if error:
            hop["error"] = True
        hops = rec.setdefault("requeue", [])
        hops.append(hop)
        del hops[:-8]           # bounded history
        if nominated is not None:
            rec["nominated_node"] = nominated

    def note_preemption(self, key: str, nominated: str, victims) -> None:
        rec = self._by_key.get(key)
        if rec is not None:
            rec["nominated_node"] = nominated
            rec["preemption_victims"] = list(victims)[:16]

    def note_gang(
        self,
        key: str,
        status: str,
        engine: str = "",
        placement: str | None = None,
        members: int = 0,
        need: int = 0,
        alignment: "int | None" = None,
        slices_considered=(),
        fragmentation_delta: "int | None" = None,
        victims=(),
        victim_group: str | None = None,
    ) -> None:
        """One record per GANG placement decision, keyed by the group's
        ``ns/name`` — WHY the gang landed where it did: the winning
        placement, its slice-alignment score, which slices the search
        considered, the fragmentation delta (slices newly opened minus
        freed), and — for topology-aware preemption — the evicted gang +
        its member pods. ``kubetpu explain ns/name`` renders it."""
        rec: dict[str, Any] = {
            "pod": key,
            "kind": "gang",
            "status": status,
            "replica": self.replica,
            "members": members,
            "need": need,
        }
        if engine:
            rec["engine"] = engine
        if placement is not None:
            rec["placement"] = placement
        if alignment is not None:
            rec["alignment_score"] = int(alignment)
        if slices_considered:
            rec["slices_considered"] = list(slices_considered)[:16]
        if fragmentation_delta is not None:
            rec["fragmentation_delta"] = int(fragmentation_delta)
        if victims:
            rec["preemption_victims"] = list(victims)[:16]
        if victim_group is not None:
            rec["victim_group"] = victim_group
        self._insert(rec)

    def note_bind(
        self,
        info,
        err: Exception | None,
        t_dispatch: float,
        t_exec: float,
        t_done: float,
    ) -> dict[str, float] | None:
        """Bind completion: compute the staged latency vector, fold it into
        the pod's record, and return it (stage -> seconds; the scheduler
        observes it into the {stage} histograms). None on bind error — and
        None for a pod with NO lifecycle flight (the gang/podgroup lane
        bypasses per-pod delivery stamping): its record still closes as
        bound, but a delivery-less pod must not pollute the staged
        histograms or the soak reservoir with a bind-span-only "e2e"."""
        key = info.key
        rec = self._by_key.get(key)
        if err is not None:
            if rec is not None:
                rec["status"] = "bind_error"
                rec["bind_error"] = f"{type(err).__name__}: {err}"
            return None
        fl = self._flights.pop(key, None)
        if rec is not None:
            rec["status"] = "bound"
        if fl is None or not fl.deliver_pc:
            return None
        # the ingest stamp is a perf_counter from the APISERVER process —
        # trust it only when it reads as the same clock domain (the
        # in-process stack; 0 <= create→delivery < 1h). A cross-host
        # deployment's foreign-epoch stamp degrades to delivery-based
        # attribution instead of corrupting every e2e percentile.
        ingest = fl.ingest_pc
        if ingest and not (0.0 <= fl.deliver_pc - ingest < 3600.0):
            ingest = 0.0
        stages: dict[str, float] = {}
        if ingest:
            stages["api_ingest"] = fl.deliver_pc - ingest
        stages["informer"] = max(fl.informer_s, 0.0)
        stages["queue_wait"] = max(getattr(info, "queue_wait_s", 0.0), 0.0)
        if rec is not None:
            stages["encode"] = max(rec.get("encode_s", 0.0), 0.0)
            stages["kernel"] = max(rec.get("kernel_s", 0.0), 0.0)
        if t_exec:
            stages["dispatch"] = max(t_exec - t_dispatch, 0.0)
            stages["bind_rtt"] = max(t_done - t_exec, 0.0)
        else:
            stages["bind_rtt"] = max(t_done - t_dispatch, 0.0)
        e2e = max(t_done - (ingest or fl.deliver_pc), 0.0)
        stages["e2e"] = e2e
        if rec is not None:
            # raw seconds; rendered (and rounded) to stages_ms at read
            # time — the bind-ack path is per-pod hot
            rec["_stages"] = stages
        self.e2e_samples.append((t_done, e2e))
        return stages

    # ----------------------------------------------------------- inspection
    def _snapshot(self) -> list[dict]:
        while True:
            try:
                return list(self._records)
            except RuntimeError:
                continue

    @staticmethod
    def _render(rec: dict) -> dict:
        """Read-time view of one record: raw per-pod seconds become the
        rounded ``stages_ms`` block (hot-path writes stay cheap; readers
        pay the formatting)."""
        out = dict(rec)
        out["queue_wait_s"] = round(out.get("queue_wait_s", 0.0), 6)
        stages = out.pop("_stages", None)
        if stages is not None:
            out["stages_ms"] = {
                k: round(v * 1000.0, 3) for k, v in stages.items()
            }
        return out

    def lookup(self, key: str) -> dict | None:
        """Latest record for a pod key, breakdown resolved and rendered
        (public read — internal updaters go through ``_by_key`` and
        tolerate a pending breakdown)."""
        self._resolve_pending()
        rec = self._by_key.get(key)
        return None if rec is None else self._render(rec)

    def records_json(
        self, pod: str | None = None, limit: int = 256
    ) -> dict:
        """The /debug/flightrecorder body: newest-first records, optionally
        scoped to one pod key (``ns/name``)."""
        self._resolve_pending()
        recs = self._snapshot()
        if pod:
            recs = [r for r in recs if r["pod"] == pod]
        recs = recs[-max(limit, 1):]
        recs.reverse()
        return {
            "records": [self._render(r) for r in recs],
            "count": len(recs),
            "breakdown_failures": self.breakdown_failures,
        }

    def soak_split(
        self, t0: float, t1: float
    ) -> dict | None:
        """The SustainedChurn gate: p99 e2e of the window's first half vs
        its second (sample ack times on this recorder's clock). None when
        either half is empty."""
        if t1 <= t0:
            return None
        mid = (t0 + t1) / 2.0
        first = [e for (t, e) in self.e2e_samples if t0 <= t < mid]
        second = [e for (t, e) in self.e2e_samples if mid <= t <= t1]
        if not first or not second:
            return None
        p99a = float(np.percentile(first, 99)) * 1000.0
        p99b = float(np.percentile(second, 99)) * 1000.0
        ratio = p99b / p99a if p99a > 0 else float("inf")
        return {
            "p99_first_half_ms": round(p99a, 2),
            "p99_second_half_ms": round(p99b, 2),
            "ratio": round(ratio, 3),
            "samples": [len(first), len(second)],
            # "flat" = the second half did not degrade past 2x the first —
            # the sustained-churn acceptance gate (ROADMAP item 2)
            "p99_flat": ratio <= 2.0,
        }
