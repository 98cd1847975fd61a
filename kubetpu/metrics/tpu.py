"""Device-side counters — the TPU half of the observability plane.

The reference judges itself on host-side scheduler histograms; kubetpu's
hot path is a fused XLA program, so the equivalent diagnosis surface is
device-shaped: how big the batches are, whether the XLA compile cache is
hitting (a miss stalls a cycle by seconds), how many bytes the host→device
encode ships, and where device wall time goes per cycle. ``SURVEY §5``'s
span-per-cycle design joins these to the host trace by CYCLE ID: every
``record_cycle`` keeps a join record the trace exporter and the perf
harness dump next to the result JSON.

Metric set (labels ``engine`` = greedy | batched):

- ``tpu_batch_size`` histogram — pods per device cycle
- ``tpu_jit_cache_hits_total`` / ``tpu_jit_cache_misses_total`` counters —
  per-cycle compile-cache outcome of the assignment program (a miss means
  XLA compiled a new (shape, params) variant this cycle)
- ``tpu_host_to_device_transfer_bytes_total`` counter — bytes ACTUALLY
  shipped host→device for the cycle (pod block + node-state delta rows;
  signature compression and device residency are what keep this small)
- ``scheduler_device_resident_bytes`` gauge — bytes of cluster node state
  living on device ACROSS cycles (pipeline mode); dashboards read resident
  state and per-cycle traffic as separate series
- ``tpu_device_kernel_wall_seconds`` histogram — wall time of the device
  assignment program incl. the blocking fetch of its outputs
- ``scheduler_encode_cache_hits_total`` / ``…_misses_total`` counters
  (label ``kind`` = filter | score | request | pod_sig) and
  ``scheduler_encode_cache_entries`` gauge — the template-keyed encode
  cache (state.encode_cache): a high steady-state hit rate is what keeps
  host encode off the cycle critical path
- ``scheduler_encode_template_index_pods_total{result}`` counter
  (``result`` = kept | keyed) — the bound pods the template-count index
  (``EncodeCache.pod_groups``) walked and kept, or had to key: keyed
  follows the pods that changed, not the pods on the nodes they touched
- ``scheduler_cache_bind_confirmations_total{result}`` counter
  (``result`` = kept | reapplied) — the confirmations of assumed pods the
  scheduler cache took (``Cache.add_pod``): kept ends the assume and moves
  no node's generation, reapplied removes and adds the pod again
- ``scheduler_encode_node_rows_total`` counter — node rows the host encode
  wrote into the node tensors: a refresh's rows whose generation moved,
  every row of a build
- ``tpu_shard_host_to_device_transfer_bytes_total{engine,shard}`` counter
  and ``tpu_shard_device_resident_bytes{engine,shard}`` gauge — the
  per-shard view of the SHARDED resident node block (delta uploads are
  routed to the owning shard on the host, so these are real per-chip
  bytes, not an even split of a broadcast)
- ``tpu_mesh_collective_wall_seconds{engine}`` gauge — one-shot cross-
  shard argmax probe on the scheduler's mesh: the collective tax the
  sharded kernel walls include (MULTICHIP evidence carries its context)
"""

from __future__ import annotations

import collections
from dataclasses import asdict, dataclass

from .registry import Registry, exponential_buckets

#: what the template-count index did with a bound pod it walked
TEMPLATE_INDEX_RESULTS = ("kept", "keyed")
#: what the scheduler cache did with the confirmation of an assumed pod
BIND_CONFIRMATION_RESULTS = ("kept", "reapplied")


@dataclass(frozen=True)
class CycleRecord:
    """Per-cycle device-side observation, joined to host spans by cycle id
    (+ ``profile``: a mixed-profile batch runs one device program per
    profile under ONE cycle id, and the matching scheduling-cycle span
    carries the same profile attribute). ``compile_miss`` is None when the
    backend exposes no compile-cache introspection — unmeasured, not a
    hit."""

    cycle: int
    engine: str
    batch_size: int
    transfer_bytes: int
    kernel_wall_s: float
    compile_miss: bool | None
    profile: str = ""
    # full encoded-batch pytree bytes — what a residency-less cycle would
    # have shipped; transfer_bytes < batch_bytes is the delta-upload win
    batch_bytes: int = 0
    # device-resident node-state bytes backing this cycle (0 = no residency)
    resident_bytes: int = 0
    # True when this cycle ran in the two-stage pipeline (encode overlapped
    # the previous cycle's device program)
    pipelined: bool = False
    # mesh the cycle ran under: device-mesh shape (() = single device) and
    # the per-shard routed delta-upload bytes (None when unsharded) — the
    # per-chip attribution MULTICHIP evidence is judged on
    mesh_shape: tuple = ()
    shard_transfer_bytes: "list[int] | None" = None
    # cross-shard reduction probe for this scheduler's mesh (seconds; None
    # when unsharded) — the collective tax the kernel walls include
    collective_wall_s: "float | None" = None
    # federation stamp: which scheduler replica ran this cycle ("" =
    # single-scheduler mode) — multi-replica cycle streams against one
    # cluster stay attributable per record
    replica: str = ""
    # packing-engine solve diagnostics (assign.packing; None for the
    # other engines): the cycle's cluster-objective value and how many
    # projection-loop iterations the warm-started solver needed
    objective_value: "float | None" = None
    solver_iters: "int | None" = None

    def to_json(self) -> dict:
        out = asdict(self)
        out["mesh_shape"] = list(self.mesh_shape)
        return out


def batch_nbytes(device_batch) -> int:
    """Total bytes of a device pytree's array leaves — the host→device
    transfer upper bound for one encoded batch (every leaf is shipped by
    ``jnp.asarray`` at encode time; cached node rows make this an upper
    bound, which is the honest direction for a transfer budget)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(device_batch):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
    return total


def jit_cache_size(fn) -> int | None:
    """Compiled-variant count of a jitted callable (None when the backend
    does not expose it) — sampled before/after a call to classify the call
    as compile-cache hit or miss."""
    size = getattr(fn, "_cache_size", None)
    if size is None:
        return None
    try:
        return int(size())
    except Exception:  # pragma: no cover - backend quirk
        return None


class TPUBackendMetrics:
    """See module docstring. Registers on a shared Registry so one
    /metrics exposition carries host and device metrics together."""

    def __init__(self, registry: Registry | None = None,
                 max_records: int = 4096) -> None:
        r = registry if registry is not None else Registry()
        self.registry = r
        self.batch_size = r.histogram(
            "tpu_batch_size",
            "Pods per device scheduling cycle.",
            labels=("engine",),
            buckets=exponential_buckets(1, 2, 14),
        )
        self.jit_cache_hits = r.counter(
            "tpu_jit_cache_hits_total",
            "Device cycles served from the XLA compile cache.",
            labels=("engine",),
        )
        self.jit_cache_misses = r.counter(
            "tpu_jit_cache_misses_total",
            "Device cycles that compiled a new XLA program variant.",
            labels=("engine",),
        )
        self.transfer_bytes = r.counter(
            "tpu_host_to_device_transfer_bytes_total",
            "Bytes actually shipped host to device per cycle "
            "(pod block + node-state delta).",
            labels=("engine",),
        )
        self.resident_bytes = r.gauge(
            "scheduler_device_resident_bytes",
            "Cluster node-state bytes resident on device across cycles.",
            labels=("engine",),
        )
        self.kernel_wall = r.histogram(
            "tpu_device_kernel_wall_seconds",
            "Wall time of the device assignment program per cycle, "
            "including the blocking output fetch.",
            labels=("engine",),
            buckets=exponential_buckets(0.0001, 2, 18),
        )
        self.encode_cache_hits = r.counter(
            "scheduler_encode_cache_hits_total",
            "Static encode rows served from the template-keyed encode "
            "cache (gathered, not rebuilt).",
            labels=("kind",),
        )
        self.encode_cache_misses = r.counter(
            "scheduler_encode_cache_misses_total",
            "Static encode rows built fresh (first sight of a template, "
            "or after a node-event invalidation).",
            labels=("kind",),
        )
        self.encode_cache_entries = r.gauge(
            "scheduler_encode_cache_entries",
            "Entries resident in the encode cache (LRU-bounded).",
        )
        self.template_index_pods = r.counter(
            "scheduler_encode_template_index_pods_total",
            "Bound pods the encode cache's template-count index walked on "
            "nodes whose generation moved, by what it did: kept (the pod it "
            "had counted, or a copy with the same template fields) or keyed "
            "(a pod new to the node, or one whose template fields changed).",
            labels=("result",),
            declared={"result": TEMPLATE_INDEX_RESULTS},
        )
        for result in TEMPLATE_INDEX_RESULTS:
            # both on the first scrape, at zero: a delta meets no gap
            self.template_index_pods.labels(result)
        self.bind_confirmations = r.counter(
            "scheduler_cache_bind_confirmations_total",
            "Confirmations of assumed pods the scheduler cache took, by "
            "what it did: kept (the bound pod equals the assumed one field "
            "for field: the assume ends, the node's generation stays) or "
            "reapplied (any difference: the pod is removed and added again "
            "and its node is re-encoded).",
            labels=("result",),
            declared={"result": BIND_CONFIRMATION_RESULTS},
        )
        for result in BIND_CONFIRMATION_RESULTS:
            self.bind_confirmations.labels(result)
        self.encode_node_rows = r.counter(
            "scheduler_encode_node_rows_total",
            "Node rows the host encode wrote into the node tensors: in a "
            "refresh the rows whose generation moved, in a build every row.",
        )
        # --- mesh-sharded assignment (parallel.mesh) ---------------------
        self.shard_transfer_bytes = r.counter(
            "tpu_shard_host_to_device_transfer_bytes_total",
            "Bytes routed to one shard of the sharded resident node block "
            "(delta uploads grouped by owning shard on the host).",
            labels=("engine", "shard"),
        )
        self.shard_resident_bytes = r.gauge(
            "tpu_shard_device_resident_bytes",
            "Per-shard bytes of the device-resident node block.",
            labels=("engine", "shard"),
        )
        self.collective_wall = r.gauge(
            "tpu_mesh_collective_wall_seconds",
            "Cross-shard argmax reduction probe on the scheduler's mesh "
            "(the collective tax included in sharded kernel walls).",
            labels=("engine",),
        )
        self.records: collections.deque[CycleRecord] = collections.deque(
            maxlen=max_records
        )

    def set_host_encode_totals(self, node_rows: int,
                               confirmations: dict) -> None:
        """Hand over the totals the loop thread keeps without a lock (the
        encoded node rows, ``Cache.bind_confirmations``): at scrape time,
        as the loop's phase clock is."""
        self.encode_node_rows.set_total(node_rows)
        for result, n in confirmations.items():
            self.bind_confirmations.labels(result).set_total(n)

    def record_cycle(
        self,
        cycle: int,
        engine: str,
        batch_size: int,
        transfer_bytes: int,
        kernel_wall_s: float,
        compile_miss: bool | None,
        profile: str = "",
        batch_bytes: int = 0,
        resident_bytes: int = 0,
        pipelined: bool = False,
        mesh_shape: tuple = (),
        shard_transfer_bytes: "list[int] | None" = None,
        shard_resident_bytes: "list[int] | None" = None,
        collective_wall_s: "float | None" = None,
        replica: str = "",
        objective_value: "float | None" = None,
        solver_iters: "int | None" = None,
    ) -> CycleRecord:
        self.batch_size.labels(engine).observe(batch_size)
        self.transfer_bytes.labels(engine).inc(transfer_bytes)
        self.resident_bytes.labels(engine).set(resident_bytes)
        self.kernel_wall.labels(engine).observe(kernel_wall_s)
        if shard_transfer_bytes:
            for s, b in enumerate(shard_transfer_bytes):
                if b:
                    self.shard_transfer_bytes.labels(engine, str(s)).inc(b)
        if shard_resident_bytes:
            # honest placement, not an even split: the single-device
            # fallback reports everything on shard 0 (runtime.
            # ResidentNodeState.nbytes_per_shard)
            for s, b in enumerate(shard_resident_bytes):
                self.shard_resident_bytes.labels(engine, str(s)).set(b)
        if collective_wall_s is not None:
            self.collective_wall.labels(engine).set(collective_wall_s)
        if compile_miss is not None:
            if compile_miss:
                self.jit_cache_misses.labels(engine).inc()
            else:
                self.jit_cache_hits.labels(engine).inc()
        rec = CycleRecord(
            cycle=cycle, engine=engine, batch_size=batch_size,
            transfer_bytes=transfer_bytes, kernel_wall_s=kernel_wall_s,
            compile_miss=(
                None if compile_miss is None else bool(compile_miss)
            ),
            profile=profile,
            batch_bytes=batch_bytes or transfer_bytes,
            resident_bytes=resident_bytes,
            pipelined=pipelined,
            mesh_shape=tuple(mesh_shape),
            shard_transfer_bytes=shard_transfer_bytes,
            collective_wall_s=collective_wall_s,
            replica=replica,
            objective_value=objective_value,
            solver_iters=solver_iters,
        )
        self.records.append(rec)
        return rec

    def records_json(self) -> list[dict]:
        return [r.to_json() for r in self.records]
