"""Mesh-sharded assignment through the whole scheduler (Scheduler(mesh=…)).

The PR-6 tentpole properties, on the conftest 8-virtual-CPU-device mesh —
the fast ``not slow`` multichip smoke that runs on EVERY tier-1 pass (the
MULTICHIP harness is no longer the only thing exercising the sharded path):

- **Parity**: a mesh-sharded Scheduler binds pod-for-pod identically to the
  single-device one across the oracle workload shapes (basic resources,
  topology spread, inter-pod affinity), both engines, serial and pipelined,
  including mid-run node add/delete (which reshards the resident block).
- **Sharded resident block**: the node block lives sharded across the mesh;
  dirty-row delta uploads are ROUTED to the owning shard (per-shard byte
  accounting sums to the total), and node add/delete within a padding
  bucket triggers an incremental reshard — a row diff + scatter — not a
  full re-upload.
- **Preemption dry-run**: the victim-search kernel is bit-identical with
  its node-axis inputs sharded over the mesh.
"""

import dataclasses

import numpy as np
import pytest

pytest.importorskip("jax")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.framework import config as C
from kubetpu.framework import runtime as rt
from kubetpu.parallel import make_mesh
from kubetpu.perf import workloads as W
from kubetpu.sched import Scheduler
from kubetpu.state import Cache

from .test_scheduler import FakeClient, make_sched


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest should provide 8 virtual CPU devices"
    return make_mesh(devs[:8])


def _drive(s: Scheduler, client: FakeClient, pods, max_batch=8, events=None):
    for p in pods:
        s.on_pod_add(p)
    calls = idle = 0
    while idle < 3 and calls < 200:
        if events and calls in events:
            events[calls](s)
        res = s.schedule_batch(max_batch)
        s.dispatcher.sync()
        calls += 1
        if res["scheduled"] == 0 and res["unschedulable"] == 0:
            idle += 1
        else:
            idle = 0
    if s._inflight is not None:
        s._complete_inflight()
    s.dispatcher.sync()
    s._drain_bind_completions()
    return dict(client.bound)


def _run_cluster(mesh_arg, factory, engine="greedy", pipeline=False,
                 events=None, num_pods=32):
    client = FakeClient()
    s, _ = make_sched(client, profile=C.Profile(), mesh=mesh_arg,
                      engine=engine, pipeline=pipeline, max_batch=8)
    for i in range(12):
        s.on_node_add(W.node_default(i, zones=("z-a", "z-b", "z-c")))
    # a seed pod matching the affinity templates' zone term (see
    # test_pipeline._parity_case): affinity batches need an existing match
    seed = make_pod(
        "seed-0", namespace="sched-0", labels={"color": "blue"},
        cpu_milli=100, memory=100 * 1024**2, node_name="scheduler-perf-0",
    )
    s.on_pod_add(seed)
    pods = [factory(f"p-{j}", "sched-0") for j in range(num_pods)]
    bound = _drive(s, client, pods, events=events)
    resident = s._resident
    s.close()
    return bound, resident


@pytest.mark.parametrize("engine", ["greedy", "batched"])
@pytest.mark.parametrize("factory", [
    W.pod_default,
    W.pod_with_topology_spreading,
    W.pod_with_pod_affinity,
], ids=["basic", "spread", "interpod-affinity"])
def test_sharded_scheduler_pod_for_pod_parity(mesh, factory, engine):
    """Scheduler(mesh=…) must bind pod-for-pod identically to the
    single-device scheduler on every oracle workload shape — the
    whole-stack twin of test_mesh's kernel parity."""
    ref, _ = _run_cluster(None, factory, engine=engine)
    got, resident = _run_cluster(mesh, factory, engine=engine)
    assert got == ref
    assert len(ref) > 0
    # the resident node block really lives sharded across the mesh
    assert resident.device is not None
    assert resident.device.alloc.sharding.spec == P("nodes")
    assert len(resident.device.alloc.sharding.device_set) == 8


def test_sharded_pipelined_parity(mesh):
    """Pipeline mode on top of the mesh: two orthogonal features, one
    answer."""
    ref, _ = _run_cluster(None, W.pod_with_topology_spreading, pipeline=True)
    got, _ = _run_cluster(mesh, W.pod_with_topology_spreading, pipeline=True)
    assert got == ref and len(ref) > 0


def test_sharded_parity_with_mid_run_node_add_delete(mesh):
    """A node added and a node deleted while the run is in flight: the
    sharded resident block reshards and the assignments still match the
    single-device scheduler event-for-event."""

    def fire_add(s: Scheduler):
        s.on_node_add(W.node_default(12, zones=("z-a", "z-b", "z-c")))

    def fire_del(s: Scheduler):
        s.on_node_delete(s.cache.get_node_info("scheduler-perf-3").node)

    events = {2: fire_add, 4: fire_del}
    ref, _ = _run_cluster(None, W.pod_default, events=events)
    got, _ = _run_cluster(mesh, W.pod_default, events=events)
    assert got == ref and len(ref) > 0


# ---------------------------------------------------------------------------
# sharded resident block: routed delta uploads + incremental reshard
# ---------------------------------------------------------------------------

def _encode_state(num_nodes=10, num_pods=6):
    cache = Cache()
    for i in range(num_nodes):
        cache.add_node(make_node(f"n{i}", cpu_milli=8000,
                                 memory=16 * 1024**3))
    pods = [make_pod(f"p{j}", cpu_milli=500, memory=512 * 1024**2)
            for j in range(num_pods)]
    return cache, pods


def _node_block_fields():
    return ("alloc", "requested", "nonzero_requested", "pod_count",
            "allowed_pods", "node_valid")


def test_sharded_delta_upload_routed_per_shard(mesh):
    """Dirty rows are grouped by owning shard on the host and scattered
    shard-locally; the result is bit-identical to a fresh unsharded encode
    and the per-shard byte accounting sums to the total."""
    cache, pods = _encode_state(num_nodes=16)
    profile = C.Profile()
    resident = rt.ResidentNodeState(mesh=mesh)
    snap = cache.update_snapshot()
    b1 = rt.encode_batch(snap, pods, profile, resident=resident, mesh=mesh)
    assert b1.resident_bytes > 0
    assert resident.device.alloc.sharding.spec == P("nodes")

    # dirty two rows in DIFFERENT shards (16 nodes / 8 shards = 2 per shard)
    cache.add_pod(make_pod("placed-a", cpu_milli=1500, memory=1024**3,
                           node_name="n1"))
    cache.add_pod(make_pod("placed-b", cpu_milli=700, memory=1024**3,
                           node_name="n14"))
    snap = cache.update_snapshot(snap)
    b2 = rt.encode_batch(snap, pods, profile, prev_nt=b1.node_tensors,
                         resident=resident, mesh=mesh)
    full = sum(
        int(np.asarray(getattr(b2.device.nodes, f)).nbytes)
        for f in _node_block_fields()
    )
    assert 0 < resident.last_upload_bytes < full
    assert sum(resident.last_upload_bytes_per_shard) == \
        resident.last_upload_bytes
    # the two dirty rows were routed to exactly their owning shards
    assert resident.last_rows_per_shard[1 // 2] >= 1    # n1 → shard 0
    assert resident.last_rows_per_shard[14 // 2] >= 1   # n14 → shard 7
    assert sum(resident.last_rows_per_shard) == 2

    ref = rt.encode_batch(cache.update_snapshot(), pods, profile)
    for f in _node_block_fields():
        np.testing.assert_array_equal(
            np.asarray(getattr(b2.device.nodes, f)),
            np.asarray(getattr(ref.device.nodes, f)), err_msg=f,
        )
    # sharding survives the scatter (donated in place, not re-laid-out)
    assert b2.device.nodes.alloc.sharding.spec == P("nodes")


@pytest.mark.parametrize("use_mesh", [False, True], ids=["single", "mesh"])
def test_incremental_reshard_on_node_add_delete(mesh, use_mesh):
    """A node ADD within the padding bucket now EXTENDS the host NodeTensors
    in place (the PR-14 append-incremental branch: same object, appended
    rows marked dirty) and the resident block ships only the delta rows; a
    node DELETE still rebuilds (order reindexes) and incrementally reshards.
    Both must stay bit-identical to a fresh encode."""
    cache, pods = _encode_state(num_nodes=10)   # pads to 16: room to grow
    profile = C.Profile()
    resident = rt.ResidentNodeState(mesh=mesh if use_mesh else None)
    snap = cache.update_snapshot()
    b1 = rt.encode_batch(snap, pods, profile, resident=resident,
                         mesh=mesh if use_mesh else None)
    full = resident.last_upload_bytes
    assert full > 0

    # node ADD: appended in place — same tensors object, delta upload only
    cache.add_node(make_node("n10", cpu_milli=2000, memory=4 * 1024**3))
    snap = cache.update_snapshot(snap)
    b2 = rt.encode_batch(snap, pods, profile, prev_nt=b1.node_tensors,
                         resident=resident, mesh=mesh if use_mesh else None)
    assert b2.node_tensors is b1.node_tensors, (
        "a pure node add should extend the tensors in place, not rebuild"
    )
    assert 0 < resident.last_upload_bytes < full, (
        "node add within the padding bucket should delta-upload, "
        f"not re-upload (shipped {resident.last_upload_bytes}/{full})"
    )
    ref = rt.encode_batch(cache.update_snapshot(), pods, profile)
    for f in _node_block_fields():
        np.testing.assert_array_equal(
            np.asarray(getattr(b2.device.nodes, f)),
            np.asarray(getattr(ref.device.nodes, f)), err_msg=f"add:{f}",
        )

    # node DELETE: rows compact (n5 gone, order shifts) + validity shrinks
    cache.remove_node("n5")
    snap = cache.update_snapshot(snap)
    b3 = rt.encode_batch(snap, pods, profile, prev_nt=b2.node_tensors,
                         resident=resident, mesh=mesh if use_mesh else None)
    assert 0 < resident.last_upload_bytes
    ref = rt.encode_batch(cache.update_snapshot(), pods, profile)
    for f in _node_block_fields():
        np.testing.assert_array_equal(
            np.asarray(getattr(b3.device.nodes, f)),
            np.asarray(getattr(ref.device.nodes, f)), err_msg=f"del:{f}",
        )


def test_reshard_skips_clean_rows(mesh):
    """The reshard diff must not re-ship rows whose values did not change:
    touching one node re-ships O(1) rows, not O(N)."""
    cache, pods = _encode_state(num_nodes=16)
    resident = rt.ResidentNodeState(mesh=mesh)
    snap = cache.update_snapshot()
    b1 = rt.encode_batch(snap, pods, C.Profile(), resident=resident,
                         mesh=mesh)
    # REPLACE one node object (same name set — no rebuild necessary, but
    # either path must ship O(changed), not O(N))
    cache.update_node(make_node("n7", cpu_milli=9000, memory=16 * 1024**3))
    snap = cache.update_snapshot(snap)
    b2 = rt.encode_batch(snap, pods, C.Profile(), prev_nt=b1.node_tensors,
                         resident=resident, mesh=mesh)
    if b2.node_tensors is b1.node_tensors:
        # incremental encode kept the object: plain delta path
        assert sum(resident.last_rows_per_shard) <= 2
    else:
        # rebuild: the reshard diff still ships only the changed rows
        assert sum(resident.last_rows_per_shard) <= 4


# ---------------------------------------------------------------------------
# preemption dry-run parity under the mesh
# ---------------------------------------------------------------------------

def _preemption_problem():
    """A saturated cluster + a high-priority preemptor, PDBs included."""
    from kubetpu.api import types as t

    cache = Cache()
    for i in range(8):
        cache.add_node(make_node(f"n{i}", cpu_milli=1000, memory=2 * 1024**3,
                                 pods=8))
        cache.add_pod(make_pod(
            f"low-{i}", cpu_milli=900, memory=1024**3, priority=0,
            node_name=f"n{i}", labels={"app": "victim"}, creation_index=i,
        ))
    pdb = t.PodDisruptionBudget(
        name="pdb",
        selector=t.LabelSelector.of({"app": "victim"}),
        disruptions_allowed=4,
    )
    pending = [make_pod("high", cpu_milli=800, memory=1024**3, priority=100,
                        creation_index=99)]
    profile = C.Profile()
    snap = cache.update_snapshot()
    batch = rt.encode_batch(snap, pending, profile)
    params = rt.score_params(profile, batch.resource_names)
    return batch, params, (pdb,)


def test_sharded_preemption_dry_run_bit_parity(mesh):
    """ops.preemption.dry_run_preemption with every node-axis input sharded
    over the mesh must return the same chosen node, victim rows and
    candidate masks as single-device."""
    from kubetpu.framework.preemption import PreemptionEvaluator
    from kubetpu.ops import preemption as OP

    batch, params, pdbs = _preemption_problem()
    ev = PreemptionEvaluator(batch, params, pdbs=pdbs)
    b = batch.device
    v = ev.victims
    i = 0
    wants_conf = (
        jnp.einsum(
            "k,kl->l", b.pod_ports[i].astype(jnp.int32),
            b.port_conflict.astype(jnp.int32),
        ) > 0
    )

    def run(shard: bool):
        potential = ev._potential_mask(i)
        node = NamedSharding(mesh, P("nodes"))

        def put(x):
            x = jnp.asarray(x)
            return jax.device_put(x, node) if shard else x

        return OP.dry_run_preemption(
            b.requests[i],
            jnp.asarray(np.int64(batch.pods[i].priority)),
            wants_conf,
            put(potential),
            put(b.alloc), put(ev.requested), put(ev.pod_count),
            put(b.allowed_pods), put(ev.port_counts),
            put(v.valid), put(v.priority), put(v.start), put(v.requests),
            put(v.victim_ports), put(v.pdb),
            jnp.asarray(ev.pdb_allowed),
        )

    ref = run(False)
    got = run(True)
    for name, a, g in zip(("node_idx", "victims", "ok", "n_pdb"), ref, got):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(g), err_msg=name
        )
    assert int(np.asarray(ref[0])) >= 0, "the fixture must actually preempt"


def test_sharded_scheduler_preemption_parity(mesh):
    """End to end: a mesh-sharded scheduler preempts the same victim and
    lands the preemptor on the same node as the single-device one."""

    def run(mesh_arg):
        deleted = []

        class Client(FakeClient):
            def delete_pod(self, pod, reason=""):
                deleted.append(pod.name)

            def nominate(self, pod, node_name):
                pass

        client = Client()
        s, _ = make_sched(client, profile=C.Profile(), mesh=mesh_arg)
        s.enable_preemption()
        for i in range(4):
            s.on_node_add(make_node(f"n{i}", cpu_milli=1000, memory=2**31))
            s.on_pod_add(make_pod(
                f"low-{i}", cpu_milli=900, priority=0, node_name=f"n{i}",
                creation_index=i,
            ))
        s.on_pod_add(make_pod("high", cpu_milli=800, priority=100,
                              creation_index=10))
        res = s.schedule_batch()
        s.dispatcher.sync()
        s.close()
        return res, sorted(deleted)

    ref_res, ref_deleted = run(None)
    got_res, got_deleted = run(mesh)
    assert got_res == ref_res
    assert got_deleted == ref_deleted and len(ref_deleted) == 1


# ---------------------------------------------------------------------------
# multichip smoke: the sharded path on every tier-1 run
# ---------------------------------------------------------------------------

def test_multichip_smoke(mesh):
    """Fast whole-loop smoke over 8 forced host devices (the CI twin of the
    MULTICHIP harness): mesh="auto" resolves to the 8-device mesh, the
    cycle runs SPMD, per-shard metrics flow, and the cycle records carry
    the mesh shape."""
    client = FakeClient()
    s, _ = make_sched(client, profile=C.minimal_profile(), mesh="auto")
    assert s.mesh is not None and s.mesh_shape == (8,)
    for i in range(8):
        s.on_node_add(make_node(f"n{i}", cpu_milli=4000, memory=8 * 1024**3))
    for j in range(16):
        s.on_pod_add(make_pod(f"p{j}", cpu_milli=500, memory=256 * 1024**2,
                              creation_index=j))
    res = s.schedule_batch()
    s.dispatcher.sync()
    assert res["scheduled"] == 16
    rec = s.metrics.tpu.records[-1]
    assert rec.mesh_shape == (8,)
    assert rec.shard_transfer_bytes is not None
    assert sum(rec.shard_transfer_bytes) > 0
    # the exposition carries the shard-labeled series
    text = s.metrics_text()
    assert "tpu_shard_host_to_device_transfer_bytes_total" in text
    assert "tpu_mesh_collective_wall_seconds" in text
    s.close()


def test_run_workload_on_the_mesh_binds_the_single_device_map(mesh):
    """The four-chip smoke's mesh check, small: the in-process harness on
    SchedulingBasic with ``mesh="on"`` binds the single-device run's map
    pod for pod (``bindings_digest``), with the resident block sharded
    over every device of the mesh and routed delta bytes on more than one
    shard."""
    from kubetpu.perf import run_workload

    wl = W.Workload("tiny", {"initNodes": 256, "initPods": 16,
                             "measurePods": 48})
    runs = {
        m: run_workload("SchedulingBasic", wl, max_batch=16, mesh=m,
                        timeout_s=120, warmup=False)
        for m in (None, "on")
    }
    single, meshed = runs[None], runs["on"]
    assert single.scheduled == meshed.scheduled == 48
    assert meshed.bindings_digest == single.bindings_digest
    assert single.mesh_placement is None
    n = meshed.n_devices
    assert n == len(jax.devices()) and n > 1
    placement = meshed.mesh_placement
    assert placement["block_sharded"]
    assert placement["resident_devices"] == n
    assert placement["shards_with_transfer"] > 1
    assert isinstance(meshed.collective_wall_s, float)
