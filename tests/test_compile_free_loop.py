"""The serving loop compiles only for a batch larger than any before it
(ISSUE 27's loop no longer stalls, so its batches are as large as the supply
makes them): a smaller batch pads to a pod bucket whose programs exist
(``Scheduler._pod_bucket``), and the resident block's dirty rows travel in
chunks of ONE length, whatever their number (``ResidentNodeState``)."""

import numpy as np
import pytest

pytest.importorskip("jax")

from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.framework import config as C
from kubetpu.framework import runtime as rt
from kubetpu.metrics.tpu import jit_cache_size
from kubetpu.perf import workloads as W
from kubetpu.sched import Scheduler
from kubetpu.state import Cache

from .test_scheduler import FakeClient, make_sched


def cycle(s, client, first, count):
    """One cycle over ``count`` new pods; returns the programs the assign
    engine and the scatter compiled for it."""
    for j in range(first, first + count):
        s.on_pod_add(W.pod_default(f"p{j}", "default"))
    before = (jit_cache_size(s._assign_device),
              jit_cache_size(rt._scatter_node_rows))
    res = s.schedule_batch()
    s.dispatcher.sync()
    s._drain_bind_completions()
    assert res["scheduled"] == count
    return (jit_cache_size(s._assign_device) - before[0],
            jit_cache_size(rt._scatter_node_rows) - before[1])


def served(nodes=24, **kw):
    client = FakeClient()
    s, _ = make_sched(client, **kw)
    for i in range(nodes):
        s.on_node_add(W.node_default(i))
    return s, client


@pytest.mark.parametrize("engine", ["greedy", "batched"])
def test_a_batch_smaller_than_one_before_compiles_nothing(engine):
    s, client = served(engine=engine)
    assert cycle(s, client, 0, 40)[0] == 1          # bucket 64: compiled
    scatters = 0
    for first, count in ((100, 3), (200, 2), (300, 17), (400, 5), (500, 64)):
        assign, scatter = cycle(s, client, first, count)
        assert assign == 0, count
        scatters += scatter
    assert scatters <= 1        # 3, 2, 17 and 5 dirty rows: one program
    assert s._pod_buckets == {s.profile.name: {64}}
    # a larger one compiles its own bucket, once
    assert cycle(s, client, 600, 70)[0] == 1
    assert cycle(s, client, 700, 100)[0] == 0
    assert s._pod_buckets == {s.profile.name: {64, 128}}
    s.close()


def test_the_padded_batch_binds_what_its_own_bucket_binds():
    placed = []
    for warm in (0, 60):
        s, client = served()
        if warm:
            cycle(s, client, 1000, warm)
        cycle(s, client, 0, 5)
        placed.append({key for key in client.bound
                       if int(key.rsplit("p", 1)[1]) < 1000})
        s.close()
    assert placed[0] == placed[1] == {f"default/p{j}" for j in range(5)}


@pytest.mark.parametrize("pods, pad", [(5, 64), (13, 16), (40, 1024)])
def test_the_greedy_loop_gives_a_padded_batch_its_own_bucket_s_answer(
        pods, pad):
    """A saturated cluster (3 nodes x 4 pods), so the order of the steps
    and the state they carry decide who is placed."""
    from kubetpu.assign.greedy import greedy_assign_device

    cache = Cache()
    for i in range(3):
        cache.add_node(make_node(f"n{i}", cpu_milli=2000, memory=4 * 1024**3))
    batch = [make_pod(f"p{j}", cpu_milli=500 - j, memory=64 * 1024**2,
                      creation_index=j) for j in range(pods)]
    snap = cache.update_snapshot()
    profile = C.minimal_profile()
    own = rt.encode_batch(snap, batch, profile)
    padded = rt.encode_batch(snap, batch, profile, pad_pods=pad)
    assert padded.device.pod_valid.shape == (pad,)
    params = rt.score_params(profile, own.resource_names)
    a_own, state_own = greedy_assign_device(own.device, params)
    a_pad, state_pad = greedy_assign_device(padded.device, params)
    a_own, a_pad = np.asarray(a_own), np.asarray(a_pad)
    np.testing.assert_array_equal(a_pad[:pods], a_own[:pods])
    assert (a_pad[pods:] == -1).all()
    assert (a_own[:pods] >= 0).sum() == min(pods, 12)
    for got, want in zip(state_pad[:3], state_own[:3]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_after_warmup_every_batch_gets_its_own_rung():
    s, client = served()
    s.warmup([W.pod_default(f"w{j}", "default") for j in range(64)])
    assert s._pod_buckets[s.profile.name] == {8, 16, 32, 64}
    assert s._pod_bucket(s.profile, 3) == 8
    assert s._pod_bucket(s.profile, 20) == 32
    assert cycle(s, client, 0, 20)[0] == 0
    s.close()


# ------------------------------------------------- the resident node block

def node_state(num_nodes):
    cache = Cache()
    for i in range(num_nodes):
        cache.add_node(make_node(f"n{i}", cpu_milli=8000,
                                 memory=16 * 1024**3))
    return cache, [make_pod("p0", cpu_milli=100, memory=1024**2)]


def dirty(cache, rows, tag):
    for i in rows:
        cache.add_pod(make_pod(f"{tag}-{i}", cpu_milli=100, memory=1024**2,
                               node_name=f"n{i}"))


@pytest.mark.parametrize("num_nodes, rows", [
    (40, [1, 5, 2, 11, 3, 19, 1]),          # one chunk of 32, always
    (2600, [5, 1100, 1025, 1290]),          # chunks of 1024: one, two, two
], ids=["40-nodes", "2600-nodes"])
def test_any_number_of_dirty_rows_goes_through_one_scatter_program(
        num_nodes, rows):
    cache, pods = node_state(num_nodes)
    profile = C.Profile()
    resident = rt.ResidentNodeState()
    snap = cache.update_snapshot()
    batch = rt.encode_batch(snap, pods, profile, resident=resident)
    programs = None
    for k, count in enumerate(rows):
        dirty(cache, range(k, k + count), f"w{k}")
        snap = cache.update_snapshot(snap)
        batch = rt.encode_batch(snap, pods, profile,
                                prev_nt=batch.node_tensors, resident=resident)
        assert resident.last_rows_per_shard == [count]
        chunk = min(1024, batch.device.nodes.alloc.shape[0] // 2)
        rows_sent = -(-count // chunk) * chunk
        assert resident.last_upload_bytes % rows_sent == 0
        if programs is None:
            programs = jit_cache_size(rt._scatter_node_rows)
        assert jit_cache_size(rt._scatter_node_rows) == programs
    ref = rt.encode_batch(cache.update_snapshot(), pods, profile)
    for field in ("alloc", "requested", "nonzero_requested", "pod_count",
                  "allowed_pods", "node_valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(batch.device.nodes, field)),
            np.asarray(getattr(ref.device.nodes, field)), err_msg=field)
