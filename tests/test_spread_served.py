"""Upstream's TopologySpreading deployment on the served path, small: the
``Scheduler`` loop over constraint-free init pods and then pods of
templates/pod-with-topology-spreading.yaml (a hard zone spread), held to the
scalar oracle pod for pod under the default profile; the two variants of the
assign program under one bucket set; the spans and counters of the spread
path; and the batched engine, which hands a hard spread to the scan."""

import collections
import re

import numpy as np
import pytest

pytest.importorskip("jax")

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_node, make_pod, spread_constraint
from kubetpu.framework import config as C
from kubetpu.framework import runtime as rt
from kubetpu.metrics.tpu import jit_cache_size
from kubetpu.state import Cache

from . import oracle
from .test_scheduler import FakeClient, make_sched

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
#: 30 nodes: zones of 20/5/5, where a round of the batched engine breaks a
#: hard maxSkew, and upstream's round-robin over three zones, where it
#: happens not to
UNEVEN = ["sun-1"] * 20 + ["sun-2"] * 5 + ["sun-3"] * 5
ROUND_ROBIN = [f"moon-{i % 3 + 1}" for i in range(30)]
SHAPES = {"uneven-skew1": (UNEVEN, 1), "upstream-skew5": (ROUND_ROBIN, 5)}
#: the oracle's view of ``C.Profile()`` for these pods (what the benchmark's
#: parity check of topologyspread-5k uses)
ORACLE = dict(w_fit=1, w_balanced=1, w_spread=2, check_spread=True)
PLUGIN = ("scheduler_plugin_execution_duration_seconds_count"
          '{plugin="PodTopologySpread",extension_point="PreFilter",'
          'status="Success"}')
CONSTRAINED = "scheduler_spread_constrained_pods_total"


def nodes_of(zones):
    return [make_node(f"n{i}", cpu_milli=4000, memory=32 * 1024 ** 3,
                      pods=110, labels={HOSTNAME: f"n{i}", ZONE: z})
            for i, z in enumerate(zones)]


def init_pod(j, cpu=100):
    """templates/pod-default.yaml: no label, no constraint."""
    return make_pod(f"i{j}", namespace="namespace-0", cpu_milli=cpu,
                    memory=500 * 1024 ** 2, creation_index=j)


def spread_pod(j, max_skew, cpu=100):
    """templates/pod-with-topology-spreading.yaml at ``max_skew``."""
    return make_pod(
        f"m{j}", namespace="namespace-1", labels={"color": "blue"},
        cpu_milli=cpu, memory=500 * 1024 ** 2, creation_index=j,
        spread=(spread_constraint(
            max_skew, ZONE,
            when=t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE,
            match_labels={"color": "blue"}),))


def deployment(shape, seed, measured=64):
    """(zones, max_skew, init pods, measured pods): the init pods' number and
    both kinds' cpu requests come from the seed."""
    zones, max_skew = SHAPES[shape]
    rng = np.random.default_rng(seed)
    n_init = int(rng.integers(8, 40))
    init = [init_pod(j, int(rng.choice([100, 200, 400])))
            for j in range(n_init)]
    pods = [spread_pod(n_init + j, max_skew,
                       int(rng.choice([100, 150, 250])))
            for j in range(measured)]
    return zones, max_skew, init, pods


def served(zones, **kw):
    client = FakeClient()
    s, _ = make_sched(client, profile=C.Profile(), **kw)
    for n in nodes_of(zones):
        s.on_node_add(n)
    return s, client


def oracle_infos(zones):
    cache = Cache()
    for n in nodes_of(zones):
        cache.add_node(n)
    return [info.clone() for info in cache.update_snapshot().node_infos()]


def blue_by_zone(bound, zones):
    zone_of = {f"n{i}": z for i, z in enumerate(zones)}
    counts = collections.Counter({z: 0 for z in set(zones)})
    for key, node in bound.items():
        if key.startswith("namespace-1/"):
            counts[zone_of[node]] += 1
    return counts


def feasible_at_its_turn(zones, pods, placed):
    """How many of ``placed`` (node names, in order) the oracle's own
    filters refuse in the state the earlier placements left."""
    infos = oracle_infos(zones)
    by_name = {info.node.name: info for info in infos}
    bad = 0
    for pod, node in zip(pods, placed):
        if node is None:
            continue
        info = by_name[node]
        if not (oracle.fits(pod, info)
                and oracle.spread_filter(pod, infos, info)):
            bad += 1
        info.add_pod(pod.with_node(node))
    return bad


def run_cycles(s, client, zones, max_skew):
    """Cycles until the queue is empty; after each, the zones' counts of
    bound measured pods lie within ``max_skew`` of each other."""
    cycles = 0
    while True:
        res = s.schedule_batch()
        s.dispatcher.sync()
        s._drain_bind_completions()
        if not res["scheduled"] and not res["unschedulable"]:
            return cycles
        cycles += 1
        counts = blue_by_zone(client.bound, zones)
        assert max(counts.values()) - min(counts.values()) <= max_skew, (
            cycles, counts)
        assert cycles < 200


# ------------------------------------------------------- (a), (b): the loop

@pytest.mark.parametrize("engine", ["greedy", "batched"])
@pytest.mark.parametrize("seed", [3, 2147483700])
@pytest.mark.parametrize("max_batch", [8, 64])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_served_loop_binds_what_the_oracle_binds(
        shape, max_batch, seed, engine):
    zones, max_skew, init, pods = deployment(shape, seed)
    s, client = served(zones, max_batch=max_batch, engine=engine)
    for pod in init + pods:
        s.on_pod_add(pod)
    run_cycles(s, client, zones, max_skew)
    s.close()
    want = oracle.greedy(oracle_infos(zones), init + pods, **ORACLE)
    got = [client.bound.get(f"{p.namespace}/{p.name}") for p in init + pods]
    assert got == want
    assert None not in got
    assert feasible_at_its_turn(zones, init + pods, got) == 0
    counts = blue_by_zone(client.bound, zones)
    assert sum(counts.values()) == len(pods)
    assert max(counts.values()) - min(counts.values()) <= max_skew


# --------------------------------------- the batched engine and a hard skew

@pytest.mark.parametrize("pad", [0, 128])
def test_the_batched_engine_hands_a_hard_spread_to_the_scan(pad):
    """Zones of 20/5/5 and 64 pods at maxSkew 1 in ONE batch: a round of the
    batched engine admits by capacity alone, and 38 of its 64 placements
    were infeasible at their turn (24/20/20 where the scan gives 22/21/21)."""
    from kubetpu.assign.batched import batched_assign_device
    from kubetpu.assign.greedy import greedy_assign_device

    pods = [spread_pod(j, 1) for j in range(64)]
    cache = Cache()
    for n in nodes_of(UNEVEN):
        cache.add_node(n)
    profile = C.Profile()
    batch = rt.encode_batch(cache.update_snapshot(), pods, profile,
                            pad_pods=pad)
    assert batch.device.spread.has_hard
    params = rt.score_params(profile, batch.resource_names)
    got, state = batched_assign_device(batch.device, params)
    want, want_state = greedy_assign_device(batch.device, params)
    got = np.asarray(got)
    np.testing.assert_array_equal(got, np.asarray(want))
    np.testing.assert_array_equal(np.asarray(state[4]),
                                  np.asarray(want_state[4]))
    placed = [batch.node_names[int(j)] for j in got[:64]]
    assert feasible_at_its_turn(UNEVEN, pods, placed) == 0
    zones = collections.Counter(UNEVEN[int(j)] for j in got[:64])
    assert sorted(zones.values()) == [21, 21, 22]
    assert (got[64:] == -1).all()


def test_a_batch_without_a_hard_spread_keeps_its_rounds():
    """Soft constraints and no constraints stay with the round loop: the
    scan is chosen from the batch's static ``has_hard`` alone."""
    from kubetpu.assign.batched import batched_assign_device

    soft = [make_pod(
        f"s{j}", namespace="namespace-1", labels={"color": "blue"},
        cpu_milli=100, memory=500 * 1024 ** 2, creation_index=j,
        spread=(spread_constraint(
            1, ZONE, when=t.UnsatisfiableConstraintAction.SCHEDULE_ANYWAY,
            match_labels={"color": "blue"}),)) for j in range(16)]
    plain = [init_pod(j) for j in range(16)]
    cache = Cache()
    for n in nodes_of(UNEVEN):
        cache.add_node(n)
    snap = cache.update_snapshot()
    profile = C.Profile()
    for pods, spread in ((soft, True), (plain, False)):
        batch = rt.encode_batch(snap, pods, profile)
        assert (batch.device.spread is not None) == spread
        params = rt.score_params(profile, batch.resource_names)
        text = batched_assign_device.lower(batch.device, params).as_text()
        # the round loop sorts pods by their chosen node; the scan never does
        assert "stablehlo.sort" in text
        got, _ = batched_assign_device(batch.device, params)
        assert (np.asarray(got)[:16] >= 0).all()
    hard = rt.encode_batch(snap, [spread_pod(j, 1) for j in range(16)],
                           profile)
    params = rt.score_params(profile, hard.resource_names)
    assert "stablehlo.sort" not in batched_assign_device.lower(
        hard.device, params).as_text()


# ------------------------------------------- (c): two variants, one bucket

def one_cycle(s, pods):
    for pod in pods:
        s.on_pod_add(pod)
    before = jit_cache_size(s._assign_device)
    res = s.schedule_batch()
    s.dispatcher.sync()
    s._drain_bind_completions()
    assert res == {"scheduled": len(pods), "unschedulable": 0}
    return jit_cache_size(s._assign_device) - before


@pytest.mark.parametrize("engine", ["greedy", "batched"])
def test_the_second_variant_compiles_once_at_the_bucket_the_first_ran(
        engine):
    # 66 nodes pad to 128: shapes no other test of this file compiles (the
    # jit caches outlive a test)
    zones = [f"moon-{i % 3 + 1}" for i in range(66)]
    s, client = served(zones, engine=engine)
    init = [init_pod(j) for j in range(40)]
    assert one_cycle(s, init) <= 1                  # bucket 64, no spread
    first = [spread_pod(100 + j, 5) for j in range(5)]
    assert one_cycle(s, first) <= 1                 # its spread variant
    second = [spread_pod(200 + j, 5) for j in range(7)]
    assert one_cycle(s, second) == 0
    # constraint-free pods again: their variant is still there
    last = [init_pod(300 + j) for j in range(3)]
    assert one_cycle(s, last) == 0
    # every batch padded to the one bucket the init pods ran
    assert s._pod_buckets == {s.profile.name: {64}}
    s.close()
    # answers as unpadded: the oracle knows no padding
    pods = init + first + second + last
    want = oracle.greedy(oracle_infos(zones), pods, **ORACLE)
    assert [client.bound[f"{p.namespace}/{p.name}"] for p in pods] == want


# --------------------------------------------------------- (d): the tracing

def sample(s, name):
    """One series of the scheduler's /metrics page, by its full name."""
    for line in s.metrics.prom.registry.expose().splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    return None


def test_the_spread_path_is_named_and_silent_without_constraints():
    s, _client = served(ROUND_ROBIN)
    one_cycle(s, [init_pod(j) for j in range(12)])
    assert sample(s, PLUGIN) is None
    assert sample(s, CONSTRAINED) == 0
    assert [sp for sp in s.tracer.drain() if sp.name == "encode-spread"] == []

    one_cycle(s, [spread_pod(100 + j, 5) for j in range(9)])
    assert sample(s, PLUGIN) == 1
    assert sample(s, CONSTRAINED) == 9
    total = sample(s, PLUGIN.replace("_count", "_sum"))
    assert total > 0
    spans = s.tracer.drain()
    [spread] = [sp for sp in spans if sp.name == "encode-spread"]
    assert {k: spread.attrs[k] for k in (
        "signatures", "domains", "constrained_pods")} == {
            "signatures": 1, "domains": 3, "constrained_pods": 9}
    encode = [sp for sp in spans if sp.name == "encode"][-1]
    assert spread.parent_id == encode.span_id
    assert encode.start <= spread.start <= spread.end <= encode.end
    assert spread.duration_s == pytest.approx(total)

    # a mixed cycle counts the constrained pods only; one without, nothing
    one_cycle(s, [init_pod(200 + j) for j in range(4)]
              + [spread_pod(300 + j, 5) for j in range(2)])
    assert sample(s, PLUGIN) == 2 and sample(s, CONSTRAINED) == 11
    one_cycle(s, [init_pod(400 + j) for j in range(4)])
    assert sample(s, PLUGIN) == 2 and sample(s, CONSTRAINED) == 11
    assert re.search(r'scheduler_schedule_attempts_total\{[^}]*\} 31',
                     s.metrics.prom.registry.expose())
    s.close()


def test_the_kernels_carry_their_scopes():
    """``jax.named_scope`` around the spread filter and the counts update,
    so that a device profile names them."""
    from kubetpu.assign.greedy import greedy_assign_device

    cache = Cache()
    for n in nodes_of(ROUND_ROBIN):
        cache.add_node(n)
    profile = C.Profile()
    batch = rt.encode_batch(cache.update_snapshot(),
                            [spread_pod(j, 5) for j in range(8)], profile)
    params = rt.score_params(profile, batch.resource_names)
    text = greedy_assign_device.lower(batch.device, params).as_text(
        debug_info=True)
    assert "spread_filter" in text and "spread_counts_update" in text


# ------------------------------------- the read-back of the chip runs' zones

def test_the_read_back_counts_bound_blue_pods_by_zone():
    """``tools/spread_zones_run.py`` wraps the benchmark's run and prints the
    zones' counts from the store: the evidence, after every chip run of
    topologyspread-5k, of the guarantee the harness's check has no rule for."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "tools", "spread_zones_run.py")
    spec = importlib.util.spec_from_file_location("spread_zones_run", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    nodes = nodes_of(UNEVEN)
    blue = [spread_pod(j, 5).with_node(f"n{i}")
            for j, i in enumerate([0, 1, 19, 20, 24, 25, 29])]
    others = [init_pod(50).with_node("n3"),          # bound, no label
              spread_pod(60, 5),                     # blue, not bound
              spread_pod(61, 5).with_node("gone")]   # its node left the store
    pods = [(f"{p.namespace}/{p.name}", p) for p in blue + others]
    assert tool.bound_by_zone(nodes, pods) == {
        "sun-1": 3, "sun-2": 2, "sun-3": 2, "?": 1}
    assert tool.bound_by_zone(nodes, []) == {}
