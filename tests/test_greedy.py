"""Parity tests for the greedy assignment engine: the device-resident
``lax.scan`` (kubetpu.assign.greedy) vs. the scalar per-pod greedy loop
(tests.oracle.greedy) — the analog of the reference's schedule_one_test.go
end-to-end scheduling assertions."""

import numpy as np
import pytest

pytest.importorskip("jax")

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.assign import greedy_assign
from kubetpu.framework import config as C
from kubetpu.framework import encode_batch, score_params
from kubetpu.state import Cache

from . import oracle
from .cluster_gen import random_cluster

RESOURCES = [(t.CPU, 1), (t.MEMORY, 1)]


def test_the_tests_oracle_is_the_benchmark_s_reference():
    """One oracle: parity here and ``correct`` in a cell mean the same."""
    import importlib

    import benchmark.reference.oracle as reference

    assert oracle is reference
    assert importlib.import_module("tests.oracle") is reference


def run_both(cache, pending, profile, **oracle_kwargs):
    snap = cache.update_snapshot()
    batch = encode_batch(snap, pending, profile)
    got = greedy_assign(batch, profile)
    infos = [info.clone() for info in snap.node_infos()]
    want = oracle.greedy(infos, pending, **oracle_kwargs)
    return got, want


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_minimal_profile_parity(seed):
    """BASELINE config #1: NodeResourcesFit(LeastAllocated) only."""
    rng = np.random.default_rng(seed)
    cache, pending = random_cluster(rng, num_nodes=50, num_existing=80, num_pending=60)
    profile = C.minimal_profile()
    got, want = run_both(cache, pending, profile, resources=RESOURCES, w_fit=1, check_ports=False, check_static=False)
    assert got == want


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("with_taints", [False, True])
def test_default_like_profile_parity(seed, with_taints):
    """Fit + BalancedAllocation + NodeAffinity + TaintToleration with the
    reference's default weights (1/1/2/3)."""
    rng = np.random.default_rng(seed + 100)
    cache, pending = random_cluster(
        rng, num_nodes=40, num_existing=60, num_pending=50, with_taints=with_taints
    )
    profile = C.Profile(
        filters=C.PluginSet(enabled=(
            (C.NODE_UNSCHEDULABLE, 1), (C.NODE_NAME, 1), (C.TAINT_TOLERATION, 1),
            (C.NODE_AFFINITY, 1), (C.NODE_PORTS, 1), (C.NODE_RESOURCES_FIT, 1),
        )),
        scores=C.PluginSet(enabled=(
            (C.TAINT_TOLERATION, 3), (C.NODE_AFFINITY, 2),
            (C.NODE_RESOURCES_FIT, 1), (C.NODE_RESOURCES_BALANCED, 1),
        )),
        default_spread_constraints=(),
    )
    got, want = run_both(
        cache, pending, profile,
        resources=RESOURCES, w_fit=1, w_balanced=1, w_node_affinity=2, w_taint=3,
    )
    assert got == want


def test_saturation_spills_in_order():
    """Capacity coupling: pods fill a small node then spill; the last pod is
    unschedulable — the scan must thread state exactly like sequential assume."""
    cache = Cache()
    cache.add_node(make_node("big", cpu_milli=3000, memory=8 * 1024**3, pods=10))
    cache.add_node(make_node("small", cpu_milli=1000, memory=8 * 1024**3, pods=10))
    pending = [
        make_pod(f"p{i}", cpu_milli=900, memory=256 * 1024**2, creation_index=i)
        for i in range(5)
    ]
    profile = C.minimal_profile()
    got, want = run_both(cache, pending, profile, resources=RESOURCES, w_fit=1, check_ports=False, check_static=False)
    assert got == want
    # 3 fit on big, 1 on small, last unschedulable
    assert got.count("big") == 3 and got.count("small") == 1 and got[-1] is None


def test_pod_count_limit_threads_through_scan():
    cache = Cache()
    cache.add_node(make_node("n1", cpu_milli=100000, pods=2))
    cache.add_node(make_node("n2", cpu_milli=100000, pods=2))
    pending = [make_pod(f"p{i}", cpu_milli=10) for i in range(5)]
    profile = C.minimal_profile()
    got, want = run_both(cache, pending, profile, resources=RESOURCES, w_fit=1, check_ports=False, check_static=False)
    assert got == want
    assert got[-1] is None and sorted(got[:4]) == ["n1", "n1", "n2", "n2"]


def test_most_allocated_strategy():
    rng = np.random.default_rng(7)
    cache, pending = random_cluster(rng, num_nodes=30, num_existing=40, num_pending=30)
    profile = C.minimal_profile(strategy=C.MOST_ALLOCATED)
    got, want = run_both(
        cache, pending, profile, resources=RESOURCES, w_fit=1, strategy="most", check_ports=False, check_static=False
    )
    assert got == want
