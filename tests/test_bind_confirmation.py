"""The scheduler cache's confirmation of an assumed pod (``Cache.add_pod``).

A bind confirmation that equals the assumed pod field for field only ends
the assume: no node's generation moves, so the next snapshot re-clones and
the next encode re-encodes nothing for it. Anything else, a confirmation
that differs in any field or an add of a pod that was never assumed, takes
the remove-and-add it always took. Held here: both paths on the cache's own
state; and over many cycles of assume and confirm on four cells' templates,
node, spread and inter-pod affinity tensors equal to a build from nothing."""

import dataclasses

import numpy as np
import pytest

pytest.importorskip("jax")

import jax

from benchmark.harness import templates
from benchmark.harness.manifest import Cell, load_manifest
from kubetpu.api import types as t
from kubetpu.api.wrappers import make_node, make_pod, pod_affinity_term
from kubetpu.framework import config as C
from kubetpu.framework import runtime as rt
from kubetpu.state.encode_cache import EncodeCache
from kubetpu.state.encoder import encode_snapshot
from kubetpu.state.snapshot import Cache


def one_node_cache():
    cache = Cache()
    cache.add_node(make_node("n1", cpu_milli=4000, memory=8 << 30))
    cache.add_node(make_node("n2", cpu_milli=4000, memory=8 << 30))
    return cache


def pending():
    return make_pod("p", cpu_milli=500, memory=1 << 30,
                    labels={"color": "blue"})


def test_an_equal_confirmation_only_ends_the_assume():
    cache = one_node_cache()
    assumed = pending().with_node("n1")
    cache.assume_pod(assumed)
    snap = cache.update_snapshot()
    info = cache.get_node_info("n1")
    gen, last = info.generation, cache._last_gen
    touched = list(cache._touched.items())
    requested = dict(info.requested)

    cache.add_pod(pending().with_node("n1"))   # the informer's rebuild

    assert not cache.is_assumed(assumed.uid)
    assert info.generation == gen and cache._last_gen == last
    assert list(cache._touched.items()) == touched
    assert cache.touched_since(snap.cache_watermark) == []
    assert info.requested == requested and len(info.pods) == 1
    assert info.pods[assumed.uid] is assumed   # the cached object stays
    assert cache.bind_confirmations == {"kept": 1, "reapplied": 0}
    # a refresh finds nothing to copy
    before = snap.nodes["n1"]
    snap = cache.update_snapshot(snap)
    assert snap.nodes["n1"] is before
    # and the bound pod is no longer the assume's: no expiry, no forget
    cache.finish_binding(assumed.uid)
    assert cache.cleanup_expired() == []
    cache.forget_pod(assumed)
    assert cache.has_pod(assumed.uid)


def test_the_kept_path_compares_what_the_pod_s_eq_compares():
    """``add_pod`` compares two pods' ``__dict__``: the dataclass's ``==``
    only while every field takes part in ``==`` and a pod holds nothing
    but its fields."""
    fields = dataclasses.fields(t.Pod)
    assert all(f.compare for f in fields)
    p = pending().with_node("n1")
    assert set(p.__dict__) == {f.name for f in fields}
    assert p == pending().with_node("n1")
    assert p.__dict__ == pending().with_node("n1").__dict__


def _term():
    return pod_affinity_term("kubernetes.io/hostname",
                             match_labels={"color": "blue"})


#: one change a field class: the confirmation differs from the assumed pod
CHANGES = {
    "node": lambda p: p.with_node("n2"),
    "labels": lambda p: dataclasses.replace(
        p, labels=t.freeze_map({"color": "green"})),
    "requests": lambda p: dataclasses.replace(
        p, requests=(("cpu", 700), ("memory", 1 << 30))),
    "ports": lambda p: dataclasses.replace(
        p, ports=(t.ContainerPort(host_port=8080),)),
    "affinity": lambda p: dataclasses.replace(
        p, affinity=t.Affinity(pod_affinity=t.PodAffinity(
            required=(_term(),)))),
    "volumes": lambda p: dataclasses.replace(
        p, volumes=(t.PodVolume(name="data", pvc_name="claim"),)),
    "phase": lambda p: dataclasses.replace(p, phase="Running"),
}


@pytest.mark.parametrize("field", list(CHANGES))
def test_a_confirmation_that_differs_reapplies(field):
    cache = one_node_cache()
    assumed = pending().with_node("n1")
    cache.assume_pod(assumed)
    snap = cache.update_snapshot()
    n1 = cache.get_node_info("n1")
    gen = n1.generation

    bound = CHANGES[field](pending().with_node("n1"))
    assert bound != assumed
    cache.add_pod(bound)

    assert not cache.is_assumed(assumed.uid)
    assert cache.bind_confirmations == {"kept": 0, "reapplied": 1}
    assert n1.generation > gen
    assert "n1" in cache.touched_since(snap.cache_watermark)
    on = cache.get_node_info(bound.node_name)
    assert on.pods[bound.uid] is bound
    assert on.requested == dict(bound.requests)
    if field == "node":
        # the accounting moved with the pod
        assert not n1.pods and n1.requested.get("cpu", 0) == 0
        assert "n2" in cache.touched_since(snap.cache_watermark)
    snap = cache.update_snapshot(snap)
    assert snap.nodes[bound.node_name].pods[bound.uid] is bound


@pytest.mark.parametrize("assumed_first", [False, True])
def test_an_add_of_a_pod_not_assumed_replaces_as_before(assumed_first):
    """A relist's duplicate, or a second Add after the confirmation: the
    remove-and-add, the generation moved, no double count, no counter."""
    cache = one_node_cache()
    if assumed_first:
        cache.assume_pod(pending().with_node("n1"))
        cache.add_pod(pending().with_node("n1"))
    else:
        cache.add_pod(pending().with_node("n1"))
    counted = dict(cache.bind_confirmations)
    info = cache.get_node_info("n1")
    gen = info.generation
    again = pending().with_node("n1")
    cache.add_pod(again)
    assert info.generation > gen
    assert info.pods[again.uid] is again and len(info.pods) == 1
    assert info.requested["cpu"] == 500
    assert cache.bind_confirmations == counted


def test_a_confirmation_with_new_labels_still_flags_the_pods_mutated():
    cache = one_node_cache()
    nt = snap = None

    def refresh():
        nonlocal nt, snap
        snap = cache.update_snapshot(snap)
        nt = encode_snapshot(snap, pad_nodes=8, prev=nt)
        return nt

    refresh()
    cache.assume_pod(pending().with_node("n1"))
    cache.assume_pod(make_pod("q", cpu_milli=100).with_node("n2"))
    refresh()
    # the equal one: no row, no flag
    cache.add_pod(make_pod("q", cpu_milli=100).with_node("n2"))
    refresh()
    assert nt.last_dirty_rows == ()
    assert not nt.last_pods_mutated and not nt.last_values_changed
    # new labels, same resources: the row re-encodes to the same values,
    # but the pod set's content moved
    cache.add_pod(CHANGES["labels"](pending().with_node("n1")))
    refresh()
    assert nt.last_dirty_rows == (nt.name_to_idx["n1"],)
    assert nt.last_pods_mutated and not nt.last_values_changed


# ------------------------------------- exactness over many cycles, 4 cells

CELLS = ["basic-5k.saturate", "topologyspread-5k.saturate",
         "preferredaffinity-5k.saturate", "podmatchinganti-5k.saturate"]
NODES, BATCH, CYCLES, INIT = 24, 16, 9, 12


def deployment(workload):
    """The cell's node and pod templates at a small scale: (nodes, init
    pods bound, measured template, namespace)."""
    config = Cell(load_manifest(), workload).config
    zones = tuple(config["zones"])
    node_of = templates.resolve(templates.NODE_TEMPLATES,
                                config["node_template"])
    nodes = [node_of(i, zones) for i in range(NODES)]
    init = config["init_pods"]
    init_of = templates.resolve(templates.POD_TEMPLATES, init["template"])
    bound = [init_of(f"init-{j}", init["namespace"]).with_node(
        nodes[(5 * j) % NODES].name) for j in range(INIT)]
    measured = config["measured_pods"]
    template = templates.resolve(templates.POD_TEMPLATES,
                                 measured["template"])
    return nodes, bound, template, measured["namespace"]


def from_nothing(nodes, cache, pods):
    """``pods`` encoded against a cache built anew from ``cache``'s nodes
    and pods, with no earlier tensors and no encode cache."""
    fresh = Cache()
    for n in nodes:
        fresh.add_node(n)
    for p in cache._pods.values():
        fresh.add_pod(p)
    return fresh, rt.encode_batch(fresh.update_snapshot(), pods, C.Profile(),
                                  pad_pods=BATCH)


def assert_same_batch(got, want):
    leaves_got, tree_got = jax.tree_util.tree_flatten(got.device)
    leaves_want, tree_want = jax.tree_util.tree_flatten(want.device)
    assert tree_got == tree_want
    for a, b in zip(leaves_got, leaves_want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in ("alloc", "requested", "nonzero_requested", "pod_count",
                 "allowed_pods"):
        np.testing.assert_array_equal(getattr(got.node_tensors, name),
                                      getattr(want.node_tensors, name))


@pytest.mark.parametrize("workload", CELLS)
def test_assume_and_confirm_cycles_encode_as_a_build_from_nothing(workload):
    nodes, init, template, ns = deployment(workload)
    cache = Cache()
    for n in nodes:
        cache.add_node(n)
    for p in init:
        cache.add_pod(p)
    ec = EncodeCache()
    snap = prev = None
    held, placed = [], []
    confirmed = 0
    for c in range(CYCLES):
        batch_pods = [template(f"m{c}-{j}", ns) for j in range(BATCH)]
        snap = cache.update_snapshot(snap)
        got = rt.encode_batch(snap, batch_pods, C.Profile(), prev_nt=prev,
                              cache=ec, pad_pods=BATCH)
        if prev is not None:
            assert got.node_tensors is prev     # refreshed, not rebuilt
        prev = got.node_tensors
        fresh, want = from_nothing(nodes, cache, batch_pods)
        assert_same_batch(got, want)
        for name, info in fresh.update_snapshot().nodes.items():
            mine = snap.nodes[name]
            assert mine.requested == info.requested
            assert mine.nonzero_requested == info.nonzero_requested
            assert mine.port_triples == info.port_triples
            assert set(mine.pods) == set(info.pods)
        # the informer's confirmations of the last batch, then this
        # batch's assumes: two pods a node, on nodes the spread moves
        for p, node in zip(held, placed):
            cache.add_pod(p.with_node(node))
        confirmed += len(held)
        placed = [nodes[(7 * c + 3 * j) % NODES].name
                  for j in range(BATCH)]
        for p, node in zip(batch_pods, placed):
            cache.assume_pod(p.with_node(node))
        held = batch_pods
    assert confirmed == (CYCLES - 1) * BATCH
    assert cache.bind_confirmations == {"kept": confirmed, "reapplied": 0}
