"""Upstream's SchedulingPodMatchingAntiAffinity deployment, small: running
pods of templates/pod-with-pod-anti-affinity.yaml (color=green, a REQUIRED
hostname anti-affinity to color=green) refuse their nodes to every pod of
templates/pod-with-pod-anti-affinity-label.yaml, which carries the label and
no term of its own. Held: both engines equal the scalar oracle pod for pod
on a sample against a cluster of init and bound pods, by the harness's own
comparison (``check.oracle_parity``, what decides ``correct``); the init
template as ONE batch through the rounds lands at most one pod a node; the
same comparison FAILS with the existing pods' anti-affinity slots blanked;
the stamp, the span and the two counters the cell's per-layer metrics read;
and a served run through the loop of ``kubetpu scheduler`` binds every pod,
none on an init pod's node."""

import collections
import copy
import dataclasses
import importlib.util
import os

import numpy as np
import pytest

pytest.importorskip("jax")

import jax

from benchmark.harness import check, templates
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.harness.templates_podmatchinganti import (
    pod_with_pod_anti_affinity,
    pod_with_pod_anti_affinity_label,
)
from kubetpu.api import types as t
from kubetpu.api.wrappers import make_pod, pod_affinity_term
from kubetpu.assign.batched import batched_assign_device
from kubetpu.client.informers import NODES, PODS
from kubetpu.framework import config as C
from kubetpu.framework import runtime as rt
from kubetpu.metrics.textparse import parse_prometheus_text
from kubetpu.state import Cache
from kubetpu.state import podaffinity as enc_podaffinity
from kubetpu.store import MemStore

from . import oracle
from .test_preferredspread_served import bound_to, oracle_infos
from .test_served_pipeline import served

CELL = "podmatchinganti-5k.saturate"
INIT_NS, MEASURED_NS = "sched-0", "sched-1"
NODE_COUNT = 200
INIT = 40
BOUND = 300
SAMPLE = 64
#: the oracle's view of ``C.Profile()``: the parity rule of
#: benchmark/configs/podmatchinganti-5k.json
ORACLE = dict(w_fit=1, w_balanced=1, w_interpod=2, check_interpod=True)
FILTER_PODS = "scheduler_podaffinity_filter_pods_total"
ANTI_NODES = "scheduler_podaffinity_existing_anti_nodes_total"
WORK = "scheduler_podaffinity_pods_total"
TERMS = ("affinity", "anti_affinity", "existing_anti_affinity")


def tool():
    """``tools/affinity_nodes_run.py``, whose ``anti`` line is the chip
    runs' census over all bindings."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "tools", "affinity_nodes_run.py")
    spec = importlib.util.spec_from_file_location("affinity_nodes_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def node_names(idx):
    return [f"scheduler-perf-{int(i)}" for i in idx]


def final_cluster(seed, nodes=NODE_COUNT, init=INIT, bound=BOUND):
    """The cell's cluster at a small cut, as a run leaves it: ``init`` init
    pods on distinct random nodes, ``bound`` measured pods on random nodes
    that hold none (uneven counts). Returns (nodes, stored pods as
    (key, pod), the init pods' nodes)."""
    rng = np.random.default_rng(seed)
    node_objs = [templates.node_default(i) for i in range(nodes)]
    init_at = node_names(rng.choice(nodes, size=init, replace=False))
    others = sorted(set(node_names(range(nodes))) - set(init_at))
    stored = []
    for j, name in enumerate(init_at):
        pod = pod_with_pod_anti_affinity(f"i{j}", INIT_NS).with_node(name)
        stored.append((f"{INIT_NS}/i{j}", pod))
    for j in range(bound):
        name = others[int(rng.integers(0, len(others)))]
        pod = pod_with_pod_anti_affinity_label(f"m{j}", MEASURED_NS)
        stored.append((f"{MEASURED_NS}/m{j}", pod.with_node(name)))
    return node_objs, stored, set(init_at)


def cell_config(engine):
    config = copy.deepcopy(Cell(load_manifest(), CELL).config)
    flags = config["scheduler_flags"]
    flags[flags.index("--engine") + 1] = engine
    config["parity"]["sample"] = SAMPLE
    return config


def oracle_infos_of(nodes, stored):
    """``final_cluster``'s nodes and pods as the oracle sees them."""
    cache = Cache()
    for n in nodes:
        cache.add_node(n)
    for _k, p in stored:
        cache.add_pod(p)
    return [info.clone() for info in cache.update_snapshot().node_infos()]


# ------------------------------------- both engines, held to the oracle

@pytest.mark.parametrize("engine", ["greedy", "batched"])
def test_both_engines_equal_the_oracle_pod_for_pod(engine):
    """What decides ``correct`` in the cell, at a cut: the engine on a
    sample of measured pods against the final cluster, held pod for pod to
    ``oracle.greedy(check_interpod=True)``. The init pods' nodes are the
    emptiest, and no placement takes one."""
    for seed in (5, 2147483713):
        nodes, stored, init_nodes = final_cluster(seed)
        parity = check.oracle_parity(cell_config(engine), nodes, stored,
                                     seed)
        assert parity["problems"] == [], parity
        assert parity["pod_for_pod"] and parity["placed"] == SAMPLE
    infos = oracle_infos_of(nodes, stored)
    want = oracle.greedy(
        infos, [pod_with_pod_anti_affinity_label(f"s{j}", MEASURED_NS)
                for j in range(SAMPLE)], **ORACLE)
    assert None not in want and not set(want) & init_nodes


def blank_existing_anti(monkeypatch):
    """The control's fault: the encoder leaves every EA slot at -1, so no
    kernel sees an existing pod's anti-affinity."""
    real = enc_podaffinity.encode_pod_affinity

    def blanked(*args, **kwargs):
        pa = real(*args, **kwargs)
        if pa is None:
            return None
        ea = np.full_like(pa.ea_rows, -1)
        return dataclasses.replace(
            pa, ea_rows=ea, has_filter_work=bool(
                (pa.fa_rows >= 0).any() or (pa.ra_rows >= 0).any()))

    monkeypatch.setattr(enc_podaffinity, "encode_pod_affinity", blanked)


@pytest.mark.parametrize("engine", ["greedy", "batched"])
def test_the_comparison_fails_with_the_existing_anti_slots_blanked(
        engine, monkeypatch):
    """The control of the comparison: an encoder that drops the existing
    pods' anti-affinity, handed the cluster a CORRECT run leaves (the init
    pods' nodes the emptiest), sends sample pods there, where the oracle
    sends none. A faulty program's own run fills those nodes like the rest,
    so there the sample can miss it: the census below is what catches the
    fault over all bindings."""
    nodes, stored, init_nodes = final_cluster(5)
    blank_existing_anti(monkeypatch)
    parity = check.oracle_parity(cell_config(engine), nodes, stored, 5)
    assert not parity["pod_for_pod"]
    assert parity["problems"]
    on_init = [p for p in parity["problems"]
               if p.split("engine ")[1].split(",")[0] in init_nodes]
    assert on_init


# ----------------------------------------- the init pods as one batch

@pytest.mark.parametrize("nodes,pods", [(NODE_COUNT, INIT), (40, 64)])
def test_the_init_template_as_one_batch_lands_one_pod_a_node(nodes, pods):
    """Required anti-affinity among a batch's OWN pods, through the batched
    rounds: every init pod matches every other's term, so a node takes one.
    With more pods than nodes the rest stay unplaced."""
    cache = Cache()
    for i in range(nodes):
        cache.add_node(templates.node_default(i))
    batch = rt.encode_batch(
        cache.update_snapshot(),
        [pod_with_pod_anti_affinity(f"i{j}", INIT_NS) for j in range(pods)],
        C.Profile())
    params = rt.score_params(C.Profile(), batch.resource_names)
    assignments, _ = batched_assign_device(batch.device, params)
    got = np.asarray(jax.device_get(assignments))[:pods]
    per_node = collections.Counter(int(j) for j in got if j >= 0)
    assert sum(per_node.values()) == min(nodes, pods)
    assert max(per_node.values()) == 1


# ---------------------------------------- the stamp, span and counters

def encode(pods, init_on=range(10), nodes=40):
    cache = Cache()
    for i in range(nodes):
        cache.add_node(templates.node_default(i, ("z1",)))
    for j, i in enumerate(init_on):
        cache.add_pod(pod_with_pod_anti_affinity(f"i{j}", INIT_NS).with_node(
            f"scheduler-perf-{i}"))
    return rt.encode_batch(cache.update_snapshot(), pods, C.Profile())


def test_the_stamp_counts_each_kind_of_filter_slot_and_the_refused_nodes():
    label = [pod_with_pod_anti_affinity_label(f"p{j}", MEASURED_NS)
             for j in range(5)]
    plain = [templates.pod_default(f"q{j}", MEASURED_NS) for j in range(3)]
    anti = [pod_with_pod_anti_affinity(f"a{j}", INIT_NS) for j in range(2)]
    required = [templates.pod_with_pod_affinity(f"r{j}", MEASURED_NS)
                for j in range(4)]
    stamp = encode(label + plain + anti + required).podaffinity_encode
    assert stamp.filter_terms == {"affinity": 4, "anti_affinity": 2,
                                  "existing_anti_affinity": 7}
    assert stamp.filter_pods == 11
    # ten init pods on ten nodes: each of the 7 pods they match loses 10
    assert stamp.existing_anti_nodes == 70
    # two init pods on one node refuse that node once
    stamp = encode(label, init_on=[3, 3, 4]).podaffinity_encode
    assert stamp.existing_anti_nodes == 5 * 2
    # a pod no existing term matches adds nothing; nor does a batch of them
    stamp = encode(plain).podaffinity_encode
    assert stamp.filter_terms == dict.fromkeys(TERMS, 0)
    assert stamp.existing_anti_nodes == 0 and stamp.filter_pods == 0
    # with no init pod bound no pod of the batch carries or meets a term:
    # the encoder does not run
    stamp = encode(label, init_on=[]).podaffinity_encode
    assert stamp is None


def anti_pod(name, labels, selector):
    """A pod of ``sched-0`` labelled ``labels`` whose one required hostname
    anti-affinity term selects ``selector``."""
    term = pod_affinity_term(templates.HOSTNAME_KEY, match_labels=selector,
                             namespaces=(MEASURED_NS, INIT_NS))
    return make_pod(name, namespace=INIT_NS, labels=labels,
                    affinity=t.Affinity(pod_anti_affinity=t.PodAffinity(
                        required=(term,))), cpu_milli=100)


def test_the_refused_nodes_are_the_union_of_a_pod_s_slots():
    """Two existing terms: ``color=green`` held on nodes 0 and 1,
    ``tier=web`` on nodes 0 and 2. A pod counts the nodes where ANY of its
    slots holds a count, once each; pods are grouped by slot tuple."""
    cache = Cache()
    for i in range(20):
        cache.add_node(templates.node_default(i))
    for j, (i, selector) in enumerate([(0, {"color": "green"}),
                                       (1, {"color": "green"}),
                                       (0, {"tier": "web"}),
                                       (2, {"tier": "web"})]):
        cache.add_pod(anti_pod(f"e{j}", {"app": "x"}, selector).with_node(
            f"scheduler-perf-{i}"))

    def pending(j, labels):
        return make_pod(f"p{j}", namespace=MEASURED_NS, labels=labels,
                        cpu_milli=100)

    pods = ([pending(j, {"color": "green"}) for j in range(2)]
            + [pending(10 + j, {"tier": "web"}) for j in range(3)]
            + [pending(20, {"color": "green", "tier": "web"}),
               pending(30, {"app": "x"})])
    stamp = rt.encode_batch(cache.update_snapshot(), pods,
                            C.Profile()).podaffinity_encode
    assert stamp.filter_terms["existing_anti_affinity"] == 6
    assert stamp.existing_anti_nodes == 2 * 2 + 3 * 2 + 3


def scrape(s, name, **labels):
    return parse_prometheus_text(s.metrics_text()).value(name, **labels)


def cluster(nodes=40):
    st = MemStore()
    for i in range(nodes):
        node = templates.node_default(i)
        st.create(NODES, node.name, node)
    return st


def post(st, pods):
    for pod in pods:
        st.create(PODS, f"{pod.namespace}/{pod.name}", pod)
    return pods


def run_until_dry(s, once, st):
    last = -1
    for _ in range(40):
        once()
        n = sum(1 for _k, p in st.list(PODS)[0] if p.node_name)
        if n == last and s._inflight is None:
            return
        last = n
    raise AssertionError("the loop did not run dry")


def test_the_counters_and_the_span_carry_the_existing_anti_affinity():
    st = cluster()
    for j in range(10):
        post(st, [pod_with_pod_anti_affinity(f"i{j}", INIT_NS).with_node(
            f"scheduler-perf-{j}")])
    s, _clock, once = served(st, engine="batched", max_batch=16)

    def terms():
        return tuple(scrape(s, FILTER_PODS, term=k) for k in TERMS)

    def work():
        return (scrape(s, WORK, work="filter"), scrape(s, WORK, work="score"))

    def spans():
        return [sp for sp in s.tracer.drain()
                if sp.name == "encode-podaffinity"]

    try:
        # all three series and the node count from the first scrape, at 0
        assert terms() == (0, 0, 0) and scrape(s, ANTI_NODES) == 0
        assert work() == (0, 0)
        post(st, [pod_with_pod_anti_affinity_label(f"p{j}", MEASURED_NS)
                  for j in range(7)])
        run_until_dry(s, once, st)
        assert terms() == (0, 0, 7)
        assert scrape(s, ANTI_NODES) == 70
        # {work} keeps its meaning: a filter slot of any kind
        assert work() == (7, 0)
        [span] = spans()
        assert (span.attrs["existing_anti_pods"],
                span.attrs["existing_anti_nodes"]) == (7, 70)
        assert span.attrs["filter_pods"] == 7

        # a pod no existing term matches: encoded, counted nowhere
        post(st, [templates.pod_default(f"q{j}", MEASURED_NS)
                  for j in range(3)])
        run_until_dry(s, once, st)
        assert terms() == (0, 0, 7) and scrape(s, ANTI_NODES) == 70
        [span] = spans()
        assert (span.attrs["existing_anti_pods"],
                span.attrs["existing_anti_nodes"]) == (0, 0)

        # an init-template pod has its own term AND matches the others'
        post(st, [pod_with_pod_anti_affinity("i10", INIT_NS)])
        run_until_dry(s, once, st)
        assert terms() == (0, 1, 8)
        assert scrape(s, ANTI_NODES) == 80
        assert work() == (8, 0)
    finally:
        s.close()


# --------------------------------------------- a served run, end to end

def test_a_served_run_binds_every_pod_and_none_on_an_init_node():
    """The loop of ``kubetpu scheduler`` over the batched engine: the init
    pods posted unbound and placed by the scheduler, then the measured pods
    in batches of 16; every pod binds, one init pod a node at most, no
    measured pod beside one, and the measured pods where the oracle puts
    them."""
    st = cluster()
    s, _clock, once = served(st, engine="batched", max_batch=16)
    try:
        post(st, [pod_with_pod_anti_affinity(f"i{j}", INIT_NS)
                  for j in range(8)])
        run_until_dry(s, once, st)
        want_infos = oracle_infos(st)
        measured = post(st, [dataclasses.replace(
            pod_with_pod_anti_affinity_label(f"p{j}", MEASURED_NS),
            creation_index=j) for j in range(160)])
        run_until_dry(s, once, st)
    finally:
        s.close()
    got = bound_to(st, measured)
    assert None not in got
    assert got == oracle.greedy(want_infos, measured, **ORACLE)
    census = tool().anti_census([n for _k, n in st.list(NODES)[0]],
                                list(st.list(PODS)[0]), INIT_NS, MEASURED_NS)
    assert census == {
        "phase": "anti", "nodes": 40, "init_bound": 8, "init_nodes": 8,
        "init_pods_max_a_node": 1, "measured_bound": 160,
        "measured_on_init_nodes": 0}


def test_the_census_catches_a_run_without_the_existing_anti_slots(
        monkeypatch):
    """The control as a whole run: with the EA slots blanked the served
    loop spreads the measured pods over the init pods' nodes too, and the
    census over all bindings counts them. The counter that
    ``podaffinity_existing_anti_pod_share`` reads stays at 0 while every
    pod is attempted, so that share reads 0 in place of 100."""
    blank_existing_anti(monkeypatch)
    st = cluster()
    s, _clock, once = served(st, engine="batched", max_batch=16)
    try:
        post(st, [pod_with_pod_anti_affinity(f"i{j}", INIT_NS)
                  for j in range(8)])
        run_until_dry(s, once, st)
        post(st, [pod_with_pod_anti_affinity_label(f"p{j}", MEASURED_NS)
                  for j in range(160)])
        run_until_dry(s, once, st)
        assert scrape(s, FILTER_PODS, term="existing_anti_affinity") == 0
        assert scrape(s, "scheduler_schedule_attempts_total",
                      result="scheduled", profile="default-scheduler") >= 168
    finally:
        s.close()
    census = tool().anti_census([n for _k, n in st.list(NODES)[0]],
                                list(st.list(PODS)[0]), INIT_NS, MEASURED_NS)
    assert census["init_pods_max_a_node"] == 1
    assert census["measured_bound"] == 160
    assert census["measured_on_init_nodes"] > 0


def test_the_census_counts_init_pods_a_node_and_measured_pods_beside_them():
    """``tools/affinity_nodes_run.py``'s census over hand-made bindings."""
    nodes = [templates.node_default(i) for i in range(6)]
    pods = [
        pod_with_pod_anti_affinity("i0", INIT_NS).with_node("scheduler-perf-0"),
        pod_with_pod_anti_affinity("i1", INIT_NS).with_node("scheduler-perf-0"),
        pod_with_pod_anti_affinity("i2", INIT_NS).with_node("scheduler-perf-1"),
        pod_with_pod_anti_affinity("i3", INIT_NS),              # not bound
        pod_with_pod_anti_affinity_label("m0", MEASURED_NS).with_node(
            "scheduler-perf-1"),
        pod_with_pod_anti_affinity_label("m1", MEASURED_NS).with_node(
            "scheduler-perf-2"),
        pod_with_pod_anti_affinity_label("m2", MEASURED_NS),     # not bound
        templates.pod_default("o0", "other").with_node("scheduler-perf-0"),
    ]
    keyed = [(f"{p.namespace}/{p.name}", p) for p in pods]
    assert tool().anti_census(nodes, keyed, INIT_NS, MEASURED_NS) == {
        "phase": "anti", "nodes": 6, "init_bound": 3, "init_nodes": 2,
        "init_pods_max_a_node": 2, "measured_bound": 2,
        "measured_on_init_nodes": 1}
    assert tool().anti_census(nodes, [], INIT_NS, MEASURED_NS)[
        "init_pods_max_a_node"] == 0
