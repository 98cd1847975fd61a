"""Upstream's SchedulingWithNodeInclusionPolicy deployment on the served path,
small: the loop of ``kubetpu scheduler`` over a pipelined ``Scheduler`` and a
store of 40 nodes, every fifth tainted ``foo=bar:NoSchedule``, with 256 pods
of templates/pod-with-node-inclusion-policy.yaml (a hard maxSkew 1 spread
over ``kubernetes.io/hostname``, both node inclusion policies ``Honor``) in
batches of 32. Held: pod for pod the scalar oracle's placements; over ALL
bindings none on a tainted node and the untainted nodes' counts within 1;
the same cluster under ``nodeTaintsPolicy: Ignore`` stalls after one pod an
untainted node, as the oracle does; ``nodeAffinityPolicy`` with a
nodeSelector that leaves nodes out, under both policies; and the stamp, the
span and the counter the cell's per-layer metric reads."""

import dataclasses
import importlib.util
import os

import pytest

pytest.importorskip("jax")

from benchmark.harness import templates
from benchmark.harness.templates_nodeinclusion import (
    node_with_taint_one_in_five,
    pod_with_node_inclusion_policy,
)
from kubetpu.api import types as t
from kubetpu.api.wrappers import make_pod, spread_constraint
from kubetpu.client.informers import NODES, PODS
from kubetpu.framework import config as C
from kubetpu.framework import runtime as rt
from kubetpu.metrics.textparse import parse_prometheus_text
from kubetpu.state import Cache
from kubetpu.store import MemStore

from . import oracle
from .test_preferredspread_served import bound_to, oracle_infos, served

NODE_COUNT = 40          # every fifth tainted: 32 untainted hostname domains
UNTAINTED = 32
PODS_TOTAL = 256
BATCH = 32
MEASURED_NS = "namespace-1"
#: the oracle's view of ``C.Profile()`` for these pods: the parity rule of
#: benchmark/configs/nodeinclusion-5k.json
ORACLE = dict(w_fit=1, w_balanced=1, w_spread=2, check_spread=True)
POLICY = "scheduler_spread_policy_pods_total"


def tool():
    """``tools/spread_zones_run.py``, whose ``--by-node`` line is the chip
    runs' evidence of the fifth guarantee."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "tools", "spread_zones_run.py")
    spec = importlib.util.spec_from_file_location("spread_zones_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cluster(node_of=node_with_taint_one_in_five):
    st = MemStore()
    for i in range(NODE_COUNT):
        node = node_of(i)
        st.create(NODES, node.name, node)
    return st


def pool_node(i):
    """node-default with a label ``pool``: ``b`` on every fourth node (10 of
    40), ``a`` elsewhere; no taint."""
    node = templates.node_default(i)
    labels = {**dict(node.labels), "pool": "b" if i % 4 == 0 else "a"}
    return dataclasses.replace(node, labels=t.freeze_map(labels))


def inclusion_pod(j, taints="Honor", affinity="Honor", node_selector=None):
    """The cell's pod, built by ``spread_constraint``'s policy arguments,
    with its two policies and a nodeSelector set."""
    return make_pod(
        f"p{j}", namespace=MEASURED_NS, labels={"foo": "bar"},
        cpu_milli=100, memory=500 * 1024 ** 2, creation_index=j,
        node_selector=node_selector,
        spread=(spread_constraint(
            1, templates.HOSTNAME_KEY, match_labels={"foo": "bar"},
            node_affinity_policy=affinity, node_taints_policy=taints),))


def test_the_wrapper_builds_the_template_s_constraint():
    assert dataclasses.replace(inclusion_pod(7), creation_index=0) == \
        pod_with_node_inclusion_policy("p7", MEASURED_NS)
    # the reference's defaults where no policy is given
    c = spread_constraint(1, templates.HOSTNAME_KEY)
    assert (c.node_affinity_policy, c.node_taints_policy) == ("Honor",
                                                               "Ignore")


def post(st, pods):
    for pod in pods:
        st.create(PODS, f"{pod.namespace}/{pod.name}", pod)


def by_node(st):
    return tool().bound_by_node([n for _k, n in st.list(NODES)[0]],
                                list(st.list(PODS)[0]), MEASURED_NS)


def run_until_dry(s, once, st, check=None):
    """Iterations of the loop until it binds nothing more and nothing is in
    flight; ``check(st)`` after each."""
    last = -1
    for _ in range(40):
        once()
        if check is not None:
            check(st)
        n = sum(1 for _k, p in st.list(PODS)[0] if p.node_name)
        if n == last and s._inflight is None:
            return
        last = n
    raise AssertionError("the loop did not run dry")


def served_run(st, pods, check=None):
    """The pods posted at once, then the loop; returns (placements in the
    order posted, the oracle's for the same cluster and pods)."""
    want = oracle.greedy(oracle_infos(st), pods, **ORACLE)
    s, once = served(st, max_batch=BATCH)
    try:
        post(st, pods)
        run_until_dry(s, once, st, check)
    finally:
        s.close()
    return bound_to(st, pods), want


def within_one(st):
    got = by_node(st)
    assert got["on_tainted"] == 0 and got["max_minus_min"] <= 1, got


# ------------------------------------------- the deployment, held to the oracle

def test_the_served_loop_binds_what_the_oracle_binds():
    st = cluster()
    pods = [inclusion_pod(j) for j in range(PODS_TOTAL)]
    got, want = served_run(st, pods, check=within_one)
    assert got == want
    assert None not in got
    # over ALL bindings: none on a tainted node, 8 on every untainted one
    assert by_node(st) == {
        "untainted_nodes": UNTAINTED, "tainted_nodes": NODE_COUNT - UNTAINTED,
        "bound": PODS_TOTAL, "on_tainted": 0, "min": 8, "max": 8,
        "max_minus_min": 0}


def test_ignoring_the_taints_stalls_after_one_pod_an_untainted_node():
    """The control: with ``nodeTaintsPolicy: Ignore`` the tainted nodes are
    counted, at 0, so the minimum stays 0; once every untainted node holds
    one pod, each is at skew 2 and the tainted ones are refused by
    TaintToleration. A program that dropped ``Honor`` reads this way."""
    st = cluster()
    pods = [inclusion_pod(j, taints="Ignore") for j in range(PODS_TOTAL)]
    got, want = served_run(st, pods, check=within_one)
    assert got == want
    assert sum(1 for g in got if g) == UNTAINTED
    assert got[:UNTAINTED].count(None) == 0
    assert by_node(st)["min"] == by_node(st)["max"] == 1


@pytest.mark.parametrize("policy,bound", [("Honor", 64), ("Ignore", 30)])
def test_the_affinity_policy_counts_the_nodes_the_selector_admits(
        policy, bound):
    """The other half of the policy code, which no cell runs: pods with
    ``nodeSelector: pool=a`` over 30 ``a`` and 10 ``b`` nodes. Under
    ``Honor`` the ``b`` nodes are left out of the minimum and 64 pods spread
    two or three a node; under ``Ignore`` they count at 0 and the pods stall
    after one an ``a`` node."""
    st = cluster(pool_node)
    pods = [inclusion_pod(j, taints="Ignore", affinity=policy,
                          node_selector={"pool": "a"}) for j in range(64)]
    got, want = served_run(st, pods)
    assert got == want
    assert sum(1 for g in got if g) == bound
    pool_b = {f"scheduler-perf-{i}" for i in range(0, NODE_COUNT, 4)}
    assert not pool_b & set(got)
    counts = [got.count(f"scheduler-perf-{i}") for i in range(NODE_COUNT)
              if i % 4]
    assert max(counts) - min(counts) <= 1


# ---------------------------------------------- the stamp, span and counter

def encode(node_of, pods):
    cache = Cache()
    for i in range(NODE_COUNT):
        cache.add_node(node_of(i))
    return rt.encode_batch(cache.update_snapshot(), pods, C.Profile())


def test_the_stamp_counts_the_pods_each_policy_left_nodes_out_for():
    plain = [templates.pod_default(f"q{j}", MEASURED_NS) for j in range(4)]
    honor = [inclusion_pod(j) for j in range(3)]
    ignore = [inclusion_pod(10 + j, taints="Ignore") for j in range(2)]
    stamp = encode(node_with_taint_one_in_five,
                   plain + honor + ignore).spread_encode
    assert stamp.constrained_pods == 5 and stamp.signatures == 2
    assert stamp.policy_pods == {"taints": 3, "affinity": 0}
    assert stamp.excluded_nodes == {"taints": 8, "affinity": 0}
    # the Ignore signature counts all 40 hostnames, the Honor one 32; every
    # value is a domain of both
    assert (stamp.counted_domains, stamp.domains) == (NODE_COUNT, NODE_COUNT)
    stamp = encode(node_with_taint_one_in_five, honor).spread_encode
    assert (stamp.counted_domains, stamp.domains) == (UNTAINTED, NODE_COUNT)

    selected = [inclusion_pod(j, node_selector={"pool": "a"})
                for j in range(5)]
    stamp = encode(pool_node, selected + plain).spread_encode
    assert stamp.policy_pods == {"taints": 0, "affinity": 5}
    assert stamp.excluded_nodes == {"taints": 0, "affinity": 10}
    assert stamp.counted_domains == 30
    assert encode(pool_node, plain).spread_encode is None


def test_the_span_and_the_counter_carry_the_policies():
    st = cluster()
    s, once = served(st, max_batch=BATCH)

    def value(policy):
        return parse_prometheus_text(s.metrics_text()).value(
            POLICY, policy=policy)

    try:
        # both series from the first scrape, at zero
        assert value("taints") == 0 and value("affinity") == 0
        post(st, [templates.pod_default(f"q{j}", MEASURED_NS)
                  for j in range(5)])
        run_until_dry(s, once, st)
        assert value("taints") == 0
        assert [sp for sp in s.tracer.drain()
                if sp.name == "encode-spread"] == []

        post(st, [inclusion_pod(j) for j in range(9)])
        run_until_dry(s, once, st)
        assert value("taints") == 9 and value("affinity") == 0
        [spread] = [sp for sp in s.tracer.drain()
                    if sp.name == "encode-spread"]
        assert {k: spread.attrs[k] for k in (
            "constrained_pods", "domains", "counted_domains",
            "taints_excluded_nodes", "affinity_excluded_nodes")} == {
                "constrained_pods": 9, "domains": NODE_COUNT,
                "counted_domains": UNTAINTED, "taints_excluded_nodes": 8,
                "affinity_excluded_nodes": 0}

        # a constraint that ignores the taints leaves no node out
        post(st, [inclusion_pod(100 + j, taints="Ignore") for j in range(4)])
        run_until_dry(s, once, st)
        assert value("taints") == 9
        [spread] = [sp for sp in s.tracer.drain()
                    if sp.name == "encode-spread"]
        assert spread.attrs["taints_excluded_nodes"] == 0
        assert spread.attrs["counted_domains"] == NODE_COUNT
    finally:
        s.close()


# ----------------------------------------- the read-back of the chip runs

def test_the_read_back_counts_bound_pods_by_node():
    """``tools/spread_zones_run.py --by-node``: bound ``foo=bar`` pods of
    the measured namespace, on tainted nodes and over the untainted ones."""
    nodes = [node_with_taint_one_in_five(i) for i in range(10)]
    placed = [inclusion_pod(j).with_node(f"scheduler-perf-{i}")
              for j, i in enumerate([0, 0, 1, 2, 3, 5, 6, 4])]
    others = [
        templates.pod_default("q0", MEASURED_NS).with_node(
            "scheduler-perf-7"),                            # no label
        pod_with_node_inclusion_policy("q1", "namespace-0").with_node(
            "scheduler-perf-8"),                            # other namespace
        inclusion_pod(50),                                  # not bound
    ]
    pods = [(f"{p.namespace}/{p.name}", p) for p in placed + others]
    assert tool().bound_by_node(nodes, pods, MEASURED_NS) == {
        "untainted_nodes": 8, "tainted_nodes": 2, "bound": 8,
        "on_tainted": 1, "min": 0, "max": 2, "max_minus_min": 2}
    assert tool().bound_by_node(nodes, [], MEASURED_NS)["max"] == 0
