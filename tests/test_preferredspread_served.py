"""Upstream's PreferredTopologySpreading deployment on the served path, small:
the loop of ``kubetpu scheduler`` over a pipelined ``Scheduler`` and a store,
with pods of templates/pod-with-preferred-topology-spreading.yaml (a soft zone
spread: no filter engages, the score alone decides), held to the scalar
oracle pod for pod under the default profile's weights; the soft score at the
counts a window of the cell reaches, compared exactly; what the two-stage
cycle does with a soft batch; and the counter, the span attribute and the
program names the cell's per-layer metrics read.

``score_disagreements`` pins no platform: the builder of ISSUE 34 ran the
same function on the chip (PERF.md section 6), and beside it the control
``float32_log_score``, the same program with its ``log`` one precision lower,
which has to come out as NOT equal."""

import dataclasses
import math

import numpy as np
import pytest

pytest.importorskip("jax")

from benchmark.harness import templates, templates_spread
from benchmark.harness.templates_preferredspread import (
    pod_with_preferred_topology_spreading,
)
from kubetpu.client.informers import NODES, PODS
from kubetpu.framework import config as C
from kubetpu.framework import runtime as rt
from kubetpu.metrics.textparse import parse_prometheus_text
from kubetpu.ops import spread as SP
from kubetpu.sched import flightrecorder
from kubetpu.state import Cache
from kubetpu.store import MemStore

from . import oracle
from .test_served_pipeline import cycles
from .test_served_pipeline import served as served_loop

ZONES = ("moon-1", "moon-2", "moon-3")
MEASURED_NS = "namespace-1"
#: the oracle's view of ``C.Profile()`` for these pods: the parity rule of
#: benchmark/configs/preferredspread-5k.json
ORACLE = dict(w_fit=1, w_balanced=1, w_spread=2)
#: bound color=blue pods a zone before the first measured pod
STARTS = {"even": (6, 6, 6), "uneven": (20, 5, 5)}
NODE_COUNT = 30
BATCH = 8
SOFT = "scheduler_spread_soft_constrained_pods_total"
CONSTRAINED = "scheduler_spread_constrained_pods_total"
TEMPLATES = {
    "plain": templates.pod_default,
    "hard": templates_spread.pod_with_topology_spreading,
    "soft": pod_with_preferred_topology_spreading,
}


def cluster(start, seed):
    """Upstream's node-default round-robin over three zones; init pods of
    pod-default, their number and nodes from the seed; ``start`` bound
    color=blue pods a zone, on nodes of that zone drawn from the seed."""
    rng = np.random.default_rng(seed)
    st = MemStore()
    for i in range(NODE_COUNT):
        node = templates.node_default(i, ZONES)
        st.create(NODES, node.name, node)
    for j in range(int(rng.integers(10, 40))):
        pod = templates.pod_default(f"i{j}", "namespace-0").with_node(
            f"scheduler-perf-{int(rng.integers(NODE_COUNT))}")
        st.create(PODS, f"namespace-0/i{j}", pod)
    j = 0
    for z, count in enumerate(start):
        for _ in range(count):
            i = 3 * int(rng.integers(NODE_COUNT // 3)) + z
            pod = pod_with_preferred_topology_spreading(
                f"b{j}", MEASURED_NS).with_node(f"scheduler-perf-{i}")
            st.create(PODS, f"{MEASURED_NS}/b{j}", pod)
            j += 1
    return st


def served(st, max_batch=BATCH):
    """The loop of ``kubetpu scheduler`` over a pipelined greedy
    ``Scheduler``, as ``test_served_pipeline`` builds it."""
    s, _clock, once = served_loop(st, max_batch=max_batch)
    return s, once


def post(st, kinds, first=0):
    """One pod a kind, in order, from the benchmark's own templates; returns
    them as posted (the order the queue pops them in)."""
    pods = []
    for j, kind in enumerate(kinds, start=first):
        pod = dataclasses.replace(TEMPLATES[kind](f"p{j}", MEASURED_NS),
                                  creation_index=j)
        st.create(PODS, f"{MEASURED_NS}/p{j}", pod)
        pods.append(pod)
    return pods


def oracle_infos(st):
    """The store's cluster as the oracle sees it: its nodes in the order the
    store lists them, with every bound pod."""
    cache = Cache()
    for _k, node in st.list(NODES)[0]:
        cache.add_node(node)
    for _k, pod in st.list(PODS)[0]:
        if pod.node_name:
            cache.add_pod(pod)
    return [info.clone() for info in cache.update_snapshot().node_infos()]


def bound_to(st, pods):
    return [st.get(PODS, f"{p.namespace}/{p.name}")[0].node_name or None
            for p in pods]


def blue_by_zone(st):
    zone_of = {n.name: dict(n.labels)[templates.ZONE_KEY]
               for _k, n in st.list(NODES)[0]}
    counts = dict.fromkeys(ZONES, 0)
    for _k, p in st.list(PODS)[0]:
        if p.node_name and dict(p.labels).get("color") == "blue":
            counts[zone_of[p.node_name]] += 1
    return [counts[z] for z in ZONES]


def sample(s, name):
    return parse_prometheus_text(s.metrics_text()).value(name)


def run_dry(s, once):
    for _ in range(12):
        once()
    assert s._inflight is None and not s.queue._in_flight


# ------------------------------------------ the loop, held to the oracle

@pytest.mark.parametrize("seed", [3, 11, 2147483700])
@pytest.mark.parametrize("start", list(STARTS))
def test_the_served_loop_binds_what_the_oracle_binds(start, seed):
    """Three batches standing, then two waves that each bring the last
    cycle's bind confirmations and a batch of new pods: the counts the score
    of a pod reads come from the pods of its own batch, from pods ASSUMED by
    the cycle before, and from pods CONFIRMED over the watch."""
    st = cluster(STARTS[start], seed)
    want_infos = oracle_infos(st)
    s, once = served(st)
    try:
        pods = post(st, ["soft"] * 3 * BATCH)
        once()
        assert s._inflight is not None
        for wave in range(2):
            pods += post(st, ["soft"] * BATCH, first=len(pods))
            once()
        run_dry(s, once)
        got = bound_to(st, pods)
        assert cycles(s, "replayed") == 0 and cycles(s, "applied") >= 3
    finally:
        s.close()
    want = oracle.greedy(want_infos, pods, **ORACLE)
    assert got == want and None not in got
    # what the preference achieved: 40 pods on top of the start's
    counts = blue_by_zone(st)
    assert sum(counts) == sum(STARTS[start]) + len(pods)
    if start == "even":
        assert max(counts) - min(counts) <= 1


@pytest.mark.parametrize("seed", [5, 2147483700])
def test_a_batch_that_mixes_plain_hard_and_soft_pods(seed):
    """One bucket, three kinds of pod, both the spread filter and the spread
    score in one program: the hard pods are held to maxSkew by the oracle's
    filter, the soft ones only scored."""
    rng = np.random.default_rng(seed)
    kinds = [str(k) for k in rng.choice(list(TEMPLATES), size=3 * BATCH)]
    assert set(kinds) == set(TEMPLATES)
    st = cluster(STARTS["uneven"], seed)
    want_infos = oracle_infos(st)
    s, once = served(st, max_batch=3 * BATCH)
    try:
        pods = post(st, kinds)
        run_dry(s, once)
        got = bound_to(st, pods)
    finally:
        s.close()
    want = oracle.greedy(want_infos, pods, check_spread=True, **ORACLE)
    assert got == want


# ----------------------------- the score at the counts a window reaches

#: bound color=blue pods a zone, each vector reached from the one before by
#: adding pods: what ``t1`` of a 51 s window finds (some 24,000 a zone)
#: and beyond, uneven between the zones. Only 19 of the 20,001 counts in
#: 20,000..40,000 round differently where ``log(5)`` is float32's
#: (``round(count x log 5 + 4)``), and NormalizeScore's floor division hides
#: most of those: a vector drawn at random does not tell the precisions
#: apart. The fourth and the fifth were searched for so that they do. At the
#: third a v5e's emulated float64, which lost the fraction before ``round``,
#: scored a whole zone one unit off (PR 34, the builder's chip runs D and E):
#: why ``spread_score_pod`` computes in int64 fixed point
COUNT_VECTORS = [(20000, 25000, 31000), (23456, 27001, 31000),
                 (23872, 29489, 35107), (24338, 30607, 36877),
                 (26563, 32256, 37949), (30001, 38765, 39999)]
SCORE_NODES = 5000


def float32_log_table(sizes):
    """The control's table: ``log(size + 2)`` rounded to float32, the nearest
    precision below the one the configuration states, in the program's own
    fixed point (so the multiply-add and the rounding stay exact, and the
    ``log`` alone is a precision lower)."""
    return SP._fixed_point(float(np.float32(math.log(size + 2.0)))
                           for size in range(sizes))


def float32_log_score(*args):
    """The control: ``spread_score_pod`` over ``float32_log_table``."""
    table = SP._log_size_table
    SP._log_size_table = float32_log_table
    try:
        return SP.spread_score_pod(*args)
    finally:
        SP._log_size_table = table


def score_disagreements(vectors=COUNT_VECTORS, nodes=SCORE_NODES):
    """``ops.spread.spread_score_pod``, and the float32 control, beside
    ``oracle.spread_scores`` for one pod of the template, all ``nodes``
    nodes feasible and scored, at each vector of per-zone counts: ``(vector,
    nodes on which the program differs, nodes on which the control differs,
    the oracle's least and largest score)`` for every vector, on whatever
    device JAX runs on. The counts get to the device as they do in a cycle:
    through ``encode_batch``."""
    import jax

    cache = Cache()
    for i in range(nodes):
        cache.add_node(templates.node_default(i, ZONES))
    pod = pod_with_preferred_topology_spreading("probe", MEASURED_NS)
    profile = C.Profile()
    have = [0, 0, 0]
    placed = 0
    out = []
    scores = [jax.jit(SP.spread_score_pod), jax.jit(float32_log_score)]
    for vector in vectors:
        for z, count in enumerate(vector):
            for _ in range(count - have[z]):
                # round-robin over the zone's nodes: 6 to 8 pods a node
                i = 3 * (placed % (nodes // 3)) + z
                cache.add_pod(pod_with_preferred_topology_spreading(
                    f"e{placed}", MEASURED_NS).with_node(
                        f"scheduler-perf-{i}"))
                placed += 1
            have[z] = count
        snap = cache.update_snapshot()
        batch = rt.encode_batch(snap, [pod], profile)
        sp = batch.device.spread
        infos = snap.node_infos()
        assert [info.node.name for info in infos] == batch.node_names[:nodes]
        want = np.asarray(oracle.spread_scores(pod, infos, [True] * nodes))
        differ = [int((np.asarray(score(
            sp, sp.node_count, sp.sig_idx[0], sp.action[0], sp.max_skew[0],
            sp.ignored[0], batch.device.node_valid))[:nodes] != want).sum())
            for score in scores]
        out.append((vector, *differ, (int(want.min()), int(want.max()))))
    return out


RAW_SIZES = (1, 2, 3, 5, 5000)
RAW_COUNTS = 300_001


def raw_score_disagreements(sizes=RAW_SIZES, counts=RAW_COUNTS):
    """The raw score of one constraint, ``round(count x log(size + 2) + 4)``,
    for EVERY count under ``counts`` at each topology size: on how many counts
    the program's fixed point differs from IEEE float64 (numpy's, which is
    Python's), on whatever device JAX runs on. This is the sweep on which a
    v5e's emulated float64 differed on 4,404 to 33,962 of 300,001 counts."""
    import jax
    import jax.numpy as jnp

    count = np.arange(counts, dtype=np.int64)
    raw = jax.jit(lambda c, weight: SP._rounded(
        *SP._times_log_size(c, weight)) + 4)
    table = SP._log_size_table(max(sizes) + 1)
    out = {}
    for size in sizes:
        want = np.round(count * math.log(size + 2.0) + 4.0).astype(np.int64)
        assert int(want[23872]) == round(23872 * math.log(size + 2.0) + 4.0)
        got = np.asarray(raw(jnp.asarray(count), jnp.asarray(table[size])))
        out[size] = int((got != want).sum())
    return out


def test_the_raw_score_is_ieee_s_at_every_count():
    assert raw_score_disagreements() == dict.fromkeys(RAW_SIZES, 0)


@pytest.fixture(scope="module")
def disagreements():
    return score_disagreements()


def test_the_score_equals_the_oracle_at_the_counts_a_window_reaches(
        disagreements):
    """Exact: float64 ``log``, multiply-add and ``round``, then the int64
    floor division of NormalizeScore, over all 5000 nodes. One unit in the
    last place of ``log(5)`` is multiplied by 30,000 before ``round``."""
    for vector, differ, _control, (low, high) in disagreements:
        assert differ == 0, vector
        # three zones, three scores: the emptiest zone 100, the fullest
        # 100 x the least raw score over the largest
        assert 0 < low < high == 100, vector


def test_a_float32_score_is_told_from_the_oracle(disagreements):
    """The comparison can fail for what it exists to hold: the same program
    over a float32 ``log`` differs from the oracle on a whole zone of nodes
    or more, at the vectors searched for it (and passes at the others: the
    rounding seldom shows)."""
    control = {vector: n for vector, _program, n, _ in disagreements}
    assert sum(control.values()) >= SCORE_NODES // 3 - 1, control
    assert min(control.values()) == 0, control


# -------------------------------------- the two-stage cycle and soft pods

def test_a_soft_cycle_dispatched_ahead_is_applied_when_its_predecessor_s_binds_are_confirmed():
    st = cluster(STARTS["even"], 7)
    s, once = served(st)
    try:
        post(st, ["soft"] * 3 * BATCH)
        once()
        assert s._inflight is not None
        applied = cycles(s, "applied")
        # the watch now holds the first cycle's binds, each a color=blue
        # pod landing in the zones the cycle in flight counted them in
        once()
        assert cycles(s, "applied") == applied + 1
        assert cycles(s, "replayed") == 0 == s.metrics.pipeline_replays
        run_dry(s, once)
        assert cycles(s, "replayed") == 0
    finally:
        s.close()


def test_a_foreign_matching_pod_under_a_cycle_in_flight_still_ends_at_the_oracle():
    """Another writer binds a color=blue pod while a soft cycle is on the
    chip: the cycle counted its zone one short. Today the loop throws the
    cycle away and replays it; whatever it does, the bindings are the
    sequential reference's with the foreign pod in its place."""
    st = cluster(STARTS["uneven"], 9)
    want_infos = oracle_infos(st)
    s, once = served(st)
    try:
        pods = post(st, ["soft"] * 3 * BATCH)
        once()
        assert s._inflight is not None
        decided = [p for p, node in zip(pods, bound_to(st, pods)) if node]
        assert 0 < len(decided) < len(pods)
        foreign = pod_with_preferred_topology_spreading(
            "foreign", MEASURED_NS).with_node("scheduler-perf-1")
        st.create(PODS, f"{MEASURED_NS}/foreign", foreign)
        run_dry(s, once)
        got = bound_to(st, pods)
    finally:
        s.close()
    want = oracle.greedy(want_infos, decided, **ORACLE)
    by_name = {info.node.name: info for info in want_infos}
    by_name["scheduler-perf-1"].add_pod(foreign)
    want += oracle.greedy(want_infos, pods[len(decided):], **ORACLE)
    assert got == want and None not in got


# ----------------------------------------------------------- the tracing

def one_cycle(s, once, st, kinds, first):
    pods = post(st, kinds, first=first)
    run_dry(s, once)
    assert None not in bound_to(st, pods)


def test_the_soft_counter_counts_soft_pods_and_the_span_carries_them():
    st = cluster(STARTS["even"], 1)
    s, once = served(st, max_batch=16)
    try:
        one_cycle(s, once, st, ["plain"] * 5, 0)
        one_cycle(s, once, st, ["hard"] * 4, 100)
        assert sample(s, SOFT) == 0 and sample(s, CONSTRAINED) == 4
        s.tracer.drain()

        one_cycle(s, once, st, ["soft"] * 7, 200)
        assert sample(s, SOFT) == 7 and sample(s, CONSTRAINED) == 11
        [spread] = [sp for sp in s.tracer.drain()
                    if sp.name == "encode-spread"]
        assert spread.attrs["soft_pods"] == 7
        assert spread.attrs["constrained_pods"] == 7

        # a mixed cycle counts each kind where it belongs
        one_cycle(s, once, st, ["plain", "soft", "hard", "soft"], 300)
        assert sample(s, SOFT) == 9 and sample(s, CONSTRAINED) == 14
        [spread] = [sp for sp in s.tracer.drain()
                    if sp.name == "encode-spread"]
        assert spread.attrs["soft_pods"] == 2
        assert spread.attrs["constrained_pods"] == 3
    finally:
        s.close()


def test_the_explain_programs_carry_names_of_their_own():
    """The trace's ``XLA Modules`` line names a program after the jitted
    function: ``jit_explain_kernel``, not the ``jit_kernel`` any closure
    called ``kernel`` would share."""
    cache = Cache()
    for i in range(NODE_COUNT):
        cache.add_node(templates.node_default(i, ZONES))
    profile = C.Profile()
    batch = rt.encode_batch(
        cache.update_snapshot(),
        [pod_with_preferred_topology_spreading(f"p{j}", MEASURED_NS)
         for j in range(4)], profile)
    assert batch.device.spread.has_soft
    params = rt.score_params(profile, batch.resource_names)
    assignments = np.zeros(batch.device.pod_valid.shape[0], dtype=np.int32)
    flightrecorder._explain_kernel(batch.device, params, assignments)
    flightrecorder._explain_masks_kernel(batch.device, params)
    explain = flightrecorder._EXPLAIN_JIT
    masks = flightrecorder._EXPLAIN_MASKS_JIT
    assert explain.__name__ == "explain_kernel"
    assert masks.__name__ == "explain_masks_kernel"
    assert "module @jit_explain_kernel " in explain.lower(
        batch.device, params, assignments).as_text()
    assert "module @jit_explain_masks_kernel " in masks.lower(
        batch.device, params).as_text()
