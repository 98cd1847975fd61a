"""InterPodAffinity parity tests: device kernels vs the scalar oracle
implementing interpodaffinity/filtering.go and scoring.go."""

import dataclasses

import numpy as np
import pytest

pytest.importorskip("jax")

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_node, make_pod, pod_affinity_term
from kubetpu.assign import greedy_assign
from kubetpu.assign.greedy import greedy_scan
from kubetpu.framework import config as C
from kubetpu.framework import encode_batch, score_params
from kubetpu.framework import runtime as rt
from kubetpu.ops import podaffinity as PA
from kubetpu.state import Cache

from . import oracle
from .cluster_gen import random_cluster

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
APPS = ["web", "db", "cache"]


def affinity_profile():
    return C.Profile(
        filters=C.PluginSet(enabled=(
            (C.NODE_RESOURCES_FIT, 1), (C.INTER_POD_AFFINITY, 1),
        )),
        scores=C.PluginSet(enabled=(
            (C.NODE_RESOURCES_FIT, 1), (C.INTER_POD_AFFINITY, 2),
        )),
        default_spread_constraints=(),
    )


def rand_affinity(rng) -> t.Affinity | None:
    """Random mix of required/preferred pod (anti)affinity terms."""
    kind = rng.random()
    app = str(rng.choice(APPS))
    key = ZONE if rng.random() < 0.6 else HOST
    term = pod_affinity_term(key, match_labels={"app": app})
    if kind < 0.25:
        return t.Affinity(pod_affinity=t.PodAffinity(required=(term,)))
    if kind < 0.5:
        return t.Affinity(pod_anti_affinity=t.PodAffinity(required=(term,)))
    if kind < 0.75:
        return t.Affinity(pod_affinity=t.PodAffinity(
            preferred=(t.WeightedPodAffinityTerm(int(rng.integers(1, 101)), term),)
        ))
    return t.Affinity(pod_anti_affinity=t.PodAffinity(
        preferred=(t.WeightedPodAffinityTerm(int(rng.integers(1, 101)), term),)
    ))


def add_affinity(rng, pods, ratio=0.6):
    out = []
    for p in pods:
        if rng.random() < ratio:
            p = dataclasses.replace(p, affinity=rand_affinity(rng))
        out.append(p)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_interpod_filter_one_shot_parity(seed):
    rng = np.random.default_rng(seed + 600)
    cache, pending = random_cluster(rng, num_nodes=16, num_existing=40, num_pending=15)
    pending = add_affinity(rng, pending)
    snap = cache.update_snapshot()
    profile = affinity_profile()
    batch = encode_batch(snap, pending, profile, pad=False)
    params = score_params(profile, batch.resource_names)
    mask, _ = rt.filter_score_batch(batch.device, params)
    mask = np.asarray(mask)
    infos = snap.node_infos()
    for i, pod in enumerate(pending):
        for j, info in enumerate(infos):
            want = oracle.fits(pod, info) and oracle.interpod_filter(pod, infos, info)
            assert mask[i, j] == want, (pod.name, info.node.name)


@pytest.mark.parametrize("seed", [0, 1])
def test_interpod_score_one_shot_parity(seed):
    rng = np.random.default_rng(seed + 700)
    cache, pending = random_cluster(rng, num_nodes=14, num_existing=35, num_pending=12)
    pending = add_affinity(rng, pending)
    snap = cache.update_snapshot()
    profile = affinity_profile()
    batch = encode_batch(snap, pending, profile, pad=False)
    params = score_params(profile, batch.resource_names)
    mask, total = rt.filter_score_batch(batch.device, params)
    mask, total = np.asarray(mask), np.asarray(total)
    infos = snap.node_infos()
    for i, pod in enumerate(pending):
        feas = [bool(mask[i, j]) for j in range(len(infos))]
        want_ip = oracle.interpod_scores(pod, infos, feas)
        for j, info in enumerate(infos):
            want = oracle.least_allocated(
                pod, info, [(t.CPU, 1), (t.MEMORY, 1)]
            ) + 2 * want_ip[j]
            assert total[i, j] == want, (pod.name, info.node.name)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_interpod_greedy_parity(seed):
    """End-to-end: assigned pods' terms take effect for later pods in the
    same batch (anti-affinity from assigned pods, affinity targets)."""
    rng = np.random.default_rng(seed + 800)
    cache, pending = random_cluster(rng, num_nodes=12, num_existing=25, num_pending=18)
    pending = add_affinity(rng, pending)
    snap = cache.update_snapshot()
    profile = affinity_profile()
    batch = encode_batch(snap, pending, profile)
    got = greedy_assign(batch, profile)
    infos = [info.clone() for info in snap.node_infos()]
    want = oracle.greedy(
        infos, pending,
        w_fit=1, w_interpod=2,
        check_ports=False, check_static=False, check_interpod=True,
    )
    assert got == want


def test_anti_affinity_excludes_one_per_host():
    """Hostname anti-affinity: at most one matching pod per node, including
    pods assigned earlier in the batch."""
    cache = Cache()
    for i in range(3):
        cache.add_node(make_node(f"n{i}", cpu_milli=100000,
                                 labels={HOST: f"n{i}"}))
    anti = t.Affinity(pod_anti_affinity=t.PodAffinity(
        required=(pod_affinity_term(HOST, match_labels={"app": "db"}),)
    ))
    pods = [
        make_pod(f"p{i}", cpu_milli=10, labels={"app": "db"}, affinity=anti)
        for i in range(4)
    ]
    profile = affinity_profile()
    snap = cache.update_snapshot()
    batch = encode_batch(snap, pods, profile)
    got = greedy_assign(batch, profile)
    assert sorted(got[:3]) == ["n0", "n1", "n2"]
    assert got[3] is None      # nowhere left


def _ns_cluster():
    """Two labeled namespaces, two nodes, one team-a db pod on n0."""
    cache = Cache()
    cache.add_namespace(t.Namespace(name="team-a", labels=(("team", "a"),)))
    cache.add_namespace(t.Namespace(name="team-b", labels=(("team", "b"),)))
    for i in range(2):
        cache.add_node(make_node(f"n{i}", cpu_milli=4000,
                                 labels={HOST: f"n{i}"}))
    cache.add_pod(make_pod("db", namespace="team-a", cpu_milli=10,
                           labels={"app": "db"}, node_name="n0"))
    return cache


def test_namespace_selector_matches_namespace_labels():
    """A term's namespaceSelector is evaluated against the TARGET pod's
    namespace labels (AffinityTerm.Matches, framework/types.go) — the
    nsLister view lives in the snapshot's namespaces map."""
    cache = _ns_cluster()
    term = pod_affinity_term(
        HOST, match_labels={"app": "db"},
        namespace_selector=t.LabelSelector(match_labels=(("team", "a"),)),
    )
    aff = t.Affinity(pod_affinity=t.PodAffinity(required=(term,)))
    p = make_pod("p", namespace="team-b", cpu_milli=10, affinity=aff)
    profile = affinity_profile()
    batch = encode_batch(cache.update_snapshot(), [p], profile)
    assert greedy_assign(batch, profile) == ["n0"]

    # selector matching no namespace labels → no target pods → unschedulable
    # (p does not self-match: wrong labels AND wrong namespace)
    term2 = pod_affinity_term(
        HOST, match_labels={"app": "db"},
        namespace_selector=t.LabelSelector(match_labels=(("team", "zzz"),)),
    )
    aff2 = t.Affinity(pod_affinity=t.PodAffinity(required=(term2,)))
    p2 = make_pod("p2", namespace="team-b", cpu_milli=10, affinity=aff2)
    batch = encode_batch(cache.update_snapshot(), [p2], profile)
    assert greedy_assign(batch, profile) == [None]


def test_namespace_selector_anti_affinity():
    """Anti-affinity across namespaces via namespaceSelector: the team-a db
    pod on n0 repels a team-b pod whose term selects team=a namespaces."""
    cache = _ns_cluster()
    term = pod_affinity_term(
        HOST, match_labels={"app": "db"},
        namespace_selector=t.LabelSelector(match_labels=(("team", "a"),)),
    )
    aff = t.Affinity(pod_anti_affinity=t.PodAffinity(required=(term,)))
    p = make_pod("p", namespace="team-b", cpu_milli=10, affinity=aff)
    profile = affinity_profile()
    batch = encode_batch(cache.update_snapshot(), [p], profile)
    assert greedy_assign(batch, profile) == ["n1"]


def test_empty_namespace_selector_matches_all():
    """A non-nil but EMPTY namespaceSelector is labels.Everything(): it
    matches every namespace (podaffinity docstring / reference nil-vs-empty
    contract), so the team-a db pod is visible from team-b."""
    cache = _ns_cluster()
    term = pod_affinity_term(
        HOST, match_labels={"app": "db"},
        namespace_selector=t.LabelSelector(),
    )
    aff = t.Affinity(pod_anti_affinity=t.PodAffinity(required=(term,)))
    p = make_pod("p", namespace="team-b", cpu_milli=10, affinity=aff)
    profile = affinity_profile()
    batch = encode_batch(cache.update_snapshot(), [p], profile)
    assert greedy_assign(batch, profile) == ["n1"]


def test_affinity_self_escape_then_colocate():
    """First pod of a self-affine series passes via the escape clause; later
    pods must land in the same zone (counting the in-batch assignment)."""
    cache = Cache()
    for i in range(4):
        cache.add_node(make_node(
            f"n{i}", cpu_milli=1000,
            labels={HOST: f"n{i}", ZONE: f"z{i % 2}"},
        ))
    aff = t.Affinity(pod_affinity=t.PodAffinity(
        required=(pod_affinity_term(ZONE, match_labels={"app": "web"}),)
    ))
    pods = [
        make_pod(f"p{i}", cpu_milli=600, labels={"app": "web"}, affinity=aff)
        for i in range(3)
    ]
    profile = affinity_profile()
    snap = cache.update_snapshot()
    batch = encode_batch(snap, pods, profile)
    got = greedy_assign(batch, profile)
    assert got[0] is not None
    zone_of = {f"n{i}": f"z{i % 2}" for i in range(4)}
    z0 = zone_of[got[0]]
    # cpu 600/1000 → one pod per node; same zone has exactly 2 nodes
    assert zone_of[got[1]] == z0
    assert got[2] is None or zone_of[got[2]] == z0


# ------------------------------------------- the per-row node table (PR 37)

def _term(key, app, **kw):
    return pod_affinity_term(key, match_labels={"app": app}, **kw)


def _preferred(weight, key, app):
    return t.WeightedPodAffinityTerm(weight, _term(key, app))


def _pending_for(feature, key, j):
    """Pending pod j of a case: what ``feature`` asks for on top of a
    required affinity to ``web`` and a preferred one to ``db``."""
    labels = {"app": APPS[j % 3]}
    aff = t.PodAffinity(required=(_term(key, "web"),),
                        preferred=(_preferred(7, key, "db"),))
    anti = None
    if feature == "unused_slots":
        # slots as wide as the widest pod; every third pod uses none, the
        # others one or two of them
        if j % 3 == 0:
            return make_pod(f"p{j}", cpu_milli=100, labels=labels)
        if j % 3 == 1:
            aff = t.PodAffinity(required=(_term(key, "web"), _term(key, "db")))
            anti = t.PodAffinity(required=(_term(key, "cache"),))
    elif feature == "self_escape":
        # no pod anywhere is ``solo``: the pods labelled solo pass by the
        # escape (row_total == 0 and fa_self), the others fail everywhere
        labels = {"app": "solo" if j % 2 == 0 else "web"}
        aff = t.PodAffinity(required=(_term(key, "solo"),))
    elif feature == "negative":
        aff = t.PodAffinity(preferred=(_preferred(3, key, "web"),))
        anti = t.PodAffinity(preferred=(
            _preferred(60 + j, key, "db"), _preferred(5, HOST, "cache")))
    return make_pod(
        f"p{j}", cpu_milli=100, labels=labels,
        affinity=t.Affinity(pod_affinity=aff, pod_anti_affinity=anti))


def _table_case(key, feature, wide, seed=37):
    """A seeded cluster on one side of the shape rule. ``wide``: twelve
    pending pods over a handful of rows (R ≪ P × slots); else ONE pending
    pod beside existing pods that own many rows it has no slot for (R > P ×
    slots). With ``missing_key`` every fourth node lacks the topology key."""
    rng = np.random.default_rng(seed)
    cache = Cache()
    names = []
    for i in range(12):
        labels = {HOST: f"n{i}", ZONE: f"z{i % 3}"}
        if feature == "missing_key" and i % 4 == 3:
            del labels[key]
        names.append(f"n{i}")
        cache.add_node(make_node(f"n{i}", cpu_milli=4000, labels=labels))
    for j in range(30):
        own = None
        if not wide and j < 14:
            # a row of its own for each: a preferred term towards an app no
            # pending pod carries
            own = t.Affinity(pod_affinity=t.PodAffinity(preferred=(
                _preferred(j + 1, (ZONE, HOST)[j % 2], f"other-{j}"),)))
        elif j % 5 == 0:
            # existing pods' own terms: anti-affinity (EA rows) and a
            # required affinity (SCH rows, scored by the hard weight)
            own = t.Affinity(
                pod_anti_affinity=t.PodAffinity(
                    required=(_term(HOST, "cache"),)),
                pod_affinity=t.PodAffinity(required=(_term(key, "db"),)))
        cache.add_pod(make_pod(
            f"e{j}", cpu_milli=int(rng.integers(0, 400)),
            labels={"app": str(rng.choice(APPS))}, affinity=own,
            node_name=names[int(rng.integers(0, 12))]))
    pending = [_pending_for(feature, key, j) for j in range(12 if wide else 1)]
    return cache.update_snapshot(), pending


@pytest.mark.parametrize("wide", [True, False],
                         ids=["rows_below_pod_slots", "rows_above_pod_slots"])
@pytest.mark.parametrize(
    "feature", ["missing_key", "unused_slots", "self_escape", "negative"])
@pytest.mark.parametrize("key", [HOST, ZONE], ids=["hostname", "zone"])
def test_table_path_equals_per_pod_path_and_oracle(key, feature, wide):
    """The kernels with ``NodeCounts`` handed in, the kernels gathering per
    (pod, slot, node) from the (R, D) sums, and the scalar oracle agree on
    every node; the path ``filter_score_batch`` takes by itself is the one
    the shapes call for."""
    import jax

    snap, pending = _table_case(key, feature, wide)
    profile = affinity_profile()
    batch = encode_batch(snap, pending, profile, pad=False)
    params = score_params(profile, batch.resource_names)
    pa = batch.device.podaffinity
    assert PA.table_pays(pa) == wide
    assert batch.podaffinity_encode.slots == PA.kernel_slots(pa)
    comps = rt.filter_components(batch.device, params)
    assert isinstance(comps[6], PA.NodeCounts) == wide
    if feature == "missing_key":
        assert (np.asarray(pa.node_domain) < 0).any()
    if feature == "unused_slots":
        assert (np.asarray(pa.fa_rows) < 0).any()
        assert (np.asarray(pa.fa_rows) >= 0).any() == wide
    if feature == "negative":
        assert (np.asarray(pa.score_vals) < 0).any()

    mask, total = rt.filter_score_batch(batch.device, params)
    mask, total = np.asarray(mask), np.asarray(total)

    def kernels(sums):
        ok = jax.vmap(lambda fr, fs, rr, er: PA.affinity_filter_pod(
            pa, sums, fr, fs, rr, er))(
                pa.fa_rows, pa.fa_self, pa.ra_rows, pa.ea_rows)
        sc = jax.vmap(lambda sr, sv, m: PA.affinity_score_pod(
            pa, sums, sr, sv, m))(pa.score_rows, pa.score_vals, mask)
        return np.asarray(ok), np.asarray(sc)

    table_ok, table_sc = kernels(PA.node_counts(pa, pa.base_sums))
    gather_ok, gather_sc = kernels(pa.base_sums)
    assert (table_ok == gather_ok).all()
    assert (table_sc == gather_sc).all()

    infos = snap.node_infos()
    n = len(infos)
    escaped = 0
    for i, pod in enumerate(pending):
        want_ok = [oracle.interpod_filter(pod, infos, info) for info in infos]
        assert table_ok[i, :n].tolist() == want_ok, pod.name
        feas = [oracle.fits(pod, info) and ok
                for info, ok in zip(infos, want_ok)]
        assert mask[i, :n].tolist() == feas, pod.name
        want_ip = oracle.interpod_scores(pod, infos, feas)
        assert table_sc[i, :n].tolist() == want_ip, pod.name
        for j, info in enumerate(infos):
            want = oracle.least_allocated(
                pod, info, [(t.CPU, 1), (t.MEMORY, 1)]) + 2 * want_ip[j]
            assert total[i, j] == want, (pod.name, info.node.name)
        escaped += pod.labels_dict().get("app") == "solo" and any(want_ok)
    if feature == "self_escape":
        # the escape was taken, and refused to the pod that matches no term
        # of its own
        assert escaped == (len(pending) + 1) // 2
        assert not mask[1:2].any()


@pytest.mark.parametrize("key", [HOST, ZONE], ids=["hostname", "zone"])
def test_the_scan_s_carried_table_is_the_table_of_its_carried_sums(key):
    """After each of K placements the ``NodeCounts`` the scan carries equals
    the one rebuilt from the (R, D) sums it carries beside it
    (``final_state[5]``): the compare-and-add of ``node_counts_add`` is the
    scatter, seen from the nodes."""
    import jax

    snap, pending = _table_case(key, "missing_key", wide=True)
    profile = affinity_profile()
    batch = encode_batch(snap, pending, profile, pad=False)
    params = score_params(profile, batch.resource_names)
    b = batch.device
    pa = b.podaffinity
    scan = jax.jit(greedy_scan, static_argnames=("params",))
    before = PA.node_counts(pa, pa.base_sums)
    moved = 0
    for k in range(1, len(pending) + 1):
        first_k = np.arange(len(pending)) < k
        assignments, final_state, counts = scan(
            dataclasses.replace(b, pod_valid=np.asarray(b.pod_valid) & first_k),
            params)
        want = PA.node_counts(pa, final_state[5])
        assert (np.asarray(counts.at_node) == np.asarray(want.at_node)).all()
        assert (np.asarray(counts.row_total)
                == np.asarray(want.row_total)).all()
        assert (np.asarray(assignments)[k:] == -1).all()
        moved += not (np.asarray(counts.at_node)
                      == np.asarray(before.at_node)).all()
        before = counts
    # placements moved the table (a placement that moved nothing would make
    # the equality above hold for free)
    assert moved >= 3
    assert (np.asarray(counts.at_node)[np.asarray(pa.node_domain) < 0]
            == 0).all()


def test_a_one_pod_view_gathers_from_the_sums_it_is_handed():
    """Preemption's re-check: ``filter_components`` for one pod with the
    scan's ``final_state[5]`` and no carried table gathers its own slots
    from THOSE sums (R > P × slots: no table is built): its masks are the
    ones the scan's own table gives."""
    import jax
    from kubetpu.assign.greedy import _pod_view

    # many rows no pending pod has a slot for, and six pods to place
    snap, _ = _table_case(ZONE, "self_escape", wide=False)
    pending = [_pending_for("self_escape", ZONE, j) for j in range(6)]
    profile = affinity_profile()
    batch = encode_batch(snap, pending, profile, pad=False)
    params = score_params(profile, batch.resource_names)
    b = batch.device
    _, final_state, counts = jax.jit(
        greedy_scan, static_argnames=("params",))(b, params)
    assert not (np.asarray(final_state[5])
                == np.asarray(b.podaffinity.base_sums)).all()
    differs = 0
    for i in range(len(pending)):
        view = _pod_view(b, i)
        assert not PA.table_pays(view.podaffinity)
        from_sums = rt.filter_components(view, params, pa_sums=final_state[5])
        from_table = rt.filter_components(view, params, pa_counts=counts)
        from_base = rt.filter_components(view, params)
        assert from_sums[6] is final_state[5] and from_table[6] is counts
        assert (np.asarray(from_sums[4]) == np.asarray(from_table[4])).all()
        differs += not (np.asarray(from_sums[4])
                        == np.asarray(from_base[4])).all()
    # the placements changed some pod's affinity mask, so the equality above
    # is not the base state's
    assert differs


def test_the_encode_span_says_what_the_shape_rule_saw():
    """``encode-podaffinity`` carries ``slots`` beside ``rows``: with the
    batch's bucket, what ``table_pays`` decides from."""
    from kubetpu.sched import Scheduler

    from .test_scheduler import FakeClient

    snap, pending = _table_case(ZONE, "unused_slots", wide=True)
    s = Scheduler(client=FakeClient(), profile=affinity_profile(),
                  dispatcher_workers=0)
    for info in snap.node_infos():
        s.on_node_add(info.node)
        for pod in info.pods.values():
            s.on_pod_add(pod)
    for j, pod in enumerate(pending):
        s.on_pod_add(dataclasses.replace(pod, creation_index=j))
    s.schedule_batch()
    [span] = [sp for sp in s.tracer.drain()
              if sp.name == "encode-podaffinity"]
    batch = encode_batch(snap, pending, affinity_profile())
    assert span.attrs["slots"] == PA.kernel_slots(batch.device.podaffinity)
    assert span.attrs["slots"] >= 4
    assert span.attrs["rows"] == batch.device.podaffinity.node_domain.shape[0]
