"""The encode cache's template-count index (``EncodeCache.pod_groups``)
follows pod deltas: after every step of a scripted cluster history it equals
``collect_pod_groups(nt)`` array for array, and the pods it had to key in a
step are no more than the pods that step added or replaced, however many
pods the touched nodes already hold. The spread and affinity encoders give
the same tensors through the index as through the from-scratch pass."""

import dataclasses

import numpy as np
import pytest

from kubetpu.api import types as t
from kubetpu.api.wrappers import (
    make_node, make_pod, pod_affinity_term, spread_constraint,
)
from kubetpu.metrics.tpu import TPUBackendMetrics
from kubetpu.state.encode_cache import EncodeCache, collect_pod_groups
from kubetpu.state.encoder import encode_snapshot
from kubetpu.state.podaffinity import encode_pod_affinity
from kubetpu.state.snapshot import Cache
from kubetpu.state.spread import encode_spread

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
OLD_MEMO = 65_536     # the per-uid memo the index no longer uses


def node(i, extra=None):
    labels = {HOST: f"n{i}", ZONE: f"z{i % 3}", **(extra or {})}
    return make_node(f"n{i}", cpu_milli=4000, memory=8 << 30, pods=110,
                     labels=labels)


def blue(name):
    """A spread template: color=blue, zone maxSkew 5, DoNotSchedule."""
    return make_pod(name, labels={"color": "blue"}, cpu_milli=10,
                    spread=[spread_constraint(5, ZONE,
                                              match_labels={"color": "blue"})])


def red(name):
    """An affinity template: color=red, required zone affinity to red."""
    term = pod_affinity_term(ZONE, match_labels={"color": "red"})
    return make_pod(name, labels={"color": "red"}, cpu_milli=10,
                    affinity=t.Affinity(
                        pod_affinity=t.PodAffinity(required=(term,))))


def plain(name, labels=None):
    return make_pod(name, labels=labels, cpu_milli=10)


class Cluster:
    """A scheduler cache, its tensors and an encode cache, stepped the way
    the loop steps them: informer events, then one encode."""

    def __init__(self, nodes):
        self.cache = Cache()
        self.ec = EncodeCache()
        for i in range(nodes):
            self.cache.add_node(node(i))
        self.snap = None
        self.nt = None
        self.step()

    def step(self, check=True):
        """Refresh the tensors and the index; check the index against the
        from-scratch pass. Returns the pods the index keyed."""
        self.snap = self.cache.update_snapshot(self.snap)
        self.nt = encode_snapshot(self.snap, pad_nodes=128, prev=self.nt)
        self.ec.sync_nodes(self.nt)
        keyed = self.ec.index_pods["keyed"]
        got = self.ec.pod_groups(self.nt)
        if check:
            assert_same_groups(got, collect_pod_groups(self.nt))
        return self.ec.index_pods["keyed"] - keyed

    def assume(self, pods, nodes):
        """Bind ``pods`` round-robin over ``nodes`` through assume; returns
        the assumed objects."""
        out = []
        for j, p in enumerate(pods):
            q = p.with_node(nodes[j % len(nodes)])
            self.cache.assume_pod(q)
            out.append(q)
        return out

    def confirm(self, pending, assumed):
        """The informer's bind delta: the pod it held, rebuilt on its node."""
        for p, q in zip(pending, assumed):
            self.cache.add_pod(p.with_node(q.node_name))

    def run(self, pods):
        """The node agent's status update: each pod's cached object replaced
        by a copy in phase Running, its template fields the very same."""
        for p in pods:
            old = self.cache._pods[p.uid]
            self.cache.update_pod(old, dataclasses.replace(old,
                                                           phase="Running"))

    def relist(self, pods):
        """A relist's Add of each bound pod, not assumed: a new object of
        the same pod on the same node."""
        for p in pods:
            old = self.cache._pods[p.uid]
            self.cache.add_pod(old.with_node(old.node_name))


def renamed(pod, name):
    """Another pod of ``pod``'s template, as ``Pod.with_node`` copies one."""
    q = object.__new__(type(pod))
    q.__dict__.update(pod.__dict__, name=name, uid=f"{pod.namespace}/{name}")
    return q


def assert_same_groups(got, want):
    assert set(got) == set(want)
    for key, vec in want.items():
        assert got[key].dtype == vec.dtype
        np.testing.assert_array_equal(got[key], vec)


def test_every_step_of_a_cluster_history_matches_the_full_pass():
    c = Cluster(12)
    names = [f"n{i}" for i in range(12)]
    pending = ([blue(f"b{j}") for j in range(20)]
               + [red(f"r{j}") for j in range(10)]
               + [plain(f"p{j}") for j in range(6)])
    assumed = c.assume(pending, names)
    assert c.step() == len(pending)             # each new pod keyed once

    c.confirm(pending, assumed)                 # the with_node copies
    assert c.step() == 0
    # equal to the assumed pods: the cache moved no node, nothing walked
    assert c.ec.index_pods["kept"] == 0

    # new objects with the same templates, on every node: kept unkeyed
    c.run(assumed[:20])
    c.relist(assumed[20:])
    assert c.step() == 0
    assert c.ec.index_pods["kept"] == len(pending)

    # a bound pod replaced under its uid with new labels moves template
    for j in range(3):
        old = c.cache._pods[f"default/b{j}"]
        new = dataclasses.replace(
            old, labels=t.freeze_map({"color": "green"}))
        c.cache.update_pod(old, new)
    kept = c.ec.index_pods["kept"]
    assert c.step() == 3
    assert c.ec.index_pods["kept"] > kept       # their nodes' other pods
    green = [k for k in c.ec.pod_groups(c.nt) if dict(k[0]) == {"color":
                                                                "green"}]
    assert len(green) == 1

    for j in range(10, 15):                     # deletes
        c.cache.remove_pod(c.cache._pods[f"default/b{j}"])
    assert c.step() == 0

    fresh = [plain(f"y{j}", {"app": "yellow"}) for j in range(7)]
    c.assume(fresh, names[:2])                  # a new template appears
    assert c.step() == 7

    n12 = node(12)                              # scoped node add
    c.cache.add_node(n12)
    c.ec.invalidate_nodes(added=n12)
    c.assume([blue(f"a{j}") for j in range(4)], ["n12"])
    nt_before = c.nt
    assert c.step() == 4
    assert c.nt is nt_before                    # extended in place

    c.cache.remove_node("n3")                   # scoped remove: compaction
    c.ec.invalidate_nodes(removed=node(3))
    assert c.step() == 0
    assert c.nt is not nt_before and "n3" not in c.nt.node_names

    c.cache.update_node(node(5, {"disk": "ssd"}))   # update: full flush
    c.ec.invalidate_nodes()
    assert c.step() == 0


def test_the_keys_follow_the_pods_bound_past_the_old_memo():
    """1024 pods a step onto a few nodes each, past 70,000 bound pods: the
    keys a step stays the pods it bound, however full the nodes get."""
    nodes = 64
    c = Cluster(nodes)
    names = [f"n{i}" for i in range(nodes)]
    protos = (red("proto-r"), blue("proto-b"))
    bound, step, pending, assumed = 0, 0, [], []
    while bound < OLD_MEMO + 5000:
        c.confirm(pending, assumed)             # last step's, confirmed
        c.run(assumed)                          # and Running: new objects
        proto = protos[step % 2]
        pending = [renamed(proto, f"s{step}-{j}") for j in range(1024)]
        on = [names[(step * 8 + k) % nodes] for k in range(8)]
        assumed = c.assume(pending, on)
        bound += len(pending)
        assert c.step(check=step % 24 == 23) <= len(pending)
        step += 1
    c.confirm(pending, assumed)
    assert c.step() == 0
    assert c.ec.index_pods["keyed"] == bound
    assert max(len(info.pods) for info in c.nt.infos) > 1000
    assert len(c.ec._group_keys) == 2           # the interner: templates


def test_a_runaway_template_count_resets_the_index():
    c = Cluster(4)
    c.assume([plain(f"u{j}", {"id": str(j)}) for j in range(40)],
             ["n0", "n1"])
    c.step()
    c.ec._group_keys.extend([None] * (1 << 16))     # as if interning ran away
    c.assume([plain("v")], ["n2"])
    c.step()
    assert len(c.ec._group_keys) == 41


def _tensors_equal(a, b):
    assert (a is None) == (b is None)
    if a is None:
        return
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            assert x.dtype == y.dtype, f.name
            np.testing.assert_array_equal(x, y, err_msg=f.name)
        else:
            assert x == y, f.name


@pytest.mark.parametrize("encoder", ["spread", "affinity"])
def test_the_encoders_agree_with_and_without_the_index(encoder):
    c = Cluster(9)
    names = [f"n{i}" for i in range(9)]
    history = []
    pending = []
    for step in range(6):
        batch = ([blue(f"q{step}-{j}") for j in range(12)]
                 + [red(f"w{step}-{j}") for j in range(6)])
        assumed = c.assume(batch, names[step:step + 4])
        c.confirm(pending, history[-1] if history else [])
        history.append(assumed)
        pending = batch
        if step == 3:                           # a replaced template
            old = c.cache._pods["default/q0-0"]
            c.cache.update_pod(old, dataclasses.replace(
                old, labels=t.freeze_map({"color": "red"})))
        if step == 4:
            c.cache.update_node(node(2, {"disk": "ssd"}))
            c.ec.invalidate_nodes()
        c.step()
        incoming = [blue(f"i{step}-{j}") for j in range(5)] + [red(f"j{step}")]
        if encoder == "spread":
            got = encode_spread(c.nt, incoming, pad_pods=8, cache=c.ec)
            want = encode_spread(c.nt, incoming, pad_pods=8)
        else:
            got = encode_pod_affinity(c.nt, incoming, pad_pods=8,
                                      cache=c.ec)
            want = encode_pod_affinity(c.nt, incoming, pad_pods=8)
        assert got is not None
        _tensors_equal(got, want)


def test_the_counter_reaches_the_metrics_page():
    metrics = TPUBackendMetrics()
    page = metrics.registry.expose()
    for result in ("kept", "keyed"):
        assert (f'scheduler_encode_template_index_pods_total'
                f'{{result="{result}"}} 0') in page
    c = Cluster(3)
    c.ec.metrics = metrics
    pending = [blue(f"b{j}") for j in range(5)]
    assumed = c.assume(pending, ["n0"])
    c.step()
    c.confirm(pending, assumed)
    c.run(assumed)                             # new objects, same template
    c.step()
    c.ec.flush_metrics()
    page = metrics.registry.expose()
    assert ('scheduler_encode_template_index_pods_total{result="keyed"} 5'
            in page)
    assert ('scheduler_encode_template_index_pods_total{result="kept"} 5'
            in page)
