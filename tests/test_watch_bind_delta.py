"""Bind deltas on the batched watch poll.

A pods ``bind`` op commits the stored pod with its node set. On the batched
poll, and only for a client that asks (``bindDeltas=1``), that event goes out
as ``{type, key, resourceVersion, bind: {uid, node}}`` with no object, and
``SharedInformer`` rebuilds the object from the pod it holds.

What is held here, on both store cores and both wires: an informer that asks
holds, object for object, what one that takes whole events holds, and its
handlers saw the same (old, new) pairs; every other watch (per kind, stream,
scoped, a poll without the parameter) sends the whole bind body byte for byte
as a get + CAS update's; a delta with no pod to rebuild (missing, another
uid, a node set) relists the kind and is counted, never guessed; compaction
between the ADDED and the bind is the 410 relist's; a federation replica
rebuilds the binds the other made; a server that ignores the parameter gives
whole events to an asking client; and a served run binds pod for pod as one
without deltas. The store keeps a bind's record only as long as its event.
"""

from __future__ import annotations

import dataclasses
import http.client
from urllib.parse import urlsplit

import pytest

pytest.importorskip("jax")

from kubetpu.api import codec
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.apiserver import APIServer, RemoteStore
from kubetpu.apiserver import server as server_mod
from kubetpu.client.informers import NODES, PODS, SchedulerInformers, StoreClient
from kubetpu.store.memstore import MemStore

from .test_bind_op import _bind, _bind_by_update
from .test_wal import CORES

WIRES = ["binary", "json"]


class _Handlers:
    """A stand-in scheduler: every ``on_*`` handler records its call."""

    loop_clock = None

    def __init__(self) -> None:
        self.calls: list = []

    def __getattr__(self, name: str):
        if not name.startswith("on_"):
            raise AttributeError(name)
        return lambda *args: self.calls.append((name, args))

    def pod_updates(self) -> list:
        return [args for name, args in self.calls if name == "on_pod_update"]


class _WholeEvents(RemoteStore):
    """A client of the batched poll that never asks for deltas."""

    def watch_bulk(self, cursors, timeout_s=0.0, bind_deltas=False):
        return super().watch_bulk(cursors, timeout_s)


def _pods_informer(informers: SchedulerInformers):
    (r,) = [r for r in informers._reflectors if r.informer.kind == PODS]
    return r


def _informers(remote: RemoteStore) -> tuple[SchedulerInformers, _Handlers]:
    handlers = _Handlers()
    informers = SchedulerInformers(remote, handlers)
    informers.start()
    return informers, handlers


def _seed(st: MemStore, n: int, ns: str = "default") -> list[str]:
    keys = []
    for i in range(n):
        st.create(PODS, f"{ns}/p{i}",
                  make_pod(f"p{i}", namespace=ns, labels={"app": "web"}))
        keys.append(f"{ns}/p{i}")
    return keys


@pytest.fixture(params=CORES)
def native(request):
    return request.param


@pytest.fixture(params=WIRES)
def wire(request):
    return request.param


@pytest.fixture
def srv(native, wire):
    server = APIServer(MemStore(native=native), wire=wire).start()
    yield server
    server.close()


def _listed(remote: RemoteStore) -> dict:
    return dict(remote.list(PODS)[0])


# ------------------------------------------------------------------ the wire

@pytest.mark.parametrize("key, uid, node, rv", [
    ("default/p0", "default/p0", "n0", 7),
    ("sched-1/" + "x" * 40, "5f0c-uid", "scheduler-perf-4999", 2**31 + 5),
    ("ns/pöd \"quoted\"", "", "nœud-1", 300),
])
def test_the_delta_s_bytes_are_the_codec_s_own(wire, key, uid, node, rv):
    tree = {"type": "MODIFIED", "key": key, "resourceVersion": rv,
            "bind": {"uid": uid, "node": node}}
    got = codec.bind_delta_wire_bytes(key, uid, node, rv, wire)
    assert got == codec.dumps(tree, wire)
    assert codec.loads(got, wire) == tree


def test_with_node_is_replace_field_for_field():
    from benchmark.harness import templates_preferredaffinity as tp

    pod = tp.pod_with_preferred_pod_affinity("p0", "sched-1")
    bound = pod.with_node("n1")
    assert bound == dataclasses.replace(pod, node_name="n1")
    assert type(bound) is type(pod) and bound is not pod
    assert hash(bound) == hash(dataclasses.replace(pod, node_name="n1"))
    assert pod.node_name == "" and bound.with_node("n2").node_name == "n2"
    assert bound.affinity is pod.affinity       # fields shared, not copied


# ------------------------------------------------------------------ the store

def test_a_bind_record_lives_as_long_as_its_event(native):
    st = MemStore(native=native, history=8)
    keys = _seed(st, 6)
    st.bulk(PODS, [_bind(k, uid="") for k in keys[:3]])
    assert sorted(st._binds) == [7, 8, 9]
    assert st._binds[8][:3] == [keys[1], st.get(PODS, keys[1])[0].uid, "n0"]
    for i in range(8):                      # the three events leave the ring
        st.create(NODES, f"n{i}", make_node(f"n{i}"))
    assert st.compacted_through >= 9
    st.bulk(PODS, [_bind(keys[3], uid="")])
    assert list(st._binds) == [st.resource_version]
    # a drain from a live cursor: the bind's delta, every other body whole
    rv = st.resource_version - 1
    st.update(PODS, keys[4], st.get(PODS, keys[4])[0])
    for wire in WIRES:
        got, _drain = st.events_body_since_bulk({PODS: rv, NODES: rv}, wire,
                                                bind_deltas=True)
        whole, _drain = st.events_body_since_bulk({PODS: rv, NODES: rv}, wire)
        assert got[NODES] == whole[NODES]
        assert got[PODS][1] == whole[PODS][1]
        assert got[PODS][0][1] == whole[PODS][0][1]
        assert got[PODS][0][0] == codec.bind_delta_wire_bytes(
            keys[3], st.get(PODS, keys[3])[0].uid, "n0", rv + 1, wire)


def test_a_bind_that_completes_a_deletion_is_no_delta(native):
    """A pod stored terminating with no finalizers: the bind's update
    deletes it (the finalizer gate), and its DELETED event is whole."""
    st = MemStore(native=native)
    st.create(PODS, "default/d",
              dataclasses.replace(make_pod("d"), deletion_timestamp=1.0))
    rv = st.resource_version
    (res,) = st.bulk(PODS, [_bind("default/d", uid="")])
    assert res["status"] == 200 and st.get(PODS, "default/d")[0] is None
    assert st._binds == {}
    got, _ = st.events_body_since_bulk({PODS: rv}, "json", bind_deltas=True)
    assert got == st.events_body_since_bulk({PODS: rv}, "json")[0]
    assert b'"DELETED"' in got[PODS][0][0]


# -------------------------------------------- the same objects, the same calls

def test_a_delta_informer_holds_what_a_whole_event_informer_holds(srv, wire):
    st = srv.store
    keys = _seed(st, 6)
    deltas, seen = _informers(RemoteStore(srv.url, wire=wire))
    whole, seen_whole = _informers(_WholeEvents(srv.url, wire=wire))
    StoreClient(RemoteStore(srv.url, wire=wire)).bulk_bind(
        [(st.get(PODS, k)[0], f"n{i % 2}") for i, k in enumerate(keys[:3])])
    # in one frame: another writer's update, a delete, a create and its
    # bind, a refused bind; then a bind of the updated pod
    p3 = st.get(PODS, keys[3])[0]
    st.update(PODS, keys[3], dataclasses.replace(p3, priority=7))
    st.delete(PODS, keys[5])
    st.create(PODS, "default/late", make_pod("late"))
    res = st.bulk(PODS, [_bind("default/late", "n1", uid=""),
                         _bind(keys[0], "n1", uid=""),
                         _bind(keys[3], "n0", uid="")])
    assert [r["status"] for r in res] == [200, 409, 200]
    for informers in (deltas, whole):
        informers.pump()
    held = _pods_informer(deltas).informer
    assert held.store == _pods_informer(whole).informer.store == _listed(
        RemoteStore(srv.url))
    assert seen.calls == seen_whole.calls
    assert len(seen.pod_updates()) == 6        # five binds and the update
    assert held.bind_delta_counts() == (5, 0)
    assert _pods_informer(whole).informer.bind_delta_counts() == (0, 0)
    text = deltas.bind_delta_metrics_text()
    assert 'scheduler_watch_bind_deltas_total{result="applied"} 5' in text
    assert 'scheduler_watch_bind_deltas_total{result="relisted"} 0' in text


def test_the_delta_is_small_and_the_client_gets_it_without_an_object(srv,
                                                                    wire):
    st = srv.store
    key = "default/web-0"
    st.create(PODS, key, make_pod("web-0", cpu_milli=100,
                                  memory=500 * 1024**2, labels={"app": "web"}))
    rv = st.resource_version
    st.bulk(PODS, [_bind(key, "n0", uid="")])
    remote = RemoteStore(srv.url, wire=wire)
    (ev,), _cursor = remote.watch_bulk({PODS: rv}, bind_deltas=True)[PODS]
    assert (ev.type, ev.key, ev.obj, ev.bind) == (
        "MODIFIED", key, None, (st.get(PODS, key)[0].uid, "n0"))
    assert ev.resource_version == st.resource_version
    (whole,), _cursor = remote.watch_bulk({PODS: rv})[PODS]
    assert whole.bind is None and whole.obj == st.get(PODS, key)[0]
    delta = codec.bind_delta_wire_bytes(key, ev.bind[0], "n0", rv + 1, wire)
    body = st.events_body_since(PODS, rv, wire)[0][0]
    assert len(delta) < len(body)


# ---------------------------------------------- every other watch: whole bodies

def _raw_get(url: str, path: str, wire: str) -> bytes:
    u = urlsplit(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=10)
    try:
        headers = ({"Accept": codec.binary_content_type()}
                   if wire == "binary" else {})
        conn.request("GET", path, headers=headers)
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200, body
        return body
    finally:
        conn.close()


def test_every_other_watch_sends_the_whole_bind_body_as_before(native, wire):
    """The bind op's events read, on every watch but an asking poll, byte
    for byte what a get + CAS update's do."""
    servers = []
    for bind in ("op", "update"):
        st = MemStore(native=native)
        st.create(NODES, "n0", make_node("n0"))
        keys = _seed(st, 4)
        if bind == "op":
            st.bulk(PODS, [_bind(k, uid="") for k in keys])
        else:
            _bind_by_update(st, keys)
        servers.append(APIServer(st, wire=wire).start())
    try:
        op, upd = servers
        forms = [
            "/apis/pods?watch=1&resourceVersion=1&timeoutSeconds=0",
            "/apis/pods?watch=1&stream=1&resourceVersion=1&timeoutSeconds=0",
            "/apis/pods?watch=1&resourceVersion=1&timeoutSeconds=0"
            "&labelSelector=app%3Dweb",
            "/apis/?watch=1&buckets=pods:1,nodes:0&timeoutSeconds=0",
        ]
        for path in forms:
            got = _raw_get(op.url, path, wire)
            assert got == _raw_get(upd.url, path, wire), path
            assert b"bind" not in got
        asking = forms[-1] + "&bindDeltas=1"
        got = _raw_get(op.url, asking, wire)
        assert got != _raw_get(upd.url, asking, wire)
        assert len(got) < len(_raw_get(op.url, forms[-1], wire))
    finally:
        for s in servers:
            s.close()


# --------------------------------------------- no pod to rebuild: relist

@pytest.mark.parametrize("held", ["missing", "another-uid", "node-set"])
def test_a_delta_with_no_pod_to_rebuild_relists_the_kind(srv, wire, held):
    st = srv.store
    keys = _seed(st, 3)
    informers, seen = _informers(RemoteStore(srv.url, wire=wire))
    r = _pods_informer(informers)
    inf = r.informer
    pod = inf.store[keys[1]]
    if held == "missing":
        del inf.store[keys[1]]
    elif held == "another-uid":
        inf.store[keys[1]] = dataclasses.replace(pod, uid="recreated")
    else:
        inf.store[keys[1]] = pod.with_node("elsewhere")
    relists0 = r.relists
    st.bulk(PODS, [_bind(k, "n0", uid="") for k in keys])
    informers.pump()
    assert inf.bind_delta_counts() == (1, 1)   # the first, then the refusal
    assert r.relists == relists0 + 1
    stored = _listed(RemoteStore(srv.url))
    assert inf.store == stored
    assert all(p.node_name == "n0" for p in inf.store.values())
    # every pod the handlers got is the stored one: none was rebuilt from
    # the pod held where it was not the store's
    assert all(new == stored[f"{new.namespace}/{new.name}"]
               for _old, new in seen.pod_updates())
    assert all(new.uid != "recreated" for _old, new in seen.pod_updates())
    assert 'scheduler_watch_bind_deltas_total{result="relisted"} 1' in \
        informers.bind_delta_metrics_text()
    # the relist's watch goes on from its revision: the next bind applies
    st.create(PODS, "default/next", make_pod("next"))
    informers.pump()
    st.bulk(PODS, [_bind("default/next", "n1", uid="")])
    informers.pump()
    assert inf.bind_delta_counts() == (2, 1)
    assert inf.store == _listed(RemoteStore(srv.url))


def test_compaction_between_the_add_and_the_bind_is_the_410_relist(native,
                                                                    wire):
    srv = APIServer(MemStore(native=native, history=4), wire=wire).start()
    try:
        st = srv.store
        informers, _seen = _informers(RemoteStore(srv.url, wire=wire))
        r = _pods_informer(informers)
        st.create(PODS, "default/a", make_pod("a"))
        for i in range(6):                  # the ADDED leaves the ring
            st.create(NODES, f"n{i}", make_node(f"n{i}"))
        st.bulk(PODS, [_bind("default/a", "n0", uid="")])
        relists0 = r.relists
        informers.pump()
        assert r.relists == relists0 + 1
        assert r.informer.store["default/a"].node_name == "n0"
        assert r.informer.bind_delta_counts() == (0, 0)
        # the cursor is live again: the next bind is a delta
        st.create(PODS, "default/b", make_pod("b"))
        informers.pump()
        st.bulk(PODS, [_bind("default/b", "n1", uid="")])
        informers.pump()
        assert r.informer.bind_delta_counts() == (1, 0)
        assert r.informer.store == _listed(RemoteStore(srv.url))
    finally:
        srv.close()


# ------------------------------------------------------------- federation

def test_a_federation_replica_rebuilds_the_binds_the_other_made(native,
                                                                 wire):
    from .test_federation import FakeClock, make_federation

    st = MemStore(native=native)
    for i in range(4):
        st.create(NODES, f"n{i}", make_node(f"n{i}", cpu_milli=8000,
                                            memory=32 * 1024**3))
    for j in range(12):
        st.create(PODS, f"default/p{j}",
                  make_pod(f"p{j}", cpu_milli=100, creation_index=j))
    srv = APIServer(st, wire=wire).start()
    fed = None
    try:
        fed, clock = make_federation(
            lambda _i: RemoteStore(srv.url, wire=wire), replicas=2,
            mode="hash", clock=FakeClock())
        fed.start()
        fed.run_until_idle(max_rounds=60, advance_clock=clock.advance)
        for h in fed.handles:
            h.informers.pump()
        listed = _listed(RemoteStore(srv.url))
        assert all(p.node_name for p in listed.values()) and len(listed) == 12
        assert fed.bound() == 12 and fed.conflicts() == 0
        for h in fed.handles:
            inf = _pods_informer(h.informers).informer
            # every bind reached every replica as a delta, its own and the
            # other's, and was rebuilt to the stored pod
            assert inf.bind_delta_counts() == (12, 0)
            assert inf.store == listed
    finally:
        if fed is not None:
            fed.close()
        srv.close()


# ------------------------------------------ a server without the parameter

def test_a_server_that_ignores_the_parameter_gives_whole_events(
    srv, wire, monkeypatch,
):
    monkeypatch.setattr(server_mod, "BIND_DELTAS_PARAM", "notKnownHere")
    st = srv.store
    keys = _seed(st, 3)
    informers, seen = _informers(RemoteStore(srv.url, wire=wire))
    rv = st.resource_version
    st.bulk(PODS, [_bind(k, "n0", uid="") for k in keys])
    events, _cursor = RemoteStore(srv.url, wire=wire).watch_bulk(
        {PODS: rv}, bind_deltas=True)[PODS]
    assert [(e.bind, e.obj.node_name) for e in events] == [(None, "n0")] * 3
    informers.pump()
    inf = _pods_informer(informers).informer
    assert inf.bind_delta_counts() == (0, 0)
    assert inf.store == _listed(RemoteStore(srv.url))
    assert len(seen.pod_updates()) == 3


# ----------------------------------------------------------- binding parity

def _served_run(srv: APIServer, remote: RemoteStore, nodes=6, pods=18):
    """A small served run (``_run_fullstack``'s loop): {pod key: node} and
    the informer bundle that fed it."""
    from kubetpu.framework import config as C
    from kubetpu.sched import Scheduler

    for i in range(nodes):
        MemStore.create(srv.store, NODES, f"n{i}",
                        make_node(f"n{i}", cpu_milli=4000))
    for j in range(pods):
        MemStore.create(srv.store, PODS, f"default/p{j}",
                        make_pod(f"p{j}", cpu_milli=100, creation_index=j))
    sched = Scheduler(StoreClient(remote), profile=C.minimal_profile(),
                      dispatcher_workers=0)
    informers = SchedulerInformers(remote, sched)
    informers.start()
    for _ in range(20):
        informers.pump()
        sched.schedule_batch()
        sched.dispatcher.sync()
        sched._drain_bind_completions()
        if all(p.node_name for p in _listed(remote).values()):
            break
    informers.pump()       # the last binds' echoes
    sched.schedule_batch()
    sched.close()
    assert not sched.cache._assumed        # every bind echoed back
    return {k: p.node_name for k, p in _listed(remote).items()}, informers


def test_a_served_run_binds_as_one_without_deltas(wire):
    srv_a = APIServer(wire=wire).start()
    srv_b = APIServer(wire=wire).start()
    try:
        bound, informers = _served_run(srv_a,
                                       RemoteStore(srv_a.url, wire=wire))
        bound_whole, whole = _served_run(
            srv_b, _WholeEvents(srv_b.url, wire=wire))
        assert len(bound) == 18 and all(bound.values())
        assert bound == bound_whole
        assert _pods_informer(informers).informer.bind_delta_counts() == \
            (18, 0)
        assert _pods_informer(whole).informer.bind_delta_counts() == (0, 0)
    finally:
        srv_a.close()
        srv_b.close()
