"""The pods ``:bulk`` verb's ``bind`` op (ISSUE 39): the binding
subresource, ``{"op": "bind", "key", "uid", "node"}``, no object either way.

What is held here: the store applies it under the batch's one lock with the
subresource's refusals (gone 404; a recreated pod's uid, any node already
set, the same node included, 409) and a failed op fails only itself; what
it commits is byte for byte what a get and a CAS update commit (the stored
object, the watch bodies, the WAL); the apiserver serves it on both paths
of the verb and on both wires and counts it in
``apiserver_pod_binds_total{result}``; ``StoreClient`` binds a cycle in ONE
request and falls back to the get and the update only against a server
that answers the op 400; federation's race mode still sees a loser's
same-node bind as a conflict; a crash between the WAL append and the
apply replays a bind batch exactly once.
"""

from __future__ import annotations

import pytest

pytest.importorskip("jax")

from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.apiserver import APIServer, RemoteStore
from kubetpu.apiserver import server as server_mod
from kubetpu.apiserver.admission import Registry
from kubetpu.client.informers import PODS, StoreClient
from kubetpu.controllers import install_quota_admission
from kubetpu.controllers.resourcequota import RESOURCE_QUOTAS
from kubetpu.sched.api_dispatcher import is_bind_conflict
from kubetpu.store import faultpoints as fp
from kubetpu.store.memstore import ConflictError, MemStore
from kubetpu.store.wal import list_segments

from .test_bulk_admission import _bulk_ops, _quota
from .test_wal import CORES


@pytest.fixture(autouse=True)
def _reset_faultpoints():
    fp.reset()
    yield
    fp.reset()


def _bind(key: str, node: str = "n0", uid: str | None = None) -> dict:
    return {"op": "bind", "key": key, "uid": key if uid is None else uid,
            "node": node}


def _seeded(native, n: int = 4, ns: str = "default", **kw) -> MemStore:
    st = MemStore(native=native, **kw)
    for i in range(n):
        st.create(PODS, f"{ns}/p{i}", make_pod(f"p{i}", namespace=ns))
    return st


def _statuses(res: list) -> list:
    return [r["status"] for r in res]


def _binds(srv: APIServer, result: str) -> int:
    return int(srv.metrics.pod_binds.labels(result).value)


def _count_requests(remote: RemoteStore) -> list:
    """Every request the client sends from here on, as (method, path)
    (the server's own count lands after its reply, too late to read)."""
    sent: list = []
    real = remote._request

    def counting(method, path, *a, **kw):
        sent.append((method, path))
        return real(method, path, *a, **kw)

    remote._request = counting
    return sent


# ----------------------------------------------------------- the store op

@pytest.mark.parametrize("native", CORES)
@pytest.mark.parametrize("case, op, status, reason", [
    pytest.param("unbound", _bind("default/p0"), 200, None, id="bound"),
    pytest.param("on-n1", _bind("default/p0"), 409, "already on n1",
                 id="bound-elsewhere"),
    pytest.param("on-n0", _bind("default/p0"), 409, "already on n0",
                 id="bound-to-the-same-node"),
    pytest.param("deleted", _bind("default/p0"), 404, "is gone", id="gone"),
    pytest.param("unbound", _bind("default/p0", uid="default/p0-old"), 409,
                 "was recreated", id="uid-mismatch"),
    pytest.param("unbound", _bind("default/p0", uid=""), 200, None,
                 id="no-uid-given"),
])
def test_memstore_bind_op(native, case, op, status, reason):
    st = _seeded(native, n=1)
    pod, _rv = st.get(PODS, "default/p0")
    if case.startswith("on-"):
        st.update(PODS, "default/p0", pod.with_node(case[3:]))
    elif case == "deleted":
        st.delete(PODS, "default/p0")
    rv0 = st.resource_version
    (res,) = st.bulk(PODS, [op])
    assert res["status"] == status and "object" not in res
    after, rv = st.get(PODS, "default/p0")
    if reason is None:
        assert res["resourceVersion"] == rv == rv0 + 1
        assert after == pod.with_node("n0")
    else:
        assert reason in res["error"] and "bind conflict" in res["error"]
        assert st.resource_version == rv0       # nothing was written


@pytest.mark.parametrize("native", CORES)
def test_a_failed_bind_mid_batch_fails_only_itself(native):
    st = _seeded(native, n=5)
    st.update(PODS, "default/p1", st.get(PODS, "default/p1")[0].with_node("x"))
    st.delete(PODS, "default/p3")
    res = st.bulk(PODS, [_bind(f"default/p{i}") for i in range(5)])
    assert _statuses(res) == [200, 409, 200, 404, 200]
    nodes = {k: p.node_name for k, p in st.list(PODS)[0]}
    assert nodes == {"default/p0": "n0", "default/p1": "x",
                     "default/p2": "n0", "default/p4": "n0"}
    # binds and other ops share the batch and its lock
    res = st.bulk(PODS, [
        {"op": "create", "key": "default/q", "object": make_pod("q")},
        _bind("default/q"),
        {"op": "get", "key": "default/q"},
    ])
    assert _statuses(res) == [201, 200, 200]
    assert res[2]["object"].node_name == "n0"


def test_bind_is_a_pods_op():
    st = MemStore()
    st.create("nodes", "n0", make_node("n0"))
    (res,) = st.bulk("nodes", [_bind("n0")])
    assert res["status"] == 400 and "unknown bulk op" in res["error"]


# ------------------------------------------ the same bytes as get + update

def _bind_by_update(st: MemStore, keys: list) -> None:
    """What ``StoreClient.bulk_bind`` did before the op: a bulk get, then
    a bulk CAS update of the same pods with their node set."""
    gets = st.bulk(PODS, [{"op": "get", "key": k} for k in keys])
    st.bulk(PODS, [
        {"op": "update", "key": k, "object": g["object"].with_node("n0"),
         "expect_rv": g["resourceVersion"]}
        for k, g in zip(keys, gets)
    ])


def _wal_bytes(d: str) -> bytes:
    out = b""
    for _seq, path in list_segments(d):
        with open(path, "rb") as f:
            out += f.read()
    return out


@pytest.mark.parametrize("native", CORES)
def test_bind_op_commits_what_get_and_update_commit(native, tmp_path):
    """The stored objects and revisions, the watch's MODIFIED bodies on both
    wires and the WAL records are byte for byte those of a get + CAS
    update, and so is what recovery brings back."""
    keys = [f"default/p{i}" for i in range(4)]
    stores = []
    for name, bind in (("update", _bind_by_update), ("op", None)):
        d = str(tmp_path / name)
        st = _seeded(native, persistence=d)
        if bind is None:
            assert _statuses(st.bulk(PODS, [_bind(k) for k in keys])) \
                == [200] * 4
        else:
            bind(st, keys)
        stores.append((st, d))
    (old, d_old), (new, d_new) = stores
    assert new.dump() == old.dump()
    assert new.resource_version == old.resource_version
    for wire in ("json", "binary"):
        bodies, cursor = new.events_body_since(PODS, 4, wire)
        assert len(bodies) == 4
        assert (bodies, cursor) == old.events_body_since(PODS, 4, wire)
    assert [e.type for e in new._events_since(PODS, 4)[0]] \
        == ["MODIFIED"] * 4
    assert _wal_bytes(d_new) == _wal_bytes(d_old) != b""
    for st, _d in stores:
        st.close()
    assert MemStore(persistence=d_new, native=native).dump() == \
        MemStore(persistence=d_old, native=native).dump()


@pytest.mark.parametrize("native", CORES)
def test_crash_after_the_wal_append_replays_a_bind_batch_once(
    native, tmp_path,
):
    """Kill at ``wal-post-append-pre-apply`` on the batch's SECOND bind: the
    first was applied, the second logged and not applied, the rest never
    reached the log. Recovery binds the first two exactly once, and the
    recovered store refuses a second bind of either."""
    d = str(tmp_path / "wal")
    st = _seeded(native, n=4, persistence=d)
    pre_rv = st.resource_version
    fp.arm("wal-post-append-pre-apply", at_hit=2)
    with pytest.raises(fp.CrashPoint):
        st.bulk(PODS, [_bind(f"default/p{i}") for i in range(4)])
    assert fp.fired() == ("wal-post-append-pre-apply",)
    del st                                  # the process is dead
    fp.reset()
    st2 = MemStore(persistence=d, native=native)
    assert st2.resource_version == pre_rv + 2
    assert {k: p.node_name for k, p in st2.list(PODS)[0]} == {
        "default/p0": "n0", "default/p1": "n0",
        "default/p2": "", "default/p3": "",
    }
    res = st2.bulk(PODS, [_bind(f"default/p{i}") for i in range(4)])
    assert _statuses(res) == [409, 409, 200, 200]
    assert st2.resource_version == pre_rv + 4
    st2.close()


# ------------------------------------------------------------ the apiserver

def _server(native, path: str, wire: str) -> APIServer:
    """An apiserver as ``kubetpu apiserver`` builds it; ``sequential``
    puts a ResourceQuota in the pods' namespace, which engages quota
    admission for the batch."""
    st = MemStore(native=native)
    registry = Registry()
    install_quota_admission(registry, st)
    if path == "sequential":
        q = _quota("q", 100)
        st.create(RESOURCE_QUOTAS, q.key, q)
    return APIServer(st, registry=registry, wire=wire).start()


@pytest.mark.parametrize("native", CORES)
@pytest.mark.parametrize("path", ["one_lock", "sequential"])
@pytest.mark.parametrize("wire", ["binary", "json"])
def test_apiserver_serves_the_bind_op(native, path, wire):
    srv = _server(native, path, wire)
    try:
        remote = RemoteStore(srv.url)
        keys = [f"q/p{i}" for i in range(5)]
        res = remote.bulk(PODS, [
            {"op": "create", "key": k,
             "object": make_pod(k[2:], namespace="q")}
            for k in keys
        ])
        assert _statuses(res) == [201] * 5
        before = {k: srv.store.get(PODS, k)[0] for k in keys}
        remote.update(PODS, keys[1], before[keys[1]].with_node("n1"))
        remote.delete(PODS, keys[3])
        sent = _count_requests(remote)
        res = remote.bulk(PODS, [
            _bind(keys[0]), _bind(keys[1]), _bind(keys[2], uid="old"),
            _bind(keys[3]), _bind(keys[4]),
            {"op": "bind", "key": keys[2], "node": ""},          # invalid
        ])
        assert sent == [("POST", "/apis/pods:bulk")]
        assert _statuses(res) == [200, 409, 409, 404, 200, 422]
        assert all(r.get("object") is None for r in res)
        assert res[0]["resourceVersion"] == srv.store.get(PODS, keys[0])[1]
        # the pod as stored, its node set: nothing else moved
        for k in (keys[0], keys[4]):
            assert srv.store.get(PODS, k)[0] == before[k].with_node("n0")
        assert srv.store.get(PODS, keys[2])[0] == before[keys[2]]
        assert (_binds(srv, "bound"), _binds(srv, "conflict"),
                _binds(srv, "gone")) == (2, 2, 1)
        assert _bulk_ops(srv, path) == 11
        assert _bulk_ops(
            srv, "one_lock" if path == "sequential" else "sequential"
        ) == 0
        assert 'apiserver_pod_binds_total{result="bound"} 2' \
            in srv.metrics.expose()
        assert remote.wire_codec == wire
    finally:
        srv.close()


@pytest.mark.parametrize("path", ["one_lock", "sequential"])
def test_bind_span_links_the_pod(path):
    """The BULK span carries the bound pods' trace ids, as an update's
    does: on the one-lock pass read after the commit, on the sequential
    chain from the update it admits."""
    import time

    srv = _server(None, path, "binary")
    try:
        remote = RemoteStore(srv.url)
        remote.create(PODS, "q/a", make_pod("a", namespace="q"))
        tid = srv.store.get(PODS, "q/a")[0].trace_id
        assert tid
        assert _statuses(remote.bulk(PODS, [_bind("q/a")])) == [200]
        deadline = time.monotonic() + 5.0
        while True:
            spans = [s for s in srv.tracer.recent(10)
                     if s.name == "apiserver.BULK"]
            if spans or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert spans[-1].attrs["pod_traces"] == [tid]
        assert spans[-1].attrs["path"] == path
    finally:
        srv.close()


# -------------------------------------------------------------- the client

@pytest.mark.parametrize("wire", ["binary", "json"])
def test_store_client_binds_a_cycle_in_one_request(wire):
    srv = APIServer(wire=wire).start()
    try:
        remote = RemoteStore(srv.url)
        pods = [make_pod(f"p{i}") for i in range(6)]
        for p in pods:
            remote.create(PODS, f"default/{p.name}", p)
        client = StoreClient(remote)
        sent = _count_requests(remote)
        errs = client.bulk_bind([(p, f"n{i % 2}") for i, p in enumerate(pods)])
        assert errs == [None] * 6
        assert sent == [("POST", "/apis/pods:bulk")]
        assert _bulk_ops(srv, "one_lock") == 6
        assert _binds(srv, "bound") == 6
        assert {k: p.node_name for k, p in remote.list(PODS)[0]} == {
            f"default/p{i}": f"n{i % 2}" for i in range(6)
        }
        # the single bind is a one-op batch: the same refusal, raised
        with pytest.raises(ConflictError, match="already on n0") as e:
            client.bind(pods[0], "n0")
        assert is_bind_conflict(e.value)
        errs = client.bulk_bind([(pods[1], "n1"), (pods[2], "n1")])
        assert all(isinstance(e, ConflictError) for e in errs)
        assert _binds(srv, "conflict") == 3
        assert client._bind_op
    finally:
        srv.close()


def test_client_falls_back_to_get_and_update_against_a_server_without_the_op(
    monkeypatch,
):
    """A server that answers the op 400 (the bulk verb as it was before
    ISSUE 39) binds nothing by that answer: the client binds the batch as a
    bulk get and a bulk CAS update, and keeps to that from then on; every
    pod is bound, and the refusals are those of the op."""
    monkeypatch.setattr(
        server_mod, "_BULK_VERBS",
        ("create", "update", "patch", "delete", "get"),
    )
    srv = APIServer().start()
    try:
        remote = RemoteStore(srv.url)
        pods = [make_pod(f"p{i}") for i in range(6)]
        for p in pods:
            remote.create(PODS, f"default/{p.name}", p)
        client = StoreClient(remote)
        ops0 = _bulk_ops(srv, "one_lock")
        assert client.bulk_bind([(p, "n0") for p in pods[:3]]) == [None] * 3
        assert not client._bind_op
        # the refused op batch, then a get and an update of three pods
        assert _bulk_ops(srv, "one_lock") - ops0 == 3 + 3 + 3
        sent = _count_requests(remote)
        errs = client.bulk_bind([(p, "n0") for p in pods[2:]])
        assert len(sent) == 2
        assert isinstance(errs[0], ConflictError) and errs[1:] == [None] * 3
        remote.create(PODS, "default/late", make_pod("late"))
        client.bind(make_pod("late"), "n1")
        with pytest.raises(ConflictError, match="already on n1"):
            client.bind(make_pod("late"), "n1")
        assert all(p.node_name for _k, p in remote.list(PODS)[0])
        assert _binds(srv, "bound") == 0      # no op was ever served
    finally:
        srv.close()


def test_client_over_a_store_without_the_bulk_verb_gets_and_updates():
    class _NoBulk:
        def __init__(self, st):
            self._st = st
            self.get, self.update = st.get, st.update

    st = _seeded(False, n=2)
    client = StoreClient(_NoBulk(st))
    client.bind(make_pod("p0"), "n0")
    with pytest.raises(ConflictError, match="already on n0"):
        client.bind(make_pod("p0"), "n0")
    with pytest.raises(KeyError, match="is gone"):
        client.bind(make_pod("gone"), "n0")
    with pytest.raises(NotImplementedError):
        client.bulk_bind([(make_pod("p1"), "n0")])
    assert st.get(PODS, "default/p0")[0].node_name == "n0"


# ------------------------------------------------------------- federation

def test_race_mode_loser_same_node_bind_is_a_conflict():
    """One node: both replicas of a race-mode federation choose it for
    every pod, so each loser's bind names the node the winner bound. It is
    a conflict, never a second win: every pod bound once, a conflict for
    each."""
    from .test_federation import FakeClock, bound_pods, make_federation

    store = MemStore()
    store.create("nodes", "n0", make_node("n0", cpu_milli=8000,
                                          memory=32 * 1024**3))
    for j in range(6):
        store.create(PODS, f"default/p{j}",
                     make_pod(f"p{j}", cpu_milli=100, creation_index=j))
    fed, clock = make_federation(store, replicas=2, mode="race",
                                 clock=FakeClock())
    fed.start()
    try:
        fed.run_until_idle(max_rounds=60, advance_clock=clock.advance)
        assert bound_pods(store) == {f"default/p{j}": "n0" for j in range(6)}
        assert fed.bound() == 6
        assert fed.conflicts() == 6
    finally:
        fed.close()
    # and the client's refusal of a same-node bind, classified
    a, b = StoreClient(store), StoreClient(store)
    store.create(PODS, "default/x", make_pod("x"))
    a.bind(make_pod("x"), "n0")
    with pytest.raises(ConflictError) as e:
        b.bind(make_pod("x"), "n0")
    assert is_bind_conflict(e.value)
