"""The pods ``:bulk`` verb and quota admission (ISSUE 31): the admission
chain tells the verb whether it ENGAGES for a batch, from what the store
holds, and a batch it does not engage for takes the one-lock pass.

What is held here: with a ResourceQuota in the namespace ``:bulk`` answers
exactly as the single verbs do (status for status, the same store, ``hard``
never passed under concurrent posts, a quota committed before a batch's
first write enforced on all of it); without one the batch pays one store
lock and one watch wake-up; the decision follows the store with no restart;
the per-kind count it rests on is kept by both store cores through snapshot
loads, WAL recovery and a follower's apply; and quota admission on a store
with no quota walks no list.
"""

import sys
import threading
import time

import pytest

pytest.importorskip("jax")

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.apiserver import APIServer, RemoteStore
from kubetpu.apiserver.admission import AdmissionDenied, Registry
from kubetpu.client.informers import NODES, PODS
from kubetpu.controllers import install_quota_admission
from kubetpu.controllers.resourcequota import (
    RESOURCE_QUOTAS,
    quota_admission,
    quota_engages,
)
from kubetpu.store import MemStore
from kubetpu.store.memstore import ConflictError

#: MemStore(native=...) per core: False is what KUBETPU_NO_NATIVE=1 runs,
#: None the native core when it builds
from .test_wal import CORES


def _quota(namespace: str, pods: int, name: str = "caps") -> t.ResourceQuota:
    return t.ResourceQuota(
        name=name, namespace=namespace, hard=(("pods", pods),),
    )


def _quota_server(native=None, quotas=()) -> APIServer:
    """An apiserver as ``kubetpu apiserver`` builds it: quota admission
    installed on every one, whether or not a quota exists."""
    st = MemStore(native=native)
    registry = Registry()
    install_quota_admission(registry, st)
    for q in quotas:
        st.create(RESOURCE_QUOTAS, q.key, q)
    return APIServer(st, registry=registry).start()


def _bulk_ops(srv: APIServer, path: str, resource: str = PODS) -> int:
    return int(srv.metrics.bulk_ops.labels(resource, path).value)


def _count_wakeups(store: MemStore) -> list:
    """Every ``notify_all`` of the store's condition from here on (one is
    one wake-up of every watcher's long-poll)."""
    calls: list = []
    real = store._lock.notify_all

    def counting():
        calls.append(1)
        real()

    store._lock.notify_all = counting
    return calls


def _pod(name: str, ns: str, **kw):
    return make_pod(name, namespace=ns, **kw)


# ----------------------------------------------- equivalence under a quota

def _ops_over_hard():
    """Five creates against hard pods=3: the last two are refused."""
    return [
        {"op": "create", "key": f"q/p{i}", "object": _pod(f"p{i}", "q")}
        for i in range(5)
    ]


def _ops_mixed():
    """Every verb, a duplicate create, an upsert that the quota counts, an
    invalid pod, a bind, a delete that frees a place, an absent key."""
    return [
        {"op": "create", "key": "q/a", "object": _pod("a", "q")},
        {"op": "create", "key": "q/a", "object": _pod("a", "q")},     # 409
        {"op": "update", "key": "q/up", "object": _pod("up", "q")},   # upsert
        {"op": "create", "key": "q/bad",
         "object": _pod("bad", "q", priority=2**31)},                 # 422
        {"op": "create", "key": "q/over", "object": _pod("over", "q")},  # 403
        {"op": "patch", "key": "q/a",
         "object": _pod("a", "q").with_node("n0")},                   # a bind
        {"op": "delete", "key": "q/up"},
        {"op": "create", "key": "q/again", "object": _pod("again", "q")},
        {"op": "get", "key": "q/a"},
        {"op": "get", "key": "q/gone"},                               # 404
        {"op": "delete", "key": "q/gone"},                            # 404
    ]


def _ops_two_namespaces():
    """One batch over a namespace with a quota and one without: the quota
    engages for the WHOLE batch, and only its own namespace is capped."""
    return [
        {"op": "create", "key": f"{ns}/p{i}", "object": _pod(f"p{i}", ns)}
        for i in range(3) for ns in ("q", "free")
    ]


def _through_single_verbs(remote: RemoteStore, ops: list) -> list[int]:
    """The same ops, one request each, as the statuses ``:bulk`` reports."""
    statuses = []
    for op in ops:
        verb, key = op["op"], op["key"]
        try:
            if verb == "create":
                remote.create(PODS, key, op["object"])
                statuses.append(201)
            elif verb in ("update", "patch"):
                remote.update(PODS, key, op["object"])
                statuses.append(200)
            elif verb == "delete":
                remote.delete(PODS, key)
                statuses.append(200)
            else:
                statuses.append(
                    200 if remote.get(PODS, key)[0] is not None else 404
                )
        except ConflictError:
            statuses.append(409)
        except KeyError:
            statuses.append(404)
        except ValueError:
            statuses.append(422)
        except PermissionError:
            statuses.append(403)
    return statuses


def _stored(srv: APIServer) -> dict:
    """The store's pods with the ingest stamp (a clock and a random id)
    taken off: what two servers given the same writes must agree on."""
    import dataclasses

    return {
        key: dataclasses.replace(pod, trace_id="", ingest_ts=0.0)
        for key, pod in srv.store.list(PODS)[0]
    }


@pytest.mark.parametrize("native", CORES)
@pytest.mark.parametrize("make_ops, hard, expect", [
    pytest.param(_ops_over_hard, 3, [201, 201, 201, 403, 403],
                 id="creates-over-hard"),
    pytest.param(_ops_mixed, 2,
                 [201, 409, 200, 422, 403, 200, 200, 201, 200, 404, 404],
                 id="every-verb"),
    pytest.param(_ops_two_namespaces, 2, [201, 201, 201, 201, 403, 201],
                 id="two-namespaces"),
])
def test_bulk_equals_single_verbs_under_a_quota(native, make_ops, hard,
                                                expect):
    """For a namespace with a quota, the same ops through ``:bulk`` and
    through the single verbs give the same statuses and the same store, on
    both store cores; the batch takes the sequential chain, all of it."""
    bulk_srv = _quota_server(native, [_quota("q", hard)])
    single_srv = _quota_server(native, [_quota("q", hard)])
    try:
        ops = make_ops()
        res = RemoteStore(bulk_srv.url).bulk(PODS, ops)
        bulk_statuses = [r["status"] for r in res]
        single_statuses = _through_single_verbs(
            RemoteStore(single_srv.url), make_ops()
        )
        assert bulk_statuses == expect
        assert single_statuses == expect
        assert _stored(bulk_srv) == _stored(single_srv)
        assert bulk_srv.store.native == single_srv.store.native == (
            native is None
        )
        assert _bulk_ops(bulk_srv, "sequential") == len(ops)
        assert _bulk_ops(bulk_srv, "one_lock") == 0
    finally:
        bulk_srv.close()
        single_srv.close()


@pytest.mark.parametrize("native", CORES)
def test_concurrent_bulk_posts_never_pass_hard(native):
    """Eight clients post batches of four into one namespace whose quota
    holds ten pods: ten are stored, whatever the interleaving."""
    srv = _quota_server(native, [_quota("q", 10)])
    statuses: list[int] = []

    def post(i: int) -> None:
        res = RemoteStore(srv.url).bulk(PODS, [
            {"op": "create", "key": f"q/p{i}-{j}",
             "object": _pod(f"p{i}-{j}", "q")}
            for j in range(4)
        ])
        statuses.extend(r["status"] for r in res)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # hand the interpreter over often
    try:
        threads = [threading.Thread(target=post, args=(i,)) for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
        stored = len(srv.store.list(PODS)[0])
    finally:
        sys.setswitchinterval(switch)
        srv.close()
    assert stored == 10, f"quota overflow: {stored} pods past hard=10"
    assert statuses.count(201) == 10 and statuses.count(403) == 22


# ------------------------------------------------------- the one-lock pass

@pytest.mark.parametrize("native", CORES)
def test_quota_free_batch_takes_one_lock_beside_a_quota_elsewhere(native):
    """A batch in a namespace without a quota takes the one-lock pass
    while ANOTHER namespace holds one: the counter's ``one_lock`` rises by
    the batch's ops, the watchers are woken once, and the BULK span says
    so; the binds of the same pods go the same way."""
    srv = _quota_server(native, [_quota("capped", 1)])
    try:
        remote = RemoteStore(srv.url)
        wakeups = _count_wakeups(srv.store)
        res = remote.bulk(PODS, [
            {"op": "create", "key": f"free/p{i}",
             "object": _pod(f"p{i}", "free")}
            for i in range(6)
        ])
        assert [r["status"] for r in res] == [201] * 6
        assert len(wakeups) == 1
        assert _bulk_ops(srv, "one_lock") == 6
        assert _bulk_ops(srv, "sequential") == 0
        res = remote.bulk(PODS, [
            {"op": "patch", "key": f"free/p{i}",
             "object": _pod(f"p{i}", "free").with_node("n0"),
             "resourceVersion": r["resourceVersion"]}
            for i, r in enumerate(res)
        ])
        assert [r["status"] for r in res] == [200] * 6
        assert len(wakeups) == 2
        assert _bulk_ops(srv, "one_lock") == 12
        # the capped namespace beside it still is: one wake-up an op there
        res = remote.bulk(PODS, [
            {"op": "create", "key": f"capped/p{i}",
             "object": _pod(f"p{i}", "capped")}
            for i in range(2)
        ])
        assert [r["status"] for r in res] == [201, 403]
        assert _bulk_ops(srv, "sequential") == 2
        assert 'apiserver_bulk_ops_total{resource="pods",path="one_lock"} 12' \
            in srv.metrics.expose()
        # a span is recorded after its reply is sent: wait for the third
        deadline = time.monotonic() + 5.0
        while True:
            paths = [
                s.attrs["path"] for s in srv.tracer.recent(10)
                if s.name == "apiserver.BULK"
            ]
            if len(paths) == 3 or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert paths == ["one_lock", "one_lock", "sequential"]
    finally:
        srv.close()


def test_engagement_follows_the_store_without_a_restart():
    """No quota: one lock. A quota is created over REST: the next batch in
    its namespace is held to it. It is deleted: one lock again. The same
    server and registry throughout."""
    srv = _quota_server()
    try:
        remote = RemoteStore(srv.url)

        def batch(tag: str, n: int = 3) -> list[int]:
            return [r["status"] for r in remote.bulk(PODS, [
                {"op": "create", "key": f"ns/{tag}{i}",
                 "object": _pod(f"{tag}{i}", "ns")}
                for i in range(n)
            ])]

        assert batch("a") == [201, 201, 201]
        assert (_bulk_ops(srv, "one_lock"), _bulk_ops(srv, "sequential")) \
            == (3, 0)
        q = _quota("ns", 4)
        remote.create(RESOURCE_QUOTAS, q.key, q)
        assert batch("b") == [201, 403, 403]        # 3 stand, hard is 4
        assert (_bulk_ops(srv, "one_lock"), _bulk_ops(srv, "sequential")) \
            == (3, 3)
        remote.delete(RESOURCE_QUOTAS, q.key)
        assert batch("c") == [201, 201, 201]
        assert (_bulk_ops(srv, "one_lock"), _bulk_ops(srv, "sequential")) \
            == (6, 3)
        # a quota with no `hard` caps nothing and engages nothing
        empty = t.ResourceQuota(name="empty", namespace="ns")
        remote.create(RESOURCE_QUOTAS, empty.key, empty)
        assert batch("d") == [201, 201, 201]
        assert _bulk_ops(srv, "one_lock") == 9
    finally:
        srv.close()


@pytest.mark.parametrize("native", CORES)
def test_quota_committed_before_the_storage_pass_is_enforced(native):
    """Guarantee 4, interleaved by hand: the quota is committed AFTER the
    batch was decoded and validated and BEFORE its storage pass takes the
    store lock. The guard reads engagement under that lock, so the whole
    batch goes down the sequential chain and ``hard`` holds for every pod
    of it; an answer taken at the top of the request would have stored all
    four."""
    srv = _quota_server(native)
    real_bulk = srv.store.bulk
    seen = []

    def quota_lands_first(kind, ops, guard=None):
        # the request has prepared its ops; another client's quota commits
        # now, before this batch's first write
        q = _quota("q", 2)
        srv.store.create(RESOURCE_QUOTAS, q.key, q)
        seen.append(len(ops))
        return real_bulk(kind, ops, guard=guard)

    srv.store.bulk = quota_lands_first
    try:
        res = RemoteStore(srv.url).bulk(PODS, [
            {"op": "create", "key": f"q/p{i}", "object": _pod(f"p{i}", "q")}
            for i in range(4)
        ])
        assert seen == [4]                  # it had chosen the one-lock pass
        assert [r["status"] for r in res] == [201, 201, 403, 403]
        assert len(srv.store.list(PODS)[0]) == 2
        assert _bulk_ops(srv, "sequential") == 4
        assert _bulk_ops(srv, "one_lock") == 0
    finally:
        srv.close()


def test_memstore_bulk_guard_is_asked_under_the_applying_lock():
    """``MemStore.bulk(guard=...)``: asked once, with the store lock held
    by this thread (so no write can land between the answer and the
    batch); False applies nothing and returns None."""
    st = MemStore()
    held = []

    def guard():
        held.append(st._lock._is_owned())
        return len(held) > 1

    ops = [{"op": "create", "key": "default/p", "object": make_pod("p")}]
    wakeups = _count_wakeups(st)
    assert st.bulk(PODS, ops, guard=guard) is None
    assert st.count(PODS) == 0 and st.resource_version == 0 and not wakeups
    assert [r["status"] for r in st.bulk(PODS, ops, guard=guard)] == [201]
    assert held == [True, True] and st.count(PODS) == 1


def test_hook_without_a_predicate_keeps_the_sequential_chain():
    """A hook or write lock registered as before, with no ``engages``,
    engages for every batch: the webhook shape loses nothing."""
    for register in ("add_validating_hook", "add_mutating_hook",
                     "add_write_lock"):
        reg = Registry()
        calls = []

        def fn(kind, key, obj, last):
            calls.append(key)

        getattr(reg, register)(fn, kinds=(PODS,))
        assert reg.has_dynamic_admission(PODS)
        assert reg.has_dynamic_admission(PODS, [make_pod("p")])
        assert not reg.has_dynamic_admission(NODES)
        srv = APIServer(registry=reg).start()
        try:
            res = RemoteStore(srv.url).bulk(PODS, [
                {"op": "create", "key": "default/a", "object": make_pod("a")},
                {"op": "create", "key": "default/b", "object": make_pod("b")},
            ])
            assert [r["status"] for r in res] == [201, 201]
            assert calls == ["default/a", "default/b"]
            assert _bulk_ops(srv, "sequential") == 2
            assert _bulk_ops(srv, "one_lock") == 0
        finally:
            srv.close()


def test_registry_asks_a_predicate_only_of_a_batch():
    """``has_dynamic_admission``: a hook with a predicate does not engage
    for the empty batch (the verb's first, cheap question), and is asked
    of the objects otherwise; a veto it would have raised is the
    predicate's to announce."""
    reg = Registry()
    asked = []

    def deny_kube_system(kind, key, obj, old):
        if obj.namespace == "kube-system":
            raise AdmissionDenied("kube-system is read-only here")

    def engages(kind, objs):
        asked.append(len(objs))
        return any(o.namespace == "kube-system" for o in objs)

    reg.add_validating_hook(deny_kube_system, kinds=(PODS,), engages=engages)
    assert not reg.has_dynamic_admission(PODS) and asked == []
    assert not reg.has_dynamic_admission(PODS, [make_pod("a")])
    assert reg.has_dynamic_admission(
        PODS, [make_pod("a"), _pod("b", "kube-system")]
    )
    assert asked == [1, 2]
    # a write lock under the SAME predicate (as quota admission installs
    # its pair): one batch, one question
    reg.add_write_lock(lambda kind, key, obj, verb: None, kinds=(PODS,),
                       engages=engages)
    assert not reg.has_dynamic_admission(PODS, [make_pod("a")])
    assert asked == [1, 2, 1]
    srv = APIServer(registry=reg).start()
    try:
        remote = RemoteStore(srv.url)
        res = remote.bulk(PODS, [
            {"op": "create", "key": "default/a", "object": make_pod("a")},
        ])
        assert [r["status"] for r in res] == [201]
        res = remote.bulk(PODS, [
            {"op": "create", "key": "default/b", "object": make_pod("b")},
            {"op": "create", "key": "kube-system/c",
             "object": _pod("c", "kube-system")},
        ])
        assert [r["status"] for r in res] == [201, 403]
        assert (_bulk_ops(srv, "one_lock"), _bulk_ops(srv, "sequential")) \
            == (1, 2)
    finally:
        srv.close()


# ------------------------------------------------------ the per-kind count

def _fill(st: MemStore) -> None:
    """Three kinds; an upsert, an update in place and a delete among them."""
    st.create(RESOURCE_QUOTAS, "q/caps", _quota("q", 3))
    for i in range(4):
        st.create(PODS, f"q/p{i}", _pod(f"p{i}", "q"))
    st.update(PODS, "q/up", _pod("up", "q"))                  # upsert: +1
    st.update(PODS, "q/p0", _pod("p0", "q").with_node("n0"))  # in place
    st.delete(PODS, "q/p3")
    st.create(NODES, "n0", make_node("n0"))


_FILLED = {PODS: 4, RESOURCE_QUOTAS: 1, NODES: 1, "events": 0}


def _counts(st: MemStore) -> dict:
    return {kind: st.count(kind) for kind in _FILLED}


@pytest.mark.parametrize("native", CORES)
def test_count_follows_every_write(native):
    st = MemStore(native=native)
    assert _counts(st) == dict.fromkeys(_FILLED, 0)
    _fill(st)
    assert _counts(st) == _FILLED
    assert all(
        st.count(kind) == len(st.list(kind)[0]) for kind in _FILLED
    )
    st.bulk(PODS, [
        {"op": "create", "key": "q/b0", "object": _pod("b0", "q")},
        {"op": "create", "key": "q/b0", "object": _pod("b0", "q")},   # 409
        {"op": "delete", "key": "q/p1"},
        {"op": "delete", "key": "q/p1"},                              # 404
    ])
    assert st.count(PODS) == 4
    st.delete(RESOURCE_QUOTAS, "q/caps")
    assert st.count(RESOURCE_QUOTAS) == 0


@pytest.mark.parametrize("native", CORES)
@pytest.mark.parametrize("how", ["load_snapshot", "wal_recovery",
                                 "wal_snapshot_and_tail", "follower_apply"])
def test_count_survives(native, how, tmp_path):
    """The count is the store's, not the process's: it comes back from a
    snapshot load, from a WAL replay (log alone, and compaction snapshot
    plus tail) and on a follower fed the leader's records."""
    if how == "load_snapshot":
        src = MemStore(native=native)
        _fill(src)
        items, rv = src.dump_with_rv()
        st = MemStore(native=native, follower=True)
        st.load_replica_snapshot(items, rv)
        # and a second load REPLACES the counts, it does not add to them
        st.load_replica_snapshot(items, rv)
    elif how in ("wal_recovery", "wal_snapshot_and_tail"):
        d = str(tmp_path / "wal")
        src = MemStore(persistence=d, native=native)
        _fill(src)
        if how == "wal_snapshot_and_tail":
            src.compact()
            src.create(PODS, "q/late", _pod("late", "q"))
            src.delete(PODS, "q/late")
        src.close()
        st = MemStore(persistence=d, native=native)
        assert st.recovery_info.replayed == (
            2 if how == "wal_snapshot_and_tail" else 9
        )
    else:
        leader = MemStore(native=native)
        _fill(leader)
        records = [
            ({"ADDED": 0, "MODIFIED": 1, "DELETED": 2}[e.type], e.kind,
             e.key, e.obj, e.resource_version)
            for e in leader._events_since(None, 0)[0]
        ]
        st = MemStore(native=native, follower=True)
        assert st.apply_replicated_batch(records[:5]) == 5
        assert st.apply_replicated_batch(records) == len(records) - 5
    assert _counts(st) == _FILLED
    assert st.native == (native is None)
    st.close()


@pytest.mark.parametrize("native", CORES)
def test_quota_admission_without_a_quota_walks_no_list(native):
    """The pin: on a store of N unrelated objects and no ResourceQuota,
    the hook and the engagement predicate never reach the store's list
    walk (``_list_page_locked``, the one seam every list goes through);
    with a quota they do, so the pin can fail."""
    st = MemStore(native=native)
    for i in range(200):
        st.create(NODES, f"n{i}", make_node(f"n{i}"))
        st.create(PODS, f"other/p{i}", _pod(f"p{i}", "other"))
    walks = []
    real = st._list_page_locked

    def counting(*a, **kw):
        walks.append(a[0])
        return real(*a, **kw)

    st._list_page_locked = counting
    hook, engages = quota_admission(st), quota_engages(st)
    pods = [_pod(f"new{i}", "other") for i in range(50)]
    for p in pods:
        hook(PODS, f"other/{p.name}", p, None)
    assert engages(PODS, pods) is False
    assert walks == []
    st.create(RESOURCE_QUOTAS, "other/caps", _quota("other", 200))
    assert engages(PODS, pods) is True
    with pytest.raises(AdmissionDenied, match="exceeded quota caps"):
        hook(PODS, "other/new0", pods[0], None)
    assert walks == [RESOURCE_QUOTAS, RESOURCE_QUOTAS, PODS]
