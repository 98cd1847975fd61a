"""API-plane batching: the bulk REST verb, serialize-once watch fan-out,
the batched informer poll, and the dispatcher's cycle-boundary micro-batches.

Parity contract under test (ISSUE 5): bulk endpoint semantics match the
single-op verbs op-for-op (conflict/admission/404), fullstack scheduling
with the bulk plane on vs off produces identical bindings — including a
mid-batch 409 exercising the partial-failure fallback — and the
serialize-once cache never serves stale bytes after an object update.
"""

import dataclasses
import threading
import time

import pytest

pytest.importorskip("jax")

from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.apiserver import APIServer, RemoteStore
from kubetpu.apiserver.admission import AdmissionDenied, Registry
from kubetpu.client import SchedulerInformers, StoreClient
from kubetpu.client.informers import NODES, PODS
from kubetpu.framework import config as C
from kubetpu.sched import Scheduler
from kubetpu.sched.api_dispatcher import APIDispatcher, BindCall
from kubetpu.store import MemStore
from kubetpu.store.memstore import (
    CompactedError,
    ConflictError,
    bulk_result_error,
)


@pytest.fixture()
def server():
    srv = APIServer().start()
    yield srv
    srv.close()


# ------------------------------------------------------------ the bulk verb

def test_bulk_verb_matches_single_op_semantics():
    """POST /apis/<kind>:bulk — per-op status/resourceVersion/error
    identical to what the single-op verbs produce, including 409 conflict,
    422 validation, 403 admission veto, 404 absence, and 400 malformed-op,
    with surviving ops landing even after a mid-batch failure."""
    reg = Registry()

    def deny_kube_system(kind, key, obj, old):
        if getattr(obj, "namespace", "") == "kube-system":
            raise AdmissionDenied("kube-system is read-only here")

    reg.add_validating_hook(deny_kube_system, kinds=(PODS,))
    srv = APIServer(registry=reg).start()
    try:
        remote = RemoteStore(srv.url)
        rv0 = remote.create(PODS, "default/seed", make_pod("seed"))
        res = remote.bulk(PODS, [
            {"op": "create", "key": "default/a", "object": make_pod("a")},
            {"op": "create", "key": "default/seed",             # exists
             "object": make_pod("seed")},
            {"op": "create", "key": "kube-system/x",            # admission
             "object": make_pod("x", namespace="kube-system")},
            {"op": "create", "key": "default/bad",              # validation
             "object": dataclasses.replace(
                 make_pod("bad"), requests=(("cpu", -5),))},
            {"op": "update", "key": "default/seed",             # CAS miss
             "object": make_pod("seed"), "expect_rv": rv0 + 999},
            {"op": "update", "key": "default/seed",             # CAS hit
             "object": dataclasses.replace(make_pod("seed"), priority=3),
             "expect_rv": rv0},
            {"op": "delete", "key": "default/missing"},         # absent
            {"op": "get", "key": "default/a"},
            {"op": "frob", "key": "default/a"},                 # bad op
        ])
        statuses = [r["status"] for r in res]
        assert statuses == [201, 409, 403, 422, 409, 200, 404, 200, 400]
        # per-op error mapping equals the single-op exception surface
        assert isinstance(bulk_result_error(res[1]), ConflictError)
        assert isinstance(bulk_result_error(res[2]), PermissionError)
        assert isinstance(bulk_result_error(res[3]), ValueError)
        assert isinstance(bulk_result_error(res[4]), ConflictError)
        assert isinstance(bulk_result_error(res[6]), KeyError)
        assert isinstance(bulk_result_error(res[8]), ValueError)
        # surviving ops landed despite the mid-batch failures
        assert srv.store.get(PODS, "default/a")[0] is not None
        assert srv.store.get(PODS, "default/seed")[0].priority == 3
        assert srv.store.get(PODS, "kube-system/x")[0] is None
        # the decoded get result round-trips the object
        assert res[7]["object"].name == "a"
        # single-op verbs agree with the bulk statuses they mirror
        with pytest.raises(ConflictError):
            remote.create(PODS, "default/seed", make_pod("seed"))
        with pytest.raises(PermissionError):
            remote.create(PODS, "kube-system/x",
                          make_pod("x", namespace="kube-system"))
        with pytest.raises(KeyError):
            remote.delete(PODS, "default/missing")
    finally:
        srv.close()


def test_bulk_verb_sequential_path_for_dynamic_admission():
    """A kind with dynamic admission (a usage-counting validator — the
    quota shape) must run bulk ops through the single-verb chain: op 2's
    admission sees op 1's write, so a batch cannot overshoot a limit the
    sequential verbs would enforce."""
    reg = Registry()

    def one_pod_per_namespace(kind, key, obj, old):
        if old is not None:
            return
        ns = getattr(obj, "namespace", "")
        existing, _rv = _srv.store.list(kind)
        if sum(1 for _k, p in existing if p.namespace == ns) >= 1:
            raise AdmissionDenied(f"namespace {ns} is at its pod quota")

    reg.add_validating_hook(one_pod_per_namespace, kinds=(PODS,))
    _srv = APIServer(registry=reg).start()
    try:
        remote = RemoteStore(_srv.url)
        res = remote.bulk(PODS, [
            {"op": "create", "key": "q/a",
             "object": make_pod("a", namespace="q")},
            {"op": "create", "key": "q/b",             # second in-batch op
             "object": make_pod("b", namespace="q")},  # must see the first
        ])
        assert [r["status"] for r in res] == [201, 403]
        assert _srv.store.get(PODS, "q/b")[0] is None
    finally:
        _srv.close()


def test_memstore_bulk_applies_under_one_lock():
    """The in-process store's bulk surface: same op/result contract as the
    REST verb (the dispatcher's in-process deployment shape)."""
    st = MemStore()
    rv0 = st.create(PODS, "default/p", make_pod("p"))
    res = st.bulk(PODS, [
        {"op": "get", "key": "default/p"},
        {"op": "update", "key": "default/p",
         "object": make_pod("p").with_node("n0"), "expect_rv": rv0},
        {"op": "update", "key": "default/p",
         "object": make_pod("p"), "expect_rv": rv0},    # now stale
        {"op": "create", "key": "default/q", "object": make_pod("q")},
        {"op": "delete", "key": "default/q"},
        {"op": "delete", "key": "default/q"},           # already gone
    ])
    assert [r["status"] for r in res] == [200, 200, 409, 201, 200, 404]
    assert res[0]["object"].name == "p"
    assert st.get(PODS, "default/p")[0].node_name == "n0"
    # the batch's watch events are ordinary store events
    events, _ = st._events_since(PODS, rv0)
    assert [e.type for e in events] == ["MODIFIED", "ADDED", "DELETED"]


# --------------------------------------- serialize-once watch fan-out

def test_serialize_once_watch_cache_shared_and_never_stale(server):
    remote = RemoteStore(server.url)
    remote.create(PODS, "default/w", make_pod("w", priority=1))
    w1 = remote.watch(PODS, 0)
    evs1 = w1.poll()
    assert [e.obj.priority for e in evs1] == [1]
    misses0, hits0 = server.event_cache.misses, server.event_cache.hits
    assert misses0 >= 1
    # a second watcher replaying the same event rides the cached bytes
    w2 = remote.watch(PODS, 0)
    evs2 = w2.poll()
    assert [e.obj.priority for e in evs2] == [1]
    assert server.event_cache.hits > hits0
    assert server.event_cache.misses == misses0
    # an update mints a NEW resourceVersion → new cache entry; both the
    # old ADDED and the new MODIFIED bytes stay correct for a replayer
    cur, rv = remote.get(PODS, "default/w")
    remote.update(PODS, "default/w",
                  dataclasses.replace(cur, priority=9), expect_rv=rv)
    evs = remote.watch(PODS, 0).poll()
    assert [(e.type, e.obj.priority) for e in evs] == [
        ("ADDED", 1), ("MODIFIED", 9),
    ]
    # the live watcher sees only the fresh event, with the fresh body
    evs = w1.poll()
    assert [(e.type, e.obj.priority) for e in evs] == [("MODIFIED", 9)]


def _settled_requests(metrics) -> int:
    """request_total observes in the handler's finally AFTER the response
    bytes reach the client — wait for the count to stop moving before
    snapshotting it."""
    import time

    last = metrics.total_requests()
    deadline = time.monotonic() + 2.0
    quiet = 0
    while time.monotonic() < deadline and quiet < 3:
        time.sleep(0.01)
        now = metrics.total_requests()
        quiet = quiet + 1 if now == last else 0
        last = now
    return last


def test_batched_watch_poll_drains_all_kinds_in_one_request(server):
    remote = RemoteStore(server.url)
    remote.create(NODES, "n0", make_node("n0"))
    rvs = {NODES: server.store.resource_version, PODS: 0}
    remote.create(PODS, "default/p", make_pod("p"))
    remote.create(NODES, "n1", make_node("n1"))
    requests0 = _settled_requests(server.metrics)
    buckets = remote.watch_bulk(rvs)
    # ONE round trip drained both kinds
    assert _settled_requests(server.metrics) - requests0 == 1
    node_events, node_cursor = buckets[NODES]
    pod_events, _ = buckets[PODS]
    assert [e.key for e in node_events] == ["n1"]
    assert [e.key for e in pod_events] == ["default/p"]
    # cursors advance independently; a drained re-poll is empty
    again = remote.watch_bulk({NODES: node_cursor})
    assert again[NODES][0] == []


def test_batched_watch_poll_compaction_is_per_kind():
    small = MemStore(history=4)
    srv = APIServer(small).start()
    try:
        remote = RemoteStore(srv.url)
        remote.create(PODS, "default/p", make_pod("p"))
        for i in range(10):
            remote.update(PODS, "default/p",
                          dataclasses.replace(make_pod("p"), priority=i))
        live_rv = small.resource_version
        buckets = remote.watch_bulk({NODES: 0, PODS: live_rv})
        # the stale cursor 410s ONLY its own bucket; the live one is fine
        assert isinstance(buckets[NODES], CompactedError)
        assert buckets[PODS] == ([], live_rv)
    finally:
        srv.close()


# ------------------------------------------- dispatcher micro-batching

class _RecordingBulkClient:
    def __init__(self, fail_keys=()):
        self.bulk_calls: list[list] = []
        self.single_binds: list[str] = []
        self.fail_keys = set(fail_keys)

    def bulk_bind(self, pairs):
        self.bulk_calls.append(list(pairs))
        return [
            ConflictError("injected")
            if f"{pod.namespace}/{pod.name}" in self.fail_keys else None
            for pod, _node in pairs
        ]

    def bind(self, pod, node_name):
        self.single_binds.append(f"{pod.namespace}/{pod.name}")


def test_dispatcher_flush_micro_batches_one_rpc_per_call_type():
    client = _RecordingBulkClient()
    d = APIDispatcher(client, workers=0, bulk=True)
    done: list = []
    order: list = []
    for i in range(5):
        pre = (lambda i=i: order.append(f"pre-{i}")) if i == 0 else None
        post = (lambda i=i: order.append(f"post-{i}")) if i == 0 else None
        d.add(BindCall(make_pod(f"p{i}"), f"n{i}",
                       on_done=done.append, pre=pre, post=post))
    assert client.bulk_calls == [] and done == []   # window still open
    d.flush()
    # one bulk RPC carried all five binds; hooks ran around the batch
    assert len(client.bulk_calls) == 1
    assert len(client.bulk_calls[0]) == 5
    assert done == [None] * 5
    assert order == ["pre-0", "post-0"]
    stats = d.stats()
    assert stats["batches"] == 1 and stats["batched_calls"] == 5
    assert stats["executed"] == 5 and stats["errors"] == 0
    d.close()


def test_dispatcher_partial_failure_falls_back_per_call():
    client = _RecordingBulkClient(fail_keys={"default/p1"})
    d = APIDispatcher(client, workers=0, bulk=True)
    done: list = []
    for i in range(3):
        d.add(BindCall(make_pod(f"p{i}"), "n0", on_done=done.append))
    d.flush()
    # the failed op re-ran per-call (and succeeded there): no error leaks
    assert client.single_binds == ["default/p1"]
    assert done == [None] * 3
    assert d.stats()["errors"] == 0
    d.close()


def test_dispatcher_extender_owned_bind_stays_per_call():
    client = _RecordingBulkClient()
    owned: list = []
    d = APIDispatcher(client, workers=0, bulk=True)
    d.add(BindCall(make_pod("a"), "n0"))
    d.add(BindCall(make_pod("b"), "n0",
                   bind_fn=lambda pod, node: owned.append(pod.name)))
    d.add(BindCall(make_pod("c"), "n0"))
    d.flush()
    assert owned == ["b"]                       # webhook bind ran itself
    assert [len(c) for c in client.bulk_calls] == [2]
    d.close()


def test_dispatcher_close_flushes_pending_bulk_window():
    """close() must drain the open micro-batch window even with workers=0
    — a pipelined scheduler's final cycle enqueues binds right before
    close, and dropping them would strand assumed pods forever."""
    client = _RecordingBulkClient()
    d = APIDispatcher(client, workers=0, bulk=True)
    done: list = []
    d.add(BindCall(make_pod("a"), "n0", on_done=done.append))
    d.add(BindCall(make_pod("b"), "n0", on_done=done.append))
    d.close()
    assert done == [None, None]
    assert d.stats()["executed"] == 2
    d.close()                                   # idempotent
    d.add(BindCall(make_pod("c"), "n0", on_done=done.append))
    assert done == [None, None, None]           # post-close adds run inline
    assert client.single_binds == ["default/c"]


def test_batched_watch_long_poll_wakes_on_write(server):
    """The long-poll waits on the revision captured AT the drain: a write
    landing right after wakes it well before the timeout."""
    import time

    remote = RemoteStore(server.url)
    rv = server.store.resource_version

    def later():
        time.sleep(0.2)
        MemStore.create(server.store, NODES, "late", make_node("late"))

    threading.Thread(target=later, daemon=True).start()
    t0 = time.monotonic()
    buckets = remote.watch_bulk({NODES: rv}, timeout_s=5.0)
    events, _cursor = buckets[NODES]
    assert [e.key for e in events] == ["late"]
    assert 0.1 < time.monotonic() - t0 < 4.0   # woke on the event


def test_dispatcher_stats_consistent_under_worker_concurrency():
    """The satellite's stats race: executed/errors are read-modify-writes
    from worker threads — with the lock, added == executed exactly."""
    class _SlowClient:
        def bind(self, pod, node_name):
            pass

    d = APIDispatcher(_SlowClient(), workers=4)
    n = 400

    def feed(base):
        for i in range(100):
            d.add(BindCall(make_pod(f"p{base}-{i}"), "n0"))

    threads = [threading.Thread(target=feed, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    d.sync()
    stats = d.stats()
    assert stats["added"] == stats["executed"] == n
    assert stats["errors"] == 0
    # the workers' clock is summed under the same lock, per call type
    (call_type, (busy, cpu)), = d.worker_clock().items()
    assert call_type == "bind" and 0 < cpu <= busy + 0.05
    d.close()


class _TimedBulkClient:
    """A bulk bind that takes 50 ms: asleep (the apiserver's reply) or on
    a core (the encode and decode of 1024 pods)."""

    def __init__(self, how: str) -> None:
        self.how = how

    def bulk_bind(self, pairs):
        end = time.perf_counter() + 0.05
        if self.how == "sleeps":
            time.sleep(0.05)
        while time.perf_counter() < end:
            pass
        return [None] * len(pairs)

    def bind(self, pod, node_name):
        pass


def _bind_a_batch(client, workers, tracer=None):
    d = APIDispatcher(client, workers=workers, bulk=True, tracer=tracer)
    for i in range(4):
        d.add(BindCall(make_pod(f"p{i}"), "n0"))
    d.sync()
    clock = d.worker_clock()
    assert d.stats()["executed"] == 4
    d.close()
    return clock


def test_a_worker_that_waits_reports_busy_far_above_its_cpu():
    (call_type, (busy, cpu)), = _bind_a_batch(
        _TimedBulkClient("sleeps"), workers=2).items()
    assert call_type == "bind"
    assert busy >= 0.05 and cpu < 0.01


def test_a_worker_that_computes_reports_cpu_near_its_busy_time():
    from kubetpu.tracing import Tracer

    for _ in range(3):      # a spin can lose its core on a shared machine
        tr = Tracer()
        busy, cpu = _bind_a_batch(
            _TimedBulkClient("spins"), workers=2, tracer=tr)["bind"]
        if cpu >= 0.8 * busy:
            break
    assert busy >= 0.05 and 0.8 * busy <= cpu <= busy + 1e-3
    # the bulk span the worker records says the same of its API phase
    (span,) = [sp for sp in tr.recent() if sp.name == "api.bind.bulk"]
    assert 0.5 * span.duration_s <= span.attrs["cpu_s"] <= busy + 1e-3


def _worker_series(text: str) -> dict:
    from kubetpu.metrics.textparse import parse_prometheus_text

    pm = parse_prometheus_text(text)
    return {kind: pm.value(
        f"scheduler_api_dispatcher_worker_{kind}_total", call_type="bind")
        for kind in ("seconds", "cpu_seconds")}


@pytest.mark.parametrize("workers", [0, 1])
def test_the_workers_clock_is_on_metrics_and_an_inline_call_is_not(workers):
    """``workers=0``: the batch ran on its caller's thread, inside one of
    the loop's phases, and the loop's clock has it."""
    client = _TimedBulkClient("sleeps")
    assert (_bind_a_batch(client, workers=workers) == {}) == (workers == 0)
    s = Scheduler(client, profile=C.minimal_profile(),
                  dispatcher_workers=workers, bulk=True)
    for i in range(2):
        s.dispatcher.add(BindCall(make_pod(f"p{i}"), "n0"))
    s.dispatcher.sync()
    got = _worker_series(s.metrics_text())
    s.close()
    if workers == 0:
        assert got == {"seconds": None, "cpu_seconds": None}
    else:
        assert got["seconds"] >= 0.05 and 0 <= got["cpu_seconds"] < 0.01


def test_dispatcher_errors_surface_in_scheduler_metrics():
    class _FailingClient:
        def bind(self, pod, node_name):
            raise RuntimeError("boom")

    s = Scheduler(_FailingClient(), profile=C.minimal_profile(),
                  dispatcher_workers=0, bulk=False)
    s.dispatcher.add(BindCall(make_pod("p"), "n0"))
    text = s.metrics_text()
    assert 'scheduler_api_dispatcher_calls{event="errors"} 1' in text
    assert 'scheduler_api_dispatcher_calls{event="executed"} 1' in text
    s.close()


# ------------------------------------------------- fullstack parity

def _run_fullstack(srv, remote, bulk, nodes=6, pods=18):
    """Drive a small fullstack scheduling run; returns {pod key: node}."""
    for i in range(nodes):
        MemStore.create(srv.store, NODES, f"n{i}",
                        make_node(f"n{i}", cpu_milli=4000))
    for j in range(pods):
        MemStore.create(
            srv.store, PODS, f"default/p{j}",
            make_pod(f"p{j}", cpu_milli=100, creation_index=j),
        )
    sched = Scheduler(StoreClient(remote), profile=C.minimal_profile(),
                      dispatcher_workers=0, bulk=bulk)
    informers = SchedulerInformers(remote, sched, bulk=bulk)
    informers.start()
    for _ in range(20):
        informers.pump()
        sched.schedule_batch()
        sched.dispatcher.sync()
        sched._drain_bind_completions()
        items, _ = remote.list(PODS)
        if len(items) == pods and all(p.node_name for _, p in items):
            break
    informers.pump()       # deliver the final binds' confirmation echoes
    sched.schedule_batch()
    sched.close()
    items, _ = remote.list(PODS)
    assert not sched.cache._assumed        # every bind echoed back
    return {k: p.node_name for k, p in items}, sched


def test_fullstack_bulk_on_off_identical_bindings():
    srv_a = APIServer().start()
    srv_b = APIServer().start()
    try:
        bound_bulk, sched_bulk = _run_fullstack(
            srv_a, RemoteStore(srv_a.url), bulk=True)
        bound_single, _ = _run_fullstack(
            srv_b, RemoteStore(srv_b.url), bulk=False)
        assert len(bound_bulk) == 18
        assert all(bound_bulk.values())
        assert bound_bulk == bound_single
        # the bulk run really batched (binds rode bulk RPCs)
        assert sched_bulk.dispatcher.stats()["batched_calls"] > 0
    finally:
        srv_a.close()
        srv_b.close()


def test_fullstack_mid_batch_conflict_falls_back_and_still_binds():
    """A mid-batch 409 must fail only that op; the dispatcher's per-call
    fallback re-binds it against fresh state, so the final bindings equal
    the single-op run's. The interposer answers one op of a cycle's bind
    batch 409 in place of the server; and an interfering writer bumps
    another pod's revision first, which a bind op (no revision in it) no
    longer conflicts on."""
    class _InterposingStore(RemoteStore):
        def __init__(self, url, raw_store):
            super().__init__(url)
            self._raw = raw_store
            self.injected = False

        def bulk(self, kind, ops):
            if (
                self.injected or kind != PODS or len(ops) <= 2
                or ops[0]["op"] != "bind"
            ):
                return super().bulk(kind, ops)
            cur, _rv = MemStore.get(self._raw, PODS, ops[0]["key"])
            MemStore.update(
                self._raw, PODS, ops[0]["key"],
                dataclasses.replace(cur, priority=cur.priority + 1),
            )
            held = len(ops) // 2
            res = super().bulk(kind, ops[:held] + ops[held + 1:])
            res.insert(held, {
                "status": 409, "resourceVersion": 0,
                "error": "bind conflict: held back by the interposer",
            })
            self.injected = True
            return res

    srv_a = APIServer().start()
    srv_b = APIServer().start()
    try:
        store = _InterposingStore(srv_a.url, srv_a.store)
        bound_conflict, sched = _run_fullstack(srv_a, store, bulk=True)
        bound_single, _ = _run_fullstack(
            srv_b, RemoteStore(srv_b.url), bulk=False)
        assert store.injected          # the 409 really happened mid-batch
        assert len(bound_conflict) == 18 and all(bound_conflict.values())
        assert bound_conflict == bound_single
        assert sched.dispatcher.stats()["errors"] == 0   # fallback healed it
    finally:
        srv_a.close()
        srv_b.close()


# ---------------------------------------------------------------- transport

def test_nagle_disabled_on_apiserver_and_diagnostics_handlers(server):
    """Server-side half of the ~40 ms Nagle + delayed-ACK stall: every
    HTTP handler in the control plane runs with TCP_NODELAY."""
    from kubetpu.sched.diagnostics import DiagnosticsServer

    assert server._httpd.RequestHandlerClass.disable_nagle_algorithm is True
    diag = DiagnosticsServer()
    try:
        assert (
            diag._httpd.RequestHandlerClass.disable_nagle_algorithm is True
        )
    finally:
        diag.close()
