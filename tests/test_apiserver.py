"""Scheme serializers + the REST/watch API server + RemoteStore — the
process-boundary deployment: scheduler and controllers running against an
API server over HTTP, informers fed by the watch endpoint.

Reference shapes: apimachinery runtime.Scheme (kind-tagged round-trip,
strict decoding), apiserver REST verbs over generic storage
(endpoints/installer.go:288, registry/store.go:514), watch-cache 410 Gone
on compacted revisions (cacher.go), and client-go running ListAndWatch
against it (reflector.go:463).
"""

import dataclasses
import json
import threading
import time

import pytest

pytest.importorskip("jax")

from kubetpu.api import scheme
from kubetpu.api import types as t
from kubetpu.api.wrappers import make_node, make_pod, pod_affinity_term
from kubetpu.apiserver import APIServer, RemoteStore
from kubetpu.client import SchedulerInformers, StoreClient
from kubetpu.client.informers import NODES, PODS
from kubetpu.controllers import REPLICA_SETS, ReplicaSetController
from kubetpu.framework import config as C
from kubetpu.sched import Scheduler
from kubetpu.store import CompactedError, MemStore
from kubetpu.store.memstore import ConflictError

from .test_scheduler import FakeClock


# -------------------------------------------------------------------- scheme

def test_scheme_round_trips_complex_objects():
    pod = make_pod(
        "p", cpu_milli=500, labels={"a": "b"},
        affinity=t.Affinity(pod_anti_affinity=t.PodAffinity(
            required=(pod_affinity_term("zone", match_labels={"x": "y"}),),
        )),
        tolerations=(t.Toleration(
            key="k", operator=t.TolerationOperator.EXISTS,
            effect=t.TaintEffect.NO_EXECUTE, toleration_seconds=5.0,
        ),),
        claims=["c0"], required_features=("F",),
    )
    assert scheme.decode(json.loads(json.dumps(scheme.encode(pod)))) == pod
    claim = t.ResourceClaim(
        name="c",
        requests=(t.DeviceRequest(
            name="r", device_class_name="gpu",
            first_available=(t.DeviceSubRequest(
                name="alt", device_class_name="small",
                selectors=(t.CELSelector('device.driver == "d"'),),
            ),),
        ),),
        allocation=t.ClaimAllocation(
            node_name="n",
            results=(t.DeviceResult("r", "drv", "pool", "dev"),),
        ),
    )
    assert scheme.decode(json.loads(json.dumps(scheme.encode(claim)))) == claim


def test_scheme_strict_decoding_fails_loudly():
    with pytest.raises(scheme.SchemeError, match="unknown field"):
        scheme.decode({"kind": "Taint", "key": "k", "bogus": 1})
    with pytest.raises(scheme.SchemeError, match="not registered"):
        scheme.decode({"kind": "Frob"})
    with pytest.raises(scheme.SchemeError, match="kind"):
        scheme.decode({"key": "k"})


def test_scheme_strict_decoding_checks_field_types():
    """Strict decoding covers primitive leaf TYPES, not only unknown
    kinds/fields (ADVICE r4): a string in an int field (and vice versa)
    must raise, while int-where-float stays legal (JSON has one number
    type)."""
    ok = scheme.decode({"kind": "Namespace", "name": "ns"})
    assert ok.name == "ns"
    with pytest.raises(scheme.SchemeError, match="expected str"):
        scheme.decode({"kind": "Namespace", "name": 7})
    with pytest.raises(scheme.SchemeError, match="expected int"):
        scheme.decode({"kind": "ContainerPort", "host_port": "eighty"})
    with pytest.raises(scheme.SchemeError, match="expected int"):
        scheme.decode({"kind": "ContainerPort", "host_port": True})
    # float-annotated field accepts an integral JSON number
    tol = scheme.decode({
        "kind": "Toleration", "key": "k", "operator": "Exists",
        "toleration_seconds": 5,
    })
    assert tol.toleration_seconds == 5


# ----------------------------------------------------------------- REST CRUD

@pytest.fixture()
def server():
    srv = APIServer().start()
    yield srv
    srv.close()


def test_rest_crud_cas_and_watch(server):
    remote = RemoteStore(server.url)
    rv1 = remote.create(NODES, "n0", make_node("n0"))
    obj, rv = remote.get(NODES, "n0")
    assert obj.name == "n0" and rv == rv1
    rv2 = remote.update(NODES, "n0", make_node("n0", cpu_milli=1), expect_rv=rv1)
    assert rv2 > rv1
    with pytest.raises(ConflictError):
        remote.update(NODES, "n0", make_node("n0"), expect_rv=rv1)
    with pytest.raises(ConflictError):
        remote.create(NODES, "n0", make_node("n0"))
    items, rv = remote.list(NODES)
    assert [k for k, _ in items] == ["n0"]
    w = remote.watch(NODES, rv)
    assert w.poll() == []
    remote.create(NODES, "n1", make_node("n1"))
    remote.delete(NODES, "n0")
    evs = w.poll()
    assert [(e.type, e.key) for e in evs] == [("ADDED", "n1"), ("DELETED", "n0")]
    assert remote.get(NODES, "n0") == (None, 0)


def test_watch_compaction_maps_to_410(server):
    small = MemStore(history=4)
    srv2 = APIServer(small).start()
    try:
        remote = RemoteStore(srv2.url)
        remote.create(NODES, "n0", make_node("n0"))
        w = remote.watch(NODES, 0)
        for i in range(10):
            remote.update(NODES, "n0", make_node("n0", cpu_milli=i))
        with pytest.raises(CompactedError):
            w.poll()
    finally:
        srv2.close()


def test_watch_long_poll_blocks_until_event(server):
    remote = RemoteStore(server.url)
    _, rv = remote.list(NODES)
    w = remote.watch(NODES, rv)
    w.poll_timeout_s = 5.0

    def later():
        time.sleep(0.2)
        MemStore.create(server.store, NODES, "late", make_node("late"))

    threading.Thread(target=later, daemon=True).start()
    t0 = time.monotonic()
    evs = w.poll()
    assert [e.key for e in evs] == ["late"]
    assert 0.1 < time.monotonic() - t0 < 4.0   # woke on the event, not timeout


# --------------------------------------- the process-boundary control plane

def test_scheduler_and_controller_over_http(server):
    """Informer + dispatcher + controller all through the REST seam: the
    components never touch the MemStore object directly."""
    remote = RemoteStore(server.url)
    for i in range(2):
        remote.create(NODES, f"n{i}", make_node(f"n{i}", cpu_milli=2000))
    remote.create(REPLICA_SETS, "default/web", t.ReplicaSet(
        name="web", replicas=4,
        selector=t.LabelSelector.of({"app": "web"}),
        template=make_pod("tpl", labels={"app": "web"}, cpu_milli=100),
    ))
    rs_ctrl = ReplicaSetController(remote)
    rs_ctrl.start()
    clock = FakeClock()
    sched = Scheduler(
        StoreClient(remote), profile=C.minimal_profile(),
        dispatcher_workers=0, clock=clock,
    )
    informers = SchedulerInformers(remote, sched)
    informers.start()
    for _ in range(6):
        rs_ctrl.step()
        informers.pump()
        sched.schedule_batch()
        sched.dispatcher.sync()
        sched._drain_bind_completions()
        clock.tick(2)
    pods, _ = remote.list(PODS)
    assert len(pods) == 4
    assert all(p.node_name for _, p in pods)
    # the bind confirmations flowed back over HTTP: nothing left assumed
    assert not sched.cache._assumed


def test_pod_v1_round_trips_claims_and_features():
    from kubetpu.bridge.convert import node_from_v1, pod_from_v1, pod_to_v1

    pod = make_pod("p", cpu_milli=100, claims=["c0"],
                   required_features=("F1", "F2"))
    back = pod_from_v1(pod_to_v1(pod))
    assert back.resource_claims == pod.resource_claims
    assert back.required_node_features == ("F1", "F2")
    node = node_from_v1({
        "metadata": {"name": "n"},
        "status": {"allocatable": {"cpu": "4"},
                   "declaredFeatures": ["B", "A"]},
    })
    assert node.declared_features == ("A", "B")
    # template-resolved claim names via status.resourceClaimStatuses
    resolved = pod_from_v1({
        "metadata": {"name": "p2", "namespace": "ns"},
        "spec": {"containers": [],
                 "resourceClaims": [{"name": "res"}]},
        "status": {"resourceClaimStatuses": [
            {"name": "res", "resourceClaimName": "p2-res-abc"},
        ]},
    })
    assert resolved.resource_claims == (
        t.PodResourceClaim(name="res", claim_name="p2-res-abc"),
    )


# ----------------------------------------------------- admission / validation

def test_invalid_writes_rejected_with_422(server):
    """Strategy validation on the write path (registry/store.go:514):
    garbage never reaches storage — the scheduler cannot see it."""
    from kubetpu.apiserver import RemoteStore
    from kubetpu.store.memstore import ConflictError

    remote = RemoteStore(server.url)
    # negative resource request
    bad_pod = dataclasses.replace(make_pod("p"), requests=(("cpu", -5),))
    with pytest.raises(ValueError, match="non-negative"):
        remote.create("pods", "default/p", bad_pod)
    # unknown phase
    with pytest.raises(ValueError, match="unknown phase"):
        remote.create("pods", "default/p",
                      dataclasses.replace(make_pod("p"), phase="Zombie"))
    # URL key disagreeing with the object's name
    with pytest.raises(ValueError, match="does not match"):
        remote.create("pods", "default/other", make_pod("p"))
    # node with negative allocatable
    bad_node = dataclasses.replace(
        make_node("n0"), allocatable=(("cpu", -1),))
    with pytest.raises(ValueError, match="non-negative"):
        remote.create("nodes", "n0", bad_node)
    # deployment with both rolling bounds zero
    bad_dep = t.Deployment(
        name="d", max_surge=0, max_unavailable=0,
        selector=t.LabelSelector.of({"a": "b"}),
        template=make_pod("tpl", labels={"a": "b"}),
    )
    with pytest.raises(ValueError, match="both be zero"):
        remote.create("deployments", "default/d", bad_dep)
    # template labels must satisfy the selector
    bad_rs = t.ReplicaSet(
        name="r", selector=t.LabelSelector.of({"app": "x"}),
        template=make_pod("tpl", labels={"app": "y"}),
    )
    with pytest.raises(ValueError, match="match selector"):
        remote.create("replicasets", "default/r", bad_rs)
    # PDB with both thresholds
    with pytest.raises(ValueError, match="mutually exclusive"):
        remote.create("poddisruptionbudgets", "default/b",
                      t.PodDisruptionBudget(
                          name="b", min_available=1, max_unavailable=1))
    # nothing landed in the store
    assert server.store.list("pods")[0] == []
    assert server.store.list("nodes")[0] == []
    # valid writes still flow (create + the validated update path)
    remote.create("pods", "default/p", make_pod("p"))
    with pytest.raises(ValueError, match="unknown phase"):
        remote.update("pods", "default/p",
                      dataclasses.replace(make_pod("p"), phase="Zombie"))
    remote.update("pods", "default/p",
                  dataclasses.replace(make_pod("p"), phase="Running"))
    assert server.store.get("pods", "default/p")[0].phase == "Running"


def test_admission_hooks_mutate_then_veto():
    """The hook chain: a mutating hook stamps a default; a validating hook
    vetoes by policy with 403 (webhook admission shape)."""
    from kubetpu.apiserver import (
        AdmissionDenied,
        APIServer,
        Registry,
        RemoteStore,
    )

    reg = Registry()

    def stamp_priority(kind, key, obj, old):
        if obj.priority == 0:
            return dataclasses.replace(obj, priority=7)
        return None

    def deny_kube_system(kind, key, obj, old):
        if obj.namespace == "kube-system":
            raise AdmissionDenied("kube-system is read-only here")

    reg.add_mutating_hook(stamp_priority, kinds=("pods",))
    reg.add_validating_hook(deny_kube_system, kinds=("pods",))
    srv = APIServer(registry=reg).start()
    try:
        remote = RemoteStore(srv.url)
        remote.create("pods", "default/p", make_pod("p"))
        assert srv.store.get("pods", "default/p")[0].priority == 7
        with pytest.raises(Exception, match="read-only"):
            remote.create("pods", "kube-system/x",
                          make_pod("x", namespace="kube-system"))
        # nodes are outside both hooks' kind filters
        remote.create("nodes", "n0", make_node("n0"))
    finally:
        srv.close()


# ------------------------------------------------- selectors + watch stream

def test_list_and_watch_selectors_server_side(server):
    """labelSelector / fieldSelector applied at the SERVER: a scoped client
    never receives filtered-out objects; an object leaving the selection
    arrives as a DELETED tombstone with no body."""
    remote = RemoteStore(server.url)
    remote.create("pods", "default/a", make_pod(
        "a", labels={"app": "web"}, node_name="n0"))
    remote.create("pods", "default/b", make_pod(
        "b", labels={"app": "db"}, node_name="n1"))
    items, rv = remote.list("pods", label_selector="app=web")
    assert [k for k, _ in items] == ["default/a"]
    items, _ = remote.list("pods", field_selector="spec.nodeName=n1")
    assert [k for k, _ in items] == ["default/b"]
    items, _ = remote.list(
        "pods", label_selector="app!=db", field_selector="spec.nodeName=n0")
    assert [k for k, _ in items] == ["default/a"]

    w = remote.watch("pods", rv, field_selector="spec.nodeName=n0")
    # bind c to n0: matching MODIFIED-chain arrives; d to n1: tombstoned
    remote.create("pods", "default/c", make_pod("c", node_name="n0"))
    remote.create("pods", "default/d", make_pod("d", node_name="n1"))
    evs = w.poll()
    assert [(e.type, e.key) for e in evs] == [
        ("ADDED", "default/c"), ("DELETED", "default/d"),
    ]
    assert evs[1].obj is None          # tombstone carries no object body
    # a's node changes away: leaves the selection as DELETED
    a, arv = remote.get("pods", "default/a")
    remote.update("pods", "default/a", a.with_node("n9"), expect_rv=arv)
    evs = w.poll()
    assert [(e.type, e.key) for e in evs] == [("DELETED", "default/a")]


def test_memstore_selectors_match_rest_semantics():
    """The same selector surface in-process (MemStore) — one contract for
    both deployment shapes."""
    st = MemStore()
    st.create("pods", "default/a", make_pod("a", labels={"app": "w"},
                                            node_name="n0"))
    st.create("pods", "default/b", make_pod("b", labels={"app": "w"}))
    items, rv = st.list("pods", field_selector="spec.nodeName=n0")
    assert [k for k, _ in items] == ["default/a"]
    w = st.watch("pods", rv, label_selector="app=w")
    st.create("pods", "default/c", make_pod("c", labels={"app": "x"}))
    st.create("pods", "default/d", make_pod("d", labels={"app": "w"}))
    assert [(e.type, e.key) for e in w.poll()] == [
        ("DELETED", "default/c"), ("ADDED", "default/d"),
    ]
    with pytest.raises(ValueError, match="malformed"):
        st.list("pods", label_selector="no-operator")


def test_streaming_watch_delivers_incrementally(server):
    """The chunked ndjson stream: events arrive over ONE held-open
    connection, across multiple polls, without re-requesting."""
    remote = RemoteStore(server.url)
    _, rv = remote.list(NODES)
    w = remote.watch(NODES, rv, stream=True)
    try:
        assert w.poll() == []              # opens the stream
        remote.create(NODES, "s0", make_node("s0"))
        deadline = time.monotonic() + 5
        evs = []
        while time.monotonic() < deadline and not evs:
            evs = w.poll()
            time.sleep(0.02)
        assert [e.key for e in evs] == ["s0"]
        remote.create(NODES, "s1", make_node("s1"))
        remote.delete(NODES, "s0")
        deadline = time.monotonic() + 5
        evs = []
        while time.monotonic() < deadline and len(evs) < 2:
            evs += w.poll()
            time.sleep(0.02)
        assert [(e.type, e.key) for e in evs] == [
            ("ADDED", "s1"), ("DELETED", "s0"),
        ]
        assert w.reconnects == 1           # one connection carried it all
    finally:
        w.close()


def test_streaming_watch_compaction_raises_410():
    small = MemStore(history=4)
    srv = APIServer(small).start()
    try:
        remote = RemoteStore(srv.url)
        remote.create(NODES, "n0", make_node("n0"))
        w = remote.watch(NODES, 0, stream=True)
        for i in range(10):
            remote.update(NODES, "n0", make_node("n0", cpu_milli=i))
        deadline = time.monotonic() + 5
        with pytest.raises(CompactedError):
            while time.monotonic() < deadline:
                w.poll()
                time.sleep(0.02)
        w.close()
    finally:
        srv.close()


def test_reflector_streams_with_field_selector(server):
    """Reflector + streaming watch + field selector together: the hollow
    kubelet shape against a remote apiserver."""
    from kubetpu.client.reflector import Reflector, SharedInformer

    remote = RemoteStore(server.url)
    remote.create("pods", "default/mine", make_pod("mine", node_name="k0"))
    remote.create("pods", "default/other", make_pod("other", node_name="k1"))
    inf = SharedInformer("pods")
    r = Reflector(remote, inf, field_selector="spec.nodeName=k0",
                  stream=True)
    r.sync()
    assert set(inf.store) == {"default/mine"}
    remote.create("pods", "default/late", make_pod("late", node_name="k0"))
    remote.create("pods", "default/elsewhere",
                  make_pod("elsewhere", node_name="k1"))
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and "default/late" not in inf.store:
        r.step()
        time.sleep(0.02)
    assert set(inf.store) == {"default/mine", "default/late"}


def test_selector_watch_suppresses_repeat_foreign_events(server):
    """Per-stream selector state: a foreign key tombstones ONCE; its later
    updates are dropped outright (the kubelet fan-out actually shrinks,
    not just the bodies)."""
    remote = RemoteStore(server.url)
    _, rv = remote.list("pods")
    w = remote.watch("pods", rv, field_selector="spec.nodeName=n0",
                     stream=True)
    w.poll()
    remote.create("pods", "default/far", make_pod("far", node_name="n9"))
    for i in range(4):
        far, frv = remote.get("pods", "default/far")
        remote.update("pods", "default/far",
                      dataclasses.replace(far, priority=i + 1),
                      expect_rv=frv)
    remote.create("pods", "default/near", make_pod("near", node_name="n0"))
    evs = []
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        evs += w.poll()
        if any(e.key == "default/near" for e in evs):
            break
        time.sleep(0.02)
    w.close()
    foreign = [e for e in evs if e.key == "default/far"]
    assert len(foreign) == 1                    # one tombstone, then silence
    assert foreign[0].type == "DELETED" and foreign[0].obj is None
    assert [e.key for e in evs if e.type == "ADDED"] == ["default/near"]


def test_malformed_selector_is_400_not_500(server):
    remote = RemoteStore(server.url)
    with pytest.raises(ValueError, match="malformed"):
        remote.list("pods", label_selector="no-operator")


# ------------------------------------------------ GVK versioning/conversion

def test_scheme_decodes_real_kubernetes_v1_manifests():
    """A genuine upstream Pod manifest (apiVersion: v1) decodes through the
    registered conversion into the hub type — kubectl apply accepts
    reference manifests verbatim; defaulting fills schedulerName."""
    manifest = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": "web", "namespace": "prod", "uid": "prod/web",
            "labels": {"app": "web"},
        },
        "spec": {
            "nodeSelector": {"disktype": "ssd"},
            "priority": 10,
            "containers": [{
                "name": "c",
                "resources": {"requests": {"cpu": "750m", "memory": "256Mi"}},
                "ports": [{"hostPort": 8080}],
            }],
            "tolerations": [{
                "key": "dedicated", "operator": "Equal", "value": "gpu",
                "effect": "NoSchedule",
            }],
        },
    }
    pod = scheme.decode(manifest)
    assert isinstance(pod, t.Pod)
    assert pod.name == "web" and pod.namespace == "prod"
    assert pod.requests_dict()["cpu"] == 750
    assert pod.requests_dict()["memory"] == 256 * 1024**2
    assert pod.node_selector == (("disktype", "ssd"),)
    assert pod.ports[0].host_port == 8080
    assert pod.scheduler_name == "default-scheduler"   # defaulting hook
    # reverse conversion: back out as v1 wire
    wire = scheme.encode_versioned(pod, "v1")
    assert wire["apiVersion"] == "v1" and wire["kind"] == "Pod"
    assert scheme.decode(wire).requests == pod.requests
    # a v1 Node manifest too
    node = scheme.decode({
        "apiVersion": "v1", "kind": "Node",
        "metadata": {"name": "n0", "labels": {"zone": "z1"}},
        "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                   "pods": "110"}},
    })
    assert node.name == "n0" and node.allocatable_dict()["cpu"] == 4000
    # unknown versions fail loudly
    with pytest.raises(scheme.SchemeError, match="no conversion"):
        scheme.decode({"apiVersion": "v9", "kind": "Pod"})
    # hub-tagged objects still round-trip, with or without the tag
    p = make_pod("x")
    tagged = scheme.encode_versioned(p)
    assert tagged["apiVersion"] == scheme.HUB_VERSION
    assert scheme.decode(tagged) == p


def test_apply_accepts_v1_manifest_over_rest(server, tmp_path):
    """kubectl-apply path: a real v1 manifest lands as a typed hub object
    the scheduler can consume."""
    import json as _json
    import subprocess
    import sys as _sys
    import os as _os

    manifest = tmp_path / "pod.json"
    manifest.write_text(_json.dumps({
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": "upstream", "namespace": "default",
                     "uid": "default/upstream"},
        "spec": {"containers": [{
            "name": "c",
            "resources": {"requests": {"cpu": "100m"}},
        }]},
    }))
    out = subprocess.run(
        [_sys.executable, "-m", "kubetpu", "apply",
         "-f", str(manifest), "--server", server.url],
        env=dict(_os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60,
        cwd=_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    pod, _ = server.store.get(PODS, "default/upstream")
    assert pod is not None and pod.requests_dict()["cpu"] == 100


# ------------------------------------------------------- the decode clock

def _decode_page(remote, sched):
    """The scheduler's /metrics page with the client's sources mounted as
    ``kubetpu scheduler`` mounts them."""
    from kubetpu.metrics.textparse import parse_prometheus_text
    from kubetpu.sched.diagnostics import DiagnosticsServer

    diag = DiagnosticsServer(sched, port=0, metrics_sources=(
        remote.reconnect_metrics_text, remote.decode_metrics_text))
    try:
        return parse_prometheus_text(diag.metrics_text())
    finally:
        diag.close()


def test_decode_clock_times_a_watch_reply_and_names_the_thread(server):
    """ISSUE 35: RemoteStore times ``codec.loads`` of every response body,
    once a response; the watch_bulk replies' part is on the scheduler's
    page and on the ``pump`` span, inside ``rpc_s``; a response decoded on
    another thread than the one that built the client is the worker's."""
    from kubetpu import cli

    WATCH = "scheduler_watch_decode_seconds_total"
    SECONDS = "apiserver_client_decode_seconds_total"
    BYTES = "apiserver_client_decoded_bytes_total"
    remote = RemoteStore(server.url)
    remote.create(NODES, "n0", make_node("n0", cpu_milli=64000))
    sched = Scheduler(
        StoreClient(remote), profile=C.minimal_profile(),
        dispatcher_workers=0, clock=FakeClock(),
    )
    informers = SchedulerInformers(remote, sched)
    informers.start()
    once = cli._scheduler_iteration(sched, informers)
    once()                      # an idle iteration: one empty reply
    before = _decode_page(remote, sched)
    for name in (WATCH, SECONDS, BYTES):
        assert before.value(name, **(
            {} if name == WATCH else {"caller": "worker"})) is not None
    assert before.value(SECONDS, caller="worker") == 0      # from scrape one
    for j in range(200):
        remote.create(PODS, f"default/p{j}",
                      make_pod(f"p{j}", cpu_milli=100, labels={"a": "b"}))
    mid = _decode_page(remote, sched)
    once()                      # delivers the 200 pods in ONE reply
    after = _decode_page(remote, sched)
    assert after.value(WATCH) > mid.value(WATCH) >= before.value(WATCH) > 0
    assert after.value(BYTES, caller="loop") \
        > mid.value(BYTES, caller="loop") + 200 * 20
    # the watch decode is the loop thread's, and only part of what it decodes
    assert after.value(WATCH) <= after.value(SECONDS, caller="loop")
    assert informers.watch_decode_s == remote.watch_decode_s \
        == pytest.approx(after.value(WATCH), abs=1e-5)
    pumps = [sp for sp in sched.tracer.recent(1000) if sp.name == "pump"]
    (pump,) = [sp for sp in pumps if sp.attrs["deliveries"] == 200]
    assert 0 < pump.attrs["decode_s"] <= pump.attrs["rpc_s"]
    assert pump.attrs["decode_s"] == pytest.approx(
        after.value(WATCH) - mid.value(WATCH), abs=1e-5)

    # a worker thread's decode lands under caller="worker"
    got = []
    worker = threading.Thread(target=lambda: got.append(remote.list(PODS)))
    worker.start()
    worker.join()
    assert len(got[0][0]) == 200
    final = _decode_page(remote, sched)
    assert final.value(SECONDS, caller="worker") > 0
    assert final.value(BYTES, caller="worker") > 200 * 20
    assert final.value(BYTES, caller="loop") == \
        after.value(BYTES, caller="loop")
    assert final.value(WATCH) == after.value(WATCH)
    sched.close()


def test_decode_clock_loses_no_response_under_many_threads(server):
    """Each thread writes a cell of its own, so no lock is taken a
    response and no update is lost: the bytes add up exactly."""
    import sys

    from kubetpu.metrics.textparse import parse_prometheus_text

    BYTES = "apiserver_client_decoded_bytes_total"
    remote = RemoteStore(server.url)
    remote.create(NODES, "n0", make_node("n0", cpu_milli=4000))

    def page():
        return parse_prometheus_text(remote.decode_metrics_text())

    remote.get(NODES, "n0")             # confirms the dialect
    b0 = page().value(BYTES, caller="loop")
    remote.get(NODES, "n0")
    one = page().value(BYTES, caller="loop") - b0
    assert one > 0
    threads, gets = 16, 40
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [
            threading.Thread(target=lambda: [
                remote.get(NODES, "n0") for _ in range(gets)])
            for _ in range(threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert page().value(BYTES, caller="worker") == threads * gets * one
    assert len(remote._decode_cells) == threads + 1


def test_a_store_in_this_process_has_no_decode_to_time():
    store = MemStore()
    sched = Scheduler(StoreClient(store), profile=C.minimal_profile(),
                      dispatcher_workers=0)
    informers = SchedulerInformers(store, sched)
    informers.start()
    assert informers.watch_decode_s == 0.0
    sched.close()
