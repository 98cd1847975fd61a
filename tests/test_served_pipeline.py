"""The served scheduler always runs the two-stage cycle (ISSUE 33): the loop
of ``kubetpu scheduler`` over a pipelined ``Scheduler``, small, on the CPU.
What is held here: the bindings are the serial loop's pod for pod on the
three cells' pod templates and both engines, with no replay when all that
arrives is the loop's own bind confirmations and new unbound pods; a batch
with nothing behind it is answered by the call that popped it; a lost lease
or a fenced partition binds nothing and strands nothing; SIGTERM completes
the cycle in flight; the parser has no ``--pipeline``; and ``/metrics``
says how often the mechanism engaged."""

import dataclasses
import threading
import time

import pytest

pytest.importorskip("jax")

from benchmark.harness import templates, templates_spread
from kubetpu import cli
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.apiserver import APIServer
from kubetpu.client import SchedulerInformers, StoreClient
from kubetpu.client.events import EventRecorder
from kubetpu.client.informers import NODES, PODS
from kubetpu.framework import config as C
from kubetpu.metrics.textparse import parse_prometheus_text
from kubetpu.sched import Scheduler
from kubetpu.sched.federation import (
    StaleOwnerError,
    _fenced_client,
    pod_partition,
)
from kubetpu.store import MemStore

from .test_scheduler import FakeClock

CYCLES = "scheduler_pipeline_cycles_total"
ZONES = ("moon-1", "moon-2", "moon-3")
#: the measured pods of basic-5k, podaffinity-5k and topologyspread-5k
TEMPLATES = {
    "pod-default": templates.pod_default,
    "pod-with-pod-affinity": templates.pod_with_pod_affinity,
    "pod-with-topology-spreading":
        templates_spread.pod_with_topology_spreading,
}
BATCH = 4


def cluster(nodes=12):
    """Upstream's node-default over three zones, and one bound color=blue
    pod in sched-0 (the init pods' role: the affinity template needs a
    match)."""
    st = MemStore()
    for i in range(nodes):
        node = templates.node_default(i, ZONES)
        st.create(NODES, node.name, node)
    seed = make_pod("seed", namespace="sched-0", labels={"color": "blue"},
                    cpu_milli=100, memory=100 * 1024 ** 2,
                    node_name="scheduler-perf-0")
    st.create(PODS, "sched-0/seed", seed)
    return st


def served(st, *, pipeline=True, engine="greedy", is_leader=lambda: True,
           client=None, max_batch=BATCH, clock=None):
    clock = clock or FakeClock()
    s = Scheduler(
        client or StoreClient(st), profile=C.Profile(), engine=engine,
        dispatcher_workers=0, clock=clock, max_batch=max_batch,
        pipeline=pipeline, recorder=EventRecorder(st, "kubetpu-scheduler"),
    )
    informers = SchedulerInformers(st, s)
    informers.start()
    return s, clock, cli._scheduler_iteration(s, informers, is_leader)


def post(st, template, first, count, namespace="sched-1"):
    for j in range(first, first + count):
        pod = dataclasses.replace(
            TEMPLATES[template](f"p{j}", namespace), creation_index=j)
        st.create(PODS, f"{namespace}/p{j}", pod)


def bound(st):
    return {k: p.node_name for k, p in st.list(PODS)[0]
            if p.node_name and k != "sched-0/seed"}


def cycles(s, result):
    return parse_prometheus_text(s.metrics_text()).value(CYCLES,
                                                         result=result)


def run_loop(template, engine, pipeline):
    """Three batches standing, then two iterations that each bring the last
    cycle's bind confirmations and a batch of new unbound pods, then the
    loop runs dry. Returns (bound map, scheduler)."""
    st = cluster()
    s, _, once = served(st, pipeline=pipeline, engine=engine)
    post(st, template, 0, 3 * BATCH)
    once()
    in_flight_after_first = s._inflight is not None
    for wave in range(2):
        post(st, template, (3 + wave) * BATCH, BATCH)
        once()
    for _ in range(8):
        once()
    assert s._inflight is None and not s.queue._in_flight
    return bound(st), s, in_flight_after_first


@pytest.mark.parametrize("engine", ["greedy", "batched"])
@pytest.mark.parametrize("template", list(TEMPLATES))
def test_the_served_loop_binds_what_the_serial_loop_binds(template, engine):
    serial, s0, _ = run_loop(template, engine, pipeline=False)
    s0.close()
    ahead, s, in_flight_after_first = run_loop(template, engine,
                                               pipeline=True)
    try:
        assert len(serial) == 5 * BATCH
        assert ahead == serial
        # with two batches queued a cycle is on the chip when once() returns
        assert in_flight_after_first
        # its own confirmations and new unbound pods never threw one away
        assert cycles(s, "replayed") == 0 == s.metrics.pipeline_replays
        assert cycles(s, "applied") >= 3
        # the serial loop counts nothing there
        assert cycles(s0, "applied") == 0 == cycles(s0, "replayed")
    finally:
        s.close()


def test_a_lone_pod_is_bound_by_the_call_that_popped_it():
    st = cluster()
    s, _, once = served(st)
    try:
        post(st, "pod-default", 0, 1)
        once()
        assert list(bound(st)) == ["sched-1/p0"]
        assert s._inflight is None
        # launched and synced in one call: not a cycle dispatched ahead
        assert cycles(s, "applied") == 0 == cycles(s, "replayed")
        (cycle,) = [sp for sp in s.tracer.recent(100)
                    if sp.name == "scheduling-cycle"]
        assert not cycle.attrs["pipelined"] and not cycle.off_stack
    finally:
        s.close()


def test_a_cycle_dispatched_ahead_observes_its_wait_and_not_the_gap():
    """What PERF.md says of the spans and histograms in a two-stage cycle:
    ``Filter+Score`` (``assign_wait_share``) and the ``assign`` span hold
    the residual sync wait, the algorithm-duration histogram the launch
    half plus the finish half and not the loop's time between them, and the
    ``scheduling-cycle`` span rides a lane of its own."""
    st = cluster()
    s, _, once = served(st, clock=time.monotonic)
    try:
        post(st, "pod-default", 0, 3 * BATCH)
        inside = 0.0
        for gap in (0.3, 0.0):
            t = time.perf_counter()
            once()
            inside += time.perf_counter() - t
            time.sleep(gap)     # the loop's sleep, with a cycle on the chip
        spans = s.tracer.recent(1000)
        ahead = [sp for sp in spans if sp.name == "scheduling-cycle"
                 and sp.attrs["pipelined"]]
        assert ahead and all(sp.off_stack for sp in ahead)
        assert max(sp.duration_s for sp in ahead) >= 0.3   # it spans the gap
        waits = {sp.attrs["cycle"]: sp for sp in spans if sp.name == "assign"}
        for sp in ahead:
            wait = waits[sp.attrs["cycle"]]
            assert wait.attrs["kernel_wall_s"] == wait.attrs["sync_wait_s"]
        # the batch's host encode is whole: the half done ahead, under the
        # cycle in flight, has its span and is in the PreFilter point
        halves = [sp for sp in spans if sp.name == "encode"
                  and sp.attrs["cycle"] == ahead[0].attrs["cycle"]]
        assert sorted(sp.attrs.get("stage", "") for sp in halves) == \
            ["", "static"]
        prom = s.metrics.prom
        assert prom.framework_extension_point_duration.labels(
            "PreFilter", "Success", s.profile.name).sum >= sum(
                sp.duration_s for sp in halves) * 0.9
        assert prom.scheduling_algorithm_duration.merged().sum <= inside
        assert prom.framework_extension_point_duration.labels(
            "Filter+Score", "Success", s.profile.name).sum <= inside
        # and the nine phases still hold all of the loop thread's wall
        seconds, _, _ = s.loop_clock.snapshot()
        assert sum(seconds.values()) >= inside + 0.3
    finally:
        s.close()


def test_a_lost_lease_binds_nothing_and_strands_nothing():
    st = cluster()
    leader = [True]
    s, clock, once = served(st, is_leader=lambda: leader[0])
    try:
        post(st, "pod-default", 0, 3 * BATCH)
        once()
        assert s._inflight is not None
        before = bound(st)
        assert len(before) == BATCH
        leader[0] = False
        once()
        once()
        assert s._inflight is None and not s.queue._in_flight
        assert bound(st) == before
        assert len(s.queue) == 2 * BATCH      # requeued, none lost
        # the lease comes back: the rest binds, each pod once
        leader[0] = True
        clock.tick(30)
        for _ in range(8):
            once()
        assert len(bound(st)) == 3 * BATCH
        assert s._inflight is None and not s.queue._in_flight
    finally:
        s.close()


class _Leases:
    """The part of PartitionLeaseManager the fence reads."""

    def __init__(self):
        self.lost = set()

    def check_fence(self, partition):
        if partition in self.lost:
            raise StaleOwnerError(f"partition {partition} handed away")


def test_a_partition_handed_away_under_a_cycle_in_flight_is_fenced():
    st = cluster()
    leases = _Leases()
    partitions = 3
    s, _, once = served(
        st, client=_fenced_client(StoreClient(st), leases, partitions))
    try:
        post(st, "pod-default", 0, 3 * BATCH)
        once()
        assert s._inflight is not None
        flying = [info.key for info in s._inflight.batch_infos]
        lost = pod_partition(flying[0], partitions)
        gone = {k for k in flying if pod_partition(k, partitions) == lost}
        assert len(gone) < len(flying), "test set-up: one partition"
        leases.lost.add(lost)       # membership.tick handed it away
        once()
        now = bound(st)
        assert not gone & set(now)
        assert set(flying) - gone <= set(now)
        assert s.metrics.bind_conflicts >= len(gone)
        # fenced pods went back to the queue; none stays popped
        assert not gone & set(s.queue._in_flight)
    finally:
        s.close()


def test_close_completes_the_cycle_in_flight():
    st = cluster()
    s, _, once = served(st)
    post(st, "pod-default", 0, 2 * BATCH)
    once()
    assert s._inflight is not None and len(bound(st)) == BATCH
    s.close()
    assert s._inflight is None and not s.queue._in_flight
    assert len(bound(st)) == 2 * BATCH
    # and the Events of those binds are in the store, not dropped
    assert s.recorder.written == 2 * BATCH


def test_abandoning_with_nothing_in_flight_does_nothing():
    st = cluster()
    s, _, once = served(st, pipeline=False, is_leader=lambda: False)
    try:
        post(st, "pod-default", 0, 2)
        once()      # a standby's iteration: no pump, nothing to give up
        assert not bound(st) and len(s.queue) == 0
        assert s._inflight is None and not s.queue._in_flight
    finally:
        s.close()


def test_the_second_refresh_of_a_call_re_encodes_no_row():
    """``_pre_encode`` and ``_complete_inflight`` both refresh the host
    snapshot; no pump runs between them, so the second finds every
    generation where the first left it."""
    st = cluster()
    s, _, once = served(st)
    try:
        post(st, "pod-default", 0, 3 * BATCH)
        once()
        once()
        # a pod another actor bound: the pump brings it and the first
        # refresh re-encodes its node (the loop's own confirmations move
        # no node)
        st.create(PODS, "sched-0/foreign", make_pod(
            "foreign", namespace="sched-0", cpu_milli=100,
            node_name="scheduler-perf-1"))
        dirty = []
        refresh = s._refresh_host_state

        def counting():
            refresh()
            dirty.append(len(s._prev_nt.last_dirty_rows))

        s._refresh_host_state = counting
        post(st, "pod-default", 3 * BATCH, BATCH)
        once()
        assert len(dirty) == 2, dirty
        assert dirty[0] > 0 and dirty[1] == 0, dirty
    finally:
        s.close()


# ------------------------------------------- bind confirmations, served

#: the oracle's view of ``C.Profile()`` for these templates (the parity
#: rules of basic-5k and topologyspread-5k)
ORACLE = {"pod-default": dict(w_fit=1, w_balanced=1),
          "pod-with-topology-spreading": dict(w_fit=1, w_balanced=1,
                                              w_spread=2, check_spread=True)}
CONFIRMED = "scheduler_cache_bind_confirmations_total"


@pytest.mark.parametrize("template", list(ORACLE))
def test_every_bind_confirmation_of_a_served_run_is_kept(template):
    """320 pods bound through an apiserver, each bind echoed to the
    informer as its delta and rebuilt there: the pod the cache gets back
    equals the pod the loop assumed in every case, the placements are the
    oracle's, and no cycle dispatched ahead is replayed."""
    from benchmark.harness import check
    from kubetpu.apiserver import RemoteStore
    from kubetpu.state import Cache

    from . import oracle

    pods, batch = 320, 32
    st = cluster(nodes=24)
    srv = APIServer(st).start()
    s = None
    try:
        remote = RemoteStore(srv.url)
        s = Scheduler(
            StoreClient(remote), profile=C.Profile(), dispatcher_workers=0,
            clock=FakeClock(), max_batch=batch, pipeline=True,
            recorder=EventRecorder(remote, "kubetpu-scheduler"),
        )
        informers = SchedulerInformers(remote, s)
        informers.start()
        once = cli._scheduler_iteration(s, informers, lambda: True)
        pm = parse_prometheus_text(s.metrics_text())
        assert pm.value(CONFIRMED, result="kept") == 0
        assert pm.value(CONFIRMED, result="reapplied") == 0
        post(st, template, 0, pods)
        for _ in range(pods // batch + 6):
            once()
        once()      # the last binds' echoes
        assert len(bound(st)) == pods
        assert not s.cache._assumed and s._inflight is None
        (r,) = [r for r in informers._reflectors if r.informer.kind == PODS]
        assert r.informer.bind_delta_counts() == (pods, 0)
        pm = parse_prometheus_text(s.metrics_text())
        assert pm.value(CONFIRMED, result="kept") == pods
        assert pm.value(CONFIRMED, result="reapplied") == 0
        assert cycles(s, "replayed") == 0 and cycles(s, "applied") > 0
    finally:
        if s is not None:
            s.close()
        srv.close()
    nodes = [n for _, n in st.list(NODES)[0]]
    stored = st.list(PODS)[0]
    assert check.validity_problems(nodes, stored) == []
    cache = Cache()
    for n in nodes:
        cache.add_node(n)
    cache.add_pod(st.get(PODS, "sched-0/seed")[0])
    infos = [i.clone() for i in cache.update_snapshot().node_infos()]
    order = [TEMPLATES[template](f"p{j}", "sched-1") for j in range(pods)]
    want = oracle.greedy(infos, order, **ORACLE[template])
    got = bound(st)
    assert [got[f"sched-1/p{j}"] for j in range(pods)] == want


# ------------------------------------------------------- the entry point

def test_the_parser_has_no_pipeline_option(capsys):
    parser = cli.build_parser()
    with pytest.raises(SystemExit):
        parser.parse_args(["scheduler", "--server", "http://x",
                           "--pipeline", "on"])
    assert "unrecognized arguments: --pipeline" in capsys.readouterr().err
    args = parser.parse_args(["scheduler", "--server", "http://x"])
    assert not hasattr(args, "pipeline")


def test_cmd_scheduler_runs_two_stage_and_sigterm_completes_the_flight(
        monkeypatch):
    """``kubetpu scheduler`` in this process, against an in-process
    apiserver: it builds a pipelined scheduler, and stopped with cycles
    still queued it completes the one in flight on its way out."""
    import kubetpu.sched as sched_pkg

    built = []

    class Recording(Scheduler):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            built.append(self)
            self.in_flight_at_close = None

        def close(self):
            if self.in_flight_at_close is None:
                self.in_flight_at_close = self._inflight is not None
            super().close()

    stop = threading.Event()
    monkeypatch.setattr(sched_pkg, "Scheduler", Recording)
    monkeypatch.setattr(cli, "_install_stop_event", lambda: stop)
    backing = cluster()
    srv = APIServer(backing).start()
    args = cli.build_parser().parse_args([
        "scheduler", "--server", srv.url, "--max-batch", str(BATCH),
        "--diagnostics-port", "off"])
    rc = []
    loop = threading.Thread(target=lambda: rc.append(cli.cmd_scheduler(args)))
    try:
        post(backing, "pod-default", 0, 12 * BATCH)
        loop.start()
        deadline = time.monotonic() + 120
        while len(bound(backing)) < BATCH and time.monotonic() < deadline:
            time.sleep(0.01)
        stop.set()          # SIGTERM, with most of the pods still queued
        loop.join(timeout=120)
        assert not loop.is_alive() and rc == [0]
        (s,) = built
        assert s.pipeline
        assert s.in_flight_at_close, "test set-up: nothing was in flight"
        assert s._inflight is None and not s.queue._in_flight
        done = bound(backing)
        assert BATCH <= len(done) < 12 * BATCH
        # every pod the loop popped is bound; the rest still waits
        assert len(done) + len(s.queue) == 12 * BATCH
    finally:
        stop.set()
        loop.join(timeout=30)
        srv.close()


# ------------------------------------------------------------- /metrics

def test_metrics_serve_both_results_and_a_forced_replay_moves_one():
    st = cluster(nodes=6)
    s, _, once = served(st)
    try:
        pm = parse_prometheus_text(s.metrics_text())
        assert pm.value(CYCLES, result="applied") == 0
        assert pm.value(CYCLES, result="replayed") == 0
        post(st, "pod-default", 0, 4 * BATCH)
        once()
        once()
        assert s._inflight is not None
        applied, replayed = cycles(s, "applied"), cycles(s, "replayed")
        assert applied >= 1 and replayed == 0
        # the cluster moves under the cycle in flight
        st.create(NODES, "n-new", make_node(
            "n-new", cpu_milli=32000, memory=64 * 1024 ** 3, pods=200))
        once()
        assert cycles(s, "replayed") == replayed + 1
        assert cycles(s, "applied") == applied
        assert s.metrics.pipeline_replays == 1
        for _ in range(6):
            once()
        assert len(bound(st)) == 4 * BATCH
    finally:
        s.close()
