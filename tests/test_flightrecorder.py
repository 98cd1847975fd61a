"""The attribution plane (ISSUE 8 tentpole): per-pod lifecycle tracing
(staged latency vector through apiserver → watch → informer → queue →
cycle → dispatcher → bind ack), the scheduling flight recorder (decision
records: win margin, top-k scores, per-plugin filter rejections, requeue
history, preemption outcomes) served at /debug/flightrecorder and rendered
by ``kubetpu explain``, the ``--flight-recorder off`` escape hatch, and
the tracer's non-destructive-read satellite."""

import json
import urllib.error
import urllib.request

import pytest

pytest.importorskip("jax")

from kubetpu import names as N
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.metrics import E2E_STAGES, parse_prometheus_text

from .test_scheduler import FakeClient, make_sched


def _get(url: str) -> tuple[int, str]:
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _schedule_mixed(client=None):
    """2 nodes, 3 schedulable pods + 1 infeasible — one cycle, drained."""
    client = client or FakeClient()
    s, _ = make_sched(client)
    s.on_node_add(make_node("n0", cpu_milli=4000))
    s.on_node_add(make_node("n1", cpu_milli=2000))
    for i in range(3):
        s.on_pod_add(make_pod(f"p{i}", cpu_milli=100, creation_index=i))
    s.on_pod_add(make_pod("big", cpu_milli=99999, creation_index=9))
    s.schedule_batch()
    s.dispatcher.sync()
    s._drain_bind_completions()
    return s, client


# ------------------------------------------------------- decision records

def test_recorder_captures_win_margin_and_filter_reasons():
    s, client = _schedule_mixed()
    fr = s.flight_recorder
    assert fr is not None
    out = fr.records_json()
    assert out["count"] == 4 and out["breakdown_failures"] == 0

    bound = fr.lookup("default/p0")
    assert bound["status"] == "bound"
    assert bound["node"] in ("n0", "n1")
    assert bound["view"] == "cycle-start"
    assert bound["feasible_nodes"] == 2 and bound["total_nodes"] == 2
    # top-k score breakdown with the winner's margin
    top = bound["top_nodes"]
    assert len(top) == 2 and top[0]["score"] >= top[1]["score"]
    assert bound["win"]["node"] == bound["node"]
    assert isinstance(bound["win"]["margin"], int)
    # staged latency vector folded in at bind ack
    stages = bound["stages_ms"]
    assert {"queue_wait", "encode", "kernel", "dispatch", "bind_rtt",
            "e2e"} <= set(stages)
    assert all(v >= 0 for v in stages.values())
    assert stages["e2e"] >= stages["bind_rtt"]

    # the infeasible pod: per-plugin rejection attribution + requeue hop
    rej = fr.lookup("default/big")
    assert rej["status"] == "unschedulable" and rej["node"] is None
    assert rej["feasible_nodes"] == 0
    assert rej["rejected_by"][N.NODE_RESOURCES_FIT] == 2
    assert set(rej["rejected_examples"][N.NODE_RESOURCES_FIT]) <= {"n0", "n1"}
    (hop,) = rej["requeue"]
    assert hop["queue"] in ("unschedulable", "backoff", "active")
    assert N.NODE_RESOURCES_FIT in hop["plugins"]


def test_recorder_stage_histograms_fill_and_stay_declared():
    s, _ = _schedule_mixed()
    pm = parse_prometheus_text(s.metrics_text())
    for stage in ("queue_wait", "encode", "kernel", "dispatch", "bind_rtt",
                  "e2e"):
        assert pm.value(
            "scheduler_e2e_scheduling_duration_seconds_count", stage=stage
        ) == 3, stage
    # direct mode has no apiserver: the fullstack-only stages stay empty
    assert pm.value(
        "scheduler_e2e_scheduling_duration_seconds_count", stage="api_ingest"
    ) is None
    # every emitted stage is a member of the declared contract
    for s_ in pm.samples("scheduler_e2e_scheduling_duration_seconds"):
        stage = s_.label("stage")
        if stage is not None:
            assert stage in E2E_STAGES


def test_flight_recorder_off_is_a_true_escape_hatch():
    client = FakeClient()
    s, _ = make_sched(client, flight_recorder=False)
    s.on_node_add(make_node("n0", cpu_milli=4000))
    for i in range(3):
        s.on_pod_add(make_pod(f"p{i}", cpu_milli=100, creation_index=i))
    s.schedule_batch()
    s.dispatcher.sync()
    s._drain_bind_completions()
    assert s.flight_recorder is None
    assert len(client.bound) == 3          # decisions unchanged
    pm = parse_prometheus_text(s.metrics_text())
    assert pm.value(
        "scheduler_e2e_scheduling_duration_seconds_count", stage="e2e"
    ) is None


def test_gang_lane_never_pollutes_staged_histograms():
    """Gang members bind outside the per-pod queue lane (no delivery
    stamp, no queue residency): they must emit NO staged samples — a
    bind-span-only 'e2e' would drag every percentile toward zero."""
    from kubetpu.api.wrappers import make_pod_group

    client = FakeClient()
    s, _ = make_sched(client, feature_gates={
        "GenericWorkload": True, "GangScheduling": True,
    })
    for i in range(4):
        s.on_node_add(make_node(f"n{i}", cpu_milli=2000))
    for j in range(2):
        s.on_pod_add(make_pod(f"p{j}", cpu_milli=500, creation_index=j))
    s.on_pod_group_add(make_pod_group("gang-0", namespace="default",
                                      min_count=2))
    for g in range(2):
        s.on_pod_add(make_pod(f"g{g}", cpu_milli=100, creation_index=50 + g,
                              scheduling_group="gang-0"))
    s.run_until_idle()
    assert len(client.bound) == 4
    pm = parse_prometheus_text(s.metrics_text())
    # only the 2 queue-lane pods carry staged samples
    assert pm.value(
        "scheduler_e2e_scheduling_duration_seconds_count", stage="e2e"
    ) == 2
    assert len(s.flight_recorder.e2e_samples) == 2


def test_foreign_clock_ingest_stamp_degrades_not_corrupts():
    """A pod stamped by a DIFFERENT host's perf_counter epoch (cross-host
    deployment) must fall back to delivery-based attribution — no
    api_ingest stage, no multi-day e2e samples."""
    import dataclasses

    client = FakeClient()
    s, _ = make_sched(client)
    s.on_node_add(make_node("n0", cpu_milli=4000))
    alien = dataclasses.replace(
        make_pod("alien", cpu_milli=100), trace_id="abc", ingest_ts=1e9,
    )
    s.on_pod_add(alien)
    s.schedule_batch()
    s.dispatcher.sync()
    s._drain_bind_completions()
    st = s.flight_recorder.lookup("default/alien")["stages_ms"]
    assert "api_ingest" not in st
    assert st["e2e"] < 60_000        # delivery-based, not epoch-delta


def test_bind_error_and_requeue_history_recorded():
    client = FakeClient(fail_binds_for=("default/p0",))
    s, clock = make_sched(client)
    s.on_node_add(make_node("n0", cpu_milli=4000))
    s.on_pod_add(make_pod("p0", cpu_milli=100))
    s.schedule_batch()
    s.dispatcher.sync()
    s._drain_bind_completions()
    rec = s.flight_recorder.lookup("default/p0")
    assert rec["status"] == "bind_error"
    assert "bind conflict" in rec["bind_error"]
    (hop,) = rec["requeue"]
    assert hop["error"] is True
    # the retry binds (FakeClient fails once): a fresh record supersedes
    clock.tick(30)                 # past the error-status backoff
    s.schedule_batch()
    s.dispatcher.sync()
    s._drain_bind_completions()
    rec = s.flight_recorder.lookup("default/p0")
    assert rec["status"] == "bound" and rec["attempts"] == 2


# --------------------------------------------- /debug/flightrecorder + CLI

def test_debug_endpoint_and_explain_cli(capsys):
    from kubetpu.cli import main as cli_main
    from kubetpu.sched import DiagnosticsServer

    s, _ = _schedule_mixed()
    diag = DiagnosticsServer(s).start()
    try:
        status, text = _get(diag.url + "/debug/flightrecorder")
        assert status == 200
        body = json.loads(text)
        assert body["enabled"] and body["count"] == 4
        assert body["records"][0]["seq"] > body["records"][-1]["seq"]

        # pod-scoped query
        status, text = _get(
            diag.url + "/debug/flightrecorder?pod=default/big"
        )
        scoped = json.loads(text)
        assert scoped["count"] == 1
        assert scoped["records"][0]["pod"] == "default/big"

        # the CLI renders timeline + win/filter reasoning from the endpoint
        rc = cli_main(["explain", "pod/default/p0", "--server", diag.url])
        out = capsys.readouterr().out
        assert rc == 0
        assert "Pod default/p0" in out and "timeline (ms):" in out
        assert "decision: bound on" in out and "top nodes:" in out

        rc = cli_main(["explain", "pod/default/big", "--server", diag.url])
        out = capsys.readouterr().out
        assert rc == 0
        assert "no feasible node" in out
        assert N.NODE_RESOURCES_FIT in out and "requeued" in out

        rc = cli_main([
            "explain", "pod/default/nope", "--server", diag.url,
        ])
        assert rc == 1
    finally:
        diag.close()


def test_debug_endpoint_reports_disabled_recorder():
    from kubetpu.sched import DiagnosticsServer

    s, _ = make_sched(flight_recorder=False)
    diag = DiagnosticsServer(s).start()
    try:
        status, text = _get(diag.url + "/debug/flightrecorder")
        assert status == 200
        assert json.loads(text) == {
            "enabled": False, "records": [], "count": 0,
        }
    finally:
        diag.close()


def test_explain_renders_from_dump_file(tmp_path, capsys):
    from kubetpu.cli import main as cli_main

    s, _ = _schedule_mixed()
    dump = tmp_path / "fr.json"
    dump.write_text(json.dumps(s.flight_recorder.records_json()))
    rc = cli_main([
        "explain", "pod/default/p1", "--file", str(dump), "-o", "json",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert json.loads(out)["pod"] == "default/p1"


# --------------------------------------------- fullstack lifecycle stages

def test_fullstack_carries_ingest_stamp_through_watch_to_stages():
    """The apiserver stamps trace id + ingest time at REST create; the
    watch frame carries it; the staged vector then includes api_ingest and
    the e2e base is the CREATE, not the informer delivery. Driven over a
    real apiserver: RemoteStore, the scheduler's informers, a Scheduler
    binding through the store."""
    import time

    from kubetpu.apiserver import APIServer, RemoteStore
    from kubetpu.client import SchedulerInformers, StoreClient
    from kubetpu.client.informers import NODES, PODS
    from kubetpu.sched import Scheduler

    srv = APIServer().start()
    s = None
    try:
        remote = RemoteStore(srv.url)
        for i in range(10):
            remote.create(NODES, f"n{i}", make_node(
                f"n{i}", cpu_milli=4000, memory=8 * 1024 ** 3))
        s = Scheduler(StoreClient(remote), max_batch=8)
        informers = SchedulerInformers(remote, s)
        informers.start()
        base = s.metrics.prom.snapshot_baseline()
        for j in range(20):
            remote.create(PODS, f"default/p{j}", make_pod(
                f"p{j}", cpu_milli=100, memory=100 * 1024 ** 2))
        stored = {}
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            informers.pump()
            s.schedule_batch()
            s.dispatcher.sync()
            s._drain_bind_completions()
            stored = {k: p for k, p in remote.list(PODS)[0]}
            if all(p.node_name for p in stored.values()):
                break
        assert len(stored) == 20
        assert all(p.node_name for p in stored.values())
        informers.pump()
        staged = s.metrics.prom.staged_percentiles(base)
        assert staged is not None
        assert {"api_ingest", "informer", "queue_wait", "encode", "kernel",
                "dispatch", "bind_rtt", "e2e"} <= set(staged)
        # e2e covers at least the non-overlapping pipeline stages it
        # contains
        assert staged["e2e"]["p99"] >= staged["bind_rtt"]["p50"]
        for key, pod in stored.items():
            rec = s.flight_recorder.lookup(key)
            # the trace id the apiserver stamped rode the watch frame
            assert pod.trace_id and rec["trace_id"] == pod.trace_id
            stages = rec["stages_ms"]
            # e2e is based at the CREATE: it spans the REST-create →
            # delivery leg and everything after it
            assert stages["api_ingest"] >= 0.0
            assert stages["e2e"] >= stages["api_ingest"]
            assert stages["e2e"] >= stages["bind_rtt"]
    finally:
        if s is not None:
            s.close()
        srv.close()


def test_apiserver_stamps_pod_ingest_once():
    import dataclasses

    from kubetpu.api import scheme
    from kubetpu.apiserver import APIServer, RemoteStore

    srv = APIServer().start()
    try:
        remote = RemoteStore(srv.url)
        remote.create("pods", "default/x", make_pod("x"))
        obj, _rv = remote.get("pods", "default/x")
        assert obj.trace_id and obj.ingest_ts > 0
        # a re-create of an already-stamped object keeps its original t0
        stamped = dataclasses.replace(obj, node_name="")
        remote.delete("pods", "default/x")
        remote.create("pods", "default/x", stamped)
        again, _rv = remote.get("pods", "default/x")
        assert again.trace_id == obj.trace_id
        assert again.ingest_ts == obj.ingest_ts
        # non-pod kinds are never stamped
        remote.create("nodes", "n0", make_node("n0"))
        node, _rv = remote.get("nodes", "n0")
        assert not hasattr(node, "trace_id") or not getattr(
            node, "trace_id", ""
        )
        # stamps survive the scheme round trip (the watch frame's codec)
        assert scheme.decode(scheme.encode(again)).trace_id == obj.trace_id
    finally:
        srv.close()


# ------------------------------------------------------ tracer satellites

def test_tracer_drain_preserves_concurrent_appends():
    """Satellite: drain() must remove only the spans it handed out — a
    span recorded between the snapshot and the removal survives for the
    next reader (the destructive-read audit's regression pin)."""
    from kubetpu.tracing import Tracer

    tr = Tracer()
    tr.record("a", 0.0, 1.0)
    tr.record("b", 1.0, 2.0)
    orig = tr._snapshot_spans

    def racing_snapshot():
        out = orig()
        tr._snapshot_spans = orig
        tr.record("c", 2.0, 3.0)     # lands AFTER the exporter's snapshot
        return out

    tr._snapshot_spans = racing_snapshot
    drained = tr.drain()
    assert [s.name for s in drained] == ["a", "b"]
    assert [s.name for s in tr.recent()] == ["c"]
    # and the drained spans are really gone
    assert [s.name for s in tr.drain()] == ["c"]
    assert tr.recent() == []


def test_queue_wait_accumulates_across_requeue_hops():
    from kubetpu.queue import PriorityQueue

    q = PriorityQueue()
    q.add(make_pod("p"))
    (info,) = q.pop_batch(10)
    first = info.queue_wait_s
    assert first > 0 and info.enqueued_pc == 0.0
    q.add_unschedulable(info, ["NodeResourcesFit"])
    assert info.enqueued_pc > 0
    # wake it (wherever the hints parked it) and pop again: the wait for
    # the SECOND residency adds onto the first
    if info.key in q._unschedulable:
        del q._unschedulable[info.key]
        q._push_active(info)
    elif info.key in q._backoff:
        del q._backoff[info.key]
        q._push_active(info)
    (info2,) = q.pop_batch(10)
    assert info2 is info
    assert info.queue_wait_s > first and info.enqueued_pc == 0.0
