"""Cluster telemetry plane (kubetpu.telemetry): trace-context
propagation across the wire, the span/metrics collector with clock-skew
correction, the live console, the WAL observability satellite — and the
MULTI-PROCESS SMOKE: apiserver + 2 scheduler replicas as real OS
processes producing ONE merged chrome trace in which a single pod's
spans cross all three processes with skew-corrected, monotonically
ordered stage boundaries, plus a federated /metrics scrape carrying both
replicas' labeled series."""

import json
import os
import re
import time
import urllib.request

import pytest

from kubetpu.api import codec
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.apiserver import APIServer, RemoteStore
from kubetpu.telemetry import collector as collector_mod
from kubetpu.telemetry.collector import (
    Collector,
    CollectorServer,
    relabel_metrics_text,
)
from kubetpu.telemetry.context import (
    TraceContext,
    format_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    pod_trace_id,
)
from kubetpu.telemetry.exporter import (
    ClockSync,
    EmbeddedCollectorClient,
    TelemetryExporter,
)
from kubetpu.tracing import Tracer

from .test_wal import CORES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------

def test_traceparent_roundtrip():
    ctx = TraceContext(new_trace_id(), new_span_id(), sampled=True)
    assert parse_traceparent(format_traceparent(ctx)) == ctx
    unsampled = TraceContext(new_trace_id(), new_span_id(), sampled=False)
    back = parse_traceparent(format_traceparent(unsampled))
    assert back is not None and not back.sampled
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-span-01",
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",      # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",      # all-zero span id
    "00-" + "g" * 32 + "-" + "1" * 16 + "-01",      # non-hex
    "zz-" + "a" * 32 + "-" + "1" * 16 + "-01",      # non-hex version
    "ff-" + "a" * 32 + "-" + "1" * 16 + "-01",      # forbidden version
    "00-" + "a" * 32 + "-" + "1" * 16,              # missing flags
])
def test_malformed_traceparent_reads_as_no_context(bad):
    assert parse_traceparent(bad) is None


def test_pod_trace_id_widening():
    assert pod_trace_id("ab" * 8) == "ab" * 16
    assert pod_trace_id("") == ""
    assert pod_trace_id("nothex!") == ""


# ---------------------------------------------------------------------------
# propagation over the wire — every mixed-codec pair, malformed tolerance,
# and the --telemetry off byte-parity escape hatch
# ---------------------------------------------------------------------------

def _one_joined_pair(server_wire: str, client_wire: str):
    """Create a pod through a propagating client; return the matched
    (client rpc span, server span) pair."""
    srv = APIServer(wire=server_wire).start()
    tracer = Tracer()
    remote = RemoteStore(
        srv.url, wire=client_wire, traceparent=True, tracer=tracer,
    )
    try:
        remote.create("pods", "ns/p0", make_pod("p0", namespace="ns"))
        # a second request AFTER negotiation settled: the binary client
        # has confirmed the dialect by now, so this one rides the binary
        # envelope's tp parameter (the first rode the JSON header)
        remote.update(
            "pods", "ns/p0",
            remote.get("pods", "ns/p0")[0].with_node("n0"),
        )
        cli_spans = [s for s in tracer.recent(10) if s.name.startswith("rpc.")]
        srv_spans = [
            s for s in srv.tracer.recent(10)
            if s.name.startswith("apiserver.") and "trace_id" in s.attrs
        ]
        assert cli_spans and srv_spans
        pairs = []
        for cs in cli_spans:
            for ss in srv_spans:
                if (
                    ss.attrs["trace_id"] == cs.attrs["trace_id"]
                    and ss.attrs["parent_span_id"] == cs.attrs["span_id"]
                ):
                    pairs.append((cs, ss))
        return pairs, remote.wire_codec
    finally:
        srv.close()


@pytest.mark.parametrize("server_wire,client_wire,negotiated", [
    ("binary", "binary", "binary"),     # tp rides the binary envelope
    ("json", "binary", "json"),         # 415 fallback: header carries it
    ("binary", "json", "json"),         # JSON client: header carries it
])
def test_traceparent_joins_across_every_codec_pair(
    server_wire, client_wire, negotiated
):
    pairs, wire = _one_joined_pair(server_wire, client_wire)
    # EVERY client rpc span found its server span (both requests joined,
    # whichever envelope carried the context)
    assert len(pairs) >= 2
    assert wire == negotiated


def test_415_fallback_reissues_the_same_trace_context():
    """The documented invariant: a 415/JSON re-issue carries the SAME
    traceparent back in the header envelope — the rejected attempt and
    its retry correlate as one trace."""
    srv = APIServer(wire="json").start()
    tracer = Tracer()
    remote = RemoteStore(srv.url, wire="binary", traceparent=True,
                         tracer=tracer)
    try:
        # force the confirmed-binary state so the next write ships a
        # binary body at a JSON-only server → a real 415 → JSON re-issue
        remote._wire_ok = True
        remote.create("pods", "ns/p0", make_pod("p0", namespace="ns"))
        rpc = [s for s in tracer.recent(10) if s.name == "rpc.POST"]
        assert len(rpc) == 2, rpc                     # 415 then 201
        assert {s.attrs["status"] for s in rpc} == {415, 201}
        assert len({s.attrs["trace_id"] for s in rpc}) == 1
        assert len({s.attrs["span_id"] for s in rpc}) == 1
        joined = [
            s for s in srv.tracer.recent(10)
            if s.attrs.get("trace_id") == rpc[0].attrs["trace_id"]
        ]
        assert joined, "server span did not join the re-issued trace"
    finally:
        srv.close()


def test_duplicate_export_batches_are_acked_not_recounted():
    """A retried delivery (reply lost after ingest) must not double the
    spans: the collector dedupes an exact (epoch, seq) repeat."""
    col = Collector()
    batch = {
        "process": "p", "clock": {},
        "batch": {"epoch": "e1", "seq": 1},
        "spans": [{"name": "x", "span_id": 1, "parent_id": None,
                   "start": 1.0, "end": 2.0, "off_stack": True,
                   "instant": False, "attrs": {}}],
    }
    col.ingest(batch)
    reply = col.ingest(batch)           # the transport retry
    assert reply.get("duplicate") is True
    assert col.spans_total == 1
    # a DIFFERENT epoch at seq 1 (restarted exporter) still lands
    col.ingest({**batch, "batch": {"epoch": "e2", "seq": 1}})
    assert col.spans_total == 2


def test_malformed_traceparent_is_ignored_not_fatal():
    srv = APIServer().start()
    try:
        import http.client

        from urllib.parse import urlsplit

        u = urlsplit(srv.url)
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=10)
        conn.request("GET", "/apis/pods", headers={
            "traceparent": "00-not-a-real-traceparent-zz",
        })
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200, body
        # the span lands just AFTER the reply bytes flush: bounded re-read
        spans = []
        deadline = time.monotonic() + 5.0
        while not spans and time.monotonic() < deadline:
            spans = [
                s for s in srv.tracer.recent(10)
                if s.name.startswith("apiserver.")
            ]
            if not spans:
                time.sleep(0.01)
        assert spans and "trace_id" not in spans[-1].attrs
        conn.close()
    finally:
        srv.close()


def _capture_raw_request(store_fn) -> bytes:
    """Point a RemoteStore at a one-shot raw socket server and return the
    exact request bytes it sent."""
    import socket
    import threading

    captured: list[bytes] = []
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]

    def serve():
        conn, _addr = lsock.accept()
        conn.settimeout(5)
        data = b""
        try:
            while b"\r\n\r\n" not in data:
                data += conn.recv(65536)
        except OSError:
            pass
        captured.append(data)
        body = b'{"items":[],"resourceVersion":0}'
        conn.sendall(
            b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
            + body
        )
        conn.close()

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    store_fn(f"http://127.0.0.1:{port}")
    th.join(timeout=10)
    lsock.close()
    assert captured, "no request captured"
    return captured[0]


def test_telemetry_off_wire_bytes_identical():
    """The escape hatch is byte-identical, not just 'mostly off': with
    traceparent off the request carries NO trace context anywhere (header
    or content-type parameter), and the on-request differs from the
    off-request by EXACTLY the traceparent header."""
    def listing(traceparent):
        def run(url):
            RemoteStore(url, traceparent=traceparent).list("pods")
        return run

    def norm(raw: bytes, drop_traceparent: bool) -> bytes:
        # each capture server listens on its own ephemeral port: the Host
        # header legitimately differs and is not telemetry's doing
        return b"\r\n".join(
            line for line in raw.split(b"\r\n")
            if not line.lower().startswith(b"host:")
            and not (drop_traceparent
                     and line.lower().startswith(b"traceparent:"))
        )

    raw_off = _capture_raw_request(listing(False))
    raw_on = _capture_raw_request(listing(True))
    assert b"traceparent" not in raw_off
    assert b"tp=" not in raw_off
    assert b"traceparent" in raw_on
    assert norm(raw_on, drop_traceparent=True) == norm(
        raw_off, drop_traceparent=False
    )


# ---------------------------------------------------------------------------
# clock-skew correction
# ---------------------------------------------------------------------------

def test_clock_sync_recovers_injected_offset():
    """Symmetric-delay probes recover the injected offset exactly; the
    min-RTT probe wins over jittered ones; the monotonic anchor round-
    trips."""
    OFFSET = 123.456
    script = iter([
        # (send time, one-way delay out, one-way delay back)
        (10.0, 0.050, 0.050),
        (20.0, 0.001, 0.001),       # the min-RTT probe: exact offset
        (30.0, 0.200, 0.020),       # asymmetric junk, bigger rtt
        (40.0, 0.010, 0.010),
        (50.0, 0.030, 0.030),
    ])
    state = {}

    def clock():
        if "t2" in state:
            return state.pop("t2")
        t0, out, back = next(script)
        state["reply"] = {"server_mono": t0 + out + OFFSET}
        state["t2"] = t0 + out + back
        return t0

    def probe(t0):
        return {"t0": t0, **state.pop("reply")}

    cs = ClockSync(probe, clock=clock)
    got = cs.sync(probes=5)
    assert abs(got - OFFSET) < 1e-9
    assert cs.rtt_s == pytest.approx(0.002)
    # anchor round trip: local -> collector -> local is the identity
    assert cs.to_local(cs.to_collector(77.7)) == pytest.approx(77.7)


def test_clock_sync_against_live_collector_is_near_zero():
    """Exporter and collector sharing one process clock must converge to
    ~zero offset (the RTT bounds the error)."""
    col = Collector()
    cs = ClockSync(lambda t0: col.clock_probe(t0))
    off = cs.sync()
    assert abs(off) <= (cs.rtt_s or 0.0) + 0.001


def test_collector_corrects_injected_skew_into_one_timeline(monkeypatch):
    """Two processes with large opposite clock offsets: the merged trace
    places their spans in TRUE order; per-process lanes carry
    process_name metadata."""
    col = Collector()
    # process A's clock reads 1000s behind the collector; B 500s ahead.
    # True order: A's span (collector 110..111) before B's (112..113).
    col.ingest({
        "process": "a", "component": "scheduler", "replica": "r0",
        "clock": {"offset_s": +1000.0},
        "spans": [{"name": "bind", "span_id": 1, "parent_id": None,
                   "start": -890.0, "end": -889.0, "off_stack": True,
                   "instant": False, "attrs": {"pod_trace": "aa" * 8}}],
    })
    col.ingest({
        "process": "b", "component": "scheduler", "replica": "r1",
        "clock": {"offset_s": -500.0},
        "spans": [{"name": "bind", "span_id": 2, "parent_id": None,
                   "start": 612.0, "end": 613.0, "off_stack": True,
                   "instant": False, "attrs": {"pod_trace": "aa" * 8}}],
    })
    spans = col.pod_spans("aa" * 8)
    assert [p for p, _s in spans] == ["a", "b"]
    assert spans[0][1]["start"] == pytest.approx(110.0)
    assert spans[1][1]["start"] == pytest.approx(112.0)
    trace = col.chrome_trace()
    meta = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
    assert {e["args"]["name"] for e in meta} == {"a", "b"}
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert len(pids) == 2


def test_collector_bounded_rings_count_drops(monkeypatch):
    monkeypatch.setattr(collector_mod, "MAX_SPANS_PER_PROCESS", 4)
    col = Collector()
    spans = [
        {"name": f"s{i}", "span_id": i, "parent_id": None,
         "start": float(i), "end": float(i), "off_stack": True,
         "instant": False, "attrs": {}}
        for i in range(10)
    ]
    reply = col.ingest({"process": "p", "clock": {}, "spans": spans})
    assert reply["dropped"] == 6
    assert col.spans_dropped == 6
    assert "kubetpu_collector_spans_dropped_total 6" in col.metrics_text()


# ---------------------------------------------------------------------------
# federation of metrics + the console
# ---------------------------------------------------------------------------

SCHED_METRICS = """\
# HELP scheduler_schedule_attempts_total attempts
# TYPE scheduler_schedule_attempts_total counter
scheduler_schedule_attempts_total{result="scheduled",profile="default-scheduler"} %d
# TYPE scheduler_pending_pods gauge
scheduler_pending_pods{queue="active"} 7
scheduler_pending_pods{queue="backoff"} 2
# TYPE scheduler_federation_conflicts_total counter
scheduler_federation_conflicts_total{mode="race",replica="r0"} 5
"""


def test_relabel_preserves_values_and_escapes():
    out = relabel_metrics_text(
        'x{a="b"} 1\ny 2.5\n# TYPE x counter\n', {"process": 'p"1'}
    )
    assert 'x{process="p\\"1",a="b"} 1' in out
    assert 'y{process="p\\"1"} 2.5' in out
    assert "# TYPE x counter" in out


def test_federated_metrics_and_console_rates():
    col = Collector()
    col.ingest({
        "process": "sched-r0", "component": "scheduler", "replica": "r0",
        "clock": {}, "spans": [], "metrics_text": SCHED_METRICS % 100,
    })
    # second ingest 1 (fake) second later: rate window
    col.ingest({
        "process": "sched-r0", "component": "scheduler", "replica": "r0",
        "clock": {}, "spans": [], "metrics_text": SCHED_METRICS % 300,
    })
    text = col.metrics_text()
    assert re.search(
        r'scheduler_schedule_attempts_total\{process="sched-r0",'
        r'replica="r0",result="scheduled"', text
    )
    summary = col.summary()
    p = summary["processes"]["sched-r0"]
    assert p["queue_depth"] == 9
    assert p["conflict_rate"] == pytest.approx(5 / 300, abs=1e-4)
    # pods/s: 200 scheduled over the (tiny) window — just assert > 0
    assert p.get("pods_per_s", 0) > 0


def test_top_renders_and_json_mode(capsys):
    from kubetpu.cli import main as cli_main, render_top

    col = Collector()
    col.ingest({
        "process": "sched-r0", "component": "scheduler", "replica": "r0",
        "clock": {}, "spans": [], "metrics_text": SCHED_METRICS % 50,
    })
    srv = CollectorServer(col).start()
    try:
        rc = cli_main(["top", "--collector", srv.url, "-o", "json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert "sched-r0" in out["processes"]
        text = render_top(out)
        assert "PROCESS" in text and "sched-r0" in text
        rc = cli_main(["top", "--collector", srv.url])
        assert rc == 0
        assert "sched-r0" in capsys.readouterr().out
    finally:
        srv.close()


def test_collector_http_ingest_negotiates_binary_and_falls_back(monkeypatch):
    """The exporter's wire client ships binary first (schema match ⇒
    accepted), a foreign-fingerprint body 415s at the collector, and the
    client's 415 drops it to JSON permanently — exports keep landing."""
    srv = CollectorServer().start()
    try:
        tr = Tracer()
        tr.record("x", start=1.0, end=2.0)
        exp = TelemetryExporter(
            srv.url, process="p1", component="scheduler", tracer=tr,
        )
        exp.flush()
        assert exp._client._wire == codec.BINARY
        assert srv.collector.spans_total == 1

        # a drifted build: garbage schema fingerprint on the content type
        import http.client
        from urllib.parse import urlsplit

        u = urlsplit(srv.url)
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=10)
        conn.request(
            "POST", "/telemetry/export", body=b"\xae\x00\x00",
            headers={"Content-Type": (
                f"{codec.CT_BINARY}; v=1; schema=deadbeef0000"
            )},
        )
        resp = conn.getresponse()
        assert resp.status == 415
        resp.read()
        conn.close()

        # client side of the same drift: advertise a foreign fingerprint
        # → 415 → permanent JSON fallback, the batch still lands
        tr2 = Tracer()
        tr2.record("y", start=1.0, end=2.0)
        exp2 = TelemetryExporter(
            srv.url, process="p2", component="scheduler", tracer=tr2,
        )
        orig = codec.content_type_for

        def foreign_ct(wire, traceparent=None):
            if wire == codec.BINARY:
                return f"{codec.CT_BINARY}; v=1; schema=deadbeef0000"
            return orig(wire, traceparent)

        monkeypatch.setattr(
            "kubetpu.telemetry.exporter.codec.content_type_for", foreign_ct
        )
        exp2.flush()
        assert exp2._client._wire == codec.JSON
        assert "p2" in srv.collector.summary()["processes"]
    finally:
        srv.close()


def test_embedded_collector_on_apiserver():
    srv = APIServer(collector=True).start()
    try:
        exp = TelemetryExporter(
            "", process="apiserver-embed", component="apiserver",
            tracer=srv.tracer, metrics_fn=srv.metrics_text,
            client=EmbeddedCollectorClient(srv.collector),
        )
        remote = RemoteStore(srv.url)
        remote.create("pods", "ns/p0", make_pod("p0", namespace="ns"))
        exp.flush()
        with urllib.request.urlopen(srv.url + "/telemetry/top") as resp:
            summary = json.load(resp)
        assert "apiserver-embed" in summary["processes"]
        with urllib.request.urlopen(srv.url + "/telemetry/metrics") as resp:
            text = resp.read().decode()
        assert 'process="apiserver-embed"' in text
        # the apiserver's own diagnostics grew /trace
        with urllib.request.urlopen(srv.url + "/trace") as resp:
            trace = json.load(resp)
        assert "traceEvents" in trace
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# dispatcher call spans
# ---------------------------------------------------------------------------

def test_dispatcher_records_call_spans_with_pod_trace():
    from kubetpu.sched.api_dispatcher import APIDispatcher, BindCall

    class _Client:
        def bind(self, pod, node):
            pass

    import dataclasses

    tr = Tracer()
    d = APIDispatcher(_Client(), workers=0, tracer=tr)
    pod = dataclasses.replace(
        make_pod("p0", namespace="ns"), trace_id="ab" * 8
    )
    d.add(BindCall(pod=pod, node_name="n0"))
    spans = [s for s in tr.recent(10) if s.name == "api.bind"]
    assert spans and spans[0].attrs["pod_trace"] == "ab" * 8
    assert spans[0].attrs["status"] == "ok"


# ---------------------------------------------------------------------------
# WAL observability satellite
# ---------------------------------------------------------------------------

def test_wal_metrics_ride_the_apiserver_scrape(tmp_path):
    srv = APIServer(persistence=str(tmp_path / "wal")).start()
    try:
        remote = RemoteStore(srv.url)
        for i in range(5):
            remote.create("pods", f"ns/p{i}", make_pod(f"p{i}",
                                                       namespace="ns"))
        with urllib.request.urlopen(srv.url + "/metrics") as resp:
            text = resp.read().decode()
        assert "store_wal_fsync_duration_seconds_bucket" in text
        assert "store_wal_segments 1" in text
        assert re.search(r"store_wal_bytes_total [1-9]", text)
        assert "store_snapshot_age_seconds" in text
        stats = srv.store.wal_stats()
        assert stats["fsync_p99_ms"] is not None
    finally:
        srv.close()


def test_memory_store_scrape_has_no_wal_series():
    srv = APIServer().start()
    try:
        with urllib.request.urlopen(srv.url + "/metrics") as resp:
            text = resp.read().decode()
        assert "store_wal_" not in text
    finally:
        srv.close()


@pytest.mark.parametrize("native", CORES)
def test_wal_stats_time_each_group_commit_of_bulk_creates_and_binds(
        tmp_path, native):
    """The durability tax's latency shape: a bulk create then a bulk bind
    per chunk, each one group commit, logs one record per op and times
    every fsync into the p99 ``wal_stats`` reports (the histogram the
    apiserver's /metrics exposes)."""
    from kubetpu.store.memstore import MemStore

    st = MemStore(persistence=str(tmp_path / "wal"), native=native)
    try:
        for i in range(0, 256, 64):
            keys = [f"ns/p-{j}" for j in range(i, i + 64)]
            st.bulk("pods", [
                {"op": "create", "key": k,
                 "object": make_pod(k.split("/", 1)[1], namespace="ns")}
                for k in keys
            ])
            st.bulk("pods", [
                {"op": "bind", "key": k, "node": "node-0"} for k in keys
            ])
        stats = st.wal_stats()
    finally:
        st.close()
    assert stats["records_appended"] == 512
    # the segment header's fsync, then ONE group commit per bulk request
    assert stats["fsyncs"] == 1 + 8
    assert stats["bytes_appended"] > 0
    assert stats["fsync_p99_ms"] is not None and stats["fsync_p99_ms"] > 0
    assert stats["fsync_p50_ms"] <= stats["fsync_p99_ms"]


# ---------------------------------------------------------------------------
# explain --collector
# ---------------------------------------------------------------------------

def test_explain_fetches_from_the_collector(capsys):
    from kubetpu.cli import main as cli_main

    col = Collector()
    col.ingest({
        "process": "scheduler-r1", "component": "scheduler",
        "replica": "r1", "clock": {}, "spans": [],
        "flight_records": {"records": [{
            "pod": "ns/p0", "cycle": 3, "profile": "default-scheduler",
            "attempts": 1, "status": "bound", "node": "n4",
            "replica": "r1", "trace_id": "ab" * 8,
            "stages_ms": {"queue_wait": 1.0, "e2e": 5.0},
        }], "count": 1},
    })
    srv = CollectorServer(col).start()
    try:
        rc = cli_main([
            "explain", "pod/ns/p0", "--collector", srv.url,
        ])
        out = capsys.readouterr().out
        assert rc == 0
        assert "replica r1" in out and "n4" in out
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the multi-process smoke: the ROADMAP-1 slice, on the PR-13 launch
# subsystem — the tier-1 smoke and the perf runner exercise the SAME
# spawn/banner/readiness/cascade code (kubetpu.launch.Supervisor)
# ---------------------------------------------------------------------------

def test_multiprocess_stitched_trace_and_federated_scrape():
    """THE acceptance smoke: apiserver + 2 scheduler replicas as real OS
    processes under the launch Supervisor, all exporting to one
    collector. A single pod's spans must cross all three processes in the
    merged trace with skew-corrected, monotonically ordered stage
    boundaries (ingest ≤ scheduler bind ≤ apiserver bind-subresource),
    and the federated /metrics must carry BOTH replicas' labeled
    series."""
    from kubetpu.launch import Supervisor, apiserver_spec, scheduler_spec

    coll = CollectorServer().start()
    sup = Supervisor(env={"JAX_PLATFORMS": "cpu"}, cwd=REPO)
    try:
        api = sup.spawn(apiserver_spec(telemetry=coll.url))
        api_url = api.url()
        assert api_url, api.banner    # the banner carries the real port
        for rid in ("r0", "r1"):
            sup.spawn(scheduler_spec(
                name=f"scheduler-{rid}", server=api_url,
                replica_id=rid, telemetry=coll.url,
            ))
        remote = RemoteStore(api_url)
        for i in range(4):
            node = make_node(f"n{i}", cpu_milli=64000, pods=110)
            remote.create("nodes", f"n{i}", node)
        n_pods = 40
        remote.bulk("pods", [
            {"op": "create", "key": f"ns/p{i}",
             "object": make_pod(f"p{i}", namespace="ns")}
            for i in range(n_pods)
        ])
        # wait until every pod bound (the schedulers race; CAS arbitrates)
        deadline = time.monotonic() + 150.0
        bound = []
        while time.monotonic() < deadline:
            items, _rv = remote.list("pods")
            bound = [o for _k, o in items if o.node_name]
            if len(bound) == n_pods:
                break
            for child in sup.children:
                assert child.alive(), (
                    f"{child.name} died: {child.tail()}"
                )
            time.sleep(0.25)
        assert len(bound) == n_pods, f"only {len(bound)}/{n_pods} bound"

        # let every process's 1s export cadence drain its spans, then
        # look for a pod whose spans cross ALL THREE processes
        three_way = None
        spans_by_proc: dict = {}
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and three_way is None:
            time.sleep(1.0)
            for obj in bound:
                with urllib.request.urlopen(
                    coll.url + "/telemetry/pod?trace=" + obj.trace_id
                ) as resp:
                    body = json.load(resp)
                procs_seen: dict = {}
                for sp in body["spans"]:
                    procs_seen.setdefault(sp["process"], []).append(sp)
                comps = {p.split("-")[0] for p in procs_seen}
                if "apiserver" in comps and {
                    "scheduler-r0", "scheduler-r1"
                } <= set(procs_seen):
                    three_way = obj
                    spans_by_proc = procs_seen
                    break
        assert three_way is not None, (
            "no pod's spans crossed all three processes"
        )
        # skew-corrected, monotonically ordered stage boundaries: the
        # apiserver ingest span starts before the scheduler bind span,
        # which starts before the apiserver bind-subresource span (all
        # on the COLLECTOR timeline; epsilon covers handshake error)
        eps = 0.05
        api_proc = next(
            p for p in spans_by_proc if p.startswith("apiserver")
        )
        api_spans = sorted(spans_by_proc[api_proc],
                           key=lambda s: s["start"])
        ingest = api_spans[0]           # the CREATE/BULK that stamped it
        later_api = api_spans[-1]       # the bind-subresource write
        assert len(api_spans) >= 2, api_spans
        binds = [
            sp for p, spans in spans_by_proc.items()
            if p.startswith("scheduler") for sp in spans
            if sp["name"] == "bind"
        ]
        assert binds, spans_by_proc
        first_bind = min(sp["start"] for sp in binds)
        assert ingest["start"] <= first_bind + eps
        assert first_bind <= later_api["start"] + eps
        # one merged chrome trace, one lane group per process
        with urllib.request.urlopen(coll.url + "/telemetry/trace") as resp:
            trace = json.load(resp)
        lanes = {
            e["args"]["name"] for e in trace["traceEvents"]
            if e.get("ph") == "M"
        }
        assert {"scheduler-r0", "scheduler-r1"} <= lanes
        assert any(name.startswith("apiserver") for name in lanes)
        # federated scrape: BOTH replicas' labeled series on one page
        with urllib.request.urlopen(
            coll.url + "/telemetry/metrics"
        ) as resp:
            text = resp.read().decode()
        for rid in ("r0", "r1"):
            assert re.search(
                r'scheduler_schedule_attempts_total\{process='
                rf'"scheduler-{rid}",replica="{rid}"', text
            ), f"federated scrape missing scheduler-{rid}"
        # nothing was dropped: the merged trace is complete evidence
        assert coll.collector.spans_dropped == 0
    finally:
        # the supervisor's SIGTERM cascade replaces the hand-rolled
        # terminate/wait/kill loop this test used to carry
        sup.shutdown()
        coll.close()
    assert not any(c.alive() for c in sup.children), "orphaned child"
