"""Span/metrics/flight-record collector — N processes, one timeline.

The sink side of the telemetry plane: each control-plane process runs a
``TelemetryExporter`` (exporter.py) that ships batched span exports, its
``/metrics`` text, and its flight-recorder snapshot here over the
existing wire codec (``kubetpu.api.codec`` — binary when the schema
fingerprints match, JSON otherwise). The collector:

- **corrects clock skew**: every process's spans are stamped on ITS
  ``time.perf_counter`` (CLOCK_MONOTONIC), whose epoch is per-boot and —
  across hosts or containers — per-process. The exporter runs a
  monotonic-offset handshake against ``/telemetry/clock`` (NTP's
  min-RTT probe shape: offset = server_mono − (t0 + t2)/2, best of N),
  and every export carries the resulting ``offset_s``; the collector
  maps each span onto ITS OWN monotonic timeline before merging.
- **merges spans** into one chrome trace with per-process lanes (one
  ``pid`` per process, a ``process_name`` metadata event each), so a
  single pod's ingest → cycle → bind → bind-subresource timeline reads
  left-to-right across process boundaries in Perfetto.
- **federates metrics**: the latest scrape text of every process is
  re-exposed under one ``/telemetry/metrics`` page with ``process`` and
  ``replica`` labels injected — the cluster view a Prometheus server
  would build, available without one.
- **serves the console**: ``/telemetry/top`` summarizes per process —
  pods/s (rate between the last two ingests), queue depth, conflict
  rate, WAL fsync p99, staged e2e percentiles — what ``kubetpu top``
  renders (firing sentinel alerts ride inline).
- **merges alerts and bundles**: each process's sentinel alert table
  ships with its export batch; ``/telemetry/alerts`` collapses them by
  (rule, series) into one cluster-wide row per alert (worst state
  wins, per-process breakdown attached), and ``/telemetry/bundle``
  serves the diagnostic bundles captured at fire time (deduped by
  per-process id, bounded per process).

Ingest is bounded: per-process span rings drop oldest-first and count
drops (``kubetpu_collector_spans_dropped_total``).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Any

from ..api import codec
from ..metrics.textparse import ParseError, parse_prometheus_text

#: per-process span-ring bound (drops beyond it are counted, never silent)
MAX_SPANS_PER_PROCESS = 131072
#: processes tracked before the oldest-idle one is evicted
MAX_PROCESSES = 256
#: diagnostic bundles retained per process (dedup by id, oldest evicted)
MAX_BUNDLES_PER_PROCESS = 8

#: alert-state precedence for the cluster-wide merge (worst wins)
_ALERT_RANK = {"firing": 0, "pending": 1, "resolved": 2}


def relabel_metrics_text(text: str, extra: "dict[str, str]") -> str:
    """Inject ``extra`` label pairs into every sample line of one
    process's exposition text (HELP/TYPE lines pass through) — the
    federation transform. Values are escaped per text format 0.0.4."""
    from ..metrics.registry import _esc_label

    pairs = ",".join(f'{k}="{_esc_label(v)}"' for k, v in extra.items())
    if not pairs:
        return text
    out: list[str] = []
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("#"):
            out.append(line)
            continue
        if "{" in stripped:
            name, _, rest = stripped.partition("{")
            body, sep, value = rest.rpartition("}")
            if not sep:
                out.append(line)        # malformed: pass through untouched
                continue
            joined = f"{pairs},{body}" if body else pairs
            out.append(f"{name}{{{joined}}}{value}")
        else:
            name, _, value = stripped.partition(" ")
            out.append(f"{name}{{{pairs}}} {value}")
    return "\n".join(out) + "\n"


def _hist_quantile(samples, q: float) -> float | None:
    """histogram_quantile over parsed ``_bucket`` samples (cumulative
    counts, ``le`` upper bounds) — the same interpolation the live
    Histogram uses, reconstructed from exposition text."""
    buckets: list[tuple[float, float]] = []
    for s in samples:
        le = s.label("le")
        if le is None or not s.name.endswith("_bucket"):
            continue
        ub = float("inf") if le == "+Inf" else float(le)
        buckets.append((ub, s.value))
    if not buckets:
        return None
    buckets.sort()
    total = buckets[-1][1]
    if total <= 0:
        return None
    rank = q * total
    prev_ub, prev_count = 0.0, 0.0
    for ub, count in buckets:
        if count >= rank and count > prev_count:
            hi = ub if ub != float("inf") else prev_ub
            frac = (rank - prev_count) / (count - prev_count)
            return prev_ub + (hi - prev_ub) * frac
        prev_ub = ub if ub != float("inf") else prev_ub
        prev_count = count
    return prev_ub


class _ProcState:
    """Everything the collector holds for one exporting process."""

    def __init__(self, index: int, component: str, replica: str) -> None:
        self.index = index
        self.component = component
        self.replica = replica
        self.offset_s = 0.0
        self.spans: deque = deque(maxlen=MAX_SPANS_PER_PROCESS)
        self.dropped = 0
        self.ingests = 0
        self.metrics_text = ""
        self.flight_records: list[dict] = []
        # the process sentinel's latest alert table (replaced wholesale
        # each ingest — alert state lives at the source, this is a view)
        self.alerts: list[dict] = []
        # diagnostic bundles, deduped by the sentinel's per-process id
        # (the exporter re-ships its retained ring every batch)
        self.bundles: "OrderedDict[Any, dict]" = OrderedDict()
        # (receive mono, {counter key: value}) of the last two ingests —
        # the rate window the console's pods/s comes from
        self.rate_prev: "tuple[float, dict] | None" = None
        self.rate_last: "tuple[float, dict] | None" = None
        self.last_seen = 0.0
        # last ingested batch id — the exporter's transport retries a
        # POST whose reply was lost after ingest, so an exact repeat of
        # (epoch, seq) is acked without re-appending its spans
        self.last_batch: "tuple | None" = None


#: the counter sums the console rates are derived from
_RATE_KEYS = {
    "scheduled": ("scheduler_schedule_attempts_total", {"result": "scheduled"}),
    "attempts": ("scheduler_schedule_attempts_total", {}),
    "conflicts": ("scheduler_federation_conflicts_total", {}),
}


class Collector:
    """See module docstring. Thread-safe: HTTP ingest threads and scrape/
    console readers share the state under one lock."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._procs: "OrderedDict[str, _ProcState]" = OrderedDict()
        self._ingests = 0

    # ------------------------------------------------------------ handshake
    def clock_probe(self, t0: Any) -> dict:
        """One leg of the monotonic-offset handshake: echo the client's
        send stamp with our receive stamp; the client derives
        offset = server_mono − (t0 + t2)/2 and keeps the min-RTT probe."""
        return {"t0": t0, "server_mono": time.perf_counter()}

    # --------------------------------------------------------------- ingest
    def _counter_sums(self, text: str) -> dict:
        try:
            parsed = parse_prometheus_text(text)
        except ParseError:
            return {}
        out: dict[str, float] = {}
        for key, (family, want) in _RATE_KEYS.items():
            total = 0.0
            seen = False
            for s in parsed.samples(family):
                if s.name != family:
                    continue
                if all(s.label(k) == v for k, v in want.items()):
                    total += s.value
                    seen = True
            if seen:
                out[key] = total
        # queue depth is a gauge: the latest value is the rate-window's too
        depth = 0.0
        seen = False
        for s in parsed.samples("scheduler_pending_pods"):
            if s.name == "scheduler_pending_pods":
                depth += s.value
                seen = True
        if seen:
            out["queue_depth"] = depth
        return out

    def ingest(self, payload: dict) -> dict:
        """One export batch from one process. Returns {"ok", "dropped"}
        — ``dropped`` is the process's lifetime span-drop count, so an
        exporter can see loss without a scrape."""
        if not isinstance(payload, dict):
            raise ValueError("export payload must be a mapping")
        name = str(payload.get("process") or "")
        if not name:
            raise ValueError("export payload carries no process name")
        now = time.perf_counter()
        clock = payload.get("clock") or {}
        spans = payload.get("spans") or ()
        with self._lock:
            st = self._procs.get(name)
            if st is None:
                while len(self._procs) >= MAX_PROCESSES:
                    self._procs.popitem(last=False)
                st = self._procs[name] = _ProcState(
                    index=len(self._procs),
                    component=str(payload.get("component") or ""),
                    replica=str(payload.get("replica") or ""),
                )
            st.last_seen = now
            batch_tag = payload.get("batch")
            if isinstance(batch_tag, dict):
                tag = (batch_tag.get("epoch"), batch_tag.get("seq"))
                if tag == st.last_batch:
                    # a retried delivery of the batch we already hold:
                    # idempotent ack, nothing double-counted
                    return {"ok": True, "dropped": st.dropped,
                            "duplicate": True}
                st.last_batch = tag
            st.ingests += 1
            self._ingests += 1
            if isinstance(clock, dict) and isinstance(
                clock.get("offset_s"), (int, float)
            ):
                st.offset_s = float(clock["offset_s"])
            overflow = (
                len(st.spans) + len(spans) - (st.spans.maxlen or 0)
            )
            if overflow > 0:
                st.dropped += overflow
            for sp in spans:
                if isinstance(sp, dict):
                    st.spans.append(sp)
            mt = payload.get("metrics_text")
            if isinstance(mt, str) and mt:
                st.metrics_text = mt
                st.rate_prev = st.rate_last
                st.rate_last = (now, self._counter_sums(mt))
            fr = payload.get("flight_records")
            if isinstance(fr, dict) and isinstance(fr.get("records"), list):
                st.flight_records = fr["records"]
            av = payload.get("alerts")
            if isinstance(av, dict):
                av = av.get("alerts")
            if isinstance(av, list):
                st.alerts = [a for a in av if isinstance(a, dict)]
            bv = payload.get("bundles")
            if isinstance(bv, list):
                for b in bv:
                    if not isinstance(b, dict) or "id" not in b:
                        continue
                    if b["id"] not in st.bundles:
                        st.bundles[b["id"]] = b
                        while len(st.bundles) > MAX_BUNDLES_PER_PROCESS:
                            st.bundles.popitem(last=False)
            return {"ok": True, "dropped": st.dropped}

    # ---------------------------------------------------------------- reads
    def _snapshot(self) -> "list[tuple[str, _ProcState, list[dict]]]":
        with self._lock:
            return [
                (name, st, list(st.spans))
                for name, st in self._procs.items()
            ]

    @property
    def spans_dropped(self) -> int:
        with self._lock:
            return sum(st.dropped for st in self._procs.values())

    @property
    def spans_total(self) -> int:
        with self._lock:
            return sum(len(st.spans) for st in self._procs.values())

    def chrome_trace(self) -> dict:
        """Every process's spans merged onto the COLLECTOR's monotonic
        timeline (per-process offset applied), one chrome-trace lane
        group per process: pid = process index, ``process_name`` metadata
        names the lane, off-stack spans pack into non-overlapping tids
        exactly like the single-process export."""
        events: list[dict] = []
        for name, st, spans in self._snapshot():
            pid = st.index + 1
            events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": name},
            })
            lane_ends: list[float] = []
            for sp in sorted(spans, key=lambda s: s.get("start", 0.0)):
                start = float(sp.get("start", 0.0)) + st.offset_s
                end = float(sp.get("end", start)) + st.offset_s
                args = {
                    "span_id": sp.get("span_id"),
                    "parent_id": sp.get("parent_id"),
                    "process": name,
                    **(sp.get("attrs") or {}),
                }
                if sp.get("instant"):
                    events.append({
                        "name": sp.get("name", ""), "cat": "kubetpu",
                        "ph": "i", "s": "p", "ts": start * 1e6,
                        "pid": pid, "tid": 1, "args": args,
                    })
                    continue
                if sp.get("off_stack", True):
                    for lane, lane_end in enumerate(lane_ends):
                        if lane_end <= start:
                            lane_ends[lane] = end
                            break
                    else:
                        lane = len(lane_ends)
                        lane_ends.append(end)
                    tid = 2 + lane
                else:
                    tid = 1
                events.append({
                    "name": sp.get("name", ""), "cat": "kubetpu",
                    "ph": "X", "ts": start * 1e6,
                    "dur": max(end - start, 0.0) * 1e6,
                    "pid": pid, "tid": tid, "args": args,
                })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def pod_spans(self, pod_trace: str) -> "list[tuple[str, dict]]":
        """(process, span) for every span linked to one pod's 16-hex
        attribution id — scheduler spans stamp it as ``pod_trace``, the
        apiserver's request spans as the ``pod_traces`` list. Times come
        back SKEW-CORRECTED onto the collector timeline."""
        out: list[tuple[str, dict]] = []
        for name, st, spans in self._snapshot():
            for sp in spans:
                attrs = sp.get("attrs") or {}
                if attrs.get("pod_trace") != pod_trace and (
                    pod_trace not in (attrs.get("pod_traces") or ())
                ):
                    continue
                corrected = dict(sp)
                corrected["start"] = float(sp.get("start", 0.0)) + st.offset_s
                corrected["end"] = float(
                    sp.get("end", sp.get("start", 0.0))
                ) + st.offset_s
                out.append((name, corrected))
        out.sort(key=lambda ps: ps[1]["start"])
        return out

    def _own_metrics_text(self) -> str:
        from ..metrics.registry import Registry

        with self._lock:
            dropped = sum(st.dropped for st in self._procs.values())
            spans = sum(len(st.spans) for st in self._procs.values())
            procs = len(self._procs)
            ingests = self._ingests
        r = Registry()
        r.counter(
            "kubetpu_collector_spans_dropped_total",
            "Spans dropped at ingest because a process's ring was full.",
        ).inc(dropped)
        r.gauge(
            "kubetpu_collector_spans",
            "Spans currently buffered across all processes.",
        ).set(spans)
        r.gauge(
            "kubetpu_collector_processes",
            "Processes that have exported at least once.",
        ).set(procs)
        r.counter(
            "kubetpu_collector_ingests_total",
            "Export batches ingested.",
        ).inc(ingests)
        return r.expose()

    def metrics_text(self) -> str:
        """The federated /metrics page: every process's latest scrape
        re-labeled with {process, replica} plus the collector's own
        counters. HELP/TYPE headers survive per process block (Prometheus
        tolerates repeats across federation blocks)."""
        chunks = [self._own_metrics_text()]
        for name, st, _spans in self._snapshot():
            if not st.metrics_text:
                continue
            labels = {"process": name}
            if st.replica:
                labels["replica"] = st.replica
            chunks.append(relabel_metrics_text(st.metrics_text, labels))
        return "".join(chunks)

    def flight_records(self, pod: "str | None" = None,
                       limit: int = 256) -> dict:
        """Merged flight-recorder view across every exporting replica —
        what ``kubetpu explain --collector`` renders. Records keep their
        per-process ``replica`` stamp; newest first per process."""
        records: list[dict] = []
        with self._lock:
            for name, st in self._procs.items():
                for rec in st.flight_records:
                    if pod and rec.get("pod") != pod:
                        continue
                    rec = dict(rec)
                    rec.setdefault("replica", st.replica)
                    rec["process"] = name
                    records.append(rec)
        records = records[: max(limit, 1)]
        return {"enabled": True, "records": records, "count": len(records)}

    # ---------------------------------------------------------------- alerts
    def alerts(self) -> dict:
        """The cluster-wide alert table (``/telemetry/alerts``): every
        process's sentinel alerts merged by (rule, series) — per-process
        fingerprints differ by design, the rule identity is what's
        cluster-wide. One replica firing while another is clean collapses
        to ONE row in the worst state (firing > pending > resolved), with
        the per-process breakdown kept in ``processes``."""
        with self._lock:
            per_proc = [
                (name, list(st.alerts)) for name, st in self._procs.items()
            ]
        merged: "OrderedDict[tuple, dict]" = OrderedDict()
        for name, alerts in per_proc:
            for a in alerts:
                key = (a.get("rule"), a.get("series"))
                entry = merged.get(key)
                if entry is None:
                    entry = merged[key] = {
                        "rule": a.get("rule"),
                        "series": a.get("series"),
                        "severity": a.get("severity"),
                        "state": a.get("state"),
                        "value": a.get("value"),
                        "reason": a.get("reason"),
                        "fires": 0,
                        "processes": [],
                    }
                entry["processes"].append({
                    "process": name,
                    "fingerprint": a.get("fingerprint"),
                    "state": a.get("state"),
                    "value": a.get("value"),
                    "bundle_id": a.get("bundle_id"),
                })
                entry["fires"] += int(a.get("fires") or 0)
                if _ALERT_RANK.get(str(a.get("state")), 3) < _ALERT_RANK.get(
                    str(entry["state"]), 3
                ):
                    entry["state"] = a.get("state")
                    entry["severity"] = a.get("severity")
                    entry["value"] = a.get("value")
                    entry["reason"] = a.get("reason")
        rows = sorted(
            merged.values(),
            key=lambda e: (
                _ALERT_RANK.get(str(e["state"]), 3), str(e["rule"])
            ),
        )
        return {
            "alerts": rows,
            "firing": sum(e["state"] == "firing" for e in rows),
            "pending": sum(e["state"] == "pending" for e in rows),
            "resolved": sum(e["state"] == "resolved" for e in rows),
        }

    def bundle_list(
        self, process: "str | None" = None,
        bundle_id: "str | None" = None,
    ) -> dict:
        """``/telemetry/bundle``: summaries without an id, the full
        capture with ``?id=N`` (``&process=`` disambiguates when two
        replicas reused the same per-process counter)."""
        with self._lock:
            items = [
                (name, b)
                for name, st in self._procs.items()
                if process is None or name == process
                for b in st.bundles.values()
            ]
        if bundle_id:
            for name, b in items:
                if str(b.get("id")) == str(bundle_id):
                    return {"bundle": b}
            return {"bundle": None, "error": f"no bundle id {bundle_id}"}
        return {
            "bundles": [{
                "id": b.get("id"),
                "process": name,
                "rule": (b.get("trigger") or {}).get("rule"),
                "severity": (b.get("trigger") or {}).get("severity"),
                "captured_wall": b.get("captured_wall"),
                "sections": sorted((b.get("sections") or {}).keys()),
                "trace_events": len(
                    (b.get("trace") or {}).get("traceEvents") or ()
                ),
                "rss_bytes": b.get("rss_bytes"),
            } for name, b in items],
            "count": len(items),
        }

    # --------------------------------------------------------------- console
    def _proc_summary(self, st: _ProcState, now: float) -> dict:
        out: dict[str, Any] = {
            "component": st.component,
            "replica": st.replica,
            "age_s": round(max(now - st.last_seen, 0.0), 1),
            "spans": len(st.spans),
            "spans_dropped": st.dropped,
        }
        firing = [a for a in st.alerts if a.get("state") == "firing"]
        if firing:
            out["alerts_firing"] = len(firing)
            out["firing_alerts"] = sorted(
                str(a.get("rule")) for a in firing
            )
        last, prev = st.rate_last, st.rate_prev
        if last:
            sums = last[1]
            if "queue_depth" in sums:
                out["queue_depth"] = int(sums["queue_depth"])
            if "conflicts" in sums and sums.get("attempts"):
                out["conflict_rate"] = round(
                    sums["conflicts"] / sums["attempts"], 4
                )
        if last and prev and last[0] > prev[0]:
            dt = last[0] - prev[0]
            for key, label in (("scheduled", "pods_per_s"),):
                a, b = prev[1].get(key), last[1].get(key)
                if a is not None and b is not None:
                    out[label] = round(max(b - a, 0.0) / dt, 1)
        if st.metrics_text:
            try:
                parsed = parse_prometheus_text(st.metrics_text)
            except ParseError:
                parsed = None
            if parsed is not None:
                p99 = _hist_quantile(
                    parsed.samples("store_wal_fsync_duration_seconds"), 0.99
                )
                if p99 is not None:
                    out["wal_fsync_p99_ms"] = round(p99 * 1000.0, 3)
                staged = {}
                for s in parsed.samples(
                    "scheduler_e2e_scheduling_duration_seconds"
                ):
                    stage = s.label("stage")
                    if stage:
                        staged.setdefault(stage, []).append(s)
                stages_out = {}
                for stage, samples in staged.items():
                    p50 = _hist_quantile(samples, 0.50)
                    sp99 = _hist_quantile(samples, 0.99)
                    if sp99 is not None:
                        stages_out[stage] = {
                            "p50_ms": round((p50 or 0.0) * 1000.0, 3),
                            "p99_ms": round(sp99 * 1000.0, 3),
                        }
                if stages_out:
                    out["e2e_stages_ms"] = stages_out
        return out

    def summary(self) -> dict:
        """The ``kubetpu top`` body: one row per process — pods/s, queue
        depth, conflict rate, WAL fsync p99, staged e2e percentiles —
        plus the collector's own drop counter."""
        now = time.perf_counter()
        with self._lock:
            procs = list(self._procs.items())
            dropped = sum(st.dropped for _n, st in procs)
            firing = sum(
                1 for _n, st in procs for a in st.alerts
                if a.get("state") == "firing"
            )
        return {
            "processes": {
                name: self._proc_summary(st, now) for name, st in procs
            },
            "spans_dropped": dropped,
            "alerts_firing": firing,
        }


# ----------------------------------------------------------------- routes

def handle_collector_request(
    collector: Collector, method: str, path: str, query: dict,
    body: bytes, content_type: "str | None",
) -> "tuple[int, str, str] | None":
    """ONE route table for both mounts (the standalone CollectorServer
    and the apiserver's embedded mode): returns (status, content type,
    body text), or None for a foreign path. Ingest bodies decode by their
    Content-Type through the wire seam (binary 415s on a fingerprint
    mismatch — the exporter falls back to JSON); replies are small JSON/
    text either way."""

    def one(name: str, default: str = "") -> str:
        v = query.get(name, default)
        return v[-1] if isinstance(v, list) else v

    def reply_json(obj, status: int = 200):
        return status, "application/json", codec.dumps(obj).decode()

    if method == "POST":
        payload = codec.loads(
            body or b"{}", codec.codec_for_content_type(content_type)
        )
        if path == "/telemetry/export":
            return reply_json(collector.ingest(payload))
        if path == "/telemetry/clock":
            return reply_json(collector.clock_probe(payload.get("t0")))
        return None
    if path == "/telemetry/trace":
        return reply_json(collector.chrome_trace())
    if path == "/telemetry/metrics":
        from ..metrics.diagmux import PROM_CONTENT_TYPE

        return 200, PROM_CONTENT_TYPE, collector.metrics_text()
    if path == "/telemetry/flightrecorder":
        try:
            limit = int(one("limit") or 256)
        except ValueError:
            limit = 256
        return reply_json(
            collector.flight_records(pod=one("pod") or None, limit=limit)
        )
    if path == "/telemetry/pod":
        spans = collector.pod_spans(one("trace"))
        return reply_json({
            "spans": [dict(sp, process=proc) for proc, sp in spans],
            "count": len(spans),
        })
    if path == "/telemetry/top":
        return reply_json(collector.summary())
    if path == "/telemetry/alerts":
        return reply_json(collector.alerts())
    if path == "/telemetry/bundle":
        return reply_json(collector.bundle_list(
            process=one("process") or None,
            bundle_id=one("id") or None,
        ))
    return None


class CollectorServer:
    """Standalone HTTP front for a Collector (``kubetpu collector``):
    /telemetry/* per ``handle_collector_request`` plus /healthz and a
    /metrics alias of the federated page."""

    def __init__(self, collector: "Collector | None" = None,
                 host: str = "127.0.0.1", port: int = 0) -> None:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        from urllib.parse import parse_qs, urlsplit

        self.collector = collector if collector is not None else Collector()
        outer = self

        class _CollHandler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            disable_nagle_algorithm = True

            def log_message(self, *args) -> None:
                pass

            def _send(self, status: int, content_type: str,
                      text: str) -> None:
                data = text.encode()
                self.send_response(status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _handle(self, method: str) -> None:
                parts = urlsplit(self.path)
                path = parts.path
                if method == "GET" and path in ("/healthz", "/readyz"):
                    self._send(200, "text/plain; charset=utf-8", "ok\n")
                    return
                if method == "GET" and path == "/metrics":
                    path = "/telemetry/metrics"
                body = b""
                if method == "POST":
                    length = int(self.headers.get("Content-Length", 0))
                    body = self.rfile.read(length) if length else b""
                try:
                    res = handle_collector_request(
                        outer.collector, method, path,
                        parse_qs(parts.query, keep_blank_values=True),
                        body, self.headers.get("Content-Type"),
                    )
                except codec.UnsupportedWireError as e:
                    self._send(415, "application/json",
                               codec.dumps({"error": str(e)}).decode())
                    return
                except Exception as e:  # noqa: BLE001 — must not crash
                    self._send(500, "application/json",
                               codec.dumps({
                                   "error": f"{type(e).__name__}: {e}",
                               }).decode())
                    return
                if res is None:
                    self._send(404, "application/json",
                               codec.dumps({"error": "unknown path"})
                               .decode())
                    return
                self._send(*res)

            def do_GET(self) -> None:  # noqa: N802
                self._handle("GET")

            def do_POST(self) -> None:  # noqa: N802
                self._handle("POST")

        class _Server(ThreadingHTTPServer):
            daemon_threads = True
            block_on_close = False

        self._httpd = _Server((host, port), _CollHandler)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )

    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "CollectorServer":
        self._thread.start()
        return self

    def close(self) -> None:
        if self._thread.is_alive():
            self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread.is_alive():
            self._thread.join(timeout=5)
