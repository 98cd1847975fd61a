"""Scheduler diagnostics listener — /metrics, /healthz//readyz//livez,
and /trace on a side port.

Every reference binary serves component-base's metrics + healthz mux next
to its real work (kube-scheduler's --secure-port mux installs /metrics,
/healthz, /livez, /readyz and debug handlers). The kubetpu scheduler is a
library object driven by an owner loop, so the serving surface is this
small listener bound to one ``Scheduler``:

- ``GET /metrics``      Prometheus text 0.0.4: the scheduler set
  (reference-named histograms + plugin/extension-point durations), the
  device-side TPU counters (same registry), and any extra bound sources —
  by default the process-wide workqueue provider, so a co-hosted
  controller family is scraped through the same port.
- ``GET /healthz|/readyz|/livez[/<check>]``   named, registrable checks
  (kubetpu.metrics.health): ``ping`` plus the scheduler's own
  ``dispatcher`` (binding pipeline alive) and, when informers are bound,
  ``informers-synced`` (readyz only — a resyncing scheduler is alive but
  not ready, the reference's install split).
- ``GET /trace``        the tracer's buffered spans as Chrome-trace JSON
  (Perfetto-loadable; cycle ids join the device counter records).
- ``GET /debug/queue``  per-pod pending reasons from the scheduling
  queue: pool, attempts, unschedulable-plugin sets, backoff deadlines.
- ``GET /debug/alerts`` the anomaly sentinel's alert state (pending →
  firing → resolved, fingerprint-deduped) when ``--sentinel on``.
- ``GET /debug/bundle`` triggered diagnostic bundles (summaries, or one
  full capture with ``?id=N``).

Every request runs on a thread of its own, under the scheduler loop's GIL,
and is gone before a scrape could find it; so the handler times itself
with its thread's CPU clock, and ``/metrics`` carries
``scheduler_diagnostics_requests_total{endpoint}`` and
``scheduler_diagnostics_request_cpu_seconds_total{endpoint}``: what the
observer takes from the observed (a request counts once it has been
answered, so a scrape reads the ones before it).
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Iterable
from urllib.parse import parse_qs, urlsplit

from ..metrics.health import HealthChecks
from ..metrics.registry import Registry
from ..tracing import thread_cpu

#: the ONLY values of {endpoint} on scheduler_diagnostics_request*_total
ENDPOINTS = ("metrics", "trace", "health", "debug", "other")


def _endpoint(path: str) -> str:
    head = path.strip("/").split("/", 1)[0]
    if head in ("healthz", "readyz", "livez"):
        return "health"
    return head if head in ENDPOINTS[:-1] else "other"


class _DiagHandler(BaseHTTPRequestHandler):
    server_ref: "DiagnosticsServer"     # bound by the factory
    protocol_version = "HTTP/1.1"

    def log_message(self, *args) -> None:
        pass

    def _reply(self, body: str, status: int = 200,
               content_type: str = "text/plain; charset=utf-8") -> None:
        data = body.encode()
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self) -> None:  # noqa: N802
        from ..metrics.diagmux import diagnostics_response

        parts = urlsplit(self.path)
        diag = self.server_ref
        cpu0 = thread_cpu() if thread_cpu is not None else None
        try:
            res = diagnostics_response(
                parts.path, parse_qs(parts.query, keep_blank_values=True),
                metrics_sources=(diag.metrics_text,),
                health=diag.health,
                extra={
                    # non-destructive by contract: chrome_trace() snapshots;
                    # a scrape never erases spans a concurrent exporter or
                    # the flight recorder still needs (Tracer.drain is the
                    # only consuming read, and it pops only its snapshot)
                    "/trace": lambda q: (
                        "application/json", json.dumps(diag.trace_json())
                    ),
                    "/debug/flightrecorder": lambda q: (
                        "application/json",
                        json.dumps(diag.flightrecorder_json(q)),
                    ),
                    "/debug/queue": lambda q: (
                        "application/json",
                        json.dumps(diag.queue_json(q)),
                    ),
                    "/debug/alerts": lambda q: (
                        "application/json",
                        json.dumps(diag.alerts_json()),
                    ),
                    "/debug/bundle": lambda q: (
                        "application/json",
                        json.dumps(diag.bundle_json(q), default=str),
                    ),
                },
            )
            if res is None:
                self._reply("404 page not found\n", status=404)
                return
            status, content_type, body = res
            self._reply(body, status=status, content_type=content_type)
        except Exception as e:  # noqa: BLE001 — diagnostics must not crash
            self._reply(f"internal error: {type(e).__name__}: {e}\n",
                        status=500)
        finally:
            diag.note_request(
                _endpoint(parts.path),
                None if cpu0 is None else thread_cpu() - cpu0)


class DiagnosticsServer:
    """See module docstring. ``metrics_sources`` are extra Prometheus-text
    providers appended after the scheduler set."""

    def __init__(
        self,
        scheduler=None,
        host: str = "127.0.0.1",
        port: int = 0,
        metrics_sources: Iterable[Callable[[], str]] = (),
        include_workqueues: bool = True,
        health: HealthChecks | None = None,
    ) -> None:
        self.scheduler = scheduler
        self.health = health if health is not None else HealthChecks()
        self._sources: list[Callable[[], str]] = list(metrics_sources)
        if include_workqueues:
            from ..metrics.workqueue import default_provider

            self._sources.append(lambda: default_provider().expose())
        if scheduler is not None:
            self._install_scheduler_checks(scheduler)
        # what this listener itself takes: every endpoint a series from the
        # first scrape (the CPU family only where threads have a CPU clock)
        self._own = Registry()
        self._requests = self._own.counter(
            "scheduler_diagnostics_requests_total",
            "Requests this listener has answered, by endpoint.",
            labels=("endpoint",), declared={"endpoint": ENDPOINTS},
        )
        self._request_cpu = self._own.counter(
            "scheduler_diagnostics_request_cpu_seconds_total",
            "CPU seconds of the request threads that answered them, run "
            "under the scheduler loop's GIL.",
            labels=("endpoint",), declared={"endpoint": ENDPOINTS},
        )
        for endpoint in ENDPOINTS:
            self._requests.labels(endpoint)
            if thread_cpu is not None:
                self._request_cpu.labels(endpoint)
        handler = type("BoundDiagHandler", (_DiagHandler,), {
            "server_ref": self,
            "disable_nagle_algorithm": True,
        })

        class _Server(ThreadingHTTPServer):
            daemon_threads = True
            block_on_close = False

        self._httpd = _Server((host, port), handler)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )

    def _install_scheduler_checks(self, sched) -> None:
        def dispatcher_alive() -> None:
            if getattr(sched.dispatcher, "_closed", False):
                raise RuntimeError("api dispatcher is closed")

        self.health.add_check("dispatcher", dispatcher_alive)

    def add_informers(self, informers) -> None:
        """Register the informer-synced READINESS check: healthy once every
        informer's initial list landed (WaitForCacheSync's condition).
        readyz only — healthz/livez may back liveness probes, and a
        relisting scheduler is alive, just not ready. Accepts a
        ``SchedulerInformers`` bundle (its ``synced`` property), a dict of
        SharedInformers, or an iterable of them."""
        def informers_synced() -> object:
            synced = getattr(informers, "synced", None)
            if isinstance(synced, bool):
                return None if synced else "informer caches not yet synced"
            pending = [
                str(getattr(inf, "kind", inf))
                for inf in _iter_informers(informers)
                if not getattr(inf, "synced", False)
            ]
            if pending:
                return "not synced: " + ", ".join(sorted(pending))
            return None

        self.health.add_check(
            "informers-synced", informers_synced, endpoints=("readyz",),
        )

    def add_check(self, name: str, fn, endpoints=None) -> None:
        if endpoints is None:
            self.health.add_check(name, fn)
        else:
            self.health.add_check(name, fn, endpoints=endpoints)

    def note_request(self, endpoint: str, cpu_s: float | None) -> None:
        """One answered request, and the CPU seconds its thread took (None
        where the platform keeps no per-thread clock)."""
        self._requests.labels(endpoint).inc()
        if cpu_s is not None:
            self._request_cpu.labels(endpoint).inc(cpu_s)

    # --------------------------------------------------------------- bodies
    def metrics_text(self) -> str:
        chunks = []
        if self.scheduler is not None:
            chunks.append(self.scheduler.metrics_text())
        for source in self._sources:
            chunks.append(source())
        chunks.append(self._own.expose())
        return "".join(chunks)

    def trace_json(self) -> dict:
        if self.scheduler is None:
            return {"traceEvents": [], "displayTimeUnit": "ms"}
        return self.scheduler.tracer.chrome_trace()

    def flightrecorder_json(self, query: "dict | None" = None) -> dict:
        """GET /debug/flightrecorder[?pod=ns/name][&limit=N]: the bounded
        ring of per-pod decision records, newest first — what ``kubetpu
        explain pod/<ns>/<name>`` renders."""
        fr = getattr(self.scheduler, "flight_recorder", None)
        if fr is None:
            return {"enabled": False, "records": [], "count": 0}
        q = query or {}

        def one(name: str, default: str = "") -> str:
            v = q.get(name, default)
            return v[-1] if isinstance(v, list) else v

        try:
            limit = int(one("limit") or 256)
        except ValueError:
            limit = 256
        out = fr.records_json(pod=one("pod") or None, limit=limit)
        out["enabled"] = True
        return out

    def queue_json(self, query: "dict | None" = None) -> dict:
        """GET /debug/queue[?limit=N]: the scheduling queue's per-pod
        pending reasons — pool, attempts/requeues, unschedulable-plugin
        sets, backoff deadlines, accumulated queue wait (the one major
        subsystem that had no introspection endpoint; the sentinel's
        bundle capture reuses it)."""
        q = getattr(self.scheduler, "queue", None)
        if q is None:
            return {"enabled": False, "counts": {}, "pods": []}
        qq = query or {}
        raw = qq.get("limit", "")
        raw = raw[-1] if isinstance(raw, list) else raw
        try:
            limit = int(raw or 512)
        except ValueError:
            limit = 512
        out = q.debug_json(limit=limit)
        out["enabled"] = True
        return out

    def alerts_json(self) -> dict:
        """GET /debug/alerts: the sentinel's alert-lifecycle state
        (pending/firing/resolved, fingerprint-deduped)."""
        s = getattr(self.scheduler, "sentinel", None)
        if s is None:
            return {"enabled": False, "alerts": [], "firing": 0}
        out = s.alerts_json()
        out["enabled"] = True
        return out

    def bundle_json(self, query: "dict | None" = None) -> dict:
        """GET /debug/bundle[?id=N]: diagnostic-bundle summaries (or one
        full capture by id) from the sentinel's bounded ring."""
        s = getattr(self.scheduler, "sentinel", None)
        if s is None:
            return {"enabled": False, "bundles": [], "count": 0}
        out = s.bundles_json(query)
        out["enabled"] = True
        return out

    # -------------------------------------------------------------- control
    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "DiagnosticsServer":
        self._thread.start()
        return self

    def close(self) -> None:
        # shutdown() blocks on an event only serve_forever() sets — if
        # start() never ran, skip straight to releasing the socket
        if self._thread.is_alive():
            self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread.is_alive():
            self._thread.join(timeout=5)


def _iter_informers(informers):
    """Accept an owner holding informers (``_informers`` dict or
    ``_reflectors`` list), a dict, or a plain iterable of SharedInformers."""
    inner = getattr(informers, "_informers", None)
    if inner is not None:
        informers = inner
    else:
        reflectors = getattr(informers, "_reflectors", None)
        if reflectors is not None:
            informers = [r.informer for r in reflectors]
    if isinstance(informers, dict):
        return list(informers.values())
    return list(informers)
