"""The kubetpu command line — the cmd/kube-scheduler analog (layer 9).

Reference: cmd/kube-scheduler/app/server.go:93 (``NewSchedulerCommand`` →
``runCommand`` → ``Setup``/``Run``): parse a versioned
KubeSchedulerConfiguration file, build the scheduler, serve healthz +
metrics + configz, optionally leader-elect. Here the serving surface is the
extender webhook bridge (``kubetpu.bridge.server``) — the integration seam
a real kube-scheduler offloads Filter/Prioritize/Bind through — with the
same side endpoints (/healthz, /metrics, /configz).

Commands (the control-plane binaries + tooling):
- ``apiserver``           REST+watch object API over the in-memory store
- ``scheduler``           the scheduler against a remote API server
- ``controller-manager``  the controller family against a remote API server
- ``kubelet``             a hollow node agent (kubemark tier)
- ``serve``               the extender webhook bridge from a config file
- ``get`` / ``apply`` / ``delete``   kubectl-style object access
- ``check-config``        decode + validate a config file, loudly
- ``explain``             render a pod's scheduling flight-recorder record
                          (timeline + why-node-won / why-filtered) from a
                          scheduler's /debug/flightrecorder or a JSON dump
- ``store fsck|compact``  durable-store tooling: offline integrity report /
                          WAL-into-snapshot compaction for a persistence dir
- ``version``             print the framework version
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Any, Sequence


def _config_to_dict(obj: Any) -> Any:
    """Dataclass → plain JSON for /configz (live-config introspection)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {
            f.name: _config_to_dict(getattr(obj, f.name))
            for f in dataclasses.fields(obj)
        }
    if isinstance(obj, (list, tuple)):
        return [_config_to_dict(x) for x in obj]
    if isinstance(obj, dict):
        return {str(k): _config_to_dict(v) for k, v in obj.items()}
    return obj


def cmd_check_config(args) -> int:
    from .framework.configload import ConfigError, load_config

    try:
        cfg = load_config(args.config)
    except (ConfigError, OSError) as e:
        print(f"invalid: {e}", file=sys.stderr)
        return 1
    names = ", ".join(p.name for p in cfg.profiles)
    print(
        f"ok: {len(cfg.profiles)} profile(s) [{names}], "
        f"{len(cfg.extenders)} extender(s)"
    )
    return 0


def cmd_serve(args) -> int:
    from .bridge.server import ExtenderBackend, ExtenderServer
    from .framework import config as C
    from .framework.configload import ConfigError, load_config

    if args.config:
        try:
            cfg = load_config(args.config)
        except (ConfigError, OSError) as e:
            print(f"invalid config: {e}", file=sys.stderr)
            return 1
    else:
        cfg = C.SchedulerConfiguration()
    try:
        profile = cfg.profile(args.profile)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 1
    backend = ExtenderBackend(profile=profile)
    backend.configz_source = lambda: _config_to_dict(cfg)
    server = ExtenderServer(backend, host=args.host, port=args.port).start()
    print(f"kubetpu extender bridge serving on {server.url} "
          f"(profile {profile.name!r}; verbs: /filter /prioritize /bind "
          f"/preempt; /cache/nodes /cache/pods; /healthz /metrics /configz)",
          flush=True)
    try:
        import threading

        threading.Event().wait()   # serve until interrupted
    except KeyboardInterrupt:
        pass
    finally:
        server.close()
    return 0


def _make_exporter(telemetry: str, process: str, component: str,
                   replica: str = "", tracer=None, metrics_fn=None,
                   flight_fn=None, alerts_fn=None, bundles_fn=None,
                   embedded_collector=None):
    """One component's telemetry exporter from its ``--telemetry`` flag:
    "off" → None (byte-identical wire, zero export work), "embed" → the
    in-process collector transport, a URL → HTTP export to a remote
    collector. Started on its cadence thread."""
    if not telemetry or telemetry == "off":
        return None
    from .telemetry.exporter import EmbeddedCollectorClient, TelemetryExporter

    client = None
    url = telemetry
    if telemetry == "embed":
        if embedded_collector is None:
            raise ValueError("--telemetry embed needs an embedded collector")
        client = EmbeddedCollectorClient(embedded_collector)
        url = ""
    return TelemetryExporter(
        url, process=process, component=component, replica=replica,
        tracer=tracer, metrics_fn=metrics_fn, flight_fn=flight_fn,
        alerts_fn=alerts_fn, bundles_fn=bundles_fn,
        client=client,
    ).start()


def _install_stop_event():
    """SIGTERM/SIGINT → a threading.Event. SIGTERM matters — the launch
    supervisor's shutdown cascade is TERM-based, and a default-action TERM
    would skip the ``finally`` blocks that close exporters and (for the
    apiserver) flush+close the WAL through the PR-11 graceful path.
    Falls back to an unarmed event when handlers cannot be installed
    (non-main thread — in-process tests; ^C still raises there)."""
    import signal
    import threading

    stop = threading.Event()

    def _stop(_signum, _frame) -> None:
        stop.set()

    try:
        signal.signal(signal.SIGTERM, _stop)
        signal.signal(signal.SIGINT, _stop)
    except ValueError:
        pass
    return stop


def _serve_until_signal(stop=None) -> None:
    """Serve-loop park for the no-work commands (apiserver, collector,
    watch-driver): block until SIGTERM/SIGINT. Pass a pre-installed
    ``stop`` event (``_install_stop_event()`` called BEFORE the serving
    work began) so a TERM arriving during startup is never lost to the
    default disposition."""
    try:
        (stop if stop is not None else _install_stop_event()).wait()
    except KeyboardInterrupt:
        pass


def _attach_alert_sink(sentinel, args) -> str:
    """Bind ``--alert-sink`` to a live sentinel. Returns an error string
    (caller prints + exits non-zero) instead of raising — a bad sink
    spec is an operator typo, not a traceback."""
    spec = getattr(args, "alert_sink", "") or ""
    if not spec:
        return ""
    if sentinel is None:
        return "--alert-sink requires --sentinel on"
    from .telemetry.sentinel import AlertSink

    try:
        sentinel.sink = AlertSink(spec)
    except ValueError as e:
        return str(e)
    return ""


def cmd_apiserver(args) -> int:
    import os

    from .apiserver import APIServer, Registry
    from .store import MemStore
    from .store.wal import WALError
    from .controllers import install_quota_admission

    # handlers BEFORE any serving work: a supervisor TERM that lands
    # mid-startup must still run the graceful close, not the default kill
    stop = _install_stop_event()
    persistence = getattr(args, "persistence", "off")
    follow = getattr(args, "follow", "")
    replicated = bool(getattr(args, "replicated", False))
    if getattr(args, "replicate_from", "") and not follow:
        print("apiserver: --replicate-from requires --follow "
              "(the chain carries a follower's feed)", file=sys.stderr)
        return 2
    if follow and persistence != "off":
        # a follower's WAL is the leader's — local persistence on a
        # replica would fork the durability story, so refuse it early
        print("apiserver: --follow ignores --persistence "
              "(the leader owns the WAL)", file=sys.stderr)
        persistence = "off"
    try:
        store = MemStore(
            persistence=None if persistence == "off" else persistence,
            follower=bool(follow),
        )
    except WALError as e:
        # a corrupt persistence dir must fail LOUDLY at boot, never boot
        # an empty cluster over a recoverable one — `kubetpu store fsck`
        # diagnoses, deleting the dir is the explicit full-resync choice
        print(f"persistence dir unrecoverable: {e}", file=sys.stderr)
        return 1
    registry = Registry()
    # quota enforcement is admission-time (the reference's resourcequota
    # admission plugin): pod creates past a namespace's hard caps get 403;
    # the install also takes the per-namespace write lock so concurrent
    # creates cannot race past hard. Both engage only where a quota
    # exists: a cluster without one pays an O(1) count per pod
    install_quota_admission(registry, store)
    telemetry = getattr(args, "telemetry", "off")
    server = APIServer(
        store, host=args.host, port=args.port, registry=registry,
        wire=getattr(args, "wire", "binary"),
        collector=(telemetry == "embed"),
        sentinel=(getattr(args, "sentinel", "off") == "on"),
    )
    sink_err = _attach_alert_sink(server.sentinel, args)
    if sink_err:
        server.close()
        store.close()
        print(sink_err, file=sys.stderr)
        return 2
    # replication binds AFTER the listener exists (the lease identity /
    # advertised self URL is this server's own address) but BEFORE
    # start() — the first request served must already know its role
    peers = tuple(
        p.strip().rstrip("/")
        for p in (getattr(args, "peers", "") or "").split(",") if p.strip()
    )
    lease_s = float(getattr(args, "lease_duration", 5.0) or 5.0)
    if follow:
        from .store.replication import FollowerReplicator

        server.attach_replication(FollowerReplicator(
            store, follow, wire=getattr(args, "wire", "binary"),
            self_url=server.url, peers=peers,
            replica_index=int(getattr(args, "replica_index", 0) or 0),
            lease_duration_s=lease_s,
            # the election grace scales with the lease so a short-lease
            # plane fails over proportionally fast (at the 5s default
            # this is exactly the replicator's own 6s default)
            grace_s=1.2 * lease_s,
            upstream_url=getattr(args, "replicate_from", "") or "",
        ))
    elif replicated:
        from .store.replication import LeaderLease

        server.attach_replication(
            LeaderLease(store, server.url, lease_duration_s=lease_s)
        )
    server.start()
    exporter = _make_exporter(
        telemetry, process=f"apiserver-{os.getpid()}",
        component="apiserver", tracer=server.tracer,
        metrics_fn=server.metrics_text,
        alerts_fn=(
            server.sentinel.alerts_json if server.sentinel is not None
            else None
        ),
        bundles_fn=(
            server.sentinel.bundles_payload if server.sentinel is not None
            else None
        ),
        embedded_collector=server.collector,
    )
    recovered = ""
    if store.recovery_info is not None:
        ri = store.recovery_info
        recovered = (
            f"; recovered rv {ri.resource_version} "
            f"(snapshot {ri.snapshot_objects} objects @ rv "
            f"{ri.snapshot_rv} + {ri.replayed} replayed"
            + (f", torn tail truncated {ri.truncated_bytes}B"
               if ri.truncated_bytes else "")
            + ")"
        )
    # the machine-readable readiness banner FIRST (one line, the launch
    # supervisor's contract — --port 0 publishes the real address here),
    # then the human serving line
    from .launch.banner import emit_banner

    banner_fields = dict(
        url=server.url, readyz=server.url + "/readyz",
        wire=getattr(args, "wire", "binary"),
        persistence=("" if persistence == "off" else persistence),
        telemetry=telemetry,
        # which store core serves: the C++ one, or the Python fallback
        # after a disabled/failed build (kubetpu.native.build_status)
        store_core=("native" if store.native else "python"),
    )
    if server.replication is not None:
        banner_fields["role"] = server.replication.role
        if follow:
            banner_fields["leader"] = follow
    emit_banner("apiserver", **banner_fields)
    print(f"kubetpu apiserver serving on {server.url} "
          f"(REST: /apis/<kind>[/<key>], watch: ?watch=1&resourceVersion=N; "
          f"diagnostics: /metrics /healthz /readyz /livez /trace"
          + ("; telemetry collector embedded at /telemetry/"
             if telemetry == "embed" else "")
          + (f"; replication: {server.replication.role}"
             + (f" following {follow}" if follow else "")
             if server.replication is not None else "")
          + f"{recovered})",
          flush=True)
    try:
        _serve_until_signal(stop)
    finally:
        if exporter is not None:
            exporter.close()
        server.close()
        # the store is OURS (passed in, so server.close leaves it alone):
        # flush + close the WAL after the listener stops — a graceful
        # stop never leaves a torn tail
        store.close()
    return 0


def cmd_collector(args) -> int:
    """``kubetpu collector``: the standalone telemetry sink — span/
    metrics/flight-record ingest at /telemetry/export, the merged chrome
    trace at /telemetry/trace, the federated /metrics view, and the
    ``kubetpu top`` summary at /telemetry/top."""
    from .telemetry.collector import CollectorServer

    from .launch.banner import emit_banner

    stop = _install_stop_event()
    server = CollectorServer(host=args.host, port=args.port).start()
    emit_banner(
        "collector", url=server.url, readyz=server.url + "/readyz",
    )
    print(f"kubetpu collector serving on {server.url} "
          f"(ingest: POST /telemetry/export /telemetry/clock; views: "
          f"/telemetry/trace /telemetry/metrics /telemetry/flightrecorder "
          f"/telemetry/top /telemetry/alerts /telemetry/bundle; "
          f"/healthz /readyz)",
          flush=True)
    try:
        _serve_until_signal(stop)
    finally:
        server.close()
    return 0


def cmd_watch_driver(args) -> int:
    """``kubetpu watch-driver``: N concurrent pod watchers against an
    apiserver, as ONE dedicated process — the unit ``kubetpu up
    --watch-fanout N --fanout-procs M`` spreads its fan-out load over (M
    driver processes instead of N threads sharing one process's GIL)."""
    from .launch import WatchFanout
    from .launch.banner import emit_banner

    stop = _install_stop_event()
    fanout = WatchFanout(args.server, args.wire, args.watchers)
    emit_banner(
        "watch-driver", server=args.server, watchers=args.watchers,
        wire=args.wire,
    )
    print(f"kubetpu watch-driver: {args.watchers} watcher(s) against "
          f"{args.server} (wire {args.wire})", flush=True)
    try:
        _serve_until_signal(stop)
    finally:
        fanout.stop()
    return 0


def cmd_up(args) -> int:
    """``kubetpu up``: the whole control plane as real OS processes — one
    apiserver + N scheduler replicas (+ optional collector / watch-fanout
    drivers) under the launch supervisor: ephemeral ports published via
    readiness banners, /readyz-polled starts, declarative restart policy,
    SIGTERM-cascade shutdown riding every component's graceful-close
    path. ^C (or a TERM from the caller) tears the whole topology down."""
    from .launch import Cluster, SupervisorError
    from .launch.banner import emit_banner

    # handlers BEFORE the children exist: a TERM landing mid-startup must
    # still cascade — an orphaned control plane is the one unforgivable
    # supervisor failure
    stop = _install_stop_event()
    persistence = args.persistence if args.persistence != "off" else None
    cluster = Cluster(
        replicas=args.replicas,
        apiservers=getattr(args, "apiservers", 1),
        replication_chain=bool(getattr(args, "replication_chain", False)),
        partition=args.partition,
        wire=args.wire,
        engine=args.engine,
        topology=getattr(args, "topology", "off"),
        max_batch=args.max_batch,
        persistence=persistence,
        telemetry=args.telemetry,
        fanout_procs=args.fanout_procs,
        fanout_watchers=args.watch_fanout,
        restart=args.restart,
        prewarm=args.prewarm,
    )
    try:
        cluster.start()
    except (SupervisorError, ValueError) as e:
        print(f"kubetpu up failed: {e}", file=sys.stderr)
        cluster.shutdown()
        return 1
    try:
        fields = dict(apiserver=cluster.api_url, replicas=args.replicas,
                      partition=args.partition, wire=args.wire)
        if len(cluster.api_urls) > 1:
            fields["apiservers"] = len(cluster.api_urls)
            fields["followers"] = ",".join(cluster.api_urls[1:])
        if cluster.collector_url:
            fields["collector"] = cluster.collector_url
        emit_banner("cluster", **fields)
        for child in cluster.supervisor.children:
            url = child.url()
            # the children's stdout is captured, so what their banners
            # advertise about the machine is repeated here: the device a
            # scheduler holds, the store core an apiserver serves from
            held = {
                k: (child.banner or {})[k]
                for k in ("platform", "device_kind", "devices", "store_core")
                if k in (child.banner or {})
            }
            print(f"  {child.name:<16} pid {child.pid}"
                  + (f"  {url}" if url else "")
                  + (f"  {json.dumps(held)}" if held else ""), flush=True)
        print(f"kubetpu up: {cluster.n_processes()} process(es) ready — "
              f"apiserver {cluster.api_url} "
              f"({args.replicas} replica(s), {args.partition}, "
              f"restart {args.restart}); ^C to stop", flush=True)
        _serve_until_signal(stop)
    finally:
        cluster.shutdown()
    return 0


def _fmt_top_row(name: str, p: dict) -> list[str]:
    def num(key, suffix="", scale=1.0, digits=1):
        v = p.get(key)
        if v is None:
            return "-"
        return f"{v * scale:.{digits}f}{suffix}"

    e2e = (p.get("e2e_stages_ms") or {}).get("e2e") or {}
    return [
        name,
        p.get("component") or "-",
        p.get("replica") or "-",
        num("pods_per_s"),
        str(int(p["queue_depth"])) if "queue_depth" in p else "-",
        num("conflict_rate", "%", scale=100.0, digits=2),
        num("wal_fsync_p99_ms", "ms", digits=2),
        (f"{e2e['p99_ms']:.1f}ms" if e2e.get("p99_ms") is not None else "-"),
        (f"{p['alerts_firing']}!" if p.get("alerts_firing") else "-"),
        num("age_s", "s"),
    ]


def render_top(summary: dict) -> str:
    """The ``kubetpu top`` console body: one row per exporting process
    (pods/s, queue depth, conflict rate, WAL fsync p99, e2e p99, firing
    sentinel alerts) plus the collector's span-drop footer — firing
    alert names print inline under the table."""
    headers = ("PROCESS", "COMPONENT", "REPLICA", "PODS/S", "QUEUE",
               "CONFLICT", "FSYNC-P99", "E2E-P99", "ALERTS", "AGE")
    procs = summary.get("processes") or {}
    rows = [
        _fmt_top_row(name, p) for name, p in sorted(procs.items())
    ]
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    lines = [
        "  ".join(c.ljust(w) for c, w in zip(cols, widths)).rstrip()
        for cols in [list(headers), *rows]
    ]
    stages: dict = {}
    for name, p in sorted(procs.items()):
        for stage, v in (p.get("e2e_stages_ms") or {}).items():
            if stage != "e2e":
                stages.setdefault(stage, []).append(v.get("p99_ms") or 0.0)
    if stages:
        from .metrics.scheduler_metrics import E2E_STAGES

        parts = [
            f"{st} {max(stages[st]):.1f}" for st in E2E_STAGES
            if st in stages
        ]
        lines.append("staged p99 (ms, worst process): " + " → ".join(parts))
    for name, p in sorted(procs.items()):
        if p.get("firing_alerts"):
            lines.append(
                f"ALERTS FIRING [{name}]: " + ", ".join(p["firing_alerts"])
            )
    lines.append(
        f"collector: {len(procs)} process(es), "
        f"{summary.get('spans_dropped', 0)} span(s) dropped, "
        f"{summary.get('alerts_firing', 0)} alert(s) firing"
    )
    return "\n".join(lines)


def cmd_top(args) -> int:
    """``kubetpu top``: the live control-plane console — per-process
    pods/s, queue depth, conflict rate, WAL fsync p99 and staged e2e
    percentiles from a collector's /telemetry/top (``-o json`` for
    scripts, ``--watch`` to refresh)."""
    import time as _time
    import urllib.request

    url = args.collector.rstrip("/") + "/telemetry/top"
    while True:
        try:
            with urllib.request.urlopen(url, timeout=10) as resp:
                summary = json.load(resp)
        except OSError as e:
            print(f"cannot reach {url}: {e}", file=sys.stderr)
            return 2
        if args.output == "json":
            print(json.dumps(summary, indent=2), flush=True)
        else:
            print(render_top(summary), flush=True)
        if not args.watch:
            return 0
        try:
            _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0
        if args.output != "json":
            print("", flush=True)


def _http_json(url: str):
    """GET one JSON body, or (None, message) on transport failure."""
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.load(resp), ""
    except OSError as e:
        return None, f"cannot reach {url}: {e}"


def render_alerts(body: dict) -> str:
    """The ``kubetpu alerts`` console body — one row per alert, the
    per-process /debug/alerts shape and the collector's merged
    /telemetry/alerts shape both render (the merged rows carry a
    ``processes`` breakdown, the per-process ones a fingerprint)."""
    rows = body.get("alerts") or []
    if not rows:
        return "no alerts (every watched series within budget)"
    headers = ("STATE", "SEVERITY", "RULE", "VALUE", "FIRES", "WHERE")
    table = []
    for a in rows:
        procs = a.get("processes")
        if isinstance(procs, list):
            where = ",".join(
                str(p.get("process") or "?") for p in procs
            )
        else:
            where = str(body.get("process") or "-")
        value = a.get("value")
        table.append([
            str(a.get("state") or "-"),
            str(a.get("severity") or "-"),
            str(a.get("rule") or "-"),
            f"{value:.2f}" if isinstance(value, (int, float)) else "-",
            str(a.get("fires") or 0),
            where,
        ])
    widths = [
        max(len(h), *(len(r[i]) for r in table))
        for i, h in enumerate(headers)
    ]
    lines = [
        "  ".join(c.ljust(w) for c, w in zip(cols, widths)).rstrip()
        for cols in [list(headers), *table]
    ]
    for a in rows:
        if a.get("reason") and a.get("state") != "resolved":
            lines.append(f"  {a.get('rule')}: {a.get('reason')}")
    lines.append(
        f"{body.get('firing', 0)} firing, {body.get('pending', 0)} "
        f"pending, {body.get('resolved', 0)} resolved"
    )
    return "\n".join(lines)


def cmd_alerts(args) -> int:
    """``kubetpu alerts``: the anomaly sentinel's live alert table —
    one process's /debug/alerts (--server, the diagnostics URL) or the
    cluster-wide merge from a collector's /telemetry/alerts."""
    if getattr(args, "collector", ""):
        url = args.collector.rstrip("/") + "/telemetry/alerts"
    else:
        url = args.server.rstrip("/") + "/debug/alerts"
    body, err = _http_json(url)
    if body is None:
        print(err, file=sys.stderr)
        return 2
    if not body.get("enabled", True):
        print("anomaly sentinel is disabled on this process "
              "(--sentinel off)", file=sys.stderr)
        return 1
    if args.output == "json":
        print(json.dumps(body, indent=2))
    else:
        print(render_alerts(body))
    return 0


def cmd_bundle(args) -> int:
    """``kubetpu bundle``: triggered diagnostic bundles — summaries
    without --id, the full capture (py stacks, queue snapshot, WAL/cache
    stats, trace slice) with it; --out writes the capture to a file for
    attaching to an incident."""
    import urllib.parse

    if getattr(args, "collector", ""):
        base = args.collector.rstrip("/") + "/telemetry/bundle"
    else:
        base = args.server.rstrip("/") + "/debug/bundle"
    q = {}
    if args.id:
        q["id"] = args.id
    if getattr(args, "process", "") and getattr(args, "collector", ""):
        q["process"] = args.process
    url = base + ("?" + urllib.parse.urlencode(q) if q else "")
    body, err = _http_json(url)
    if body is None:
        print(err, file=sys.stderr)
        return 2
    if not body.get("enabled", True):
        print("anomaly sentinel is disabled on this process "
              "(--sentinel off)", file=sys.stderr)
        return 1
    if args.id:
        bundle = body.get("bundle")
        if bundle is None:
            print(body.get("error") or f"no bundle id {args.id}",
                  file=sys.stderr)
            return 1
        text = json.dumps(bundle, indent=2, default=str)
        if args.out:
            with open(args.out, "w", encoding="utf-8") as f:
                f.write(text + "\n")
            trig = bundle.get("trigger") or {}
            print(f"bundle {bundle.get('id')} "
                  f"({trig.get('rule') or 'manual'}, "
                  f"{len(bundle.get('sections') or {})} section(s), "
                  f"{len((bundle.get('trace') or {}).get('traceEvents') or ())}"
                  f" trace event(s)) -> {args.out}")
        else:
            print(text)
        return 0
    bundles = body.get("bundles") or []
    if args.output == "json":
        print(json.dumps(body, indent=2))
        return 0
    if not bundles:
        print("no diagnostic bundles captured (no alert has fired)")
        return 0
    for b in bundles:
        proc = b.get("process")
        print(f"bundle {b.get('id')}"
              + (f" [{proc}]" if proc else "")
              + f": rule={b.get('rule') or 'manual'} "
              f"severity={b.get('severity') or '-'} "
              f"sections={','.join(b.get('sections') or ())} "
              f"trace_events={b.get('trace_events', 0)}")
    print(f"{len(bundles)} bundle(s); "
          f"--id N for the full capture, --out FILE to save it")
    return 0


def _object_key(obj: Any) -> str:
    """Store key for a typed object: namespace/name when namespaced."""
    key = getattr(obj, "key", None)
    if isinstance(key, str):
        return key
    ns = getattr(obj, "namespace", None)
    name = getattr(obj, "name", None) or getattr(obj, "node_name", None)
    if name is None:
        raise ValueError(f"cannot derive a key for {type(obj).__name__}")
    return f"{ns}/{name}" if ns else str(name)


def _kind_buckets() -> dict:
    """Typed object -> store bucket, built from the SHARED bucket constants
    (one source of truth with the informers/controllers — a literal copy
    here could silently drift into a bucket nothing watches)."""
    from .client import informers as I
    from .controllers.daemonset import DAEMON_SETS
    from .controllers.deployment import DEPLOYMENTS
    from .controllers.job import JOBS
    from .controllers.replicaset import REPLICA_SETS
    from .controllers.resourceclaim import RESOURCE_CLAIM_TEMPLATES
    from .controllers.statefulset import STATEFUL_SETS

    return {
        "ResourceClaimTemplate": RESOURCE_CLAIM_TEMPLATES,
        "Node": I.NODES, "Pod": I.PODS, "ReplicaSet": REPLICA_SETS,
        "Deployment": DEPLOYMENTS, "Job": JOBS,
        "StatefulSet": STATEFUL_SETS, "DaemonSet": DAEMON_SETS,
        "Service": I.SERVICES, "Namespace": I.NAMESPACES,
        "PersistentVolume": I.PERSISTENT_VOLUMES,
        "PersistentVolumeClaim": I.PERSISTENT_VOLUME_CLAIMS,
        "StorageClass": I.STORAGE_CLASSES,
        "PodDisruptionBudget": I.PDBS,
        "PodGroup": I.POD_GROUPS, "DeviceClass": I.DEVICE_CLASSES,
        "ResourceSlice": I.RESOURCE_SLICES,
        "ResourceClaim": I.RESOURCE_CLAIMS,
        "Event": "events", "CronJob": "cronjobs",
        "ResourceQuota": "resourcequotas",
    }


def _retry_start(fn, what: str) -> None:
    """Component startup against a possibly-still-booting apiserver: retry
    transient transport failures forever (the reference components block on
    WaitForCacheSync the same way)."""
    import time

    while True:
        try:
            fn()
            return
        except ConnectionError as e:
            print(f"{what}: apiserver unavailable at startup, retrying: {e}",
                  file=sys.stderr, flush=True)
            time.sleep(2.0)


def _make_loop(run_once, period_s: float = 0.05, stop=None, clock=None):
    """Component work loop; ``stop`` (an Event from
    ``_install_stop_event``) makes SIGTERM a graceful exit through the
    caller's ``finally`` instead of a mid-cycle kill. ``clock`` (the
    scheduler's ``tracing.PhaseClock``) counts the completed iterations
    and takes the sleeps as its ``sleep`` phase."""
    import contextlib
    import time

    def sleep(seconds: float) -> None:
        with (clock.phase("sleep") if clock is not None
              else contextlib.nullcontext()):
            time.sleep(seconds)

    def loop() -> int:
        try:
            while stop is None or not stop.is_set():
                try:
                    run_once()
                except ConnectionError as e:
                    # apiserver unreachable: back off and retry — one
                    # restart must not kill the component
                    print(f"apiserver unavailable, retrying: {e}",
                          file=sys.stderr, flush=True)
                    sleep(2.0)
                    continue
                if clock is not None:
                    clock.iteration_done()
                sleep(period_s)
        except KeyboardInterrupt:
            pass
        return 0
    return loop


def _scheduler_iteration(sched, informers, is_leader=lambda: True,
                         membership=None):
    """One iteration of the served scheduler loop, as a callable for
    ``_make_loop``: pump the informers, run a cycle, drain the bind
    completions. An iteration that delivered an event, popped a pod or took
    a completion leaves a ``loop-iteration`` span with ``pump``, the
    cycle's spans and ``drain`` under it; an idle one leaves none (its time
    is in the phase clock's counters)."""
    tracer, clock = sched.tracer, sched.loop_clock

    def pump() -> int:
        with tracer.span("pump") as sp:
            rpc_s0 = clock.seconds["pump_rpc"]
            decode_s0 = informers.watch_decode_s
            deliveries = informers.pump()
            if sp is not None:
                sp.discard = not deliveries
                sp.attrs.update(
                    deliveries=deliveries,
                    rpc_s=round(clock.seconds["pump_rpc"] - rpc_s0, 6),
                    # the reply's decode, inside rpc_s; the rest of rpc_s
                    # is the wait for the apiserver and the read
                    decode_s=round(informers.watch_decode_s - decode_s0, 6),
                )
        return deliveries

    def once() -> None:
        # the envelope is long by design: no LogIfLong line per iteration
        with tracer.span("loop-iteration", log_long=False) as sp:
            work0 = sched.loop_work
            deliveries = 0
            if is_leader():
                if membership is not None:
                    membership.tick(sched)
                deliveries = pump()
                sched.schedule_batch()
                sched._drain_bind_completions()
            else:
                # not (or no longer) the leader: what is in flight must not
                # be bound by this process, nor stay popped
                sched.abandon_inflight()
            if sp is not None:
                sp.discard = not (deliveries or sched.loop_work != work0)
    return once


def _maybe_elect(args, store, component: str):
    """Optional --leader-elect wrapper: returns a tick() gate."""
    if not getattr(args, "leader_elect", False):
        return lambda: True
    import os
    import socket
    import uuid

    from .sched.leaderelection import LeaderElector, StoreLeaseClient

    elector = LeaderElector(
        client=StoreLeaseClient(store),
        # hostname + random suffix (client-go's id = hostname + "_" + uuid):
        # a bare PID collides across containers (every replica is PID 1)
        # and two same-identity electors would BOTH take the renew path
        identity=(
            f"{component}-{socket.gethostname()}-{os.getpid()}-"
            f"{uuid.uuid4().hex[:8]}"
        ),
        name=component,
    )
    return elector.tick


def cmd_scheduler(args) -> int:
    """The kube-scheduler binary: informers + batch loop against a remote
    API server (cmd/kube-scheduler/app/server.go Run shape)."""
    from .apiserver import RemoteStore
    from .client import SchedulerInformers, StoreClient
    from .client.events import EventRecorder
    from .framework import config as C
    from .framework.configload import ConfigError, load_config
    from .sched import Scheduler

    try:
        cfg = load_config(args.config) if args.config else C.SchedulerConfiguration()
    except (ConfigError, OSError) as e:
        print(f"invalid config: {e}", file=sys.stderr)
        return 1
    from . import device_stamp
    from .parallel.mesh import resolve_mesh

    # take the accelerator FIRST, and say so when it cannot be taken: a
    # chip belongs to one process, and what libtpu reports when a second
    # process asks (a multi-process lockfile error) does not name the cause
    try:
        held = device_stamp()
    except RuntimeError as e:
        print(
            "scheduler: cannot take the accelerator. A chip belongs to ONE "
            "process — is another scheduler (kubetpu up --replicas N, "
            "--processes N) already holding it? Run one scheduler per chip, "
            "or pin this one to the CPU with JAX_PLATFORMS=cpu.\n"
            f"  jax: {e}", file=sys.stderr,
        )
        return 1
    try:
        mesh = resolve_mesh(args.mesh)
    except ValueError as e:
        # --mesh on with a single visible device is a config error, not a
        # silent single-chip run misreported as multichip
        print(f"invalid --mesh: {e}", file=sys.stderr)
        return 1
    # flag validation BEFORE any real work: --diagnostics-port lost
    # argparse's type=int when it grew the ephemeral/off keywords, so a
    # typo must still die here with a usage error, not mid-startup
    diag_raw = str(getattr(args, "diagnostics_port", "off")).strip()
    if diag_raw not in ("off", "0", "ephemeral", "auto"):
        try:
            int(diag_raw)
        except ValueError:
            print(f"invalid --diagnostics-port {diag_raw!r} "
                  f"(a port number, 'ephemeral', or 'off')",
                  file=sys.stderr)
            return 1
    # handlers BEFORE the (possibly retrying) startup: a supervisor TERM
    # mid-boot must run the graceful teardown, not the default kill
    stop = _install_stop_event()
    telemetry = getattr(args, "telemetry", "off")
    store = RemoteStore(
        args.server, wire=getattr(args, "wire", "binary"),
        # trace-context propagation rides the telemetry switch: off =
        # byte-identical wire (no traceparent header / tp parameter)
        traceparent=(telemetry != "off"),
    )
    # cross-process federation: --partition declares this process one of
    # --replica-count replicas (hash rank / lease fair share / race); the
    # bare --replica-id backcompat stays race mode
    partition = getattr(args, "partition", "")
    membership = None
    if partition:
        from .sched.federation import ReplicaMembership

        try:
            membership = ReplicaMembership(
                store,
                replica_id=args.replica_id or "r0",
                partition=partition,
                replica_count=max(getattr(args, "replica_count", 0) or 1, 1),
                partitions=getattr(args, "partitions", 0) or None,
            )
        except ValueError as e:
            print(f"invalid federation flags: {e}", file=sys.stderr)
            return 1
    client = StoreClient(store)
    if membership is not None:
        client = membership.wrap_client(client)
    sched = Scheduler(
        client, cfg=cfg, engine=args.engine,
        max_batch=getattr(args, "max_batch", 1024),
        # this process IS a loop: it runs the two-stage cycle, so a batch's
        # device program runs under the loop's flush, drain and pump
        pipeline=True,
        encode_cache=(args.encode_cache == "on"),
        bulk=(args.bulk == "on"),
        mesh=mesh,
        topology=getattr(args, "topology", "off"),
        flight_recorder=(args.flight_recorder == "on"),
        replica_id=args.replica_id,
        federation_mode=(
            partition or ("race" if args.replica_id else "")
        ),
        recorder=EventRecorder(store, "kubetpu-scheduler"),
        sentinel=(getattr(args, "sentinel", "off") == "on"),
    )
    sched.enable_preemption()
    sink_err = _attach_alert_sink(sched.sentinel, args)
    if sink_err:
        print(sink_err, file=sys.stderr)
        return 2
    exporter = None
    if telemetry != "off":
        import os

        store.set_tracer(sched.tracer)  # client rpc spans join server spans
        fr = sched.flight_recorder
        exporter = _make_exporter(
            telemetry,
            process=(
                f"scheduler-{args.replica_id}" if args.replica_id
                else f"scheduler-{os.getpid()}"
            ),
            component="scheduler", replica=args.replica_id,
            tracer=sched.tracer, metrics_fn=sched.metrics_text,
            flight_fn=(
                (lambda: fr.records_json(limit=512))
                if fr is not None else None
            ),
            alerts_fn=(
                sched.sentinel.alerts_json if sched.sentinel is not None
                else None
            ),
            bundles_fn=(
                sched.sentinel.bundles_payload if sched.sentinel is not None
                else None
            ),
        )
    informers = SchedulerInformers(
        store, sched, bulk=(args.bulk == "on"),
        pod_filter=(
            membership.pod_filter() if membership is not None else None
        ),
    )
    _retry_start(informers.start, "scheduler informers")
    if args.prewarm:
        # pay the XLA bucket ladder up front so the first real cycles never
        # stall on compilation (the informers have already synced the node
        # set, so the warmed shapes match the live cluster)
        informers.pump()
        sched.prewarm()
    is_leader = _maybe_elect(args, store, "kube-scheduler")
    # --diagnostics-port: a number, 'off' (no listener), or 'ephemeral'
    # (bind port 0 — the launch supervisor's no-collision default; the
    # real address is published in the readiness banner; validated above)
    diag = None
    if diag_raw not in ("off", "0"):
        from .sched.diagnostics import DiagnosticsServer

        diag_port = 0 if diag_raw in ("ephemeral", "auto") else int(diag_raw)
        try:
            diag = DiagnosticsServer(
                sched, port=diag_port,
                # restart visibility: the client's watch-path reconnect
                # counter rides the scheduler's /metrics page, and its
                # decode clock (what the wire decode costs the loop thread
                # and the dispatcher's worker), and the informers' count of
                # the bind deltas they rebuilt
                metrics_sources=(store.reconnect_metrics_text,
                                 store.decode_metrics_text,
                                 informers.bind_delta_metrics_text),
            )
        except OSError as e:
            # a second scheduler on the host (HA standby) must not die on
            # the diagnostics side port; it just runs unobserved
            print(
                f"diagnostics port {diag_raw} unavailable "
                f"({e}); continuing without the diagnostics listener",
                file=sys.stderr, flush=True,
            )
        else:
            diag.add_informers(informers)
            diag.start()
    # the machine-readable readiness banner (launch supervisor contract):
    # printed only once the informers synced, so "banner seen" already
    # means "connected to the apiserver and caches listed"
    from .launch.banner import emit_banner

    banner_fields = dict(
        server=args.server, engine=args.engine,
        replica=args.replica_id, partition=partition,
        # what this scheduler holds (platform / device_kind / devices):
        # whoever launched it reads the chip — or its absence — here
        **held,
    )
    if diag is not None:
        banner_fields["url"] = diag.url
        banner_fields["readyz"] = diag.url + "/readyz"
    emit_banner("scheduler", **banner_fields)
    print(f"kubetpu scheduler running against {args.server} "
          f"(engine {args.engine}"
          + (f"; diagnostics on {diag.url}" if diag is not None else "")
          + (
              "; sentinel on (/debug/alerts /debug/bundle /debug/queue)"
              if sched.sentinel is not None else ""
          )
          + ")", flush=True)

    once = _scheduler_iteration(sched, informers, is_leader, membership)
    try:
        return _make_loop(once, stop=stop, clock=sched.loop_clock)()
    finally:
        try:
            # the cycle in flight is completed, and its binds and Events
            # written, before the leases go: a SIGTERM (a rolling restart)
            # drops no popped pod. An iteration that was not the leader
            # left nothing in flight
            sched.close()
        finally:
            if exporter is not None:
                exporter.close()
            if membership is not None:
                membership.release()
            if diag is not None:
                diag.close()


def cmd_controller_manager(args) -> int:
    """kube-controller-manager: every controller stepping over the remote
    store (cmd/kube-controller-manager controllermanager.go shape)."""
    from .apiserver import RemoteStore
    from .controllers import (
        CronJobController,
        DaemonSetController,
        DeploymentController,
        DisruptionController,
        GarbageCollector,
        JobController,
        NamespaceController,
        ResourceClaimController,
        ResourceQuotaController,
        StatefulSetController,
        NodeLifecycleController,
        PodGCController,
        ReplicaSetController,
        TaintEvictionController,
        TTLAfterFinishedController,
    )

    store = RemoteStore(args.server)
    ctrls = [
        DeploymentController(store),
        JobController(store),
        CronJobController(store),
        DaemonSetController(store),
        ResourceClaimController(store),
        StatefulSetController(store),
        ReplicaSetController(store),
        NodeLifecycleController(store, grace_s=args.node_monitor_grace),
        TaintEvictionController(store),
        PodGCController(store, terminated_threshold=args.terminated_pod_gc),
        DisruptionController(store),
        GarbageCollector(store),
        TTLAfterFinishedController(store),
        NamespaceController(store),
        ResourceQuotaController(store),
    ]
    for c in ctrls:
        _retry_start(c.start, type(c).__name__)
    is_leader = _maybe_elect(args, store, "kube-controller-manager")
    print(f"kubetpu controller-manager running against {args.server} "
          f"({len(ctrls)} controllers)", flush=True)

    def once():
        if not is_leader():
            return
        for c in ctrls:
            c.step()
    return _make_loop(once, period_s=0.2)()


def cmd_kubelet(args) -> int:
    """The hollow node agent (kubemark tier) against a remote API server."""
    from .api.wrappers import make_node
    from .apiserver import RemoteStore
    from .kubelet import HollowKubelet

    store = RemoteStore(args.server)
    kubelet = HollowKubelet(store, make_node(
        args.node_name, cpu_milli=args.cpu_milli, memory=args.memory,
        pods=args.pods,
    ))
    _retry_start(kubelet.start, f"kubelet {args.node_name}")
    print(f"kubetpu kubelet {args.node_name} registered with {args.server}",
          flush=True)
    return _make_loop(kubelet.pump, period_s=0.2)()


# kubectl-style table printers: kind bucket -> (headers, row fn) — the
# printers registry shape (staging/src/k8s.io/kubectl printers; server-side
# TableConvertor columns per kind)
def _printer_for(bucket: str):
    def pods(key, o):
        return (key, o.phase or "", o.node_name or "<pending>",
                str(getattr(o, "priority", 0)))

    def nodes(key, o):
        status = "SchedulingDisabled" if o.unschedulable else "Ready"
        alloc = o.allocatable_dict()
        return (key, status, str(alloc.get("cpu", "")),
                str(alloc.get("memory", "")))

    def workload(key, o):
        return (key, str(getattr(o, "replicas", "")))

    def jobs(key, o):
        status = ("Complete" if o.complete
                  else "Failed" if o.failed_state else "Running")
        return (key, f"{o.succeeded}/{o.completions}", status)

    def events(key, o):
        return (o.type, o.reason, o.regarding, str(o.count), o.note)

    def quotas(key, o):
        pairs = ", ".join(
            f"{k}: {o.used_dict().get(k, 0)}/{v}" for k, v in o.hard
        )
        return (key, pairs)

    table = {
        "pods": (("NAME", "STATUS", "NODE", "PRIORITY"), pods),
        "nodes": (("NAME", "STATUS", "CPU(m)", "MEMORY"), nodes),
        "replicasets": (("NAME", "REPLICAS"), workload),
        "deployments": (("NAME", "REPLICAS"), workload),
        "statefulsets": (("NAME", "REPLICAS"), workload),
        "jobs": (("NAME", "COMPLETIONS", "STATUS"), jobs),
        "events": (("TYPE", "REASON", "REGARDING", "COUNT", "NOTE"), events),
        "resourcequotas": (("NAME", "USAGE"), quotas),
    }
    return table.get(
        bucket, (("NAME",), lambda key, o: (key,))
    )


def _print_table(bucket: str, items) -> None:
    headers, row_fn = _printer_for(bucket)
    rows = [row_fn(key, obj) for key, obj in items]
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    for cols in [headers, *rows]:
        print("  ".join(c.ljust(w) for c, w in zip(cols, widths)).rstrip())


def cmd_get(args) -> int:
    import yaml as _yaml

    from .api import scheme
    from .apiserver import RemoteStore

    store = RemoteStore(args.server)
    if args.key:
        obj, rv = store.get(args.kind, args.key)
        if obj is None:
            print(f"{args.kind}/{args.key} not found", file=sys.stderr)
            return 1
        if args.output == "yaml":
            print(_yaml.safe_dump(scheme.encode(obj), sort_keys=False))
        else:
            print(json.dumps(scheme.encode(obj), indent=2))
        return 0
    selectors = dict(
        label_selector=args.selector or "",
        field_selector=args.field_selector or "",
    )
    items, rv = store.list(args.kind, **selectors)
    if args.output == "json":
        print(json.dumps([scheme.encode(o) for _, o in items], indent=2))
    elif args.output == "yaml":
        print(_yaml.safe_dump([scheme.encode(o) for _, o in items],
                              sort_keys=False))
    else:
        _print_table(args.kind, sorted(items))
    if not args.watch:
        return 0
    # kubectl get -w: follow the (selector-scoped) watch stream
    w = store.watch(args.kind, rv, stream=True, **selectors)
    try:
        import time as _time

        while True:
            for ev in w.poll():
                if ev.type == "DELETED":
                    print(f"{ev.key}\tDELETED", flush=True)
                else:
                    _print_table(args.kind, [(ev.key, ev.obj)])
            _time.sleep(0.2)
    except KeyboardInterrupt:
        return 0
    finally:
        w.close()


def cmd_apply(args) -> int:
    """Create-or-update kind-tagged YAML/JSON documents (kubectl apply)."""
    import yaml

    from .api import scheme
    from .apiserver import RemoteStore
    from .store.memstore import ConflictError

    store = RemoteStore(args.server)
    with open(args.file) as f:
        docs = [d for d in yaml.safe_load_all(f.read()) if d]
    applied = 0
    for doc in docs:
        obj = scheme.decode(doc)
        kind = _kind_buckets().get(type(obj).__name__)
        if kind is None:
            print(f"no bucket for kind {type(obj).__name__}", file=sys.stderr)
            return 1
        key = _object_key(obj)
        try:
            store.create(kind, key, obj)
        except ConflictError:
            store.update(kind, key, obj)
        applied += 1
        print(f"{kind}/{key} applied")
    return 0 if applied else 1


def cmd_delete(args) -> int:
    from .apiserver import RemoteStore

    store = RemoteStore(args.server)
    try:
        store.delete(args.kind, args.key)
    except KeyError:
        print(f"{args.kind}/{args.key} not found", file=sys.stderr)
        return 1
    print(f"{args.kind}/{args.key} deleted")
    return 0


def _render_gang_explain(rec: dict) -> str:
    """A GANG placement record: the topology rationale — the winning
    placement, its slice-alignment score, which slices the search
    considered, the fragmentation delta, and (preemption mode) the ONE
    evicted gang with its member pods."""
    lines = [
        f"Gang {rec['pod']} — status {rec.get('status')}"
        + (f", engine {rec['engine']}" if rec.get("engine") else "")
        + (f", replica {rec['replica']}" if rec.get("replica") else "")
    ]
    lines.append(
        f"  members {rec.get('members')}, quorum need {rec.get('need')}"
    )
    if rec.get("placement") is not None:
        head = f"  decision: {rec['status']} on {rec['placement']}"
        if rec.get("alignment_score") is not None:
            head += f" (alignment {rec['alignment_score']})"
        lines.append(head)
    if rec.get("slices_considered"):
        lines.append(
            "    slices considered: " + ", ".join(rec["slices_considered"])
        )
    if rec.get("fragmentation_delta") is not None:
        lines.append(
            f"    fragmentation delta: {rec['fragmentation_delta']:+d} "
            f"free slice(s) newly opened"
        )
    if rec.get("victim_group"):
        victims = rec.get("preemption_victims") or ()
        lines.append(
            f"  preemption: evicting gang {rec['victim_group']}"
            + (f" (victims: {', '.join(victims)})" if victims else "")
        )
    return "\n".join(lines)


def _render_explain(rec: dict) -> str:
    """One flight-recorder record as the ``kubetpu explain`` report:
    staged timeline + decision reasoning (sched.flightrecorder)."""
    from .metrics.scheduler_metrics import E2E_STAGES

    if rec.get("kind") == "gang":
        return _render_gang_explain(rec)
    lines = [
        f"Pod {rec['pod']} — cycle {rec.get('cycle')}, "
        f"profile {rec.get('profile')}, attempts {rec.get('attempts')}, "
        f"status {rec.get('status')}"
        # federation attribution: which replica made this decision
        # (absent/empty in single-scheduler mode — render nothing)
        + (
            f", replica {rec['replica']}" if rec.get("replica") else ""
        )
    ]
    if rec.get("trace_id"):
        lines.append(f"  trace id: {rec['trace_id']}")
    stages = rec.get("stages_ms") or {}
    if stages:
        parts = [
            f"{st} {stages[st]:.2f}" for st in E2E_STAGES
            if st in stages and st != "e2e"
        ]
        e2e = stages.get("e2e")
        lines.append(
            "  timeline (ms): " + " → ".join(parts)
            + (f"  |  e2e {e2e:.2f}" if e2e is not None else "")
        )
    elif rec.get("queue_wait_s") is not None:
        lines.append(
            f"  queue_wait {rec['queue_wait_s'] * 1000:.2f} ms, "
            f"encode {rec.get('encode_s', 0) * 1000:.2f} ms, "
            f"kernel {rec.get('kernel_s', 0) * 1000:.2f} ms (not yet bound)"
        )
    win = rec.get("win")
    if rec.get("node"):
        head = f"  decision: {rec['status']} on {rec['node']}"
        if win and win.get("score") is not None:
            head += f" (score {win['score']}"
            if win.get("margin") is not None:
                head += f", margin {win['margin']:+d}"
            head += f", {rec.get('view', 'cycle-start')} view)"
        lines.append(head)
    else:
        lines.append("  decision: no feasible node")
    top = rec.get("top_nodes")
    if top:
        lines.append("    top nodes: " + "  ".join(
            f"{t['node']}={t['score']}" for t in top
        ))
    if rec.get("engine") == "packing" and rec.get("objective_value") is not None:
        # packing rationale: the cluster objective this cycle optimized,
        # plus the greedy counterfactual — top_nodes[0] is the cycle-start
        # masked argmax, i.e. what the greedy scan would have picked first
        line = (
            f"  packing: objective {rec['objective_value']:.3f}"
        )
        if rec.get("solver_iters") is not None:
            line += f", {rec['solver_iters']} solver iters"
        counterfactual = top[0]["node"] if top else None
        if counterfactual and rec.get("node"):
            line += (
                f"; greedy would pick {counterfactual}"
                if counterfactual != rec["node"]
                else "; greedy agrees"
            )
        lines.append(line)
    rejected = rec.get("rejected_by")
    if rejected is not None:
        total = rec.get("total_nodes", 0)
        feasible = rec.get("feasible_nodes", 0)
        lines.append(
            f"    filtered: {total - feasible}/{total} nodes infeasible"
            + (
                " — " + ", ".join(
                    f"{plugin} {cnt}"
                    + (
                        f" (e.g. {', '.join(ex)})"
                        if (ex := (rec.get('rejected_examples') or {}).get(
                            plugin
                        )) else ""
                    )
                    for plugin, cnt in sorted(rejected.items())
                ) if rejected else ""
            )
        )
    elif rec.get("skipped_reason"):
        # satellite of the mesh path: the per-plugin rejection kernel is
        # host-gather only, so sharded cycles skip it EXPLICITLY — render
        # the reason instead of an empty breakdown masquerading as
        # "no rejections"
        lines.append(
            "    filtered: per-plugin breakdown skipped "
            f"({rec['skipped_reason']})"
        )
    if rec.get("nominated_node"):
        line = f"  preemption: nominated {rec['nominated_node']}"
        victims = rec.get("preemption_victims")
        if victims:
            line += f" (victims: {', '.join(victims)})"
        lines.append(line)
    for hop in rec.get("requeue", ()):
        lines.append(
            f"  requeued → {hop.get('queue')}"
            + (f" [{', '.join(hop['plugins'])}]" if hop.get("plugins") else "")
            + (" (error status)" if hop.get("error") else "")
        )
    if rec.get("bind_error"):
        lines.append(f"  bind error: {rec['bind_error']}")
    return "\n".join(lines)


def _pod_event_lines(api_url: str, target: str) -> list[str]:
    """The pod's Event timeline from an apiserver ("events" bucket) —
    what every recorder said about it (Scheduled, FailedScheduling, …),
    ordered by last occurrence, aggregation counts shown."""
    import time as _time

    from .apiserver import RemoteStore

    items, _rv = RemoteStore(api_url).list("events")
    evs = [
        o for _k, o in items
        if getattr(o, "regarding", "") == f"Pod/{target}"
    ]
    evs.sort(key=lambda e: getattr(e, "last_timestamp", 0.0) or 0.0)
    lines = []
    for e in evs:
        last = getattr(e, "last_timestamp", 0.0) or 0.0
        ts = _time.strftime("%H:%M:%S", _time.localtime(last)) if last else "-"
        count = getattr(e, "count", 1) or 1
        lines.append(
            f"  {ts}  {e.type:<8} {e.reason:<18} {e.note}"
            + (f"  (x{count})" if count > 1 else "")
            + f"  [{e.reporting_controller}]"
        )
    return lines


def cmd_explain(args) -> int:
    """``kubetpu explain pod/<ns>/<name>``: fetch the pod's decision record
    from a running scheduler's /debug/flightrecorder (--server, the
    diagnostics URL) or a dumped recorder JSON (--file) and render its
    timeline + win/filter reasoning; ``--api URL`` appends the pod's
    Event timeline from the apiserver (the recorders' view)."""
    target = args.target
    if target.startswith("pod/"):
        target = target[len("pod/"):]
    if "/" not in target:
        target = f"default/{target}"
    if args.file:
        with open(args.file, encoding="utf-8") as f:
            body = json.load(f)
    else:
        import urllib.parse
        import urllib.request

        if getattr(args, "collector", ""):
            # the collector's merged view: a pod's record is findable
            # whichever replica scheduled it (one process's
            # /debug/flightrecorder only knows its own decisions)
            url = (
                args.collector.rstrip("/")
                + "/telemetry/flightrecorder?pod="
                + urllib.parse.quote(target, safe="")
            )
        else:
            url = (
                args.server.rstrip("/")
                + "/debug/flightrecorder?pod="
                + urllib.parse.quote(target, safe="")
            )
        try:
            with urllib.request.urlopen(url, timeout=10) as resp:
                body = json.load(resp)
        except OSError as e:
            print(f"cannot reach {url}: {e}", file=sys.stderr)
            return 2
    if not body.get("enabled", True):
        print("flight recorder is disabled on this scheduler "
              "(--flight-recorder off)", file=sys.stderr)
        return 1
    records = [
        r for r in body.get("records", ()) if r.get("pod") == target
    ]
    event_lines: list[str] = []
    if getattr(args, "api", ""):
        try:
            event_lines = _pod_event_lines(args.api, target)
        except (ConnectionError, OSError) as e:
            print(f"cannot fetch events from {args.api}: {e}",
                  file=sys.stderr)
    if not records:
        if event_lines:
            # no decision record here (other replica, or ring-evicted)
            # but the recorders' Event trail still tells the story
            print(f"no flight-recorder record for pod {target}; "
                  f"event timeline:")
            print("\n".join(event_lines))
            return 0
        print(f"no flight-recorder record for pod {target} "
              f"(evicted from the ring, or never scheduled here)",
              file=sys.stderr)
        return 1
    if args.output == "json":
        print(json.dumps(records if args.all else records[0], indent=2))
        return 0
    for rec in records if args.all else records[:1]:
        print(_render_explain(rec))
    if event_lines:
        print("event timeline:")
        print("\n".join(event_lines))
    return 0


def cmd_store_fsck(args) -> int:
    """``kubetpu store fsck --dir D``: offline integrity report for a
    persistence dir — snapshot validity, per-segment record counts, torn
    tail position, replay-chain continuity. Exit 0 = recovery would
    succeed cleanly."""
    from .api import types  # noqa: F401 — register kinds for decode
    from .store.wal import fsck

    report = fsck(args.dir)
    if args.output == "json":
        print(json.dumps(report, indent=2))
    else:
        print(f"persistence dir {report['dir']}: "
              f"{'OK' if report['ok'] else 'PROBLEMS'} "
              f"(replay chain reaches rv {report.get('resource_version', 0)})")
        for s in report["snapshots"]:
            state = (
                f"{s['objects']} objects" if s.get("valid")
                else f"INVALID: {s.get('error')}"
            )
            print(f"  snapshot {s['file']} @ rv {s['rv']}: {state}")
        for s in report["segments"]:
            extra = ""
            if "torn_at" in s:
                extra = f", torn tail at offset {s['torn_at']}"
            if "error" in s:
                extra += f", ERROR: {s['error']}"
            print(f"  segment {s['file']}: {s['records']} records{extra}")
        for e in report["errors"]:
            print(f"  error: {e}")
    return 0 if report["ok"] else 1


def cmd_store_compact(args) -> int:
    """``kubetpu store compact --dir D``: offline compaction — recover the
    dir into a fresh core, write one snapshot at the recovered revision,
    truncate every superseded segment/snapshot. Run it against a STOPPED
    apiserver's dir to bound the next boot's replay."""
    from .api import types  # noqa: F401 — register kinds for decode
    from .store import MemStore
    from .store.wal import WALError

    try:
        store = MemStore(persistence=args.dir)
    except WALError as e:
        print(f"unrecoverable: {e}", file=sys.stderr)
        return 1
    ri = store.recovery_info
    n_objects = len(store.dump())
    path = store.compact()
    store.close()
    print(f"compacted {args.dir} at rv {ri.resource_version}: "
          f"snapshot {path} ({n_objects} objects; was snapshot@rv"
          f"{ri.snapshot_rv} + {ri.replayed} tail records)")
    return 0


def cmd_version(_args) -> int:
    from . import __version__

    print(f"kubetpu {__version__}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="kubetpu",
        description="TPU-native scheduling framework (kube-scheduler parity)",
    )
    sub = p.add_subparsers(dest="command", required=True)

    serve = sub.add_parser(
        "serve", help="run the extender webhook bridge from a config file"
    )
    serve.add_argument("--config", default="", help="KubeSchedulerConfiguration file")
    serve.add_argument("--profile", default=None, help="profile (schedulerName) to serve")
    serve.add_argument("--host", default="127.0.0.1")
    serve.add_argument("--port", type=int, default=10259)
    serve.set_defaults(fn=cmd_serve)

    api = sub.add_parser(
        "apiserver",
        help="serve the REST+watch object API over an in-memory store",
    )
    api.add_argument("--host", default="127.0.0.1")
    api.add_argument("--port", type=int, default=10250)
    api.add_argument("--wire", default="binary", choices=["binary", "json"],
                     help="wire protocol: 'binary' negotiates the compact "
                          "binary codec per request via Accept/Content-Type "
                          "(JSON clients keep working unchanged); 'json' is "
                          "the escape hatch — a JSON-only server that 415s "
                          "binary bodies, exactly what a pre-binary build "
                          "does")
    api.add_argument("--persistence", default="off", metavar="DIR|off",
                     help="durability: a directory path turns on the "
                          "write-ahead log + compaction snapshots "
                          "(kubetpu.store.wal) — every committed write is "
                          "logged-then-applied and fsync'd before the ack, "
                          "restart recovers snapshot+tail with "
                          "resourceVersion continuity (reconnecting "
                          "watchers take a bounded relist). 'off' (default) "
                          "is the memory-only store, byte-identical to the "
                          "pre-WAL behavior")
    api.add_argument("--telemetry", default="off", metavar="URL|embed|off",
                     help="telemetry plane: a collector URL exports this "
                          "apiserver's server spans + /metrics there on a "
                          "1s cadence; 'embed' mounts the collector ON this "
                          "server (/telemetry/*) and self-ingests — the "
                          "single-process sink; 'off' (default) exports "
                          "nothing and the wire stays byte-identical")
    api.add_argument("--sentinel", default="off", choices=["on", "off"],
                     help="embed the anomaly sentinel: burn-rate/outlier "
                          "rules over this apiserver's own /metrics (WAL "
                          "fsync stalls, encode-cache collapse), alert "
                          "state at /debug/alerts, triggered diagnostic "
                          "bundles at /debug/bundle; 'off' (default) runs "
                          "zero evaluation work")
    api.add_argument("--alert-sink", default="", metavar="file:PATH|webhook:URL",
                     help="out-of-process sentinel alert delivery: "
                          "'file:PATH' appends one ndjson line per alert "
                          "transition; 'webhook:URL' POSTs the transition "
                          "JSON. Delivery failures are counted "
                          "(sentinel_sink_errors), never fatal. Requires "
                          "--sentinel on")
    api.add_argument("--replicated", action="store_true",
                     help="serve as the replicated read plane's LEADER: "
                          "hold the apiserver-writer lease in this store "
                          "(renewals replicate, so the lease doubles as "
                          "the heartbeat) and serve the WAL log-shipping "
                          "feed at /replication/log for followers")
    api.add_argument("--follow", default="", metavar="URL",
                     help="serve as a FOLLOWER of the given leader "
                          "apiserver: bootstrap from its /replication/"
                          "snapshot, tail /replication/log into a local "
                          "replica store, serve reads/lists/watches from "
                          "replayed state at full resourceVersion "
                          "continuity, and 307-redirect writes to the "
                          "leader. On leader death the most-caught-up "
                          "follower wins the writer lease (failover by "
                          "log position)")
    api.add_argument("--peers", default="", metavar="URL,URL,...",
                     help="the full apiserver electorate (leader + all "
                          "followers) — a failing-over follower polls "
                          "these /replication/status endpoints to defer "
                          "to any more-caught-up peer")
    api.add_argument("--replica-index", type=int, default=0,
                     help="this follower's stable index (election "
                          "tie-break: equal log position → lowest index "
                          "wins)")
    api.add_argument("--replicate-from", default="", metavar="URL",
                     help="CHAINED shipping: tail the replication feed "
                          "from this peer (another follower re-serving "
                          "/replication/log) instead of the leader — "
                          "leader egress stays O(direct fan-out). Writes "
                          "still redirect to --follow's leader; a stale "
                          "(fenced-epoch) or dead upstream falls this "
                          "replica back to the leader's feed. Requires "
                          "--follow")
    api.add_argument("--lease-duration", type=float, default=5.0,
                     help="writer-lease duration in seconds — the "
                          "failover detection floor (default 5.0)")
    api.set_defaults(fn=cmd_apiserver)

    check = sub.add_parser("check-config", help="validate a config file")
    check.add_argument("config")
    check.set_defaults(fn=cmd_check_config)

    schd = sub.add_parser(
        "scheduler", help="run the scheduler against a remote API server"
    )
    schd.add_argument("--server", required=True, help="API server base URL")
    schd.add_argument("--config", default="", help="KubeSchedulerConfiguration file")
    schd.add_argument("--engine", default="greedy",
                      choices=["greedy", "batched", "packing"])
    schd.add_argument("--encode-cache", default="on", choices=["on", "off"],
                      help="event-time template-keyed pod encoding: static "
                           "tensor rows built at informer delivery and "
                           "gathered at cycle time; cached encodes are "
                           "bit-identical to fresh ones ('off' is the "
                           "debugging escape hatch)")
    schd.add_argument("--bulk", default="on", choices=["on", "off"],
                      help="opportunistic API-plane batching: a cycle's "
                           "binds/status patches flush as bulk RPCs at the "
                           "cycle boundary and the informer bundle polls "
                           "all kinds in one batched request; bindings "
                           "stay pod-for-pod identical to per-call mode "
                           "('off' is the debugging escape hatch)")
    schd.add_argument("--mesh", default="off", choices=["on", "off", "auto"],
                      help="shard the node axis of the scheduling tensors "
                           "over a device mesh (parallel.mesh rules): the "
                           "resident node block becomes a sharded resident "
                           "block with per-shard routed delta uploads, and "
                           "both engines run SPMD with XLA-inserted "
                           "collectives. 'auto' engages when >1 device is "
                           "visible; 'on' requires one; assignments are "
                           "bit-identical to single-device either way")
    schd.add_argument("--topology", default="off",
                      choices=["on", "off", "auto"],
                      help="node-topology axis for scoring + gang "
                           "placement: rack/TPU-slice labels become "
                           "per-node coordinate tensors, gangs land "
                           "alignment-first via per-slice placement "
                           "candidates, the packing objective gains "
                           "slice-fragmentation terms, and preemption "
                           "can evict ONE low-priority gang to free a "
                           "contiguous slice. 'auto' engages only when "
                           "nodes carry topology labels; 'off' (and "
                           "'auto' on unlabeled clusters) is "
                           "bit-identical to before")
    schd.add_argument("--flight-recorder", default="on",
                      choices=["on", "off"],
                      help="scheduling flight recorder + per-pod staged "
                           "latency attribution: bounded ring of decision "
                           "records at /debug/flightrecorder (rendered by "
                           "'kubetpu explain') and the "
                           "scheduler_e2e_scheduling_duration_seconds"
                           "{stage} histograms; 'off' is the overhead "
                           "escape hatch — decisions are identical")
    schd.add_argument("--prewarm", action="store_true",
                      help="compile the assign program for the full "
                           "batch-size bucket ladder at startup, so "
                           "steady state never pays XLA compilation "
                           "mid-cycle")
    schd.add_argument("--replica-id", default="",
                      help="active-active federation stamp (e.g. r0): "
                           "marks this process as one of N replicas racing "
                           "the same apiserver — cycle records, flight-"
                           "recorder entries and the federation conflict "
                           "counter carry it, and the CAS bind path "
                           "arbitrates overlap (409 losers requeue with "
                           "conflict backoff). Empty = single scheduler. "
                           "Contrast --leader-elect, which is "
                           "active/PASSIVE (one leader runs, the rest "
                           "stand by)")
    schd.add_argument("--wire", default="binary", choices=["binary", "json"],
                      help="client wire protocol: 'binary' advertises the "
                           "compact binary codec and switches to it once "
                           "the server confirms the dialect (a 415 falls "
                           "back to JSON permanently — mixed-version pairs "
                           "keep working); 'json' pins the original JSON "
                           "wire")
    schd.add_argument("--partition", default="",
                      choices=["", "race", "hash", "lease"],
                      help="cross-process federation partition mode (with "
                           "--replica-count N): 'race' = every replica "
                           "sees every pod, the CAS bind arbitrates; "
                           "'hash' = static crc32 rank of --replica-count "
                           "(no overlap; a supervisor respawn re-adopts "
                           "the rank's backlog via the informer relist); "
                           "'lease' = epoch-fenced renewable partition "
                           "leases in the SHARED store (expiry/fair-share/"
                           "fencing work across processes). Empty with "
                           "--replica-id = race (backcompat)")
    schd.add_argument("--replica-count", type=int, default=0,
                      help="declared replica count for --partition "
                           "hash|lease (cross-process membership is "
                           "supervisor-declared, not gossiped)")
    schd.add_argument("--partitions", type=int, default=0,
                      help="lease-mode keyspace partitions (default "
                           "2x replica count)")
    schd.add_argument("--max-batch", type=int, default=1024,
                      help="max pods per scheduling cycle batch")
    schd.add_argument("--leader-elect", action="store_true")
    schd.add_argument("--diagnostics-port", default="10251",
                      metavar="N|ephemeral|off",
                      help="side port for /metrics /healthz /readyz /livez "
                           "/trace; 'ephemeral' binds port 0 and publishes "
                           "the real address in the readiness banner (the "
                           "supervisor default — parallel runs never "
                           "collide); 'off' (or 0) disables")
    schd.add_argument("--telemetry", default="off", metavar="URL|off",
                      help="telemetry plane: a collector URL stamps a W3C-"
                           "style traceparent on every RPC (binary envelope "
                           "field or JSON header — the apiserver joins its "
                           "server span to the client span) and exports "
                           "spans + /metrics + flight records there on a 1s "
                           "cadence; 'off' (default) exports nothing and "
                           "every request is byte-identical to a pre-"
                           "telemetry build")
    schd.add_argument("--sentinel", default="off", choices=["on", "off"],
                      help="anomaly sentinel: declarative burn-rate SLO "
                           "rules + robust outlier detection over this "
                           "scheduler's own /metrics, evaluated at the "
                           "cycle boundary (alert lifecycle at "
                           "/debug/alerts, a diagnostic bundle — py "
                           "stacks, queue snapshot, trace slice — "
                           "captured at fire time at /debug/bundle; "
                           "rendered by 'kubetpu alerts'/'kubetpu "
                           "bundle'); 'off' (default) runs zero "
                           "evaluation work")
    schd.add_argument("--alert-sink", default="",
                      metavar="file:PATH|webhook:URL",
                      help="out-of-process sentinel alert delivery: "
                           "'file:PATH' appends one ndjson line per alert "
                           "transition; 'webhook:URL' POSTs the "
                           "transition JSON. Delivery failures are "
                           "counted, never fatal. Requires --sentinel on")
    schd.set_defaults(fn=cmd_scheduler)

    cm = sub.add_parser(
        "controller-manager",
        help="run the controller family against a remote API server",
    )
    cm.add_argument("--server", required=True)
    cm.add_argument("--node-monitor-grace", type=float, default=40.0)
    cm.add_argument("--terminated-pod-gc", type=int, default=0)
    cm.add_argument("--leader-elect", action="store_true")
    cm.set_defaults(fn=cmd_controller_manager)

    kblt = sub.add_parser(
        "kubelet", help="run a hollow node agent (kubemark tier)"
    )
    kblt.add_argument("--server", required=True)
    kblt.add_argument("--node-name", required=True)
    kblt.add_argument("--cpu-milli", type=int, default=4000)
    kblt.add_argument("--memory", type=int, default=16 * 1024**3)
    kblt.add_argument("--pods", type=int, default=110)
    kblt.set_defaults(fn=cmd_kubelet)

    get = sub.add_parser("get", help="list/get objects from an API server")
    get.add_argument("kind")
    get.add_argument("key", nargs="?", default="")
    get.add_argument("--server", required=True)
    get.add_argument("-o", "--output", default="table",
                     choices=("table", "json", "yaml"))
    get.add_argument("-l", "--selector", default="",
                     help="label selector (k=v,k2!=v2)")
    get.add_argument("--field-selector", default="",
                     help="field selector (e.g. spec.nodeName=n0)")
    get.add_argument("-w", "--watch", action="store_true",
                     help="follow the watch stream after listing")
    get.set_defaults(fn=cmd_get)

    apply = sub.add_parser("apply", help="apply kind-tagged YAML documents")
    apply.add_argument("-f", "--file", required=True)
    apply.add_argument("--server", required=True)
    apply.set_defaults(fn=cmd_apply)

    delete = sub.add_parser("delete", help="delete an object")
    delete.add_argument("kind")
    delete.add_argument("key")
    delete.add_argument("--server", required=True)
    delete.set_defaults(fn=cmd_delete)

    explain = sub.add_parser(
        "explain",
        help="render a pod's flight-recorder record: staged latency "
             "timeline + why node Y won / why nodes were filtered",
    )
    explain.add_argument("target", help="pod/<ns>/<name> (or ns/name)")
    explain.add_argument("--server", default="http://127.0.0.1:10251",
                         help="scheduler DIAGNOSTICS base URL "
                              "(the --diagnostics-port listener)")
    explain.add_argument("--file", default="",
                         help="render from a dumped /debug/flightrecorder "
                              "JSON instead of a live scheduler")
    explain.add_argument("--collector", default="",
                         help="fetch the record from a telemetry "
                              "collector's merged view instead "
                              "(/telemetry/flightrecorder) — finds the pod "
                              "whichever scheduler replica decided it")
    explain.add_argument("-o", "--output", default="text",
                         choices=("text", "json"))
    explain.add_argument("--all", action="store_true",
                         help="render every matching record, not just the "
                              "latest")
    explain.add_argument("--api", default="",
                         help="apiserver base URL: append the pod's Event "
                              "timeline (Scheduled / FailedScheduling "
                              "from the recorders, with aggregation "
                              "counts) to the explanation")
    explain.set_defaults(fn=cmd_explain)

    st = sub.add_parser(
        "store",
        help="durable-store tooling: fsck (offline integrity report for a "
             "persistence dir) and compact (fold the WAL into one "
             "snapshot, truncate superseded segments)",
    )
    st_sub = st.add_subparsers(dest="store_command", required=True)
    st_fsck = st_sub.add_parser(
        "fsck", help="report snapshot/segment validity, torn tails, and "
                     "replay-chain continuity without mutating anything",
    )
    st_fsck.add_argument("--dir", required=True,
                         help="the persistence directory "
                              "(apiserver --persistence DIR)")
    st_fsck.add_argument("-o", "--output", default="text",
                         choices=("text", "json"))
    st_fsck.set_defaults(fn=cmd_store_fsck)
    st_compact = st_sub.add_parser(
        "compact", help="offline compaction of a STOPPED apiserver's "
                        "persistence dir (bounds the next boot's replay)",
    )
    st_compact.add_argument("--dir", required=True)
    st_compact.set_defaults(fn=cmd_store_compact)

    coll = sub.add_parser(
        "collector",
        help="run the telemetry collector: span/metrics/flight-record "
             "ingest from N processes, skew-corrected merged chrome "
             "trace, federated /metrics, and the `kubetpu top` summary",
    )
    coll.add_argument("--host", default="127.0.0.1")
    coll.add_argument("--port", type=int, default=10252)
    coll.set_defaults(fn=cmd_collector)

    top = sub.add_parser(
        "top",
        help="live control-plane console from a collector: per-process "
             "pods/s, queue depth, conflict rate, WAL fsync p99, staged "
             "e2e percentiles",
    )
    top.add_argument("--collector", default="http://127.0.0.1:10252",
                     help="collector base URL (kubetpu collector, or an "
                          "apiserver running --telemetry embed)")
    top.add_argument("-o", "--output", default="text",
                     choices=("text", "json"))
    top.add_argument("-w", "--watch", action="store_true",
                     help="refresh every --interval seconds until ^C")
    top.add_argument("--interval", type=float, default=2.0)
    top.set_defaults(fn=cmd_top)

    al = sub.add_parser(
        "alerts",
        help="the anomaly sentinel's live alert table: one process's "
             "/debug/alerts, or the cluster-wide merge from a "
             "collector's /telemetry/alerts",
    )
    al.add_argument("--server", default="http://127.0.0.1:10251",
                    help="scheduler DIAGNOSTICS base URL "
                         "(the --diagnostics-port listener)")
    al.add_argument("--collector", default="",
                    help="read the merged cluster-wide table from a "
                         "collector instead (one row per rule, worst "
                         "state across processes wins)")
    al.add_argument("-o", "--output", default="text",
                    choices=("text", "json"))
    al.set_defaults(fn=cmd_alerts)

    bu = sub.add_parser(
        "bundle",
        help="triggered diagnostic bundles: summaries, or one full "
             "capture (py stacks, queue snapshot, WAL/cache stats, "
             "chrome-trace slice) with --id",
    )
    bu.add_argument("--server", default="http://127.0.0.1:10251",
                    help="scheduler DIAGNOSTICS base URL")
    bu.add_argument("--collector", default="",
                    help="fetch from a collector's merged store instead")
    bu.add_argument("--id", default="",
                    help="bundle id (from the summary list or an alert's "
                         "bundle_id); omit to list summaries")
    bu.add_argument("--process", default="",
                    help="disambiguate --id by process (collector mode)")
    bu.add_argument("--out", default="",
                    help="write the full bundle JSON to FILE instead of "
                         "stdout")
    bu.add_argument("-o", "--output", default="text",
                    choices=("text", "json"))
    bu.set_defaults(fn=cmd_bundle)

    wd = sub.add_parser(
        "watch-driver",
        help="run N concurrent pod watchers against an apiserver as one "
             "dedicated process (the unit kubetpu up --fanout-procs "
             "spreads its watchers over)",
    )
    wd.add_argument("--server", required=True, help="API server base URL")
    wd.add_argument("--watchers", type=int, default=50)
    wd.add_argument("--wire", default="binary", choices=["binary", "json"])
    wd.set_defaults(fn=cmd_watch_driver)

    up = sub.add_parser(
        "up",
        help="run the whole control plane as real OS processes under the "
             "launch supervisor: apiserver + N scheduler replicas "
             "(+ collector / watch-fanout drivers), ephemeral ports via "
             "readiness banners, restart policy, SIGTERM-cascade shutdown",
    )
    up.add_argument("--replicas", type=int, default=1,
                    help="scheduler replica processes")
    up.add_argument("--apiservers", type=int, default=1,
                    help="apiserver processes: 1 (default) is the classic "
                         "single-writer topology, byte-identical to "
                         "before; N>1 runs one leader + N-1 WAL-log-"
                         "shipping follower apiservers — watch-fanout "
                         "drivers spread their read load over the "
                         "followers, and the most-caught-up follower "
                         "takes over on leader death (failover by log "
                         "position)")
    up.add_argument("--replication-chain", action="store_true",
                    help="chain the followers' replication tails (f1 "
                         "tails the leader, f2 tails f1, …) so leader "
                         "replication egress is one follower's worth "
                         "regardless of --apiservers; a stale or dead "
                         "chain link falls its downstream back to the "
                         "leader's feed. Default: every follower tails "
                         "the leader directly")
    up.add_argument("--partition", default="race",
                    choices=["race", "hash", "lease"],
                    help="federation partition mode across the replica "
                         "processes (see kubetpu scheduler --partition)")
    up.add_argument("--wire", default="binary", choices=["binary", "json"],
                    help="wire codec for every child (and the 415-fallback "
                         "escape hatch)")
    up.add_argument("--engine", default="greedy",
                    choices=["greedy", "batched", "packing"])
    up.add_argument("--topology", default="off",
                    choices=["on", "off", "auto"],
                    help="node-topology axis on every scheduler replica "
                         "(see kubetpu scheduler --topology)")
    up.add_argument("--max-batch", type=int, default=1024)
    up.add_argument("--persistence", default="off", metavar="DIR|off",
                    help="apiserver durability dir (WAL + snapshots); the "
                         "SIGTERM cascade rides the graceful close — "
                         "`kubetpu store fsck` passes afterwards")
    up.add_argument("--telemetry", default="off",
                    metavar="off|embed|collector|URL",
                    help="'embed' mounts the collector ON the apiserver "
                         "and points every scheduler's exporter there; "
                         "'collector' spawns a collector child; a URL "
                         "uses an external collector; 'off' exports "
                         "nothing")
    up.add_argument("--watch-fanout", type=int, default=0,
                    help="total extra pod watchers, spread over "
                         "--fanout-procs driver processes")
    up.add_argument("--fanout-procs", type=int, default=0,
                    help="watch-driver processes carrying --watch-fanout")
    up.add_argument("--restart", default="on-failure:2",
                    metavar="never|on-failure[:max]",
                    help="per-scheduler restart policy: a killed replica "
                         "is respawned and re-federates (hash re-adopts "
                         "its rank's backlog, lease re-acquires)")
    up.add_argument("--prewarm", action="store_true",
                    help="schedulers compile the bucket ladder at startup")
    up.set_defaults(fn=cmd_up)

    ver = sub.add_parser("version", help="print version")
    ver.set_defaults(fn=cmd_version)

    analyze = sub.add_parser(
        "analyze",
        help="graftcheck static-analysis suite "
             "(see python -m kubetpu.analysis)",
    )
    analyze.add_argument("rest", nargs=argparse.REMAINDER)
    analyze.set_defaults(fn=None)
    return p


def main(argv: Sequence[str] | None = None) -> int:
    raw = list(argv) if argv is not None else sys.argv[1:]
    if raw and raw[0] == "analyze":
        # dispatch before argparse: REMAINDER drops leading flags
        # (`kubetpu analyze --list-checkers` must reach the sub-CLI intact)
        from .analysis.__main__ import main as analyze_main

        return analyze_main(raw[1:]) or 0
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
