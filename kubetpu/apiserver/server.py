"""API server — REST + watch over the versioned store (layer 3).

Reference shape (staging/src/k8s.io/apiserver): per-resource REST verbs
installed over generic storage (`registerResourceHandlers`,
endpoints/installer.go:288; generic registry Store, registry/store.go:514)
with watch streams fanned out from the watch cache (cacher.go:263). The
envelope here:

    GET    /apis/<kind>                 list → {"items": [...], "resourceVersion": N}
    GET    /apis/<kind>?watch=1&resourceVersion=N
                                        drain events AFTER N (long-poll up to
                                        ``timeoutSeconds``); 410 Gone when N
                                        predates the event buffer (relist)
    GET    /apis/<kind>?watch=1&stream=1&resourceVersion=N
                                        STREAMING watch: chunked ndjson, one
                                        event per line, the connection held
                                        open up to ``timeoutSeconds`` —
                                        the reference's watch stream shape
                                        (cacher.go fan-out); long-poll above
                                        stays as the fallback
    both list and watch accept ``labelSelector`` / ``fieldSelector``
    (``k=v,k2!=v2``) applied SERVER-side (endpoints/installer.go:288 list
    options; spec.nodeName is how a kubelet watches only its own pods) —
    a non-matching ADDED/MODIFIED is delivered as a DELETED tombstone with
    no object body
    GET    /apis/?watch=1&buckets=pods:12,nodes:7[&timeoutSeconds=T]
                                        BATCHED watch poll: drain several
                                        kinds' cursors in ONE round trip;
                                        per-kind {"events", "resourceVersion"}
                                        (or {"code": 410} — only that kind
                                        relists). One request replaces the
                                        informer bundle's N per-kind polls.
    GET    /apis/?watch=1&buckets=…&bindDeltas=1
                                        the same poll, but each MODIFIED
                                        event a pods ``bind`` op committed
                                        comes as {"type", "key",
                                        "resourceVersion", "bind": {"uid",
                                        "node"}}, no object, on both wires.
                                        GUARANTEE: a client that applies
                                        events in order to the objects it
                                        holds, and takes a delta's object
                                        to be its pod with the node set,
                                        sees event for event the (type,
                                        key, object, resourceVersion) a
                                        client without the parameter sees.
                                        The watch is ordered and complete
                                        per key, and a relist is at a
                                        revision before any later event,
                                        so the pod it holds is the store's
                                        pre-bind pod, whichever client
                                        made the bind. A client that holds
                                        no such pod (missing, another uid,
                                        a node already set) relists the
                                        kind. The per-kind watch, the
                                        stream, a scoped watch and a poll
                                        without the parameter send whole
                                        bodies as ever; nothing stored
                                        changes.
    GET    /apis/<kind>/<key…>          get → {"object": …, "resourceVersion": N}
    POST   /apis/<kind>/<key…>          create (409 on exists)
    POST   /apis/<kind>:bulk            BULK verb: {"ops": [{"op": "create|
                                        update|patch|delete|get", "key": …,
                                        "object": …, "resourceVersion": N?},
                                        …]} applied under ONE store lock
                                        acquisition → {"results": [{"status",
                                        "resourceVersion", "error"?,
                                        "object"?}, …]} positional, per-op
                                        conflict/admission semantics
                                        identical to the single-op verbs
                                        (a mid-batch 409 fails only its op);
                                        on pods also {"op": "bind", "key",
                                        "uid"?, "node"}: the binding
                                        subresource, no object either way
    PUT    /apis/<kind>/<key…>[?resourceVersion=N]
                                        update; CAS conflict → 409
    DELETE /apis/<kind>/<key…>          delete (404 when absent)

Watch responses are assembled from a serialize-once event cache (the
reference watch cache's CachingObject): each event's wire body is encoded
once per (kind, resourceVersion, codec) and the cached bytes are shared
across every watcher poll, batched poll, and stream frame — N watchers pay
one encode, not N. Staleness is impossible by construction: every store
write mints a fresh resourceVersion, so a mutated object can never be
served from an old entry. When the store exposes its per-event body ring
(``MemStore.events_body_since`` — backed by the native core), the unscoped
watch paths serve cached bodies STRAIGHT from the ring without ever
materializing a WatchEvent.

Every reply rides the wire-codec seam (kubetpu.api.codec — the negotiated
serializer): the reply codec is negotiated per request from the ``Accept``
header (binary only when the client's schema fingerprint matches ours),
request bodies decode by their ``Content-Type`` (an unknown/mismatched
binary dialect 415s — the client's fall-back-to-JSON signal), and NO
handler hand-rolls serialization. The watch response is the pull form of
the reference's chunked watch stream: clients poll with their cursor, the
server long-polls against the store's condition variable — the Reflector's
ListAndWatch maps onto exactly these two endpoints (see
kubetpu.apiserver.remote.RemoteStore).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlsplit

from ..api import codec, scheme
from ..metrics.health import HealthChecks
from ..store.memstore import (
    CompactedError, ConflictError, FollowerWriteError, MemStore,
    bind_refusal,
)
from .admission import AdmissionDenied, Registry, ValidationError
from .metrics import APIServerMetrics
from .remote import (   # ONE wire constant for both sides
    BIND_DELTAS_PARAM, BULK_SUFFIX,
)

PREFIX = "/apis/"

#: the bulk paths' exception ladder: ONE copy of the per-op
#: exception→status mapping (the inverse of memstore.bulk_result_error),
#: so the fast path, the sequential path, and the single verbs cannot
#: drift. Order matters: ValidationError IS a ValueError.
_OP_ERROR_STATUS: tuple = (
    (ConflictError, 409),
    (ValidationError, 422),
    (AdmissionDenied, 403),
    (KeyError, 404),
    ((scheme.SchemeError, ValueError), 400),
)

#: the union, for except clauses
_OP_ERRORS = (
    ConflictError, ValidationError, AdmissionDenied, KeyError,
    scheme.SchemeError, ValueError,
)


#: the ops of the bulk verb, and the answer to any other
_BULK_VERBS = ("create", "update", "patch", "delete", "get", "bind")
_BULK_VERBS_ERROR = (
    "op must carry a key and one of create/update/patch/delete/get/bind"
)


def _op_error_result(e: Exception) -> dict:
    """Map one bulk-op exception to its per-op result dict."""
    for types, status in _OP_ERROR_STATUS:
        if isinstance(e, types):
            reason = (
                str(e).strip("'\"") if isinstance(e, KeyError) else str(e)
            )
            return {"status": status, "resourceVersion": 0, "error": reason}
    raise e  # unmapped: let the request-level 500 handler see it


def _bind_node(kind: str, key: str, op: dict) -> str:
    """A bind op's node, validated as what the bind changes: the op is the
    pods binding subresource (name, uid, node), and it names a node."""
    if kind != "pods":
        raise ValueError("bind is an op of the pods :bulk verb")
    node = op.get("node")
    if not isinstance(node, str) or not node:
        raise ValidationError(kind, key, ["spec.nodeName: a bind names one"])
    return node


class _BatchObjects:
    """What a bulk batch writes, as ``Registry.has_dynamic_admission``
    sees it: the ops' own objects, then the stored pods its bind ops name,
    read only if a predicate iterates them (under the store lock that
    applies the batch, where the verb asks)."""

    def __init__(self, store, kind: str, objs: list, bind_keys: list):
        self._store, self._kind = store, kind
        self._objs, self._bind_keys = objs, bind_keys

    def __len__(self) -> int:
        return len(self._objs) + len(self._bind_keys)

    def __iter__(self):
        yield from self._objs
        for key in self._bind_keys:
            obj, _rv = self._store.get(self._kind, key)
            if obj is not None:
                yield obj


def _stamp_pod_ingest(kind: str, obj):
    """The attribution plane's t0 (sched.flightrecorder): a freshly created
    pod gets a trace id + monotonic ingest timestamp HERE, at REST create —
    carried through the store and every watch frame so the scheduler's
    flight recorder can attribute api_ingest/e2e latency per pod. A pod
    arriving already stamped (a relayed create, a test fixture) keeps its
    original stamp — t0 means FIRST ingest."""
    if kind != "pods" or getattr(obj, "ingest_ts", 0.0):
        return obj
    import dataclasses
    import time
    import uuid

    try:
        return dataclasses.replace(
            obj,
            trace_id=uuid.uuid4().hex[:16],
            ingest_ts=time.perf_counter(),
        )
    except TypeError:       # a pod stand-in without the stamp fields
        return obj


class EventEncodeCache:
    """Serialize-once watch fan-out (the reference watch cache's
    CachingObject, cacher/caching_object.go): one wire encoding per event
    PER CODEC, keyed by (kind, resourceVersion, codec, tombstone) — unique
    per event because every store write bumps the global revision exactly
    once — and shared by every long-poll reply, batched poll bucket, and
    stream frame. The ``tombstone`` key dimension is the selector-scoped
    view: a scoped DELETED (including a selector REWRITE of an
    ADDED/MODIFIED) ships no object body, and because the tombstone's
    bytes depend only on (key, rv) — never on WHICH selector scoped it —
    one cached tombstone serves every scoped watcher (scoped fan-out used
    to bypass the cache entirely and re-serialize per watcher per event).
    Bounded LRU sized to the store's event history TIMES the key-space
    growth (2 codecs x body/tombstone = up to 4 entries per ring event —
    an 8192-entry LRU would cover as little as a quarter of the history
    under mixed-codec scoped fan-out, quietly reintroducing per-poll
    re-encodes); hit/miss counters (merged with the store body ring's,
    when one is bound) feed the codec-labeled apiserver metric set."""

    def __init__(self, maxsize: int = 4 * 8192, store=None) -> None:
        import collections
        import threading

        self._maxsize = maxsize
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[tuple, bytes]" = (
            collections.OrderedDict()
        )
        # the store whose native body ring ALSO serves cached event bodies
        # (the unscoped fast path bypasses this LRU entirely) — its
        # hit/miss counters merge into ours so "serialize-once" reads as
        # one number regardless of which cache carried the bytes
        self._store = store
        self._hits = {codec.JSON: 0, codec.BINARY: 0}
        self._misses = {codec.JSON: 0, codec.BINARY: 0}

    def event_bytes(self, e, wire: str = codec.JSON,
                    tombstone: bool = False) -> bytes:
        # the registry generation keys the entry too: binary bodies embed
        # schema-table ids, so a kind registered after an entry was cached
        # must never let that entry splice into a new-fingerprint reply
        # (old-generation entries just age out of the LRU)
        key = (e.kind, e.resource_version, wire, tombstone,
               scheme.registry_generation())
        with self._lock:
            cached = self._entries.get(key)
            if cached is not None:
                self._entries.move_to_end(key)
                self._hits[wire] += 1
                return cached
        # encode OUTSIDE the lock, last-writer-wins on insert: when a
        # write wakes N long-poll watchers at once, the worst case is a
        # handful of concurrent encodes of one small event — cheaper than
        # ever blocking a request thread on another's encode. The steady
        # win (every later poll/stream frame reuses the bytes) is carried
        # by the LRU.
        body = codec.event_wire_bytes(
            "DELETED" if tombstone else e.type,
            e.key,
            None if tombstone else e.obj,
            e.resource_version,
            wire,
        )
        with self._lock:
            self._misses[wire] += 1
            self._entries[key] = body
            while len(self._entries) > self._maxsize:
                self._entries.popitem(last=False)
        return body

    def item_bytes(self, kind: str, key: str, obj, rv: int,
                   wire: str = codec.JSON) -> bytes:
        """One LIST item's wire body through the same serialize-once LRU
        — the paged-list splice path: keyed by (kind, rv, codec) like an
        event body (every store write mints a fresh rv, so a mutated
        object can never serve from an old entry), with an "item"
        dimension keeping list bodies distinct from watch-event bodies
        at the same rv. A 50k-node relist walk re-encodes only the
        objects that changed since the last walk."""
        cache_key = (kind, rv, wire, "item", scheme.registry_generation())
        with self._lock:
            cached = self._entries.get(cache_key)
            if cached is not None:
                self._entries.move_to_end(cache_key)
                self._hits[wire] += 1
                return cached
        body = codec.list_item_wire_bytes(key, obj, wire)
        with self._lock:
            self._misses[wire] += 1
            self._entries[cache_key] = body
            while len(self._entries) > self._maxsize:
                self._entries.popitem(last=False)
        return body

    def _ring_stats(self) -> dict:
        stats = getattr(self._store, "body_cache_stats", None)
        return stats() if stats is not None else {}

    def stats_by_codec(self) -> "dict[str, tuple[int, int]]":
        """{codec: (hits, misses)} — this LRU plus the store body ring."""
        ring = self._ring_stats()
        out = {}
        with self._lock:
            for c in (codec.JSON, codec.BINARY):
                rh, rm = ring.get(c, (0, 0))
                out[c] = (self._hits[c] + rh, self._misses[c] + rm)
        return out

    @property
    def hits(self) -> int:
        return sum(h for h, _m in self.stats_by_codec().values())

    @property
    def misses(self) -> int:
        return sum(m for _h, m in self.stats_by_codec().values())


class _Handler(BaseHTTPRequestHandler):
    store: MemStore     # bound by the server factory
    registry: Registry  # admission + validation chain (bound by the factory)
    metrics: APIServerMetrics   # request instrumentation (bound by factory)
    health: HealthChecks        # /healthz /readyz /livez (bound by factory)
    event_cache: EventEncodeCache   # serialize-once fan-out (bound by factory)
    tracer = None       # server-span recorder (bound by factory)
    collector = None    # embedded telemetry collector (bound when enabled)
    sentinel = None     # embedded anomaly sentinel (bound when enabled)
    replication = None  # LeaderLease | FollowerReplicator (when replicated)
    metrics_sources: tuple = ()  # extra Prometheus-text providers
    wire_enabled: bool = True    # False = JSON-only server (--wire json):
    #                              ignores binary Accept, 415s binary bodies
    protocol_version = "HTTP/1.1"

    def log_message(self, *args) -> None:
        pass

    # ------------------------------------------------------------- tracing
    @contextmanager
    def _track_span(self, verb: str, resource: str,
                    long_running: bool = False):
        """THE request-instrumentation seam: every handler runs under it
        (graftcheck TR003 pins this). One ``metrics.track`` window plus
        one server span recorded at completion — joined to the client's
        span when the request carried a traceparent (the ``traceparent``
        header on the JSON wire, the binary envelope's ``tp`` media-type
        parameter; a malformed value is IGNORED, never a 4xx). Pod writes
        stash their attribution ids via ``_note_pod_trace`` so the span
        links the pod's cross-process timeline."""
        from ..telemetry.context import parse_traceparent

        ctx = parse_traceparent(codec.traceparent_from_headers(self.headers))
        # per-request stash (one handler instance serves one connection's
        # requests sequentially, so a plain attribute is race-free)
        self._span_pod_traces: list[str] = []
        self._span_attrs: dict = {}     # a handler's own (BULK: its path)
        t0 = time.perf_counter()
        try:
            with self.metrics.track(
                verb, resource, lambda: getattr(self, "_status", 0),
                long_running=long_running,
            ):
                yield
        finally:
            attrs: dict = {
                "verb": verb, "resource": resource,
                "code": getattr(self, "_status", 0),
                **self._span_attrs,
            }
            if ctx is not None:
                # the cross-process join: same trace id as the client's
                # rpc span, the client span as this span's remote parent
                attrs["trace_id"] = ctx.trace_id
                attrs["parent_span_id"] = ctx.span_id
            if self._span_pod_traces:
                attrs["pod_traces"] = self._span_pod_traces[:64]
            self.tracer.record(
                f"apiserver.{verb}", start=t0, end=time.perf_counter(),
                **attrs,
            )

    def _note_pod_trace(self, kind: str, obj) -> None:
        """Link this request's server span to a pod's attribution id (the
        16-hex ``trace_id`` stamped at ingest) — how an ingest or
        bind-subresource span joins the pod's scheduler-side timeline."""
        if kind != "pods":
            return
        tid = getattr(obj, "trace_id", "") or ""
        if tid:
            stash = getattr(self, "_span_pod_traces", None)
            if stash is not None and len(stash) < 64:
                stash.append(tid)

    # ------------------------------------------------------------ plumbing
    def _reply_codec(self) -> str:
        """The negotiated REPLY codec for this request: binary only when
        the Accept header names our exact binary dialect (media type +
        schema fingerprint) and the server has binary enabled — anything
        else degrades to JSON, never to an undecodable reply."""
        if not self.wire_enabled:
            return codec.JSON
        return (
            codec.BINARY
            if codec.accepts_binary(self.headers.get("Accept"))
            else codec.JSON
        )

    def _body_codec(self) -> str:
        """The codec this request's BODY is encoded in (Content-Type).
        Raises UnsupportedWireError — the 415 — for a binary dialect we
        cannot decode, or any binary body when the server is JSON-only.
        The JSON-only check parses the media type (same normalization as
        codec_for_content_type) so a mixed-case binary Content-Type
        cannot slip a binary body past --wire json."""
        ct = self.headers.get("Content-Type")
        media, _params = codec.parse_content_type(ct)
        if not self.wire_enabled and media in (
            codec.CT_BINARY, codec.CT_BINARY_STREAM,
        ):
            raise codec.UnsupportedWireError(
                "binary wire disabled on this server (negotiate JSON)"
            )
        return codec.codec_for_content_type(ct)

    def _reply(self, obj, status: int = 200) -> None:
        """One reply through the wire seam — ``obj`` may contain live
        registered dataclasses; the negotiated codec encodes them in
        place (no handler pre-serializes)."""
        wire = self._reply_codec()
        self._reply_wire(codec.dumps(obj, wire), wire, status=status)

    def _reply_wire(self, body: bytes, wire: str, status: int = 200) -> None:
        """Pre-serialized reply in ``wire`` — the serialize-once watch
        paths hand cached event bytes straight to the socket."""
        self.metrics.count_wire(wire, "out", len(body))
        self._status = status
        self.send_response(status)
        self.send_header("Content-Type", codec.content_type_for(wire))
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_text(self, body: str, status: int = 200,
                    content_type: str = "text/plain; charset=utf-8") -> None:
        self._status = status
        data = body.encode()
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _error(self, status: int, reason: str) -> None:
        self._reply({"error": reason}, status=status)

    def _route(self):
        """(kind, key or None, query) — key may contain '/'."""
        parts = urlsplit(self.path)
        if not parts.path.startswith(PREFIX):
            return None, None, {}
        rest = parts.path[len(PREFIX):].strip("/")
        q = {k: v[-1] for k, v in parse_qs(parts.query).items()}
        if not rest:
            return None, None, q
        kind, _, key = rest.partition("/")
        return kind, (key or None), q

    def _read_body(self):
        length = int(self.headers.get("Content-Length", 0))
        raw = self.rfile.read(length) if length else b""
        wire = self._body_codec()   # may raise the 415
        self.metrics.count_wire(wire, "in", len(raw))
        if not raw:
            return {}
        return codec.loads(raw, wire)

    # -------------------------------------------------------- diagnostics
    def _serve_diagnostics(self) -> None:
        """GET outside /apis/: /metrics (Prometheus text 0.0.4, the server
        set plus any extra bound sources) and the component-base-style
        /healthz /readyz /livez named-check endpoints — served through the
        shared mux (kubetpu.metrics.diagmux) the scheduler listener also
        mounts."""
        from ..metrics.diagmux import diagnostics_response

        parts = urlsplit(self.path)
        try:
            res = diagnostics_response(
                parts.path, parse_qs(parts.query, keep_blank_values=True),
                metrics_sources=(self.metrics.expose, *self.metrics_sources),
                health=self.health,
                extra={
                    # the apiserver's server spans as Chrome-trace JSON —
                    # same shape as the scheduler diagnostics /trace
                    # (non-destructive; the telemetry exporter drains)
                    "/trace": lambda q: (
                        "application/json",
                        codec.dumps(self.tracer.chrome_trace()).decode(),
                    ),
                    # the embedded sentinel's alert/bundle state — same
                    # shapes as the scheduler diagnostics endpoints
                    "/debug/alerts": lambda q: (
                        "application/json",
                        codec.dumps(self._alerts_body()).decode(),
                    ),
                    "/debug/bundle": lambda q: (
                        "application/json",
                        codec.dumps(self._bundle_body(q)).decode(),
                    ),
                },
            )
        except Exception as e:  # noqa: BLE001 — diagnostics must not crash
            self._error(500, f"{type(e).__name__}: {e}")
            return
        if res is None:
            self._error(404, "unknown path")
            return
        status, content_type, body = res
        self._reply_text(body, status=status, content_type=content_type)

    def _alerts_body(self) -> dict:
        if self.sentinel is None:
            return {"enabled": False, "alerts": [], "firing": 0}
        out = self.sentinel.alerts_json()
        out["enabled"] = True
        return out

    def _bundle_body(self, query: dict) -> dict:
        if self.sentinel is None:
            return {"enabled": False, "bundles": [], "count": 0}
        out = self.sentinel.bundles_json(query)
        out["enabled"] = True
        return out

    # --------------------------------------------------------------- verbs
    def _serve_collector(self, method: str) -> bool:
        """Embedded-collector mode: /telemetry/* routed to the bound
        collector (the apiserver doubles as the telemetry sink — one less
        process for small clusters). False when the path is not ours."""
        if self.collector is None:
            return False
        parts = urlsplit(self.path)
        if not parts.path.startswith("/telemetry/"):
            return False
        from ..telemetry.collector import handle_collector_request

        body = b""
        if method == "POST":
            length = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(length) if length else b""
        try:
            res = handle_collector_request(
                self.collector, method, parts.path,
                parse_qs(parts.query, keep_blank_values=True),
                body, self.headers.get("Content-Type"),
            )
        except codec.UnsupportedWireError as e:
            self._error(415, str(e))
            return True
        except Exception as e:  # noqa: BLE001 — telemetry must not crash
            self._error(500, f"{type(e).__name__}: {e}")
            return True
        if res is None:
            self._error(404, "unknown telemetry path")
            return True
        status, content_type, data = res
        self._reply_text(
            data.decode() if isinstance(data, bytes) else data,
            status=status, content_type=content_type,
        )
        return True

    # ---------------------------------------------------------- replication
    def _serve_replication(self, method: str) -> bool:
        """Replicated read plane (kubetpu.store.replication):
        /replication/log is the leader's ship feed (WAL frames off the
        serialize-once body ring, long-polled like a watch),
        /replication/snapshot the follower bootstrap, and
        /replication/status the election/lag probe. Mounted only when a
        replication role is bound — an unreplicated server keeps PR-16
        routing exactly (the paths fall through to diagnostics' 404).
        False when the path is not ours."""
        if self.replication is None:
            return False
        parts = urlsplit(self.path)
        if not parts.path.startswith("/replication/"):
            return False
        q = {k: v[-1] for k, v in parse_qs(parts.query).items()}
        try:
            if parts.path == "/replication/status":
                self._reply(self.replication.status())
            elif parts.path == "/replication/log":
                self._serve_replication_log(q)
            elif parts.path == "/replication/snapshot":
                from ..store.wal import encode_snapshot_stream

                items, rv = self.store.dump_with_rv()
                self._reply_rep(
                    encode_snapshot_stream(items, rv, self._rep_wire(q)),
                    rv, path="snapshot",
                )
            else:
                self._error(404, "unknown replication path")
        except ValueError as e:
            self._error(400, str(e))
        except Exception as e:  # noqa: BLE001 — replication must not crash
            self._error(500, f"{type(e).__name__}: {e}")
        return True

    def _rep_wire(self, q: dict) -> str:
        """The ship body's codec: the follower asks for one (it knows its
        own build); default to the server's negotiated-wire stance."""
        wire = q.get(
            "codec", codec.BINARY if self.wire_enabled else codec.JSON
        )
        if wire not in (codec.JSON, codec.BINARY):
            raise ValueError(f"codec must be json|binary, got {wire!r}")
        if wire == codec.BINARY and not self.wire_enabled:
            raise ValueError("binary wire disabled on this server")
        return wire

    def _serve_replication_log(self, q: dict) -> None:
        from ..store.replication import build_log_body

        after = int(q.get("after", 0))
        timeout = min(float(q.get("timeoutSeconds", 0)), 60.0)
        wire = self._rep_wire(q)
        try:
            body, cursor, n = build_log_body(self.store, after, wire)
            if not n and timeout > 0:
                # the long-poll: a leader with nothing new holds the
                # follower's request on the store's condition variable —
                # shipping latency is write-wakeup latency, not a poll
                # interval
                self.store.wait_for(after, timeout=timeout)
                body, cursor, n = build_log_body(self.store, after, wire)
        except CompactedError as e:
            # the follower's cursor predates the body ring: 410 → it
            # bootstraps from /replication/snapshot (recovery's contract)
            self._error(410, str(e))
            return
        self._reply_rep(body, cursor, wire=wire)

    def _reply_rep(self, body: bytes, cursor: int,
                   wire: str = "", path: str = "log") -> None:
        """Raw replication bytes + the feed position/fencing headers."""
        from ..store import replication as rep

        # the ship plane's egress evidence: chained fan-out is judged by
        # this counter's delta on the leader (O(fan-out), not O(followers))
        self.metrics.count_replication(path, len(body))
        self._status = 200
        self.send_response(200)
        self.send_header("Content-Type", rep.CT_WAL)
        self.send_header("Content-Length", str(len(body)))
        self.send_header(rep.H_CURSOR, str(cursor))
        self.send_header(rep.H_EPOCH, str(self.replication.epoch))
        if wire:
            self.send_header(rep.H_CODEC, wire)
        self.end_headers()
        self.wfile.write(body)

    def _redirect_to_leader(self) -> bool:
        """Follower write redirect: a write verb landing on a follower
        apiserver answers 307 with the leader's URL (Location header +
        reply body) — RemoteStore retries the write there while its reads
        stay here. False when this server takes writes itself."""
        if not getattr(self.store, "follower", False):
            return False
        self._reply_redirect()
        return True

    def _reply_redirect(self) -> None:
        leader = ""
        if self.replication is not None:
            leader = getattr(self.replication, "leader_url", "") or ""
        # drain the request body first: leaving it unread would desync
        # the keep-alive connection's framing for the next request
        length = int(self.headers.get("Content-Length", 0) or 0)
        if length:
            self.rfile.read(length)
        wire = self._reply_codec()
        body = codec.dumps({
            "error": "follower apiserver: writes go to the leader",
            "leader": leader,
        }, wire)
        self.metrics.count_wire(wire, "out", len(body))
        self._status = 307
        self.send_response(307)
        if leader:
            self.send_header("Location", leader + self.path)
        self.send_header("Content-Type", codec.content_type_for(wire))
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self) -> None:  # noqa: N802
        if not urlsplit(self.path).path.startswith(PREFIX):
            if not self._serve_replication("GET"):
                if not self._serve_collector("GET"):
                    self._serve_diagnostics()
            return
        kind, key, q = self._route()
        if kind is None:
            if q.get("watch") and q.get("buckets"):
                # batched multi-kind watch poll: N informer cursors, one
                # round trip (long-running like every watch)
                with self._track_span("WATCH", "multi", long_running=True):
                    try:
                        self._watch_bulk(q)
                    except ValueError as e:
                        self._error(400, str(e))
                    except Exception as e:
                        self._error(500, f"{type(e).__name__}: {e}")
                return
            self._error(404, "unknown path")
            return
        if key is None and q.get("watch"):
            verb = "WATCH"
        elif key is None:
            verb = "LIST"
        else:
            verb = "GET"
        # EVERY watch is long-running (the reference's longrunning
        # predicate covers long-polls too): a blocked wait_for must not
        # hold the in-flight gauge
        with self._track_span(verb, kind, long_running=(verb == "WATCH")):
            self._do_get(kind, key, q)

    def _do_get(self, kind, key, q) -> None:
        try:
            if key is None and q.get("watch"):
                if q.get("stream"):
                    self._watch_stream(kind, q)
                else:
                    self._watch(kind, q)
            elif key is None:
                self._list(kind, q)
            else:
                obj, rv = self.store.get(kind, key)
                if obj is None:
                    self._error(404, f"{kind}/{key} not found")
                else:
                    self._reply({"object": obj, "resourceVersion": rv})
        except ValueError as e:
            # malformed selector / resourceVersion: the CLIENT's error —
            # a retry-on-5xx loop must not hammer a permanently-bad request
            self._error(400, str(e))
        except Exception as e:
            self._error(500, f"{type(e).__name__}: {e}")

    def _list_lag_records(self) -> int:
        """The replication lag (in records) a bounded-staleness read may
        trail the leader by right now — 0 on an unreplicated server or
        the leader itself (their watch cache IS the write path)."""
        if not getattr(self.store, "follower", False):
            return 0
        status = getattr(self.replication, "status", None)
        if status is None:
            return 0
        try:
            return int(status().get("lagRecords", 0) or 0)
        except Exception:  # noqa: BLE001 — lag surfacing must not 500 a read
            return 0

    def _list(self, kind: str, q: dict) -> None:
        """GET /apis/<kind> — the (paged) LIST. ``limit`` caps the page
        size; a truncated page's reply carries an opaque ``continue``
        token pinned to the walk's resourceVersion snapshot, and a token
        whose snapshot fell behind the event ring's compaction horizon
        410s into a fresh walk (the reference's expired-continue
        semantics). Pages splice cached item bodies off the
        serialize-once cache — nothing re-encodes on a relist walk
        unless the object changed. ``resourceVersion=0`` is the
        bounded-staleness read: served from the local watch-ring-backed
        cache (on a follower, the replica) with the observed replication
        lag surfaced as ``store_list_lag_records``; ``maxLagRecords``
        declares the client's bound (503 when exceeded). Exact/absent-rv
        lists keep their pre-pagination semantics and bytes."""
        ls = q.get("labelSelector", "")
        fs = q.get("fieldSelector", "")
        limit = int(q.get("limit", 0))
        if limit < 0:
            raise ValueError(f"limit must be >= 0, got {limit}")
        token = q.get("continue", "")
        if q.get("resourceVersion", "") == "0":
            lag = self._list_lag_records()
            self.metrics.list_lag_last = lag
            max_lag = q.get("maxLagRecords")
            if max_lag is not None and lag > int(max_lag):
                self._error(
                    503,
                    f"bounded-staleness list lag {lag} records exceeds "
                    f"declared maxLagRecords {max_lag}",
                )
                return
        pager = getattr(self.store, "list_page", None)
        if pager is None or (limit <= 0 and not token):
            # the unpaged reply — byte-identical to the pre-pagination
            # wire (and therefore to a lag-0 rv=0 read of the same state)
            items, rv = self.store.list(
                kind, label_selector=ls, field_selector=fs,
            )
            if items:
                # a non-empty list proves the kind exists; an empty
                # 200 proves nothing (MemStore lists unknown kinds as
                # empty), so bare LIST successes never admit labels
                self.metrics.admit_resource(kind)
            self.metrics.list_pages.labels("full").inc()
            self._reply({
                "items": [
                    {"key": k, "object": o} for k, o in items
                ],
                "resourceVersion": rv,
            })
            return
        after_seq = 0
        through_seq = 0
        snapshot_rv = None
        if token:
            # malformed → ValueError → the caller's 400 (a retry loop
            # must not hammer a permanently-bad token); EXPIRED → 410
            snapshot_rv, after_seq, token_gen, through_seq = (
                codec.decode_continue(token)
            )
            horizon = self.store.compacted_through
            if snapshot_rv < horizon:
                self._error(
                    410,
                    f"continue token snapshot rv {snapshot_rv} compacted "
                    f"(through {horizon}) — restart the paged walk",
                )
                return
            store_gen = getattr(self.store, "list_generation", 0)
            if token_gen != store_gen:
                # seqs renumbered since the token was minted (crash
                # recovery / replica resync loaded a snapshot): the
                # cursor would silently skip or duplicate across the
                # renumbering, so expire it even when its rv clears the
                # compaction horizon
                self._error(
                    410,
                    "continue token predates a store snapshot load "
                    "(seq numbering reset) — restart the paged walk",
                )
                return
        wire = self._reply_codec()
        # the first page captures the walk's seq bound (echoed back by
        # the store) — later pages carry it in the token, so an object
        # created mid-walk (higher seq) can never splice into the cut
        items, store_rv, next_seq, has_more, through_seq = pager(
            kind, label_selector=ls, field_selector=fs,
            limit=limit, after_seq=after_seq, through_seq=through_seq,
        )
        if snapshot_rv is None:
            snapshot_rv = store_rv
        if items:
            self.metrics.admit_resource(kind)
        parts = [
            self.event_cache.item_bytes(kind, k, o, orv, wire)
            for k, o, orv in items
        ]
        cont = (
            codec.encode_continue(
                snapshot_rv, next_seq,
                getattr(self.store, "list_generation", 0),
                through_seq,
            )
            if has_more else None
        )
        self.metrics.list_pages.labels("paged").inc()
        self._reply_wire(
            codec.items_envelope(parts, snapshot_rv, wire, cont), wire,
        )

    @staticmethod
    def _selector_view(q: dict):
        """Per-watch SelectorView, or None without selectors. The streaming
        watch holds ONE view for the connection's lifetime (repeat foreign
        events are dropped); a long-poll request gets a fresh view each
        time (stateless protocol — degraded to one tombstone per foreign
        key per poll, still correct)."""
        from ..store.memstore import SelectorView

        ls = q.get("labelSelector", "")
        fs = q.get("fieldSelector", "")
        return SelectorView(ls, fs) if (ls or fs) else None

    def _event_bytes(self, e, scoped: bool, wire: str) -> bytes:
        """One event's wire body, always through the serialize-once cache.
        A scoped DELETED (including a selector REWRITE of the original
        event) ships no object body — the cache's ``tombstone`` key
        dimension keeps it distinct from the unscoped full-body entry
        while still sharing ONE encoding across every scoped watcher."""
        if scoped and e.type == "DELETED":
            # selector-scoped stream: never ship a body on DELETED (the
            # informer deletes by key; a tombstoned object may not even
            # match the selector)
            return self.event_cache.event_bytes(e, wire, tombstone=True)
        return self.event_cache.event_bytes(e, wire)

    def _events_body(self, events, cursor: int, scoped: bool,
                     wire: str) -> bytes:
        """The long-poll reply (and a batched-poll bucket) assembled by
        SPLICING cached event bytes — no event re-encodes on fan-out."""
        return codec.events_envelope(
            [self._event_bytes(e, scoped, wire) for e in events],
            cursor, wire,
        )

    def _watch(self, kind: str, q: dict) -> None:
        wire = self._reply_codec()
        rv = int(q.get("resourceVersion", 0))
        timeout = min(float(q.get("timeoutSeconds", 10)), 60.0)
        view = self._selector_view(q)
        # unscoped fast path: the store's per-event body ring hands back
        # cached wire bodies directly — no WatchEvent is ever materialized
        # on the fan-out path (the native core's list/watch hot loop)
        body_since = (
            getattr(self.store, "events_body_since", None)
            if view is None else None
        )
        try:
            if body_since is not None:
                parts, cursor = body_since(kind, rv, wire)
                if not parts and timeout > 0:
                    self.store.wait_for(rv, timeout=timeout)
                    parts, cursor = body_since(kind, rv, wire)
                body = codec.events_envelope(parts, cursor, wire)
            else:
                events, cursor = self.store._events_since(kind, rv)
                if not events and timeout > 0:
                    self.store.wait_for(rv, timeout=timeout)
                    events, cursor = self.store._events_since(kind, rv)
                if view is not None:
                    events = view.filter(events)
                body = self._events_body(events, cursor, view is not None,
                                         wire)
        except CompactedError as e:
            # the watch cache's "too old resource version" → HTTP 410
            self._error(410, str(e))
            return
        self._reply_wire(body, wire)

    def _drain_buckets(self, buckets: dict, wire: str,
                       bind_deltas: bool = False):
        """One drain of every bucket's cursor → ({kind: (event bodies,
        cursor) | CompactedError}, drain revision). Uses the store's
        body-ring bulk drain when it has one (ONE lock round, cached
        bodies, zero WatchEvent churn; bind deltas where asked);
        otherwise materializes through ``events_since_bulk`` + the
        serialize-once cache, whole bodies only."""
        bulk_bodies = getattr(self.store, "events_body_since_bulk", None)
        if bulk_bodies is not None:
            return bulk_bodies(buckets, wire, bind_deltas)
        results, drain_rv = self.store.events_since_bulk(buckets)
        out: dict = {}
        for kind, res in results.items():
            if isinstance(res, CompactedError):
                out[kind] = res
                continue
            events, cursor = res
            out[kind] = (
                [self._event_bytes(e, False, wire) for e in events],
                cursor,
            )
        return out, drain_rv

    def _watch_bulk(self, q: dict) -> None:
        """Batched watch poll: ``buckets=pods:12,nodes:7`` drains every
        kind's cursor — ONE store lock acquisition, ONE HTTP round trip —
        with per-kind results (a compacted cursor 410s only its own
        bucket). Selectors are not supported on the batched poll (the
        per-kind endpoint remains for scoped watchers). With
        ``bindDeltas=1`` a pods bind op's event is its delta (the module
        docstring's guarantee)."""
        wire = self._reply_codec()
        buckets: dict[str, int] = {}
        for part in q["buckets"].split(","):
            kind, sep, rv = part.rpartition(":")
            if not sep or not kind:
                raise ValueError(f"malformed bucket {part!r} (want kind:rv)")
            buckets[kind] = int(rv)
        timeout = min(float(q.get("timeoutSeconds", 0)), 60.0)
        bind_deltas = q.get(BIND_DELTAS_PARAM) == "1"
        results, drain_rv = self._drain_buckets(buckets, wire, bind_deltas)
        if timeout > 0 and not any(
            isinstance(r, CompactedError) or r[0]
            for r in results.values()
        ):
            # wait on the revision captured AT the drain (same lock round):
            # a write landing after the drain wakes this immediately
            self.store.wait_for(drain_rv, timeout=timeout)
            results, _ = self._drain_buckets(buckets, wire, bind_deltas)
        parts = []
        for kind in buckets:
            res = results[kind]
            if isinstance(res, CompactedError):
                body = codec.dumps({"error": str(res), "code": 410}, wire)
            else:
                bodies, cursor = res
                body = codec.events_envelope(bodies, cursor, wire)
            parts.append((kind, body))
        self._reply_wire(codec.buckets_envelope(parts, wire), wire)

    def _watch_stream(self, kind: str, q: dict) -> None:
        """Chunked watch stream: events written as they happen, connection
        held open up to ``timeoutSeconds`` (capped) — the watch-stream form
        of the same cursor protocol. JSON streams are ndjson (one event
        per line); a negotiated binary stream is u32-length-prefixed
        frames (``application/x-kubetpu-bin-seq``). A compaction
        mid-stream emits an error frame with code 410 and ends the stream
        (client relists)."""
        import time as _time

        wire = self._reply_codec()
        rv = int(q.get("resourceVersion", 0))
        timeout = min(float(q.get("timeoutSeconds", 30)), 300.0)
        try:
            view = self._selector_view(q)
        except ValueError as e:
            self._error(400, str(e))
            return
        body_since = (
            getattr(self.store, "events_body_since", None)
            if view is None else None
        )
        deadline = _time.monotonic() + timeout
        self._status = 200
        self.send_response(200)
        self.send_header("Content-Type", (
            codec.binary_stream_content_type()
            if wire == codec.BINARY else "application/x-ndjson"
        ))
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk_bytes(data: bytes) -> bool:
            self.metrics.count_wire(wire, "out", len(data))
            try:
                self.wfile.write(f"{len(data):x}\r\n".encode())
                self.wfile.write(data + b"\r\n")
                self.wfile.flush()
                return True
            except (BrokenPipeError, ConnectionResetError, OSError):
                return False

        def frame(body: bytes) -> bool:
            return chunk_bytes(codec.stream_frame(body, wire))
        try:
            while True:
                try:
                    if body_since is not None:
                        # unscoped: cached bodies straight off the store's
                        # body ring — no WatchEvent materialization
                        bodies, cursor = body_since(kind, rv, wire)
                    else:
                        events, cursor = self.store._events_since(kind, rv)
                        if view is not None:
                            events = view.filter(events)
                        bodies = [
                            self._event_bytes(e, view is not None, wire)
                            for e in events
                        ]
                except CompactedError as e:
                    frame(codec.dumps({"error": str(e), "code": 410}, wire))
                    break
                for body in bodies:
                    # stream frames share the serialize-once cache with the
                    # poll paths — one encode serves every watcher
                    if not frame(body):
                        return   # client hung up: no terminator possible
                rv = cursor
                remaining = deadline - _time.monotonic()
                if remaining <= 0 or getattr(self.server, "closing", False):
                    break
                self.store.wait_for(rv, timeout=min(remaining, 1.0))
        finally:
            try:
                self.wfile.write(b"0\r\n\r\n")   # chunked terminator
                self.wfile.flush()
            except OSError:
                pass

    # -------------------------------------------------- shared verb cores
    # decode → admission → storage for one object, shared verbatim by the
    # single-op handlers and the bulk sequential path (the write path of
    # registry/store.go:514) — one copy, so the two surfaces cannot drift

    def _apply_create(self, kind: str, key: str, payload) -> int:
        # as_object: a binary body already materialized the typed object;
        # a JSON body left the kind-tagged dict — one normalization point
        obj = _stamp_pod_ingest(kind, codec.as_object(payload))
        self._note_pod_trace(kind, obj)     # ingest span ↔ pod timeline
        # the admission chain's write locks span admit AND create so a
        # usage-counting validator (quota) cannot race a concurrent
        # create of the same scope
        with self.registry.locked(kind, key, obj, verb="create"):
            obj = self.registry.admit(kind, key, obj, verb="create")
            return self.store.create(kind, key, obj)

    def _apply_update(
        self, kind: str, key: str, payload, expect_rv: int | None
    ) -> int:
        obj = codec.as_object(payload)
        self._note_pod_trace(kind, obj)     # bind-subresource span ↔ pod
        with self.registry.locked(kind, key, obj, verb="update"):
            old, _old_rv = self.store.get(kind, key)
            obj = self.registry.admit(kind, key, obj, old=old, verb="update")
            return self.store.update(kind, key, obj, expect_rv=expect_rv)

    def do_POST(self) -> None:  # noqa: N802
        if not urlsplit(self.path).path.startswith(PREFIX):
            if not self._serve_collector("POST"):
                self._error(404, "unknown path")
            return
        if self._redirect_to_leader():
            return
        kind, key, _ = self._route()
        if kind is not None and key is None and kind.endswith(BULK_SUFFIX):
            resource = kind[: -len(BULK_SUFFIX)]
            with self._track_span("BULK", resource):
                try:
                    self._do_bulk(resource)
                except codec.UnsupportedWireError as e:
                    self._error(415, str(e))
                except FollowerWriteError:
                    # demoted mid-request (failover race): same answer as
                    # the up-front guard — go to the leader
                    self._reply_redirect()
                except Exception as e:
                    self._error(500, f"{type(e).__name__}: {e}")
            return
        if kind is None or key is None:
            self._error(404, "kind and key required")
            return
        with self._track_span("CREATE", kind):
            try:
                rv = self._apply_create(kind, key, self._read_body())
                self._reply({"resourceVersion": rv}, status=201)
            except FollowerWriteError:
                self._reply_redirect()
            except ConflictError as e:
                self._error(409, str(e))
            except ValidationError as e:
                self._error(422, str(e))
            except AdmissionDenied as e:
                self._error(403, str(e))
            except codec.UnsupportedWireError as e:
                self._error(415, str(e))
            except scheme.SchemeError as e:
                self._error(400, str(e))
            except Exception as e:
                self._error(500, f"{type(e).__name__}: {e}")

    def do_PUT(self) -> None:  # noqa: N802
        if self._redirect_to_leader():
            return
        kind, key, q = self._route()
        if kind is None or key is None:
            self._error(404, "kind and key required")
            return
        with self._track_span("UPDATE", kind):
            try:
                expect = (
                    int(q["resourceVersion"])
                    if "resourceVersion" in q else None
                )
                rv = self._apply_update(kind, key, self._read_body(), expect)
                self._reply({"resourceVersion": rv})
            except FollowerWriteError:
                self._reply_redirect()
            except ConflictError as e:
                self._error(409, str(e))
            except ValidationError as e:
                self._error(422, str(e))
            except AdmissionDenied as e:
                self._error(403, str(e))
            except codec.UnsupportedWireError as e:
                self._error(415, str(e))
            except scheme.SchemeError as e:
                self._error(400, str(e))
            except Exception as e:
                self._error(500, f"{type(e).__name__}: {e}")

    def _do_bulk(self, kind: str) -> None:
        """POST /apis/<kind>:bulk — results are positional; each op's
        status/resourceVersion/error matches what its single-op verb would
        have returned, so a mid-batch conflict or admission veto fails only
        its own op. Two execution paths, chosen per BATCH by whether the
        admission chain engages for its objects
        (``Registry.has_dynamic_admission``):

        - ``one_lock``: no hook and no write lock engages (none registered
          for the kind, or each says so of this batch: quota admission
          where no namespace of the batch holds a quota — the generator's
          creates, the scheduler's binds and Events): decode +
          strategy-validate per op, then apply every surviving storage
          write under ONE store lock acquisition, one WAL group commit and
          one watch wake-up (``MemStore.bulk``);
        - ``sequential``: something engages (a quota in a namespace of the
          batch, a webhook): the WHOLE batch runs the EXACT single-verb
          chain op by op — lock spans admit AND write, and an update's
          ``old`` reflects earlier ops in the same batch — trading the
          one-lock storage pass for unchanged admission atomicity (the
          round trip is still one).

        The decision is the storage pass's ``guard``, asked under the same
        store lock acquisition that applies the batch: a ResourceQuota the
        store committed before the batch's first write sends all of it
        down the sequential chain. ``apiserver_bulk_ops_total{resource,
        path}`` and the BULK span's ``path`` say which was taken.

        A pods ``bind`` op (the binding subresource: key, uid, node; no
        object) decodes nothing and validates the node it sets: on the
        one-lock pass the store sets it on the pod it holds
        (``MemStore.bulk``); on the sequential chain the stored pod with
        its node set is the update the chain admits, CAS on the revision
        read. ``apiserver_pod_binds_total{result}`` counts both."""
        body = self._read_body()
        ops = body.get("ops")
        if not isinstance(ops, list):
            self._error(400, "body must carry an ops list")
            return
        # a hook registered without a predicate engages whatever the batch
        # holds: nothing to prepare, nothing to ask the store
        path, out = "one_lock", None
        if not self.registry.has_dynamic_admission(kind):
            out = self._bulk_one_lock(kind, ops)
        if out is None:
            path = "sequential"
            self._span_pod_traces.clear()   # the chain notes each pod anew
            out = [self._bulk_op_sequential(kind, op) for op in ops]
        if any(r.get("status", 500) < 400 for r in out):
            # a 2xx op proves the kind exists (same gate as the single
            # verbs' proving responses)
            self.metrics.admit_resource(kind)
        self.metrics.count_bulk_ops(kind, path, len(ops))
        self.metrics.count_pod_binds(
            r["status"] for op, r in zip(ops, out)
            if isinstance(op, dict) and op.get("op") == "bind"
        )
        self._span_attrs["path"] = path
        self._reply({"results": out})

    def _bulk_one_lock(self, kind: str, ops: list) -> "list[dict] | None":
        """The one-lock pass of ``_do_bulk``: the positional results, or
        None (nothing written) when the admission chain engages for the
        batch's objects after all."""
        results: list[dict | None] = []
        prepared: list[dict | None] = []
        for op in ops:
            verb = op.get("op") if isinstance(op, dict) else None
            key = op.get("key") if isinstance(op, dict) else None
            try:
                if not key or verb not in _BULK_VERBS:
                    raise ValueError(_BULK_VERBS_ERROR)
                if verb in ("create", "update", "patch"):
                    obj = codec.as_object(op.get("object") or {})
                    real = "create" if verb == "create" else "update"
                    if real == "create":
                        obj = _stamp_pod_ingest(kind, obj)
                    self._note_pod_trace(kind, obj)
                    # strategy validation only: the guard below holds that
                    # no hook engages — no locker to hold, no hook to feed
                    # `old`, no per-op store read
                    self.registry.validate(kind, key, obj)
                    prepared.append({
                        "op": real, "key": key, "object": obj,
                        "expect_rv": op.get("resourceVersion"),
                    })
                elif verb == "bind":
                    # nothing to decode: the store sets the node on the
                    # pod it holds
                    prepared.append({
                        "op": "bind", "key": key, "uid": op.get("uid") or "",
                        "node": _bind_node(kind, key, op),
                    })
                else:
                    prepared.append({"op": verb, "key": key})
                results.append(None)     # filled from the storage pass
            except _OP_ERRORS as e:
                results.append(_op_error_result(e))
                prepared.append(None)
        store_ops = [p for p in prepared if p is not None]
        objs = _BatchObjects(
            self.store, kind,
            [p["object"] for p in store_ops if "object" in p],
            [p["key"] for p in store_ops if p["op"] == "bind"],
        )
        stored = self.store.bulk(
            kind, store_ops,
            guard=lambda: not self.registry.has_dynamic_admission(kind, objs),
        )
        if stored is None:
            return None
        for p, res in zip(store_ops, stored):
            if (
                p["op"] == "bind" and res["status"] == 200
                and len(self._span_pod_traces) < 64
            ):
                # the bind-subresource span's link to the pod, as an
                # update's: the pod as committed
                self._note_pod_trace(kind, self.store.get(kind, p["key"])[0])
        store_res = iter(stored)
        out = []
        for res in results:
            if res is None:
                # result objects stay LIVE — the negotiated reply codec
                # encodes them in _reply (no per-op pre-serialization)
                res = dict(next(store_res))
            res.setdefault("resourceVersion", 0)
            out.append(res)
        return out

    def _bulk_op_sequential(self, kind: str, op) -> dict:
        """One bulk op through the exact single-verb chain (the dynamic-
        admission path): write lock spanning admit AND storage write,
        ``old`` read inside the lock after every earlier op applied."""
        verb = op.get("op") if isinstance(op, dict) else None
        key = op.get("key") if isinstance(op, dict) else None
        try:
            if not key or verb not in _BULK_VERBS:
                raise ValueError(_BULK_VERBS_ERROR)
            if verb == "create":
                rv = self._apply_create(kind, key, op.get("object") or {})
                return {"status": 201, "resourceVersion": rv}
            if verb in ("update", "patch"):
                rv = self._apply_update(
                    kind, key, op.get("object") or {},
                    op.get("resourceVersion"),
                )
                return {"status": 200, "resourceVersion": rv}
            if verb == "delete":
                rv = self.store.delete(kind, key)
                return {"status": 200, "resourceVersion": rv}
            if verb == "bind":
                # the update the admission chain sees today: the stored
                # pod with its node set, CAS on the revision read
                node = _bind_node(kind, key, op)
                current, rv = self.store.get(kind, key)
                refused = bind_refusal(key, current, op.get("uid") or "")
                if refused is not None:
                    return refused
                rv = self._apply_update(kind, key, current.with_node(node), rv)
                return {"status": 200, "resourceVersion": rv}
            obj, rv = self.store.get(kind, key)      # verb == "get"
            if obj is None:
                return {
                    "status": 404, "resourceVersion": 0,
                    "error": f"{kind}/{key} not found",
                }
            return {"status": 200, "resourceVersion": rv, "object": obj}
        except _OP_ERRORS as e:
            return _op_error_result(e)

    def do_DELETE(self) -> None:  # noqa: N802
        if self._redirect_to_leader():
            return
        kind, key, _ = self._route()
        if kind is None or key is None:
            self._error(404, "kind and key required")
            return
        with self._track_span("DELETE", kind):
            try:
                rv = self.store.delete(kind, key)
                self._reply({"resourceVersion": rv})
            except FollowerWriteError:
                self._reply_redirect()
            except KeyError:
                self._error(404, f"{kind}/{key} not found")
            except Exception as e:
                self._error(500, f"{type(e).__name__}: {e}")


class APIServer:
    """In-process HTTP front for a MemStore (httptest.NewServer shape)."""

    def __init__(
        self, store: MemStore | None = None,
        host: str = "127.0.0.1", port: int = 0,
        registry: Registry | None = None,
        metrics_sources: tuple = (),
        wire: str = "binary",
        persistence: "str | None" = None,
        collector: bool = False,
        sentinel: "bool | object" = False,
        replication: "object | None" = None,
    ) -> None:
        """``metrics_sources``: extra Prometheus-text providers appended to
        GET /metrics (e.g. a co-hosted controller family's workqueue set).
        ``wire``: "binary" (default) negotiates the compact binary codec
        per request via Accept/Content-Type; "json" is the escape hatch —
        a JSON-only server that ignores binary Accept headers and 415s
        binary bodies (exactly what a pre-binary server build does, so
        mixed-version client/server pairs are testable).
        ``collector``: mount the embedded telemetry collector on this
        server's listener (/telemetry/export /telemetry/clock
        /telemetry/trace /telemetry/metrics /telemetry/flightrecorder
        /telemetry/top) — the apiserver doubles as the cluster's span/
        metrics sink, the ``kubetpu collector``-less deployment shape.
        ``sentinel``: embed the anomaly sentinel (telemetry.sentinel) —
        ``True`` builds one over the default rule table (or pass a
        pre-built ``Sentinel``), bound to THIS server's /metrics text
        (request histograms + the WAL fsync set), evaluated by a cadence
        thread (``start()`` spawns it), and served at /debug/alerts +
        /debug/bundle next to the other diagnostics.
        ``persistence``: a directory path makes the server's store durable
        (``--persistence dir``): recover-on-start replays the WAL +
        snapshot, every committed write is logged-then-applied, and
        ``close()`` flushes the log so a graceful stop never leaves a
        torn tail. Ignored when an existing ``store`` is passed in — its
        durability is the caller's choice.
        ``replication``: a pre-built replication role
        (``store.replication.LeaderLease`` over this server's own store,
        or a ``FollowerReplicator`` tailing a leader into it) — mounts
        /replication/log, /replication/snapshot, /replication/status,
        turns on the follower write redirect, and adds the role's metrics
        to /metrics. ``start()``/``close()`` run its lifecycle. ``None``
        (the default) leaves the server exactly as before — the
        single-apiserver escape hatch."""
        if wire not in ("binary", "json"):
            raise ValueError(f"wire must be binary|json, got {wire!r}")
        # close() tears down only a store THIS server created — a passed-in
        # store's lifecycle (and durability) stays the caller's
        self._owns_store = store is None
        self.store = (
            store if store is not None else MemStore(persistence=persistence)
        )
        self.registry = registry if registry is not None else Registry()
        self.metrics = APIServerMetrics()
        self.health = HealthChecks()
        # the storage-backend check (the reference's etcd check): probing
        # the store's revision counter exercises its lock + native core
        def _store_check() -> None:
            rv = self.store.resource_version   # property on MemStore
            if callable(rv):                   # method on store stand-ins
                rv()

        # healthz/readyz only — the reference excludes its etcd check
        # from /livez: a storage outage must mark the server NOT-READY,
        # not not-alive, or a liveness probe restart-loops a process
        # that is still serving watches
        self.health.add_check(
            "store", _store_check, endpoints=("healthz", "readyz")
        )
        # serialize-once watch fan-out: one wire encode per event per
        # codec, shared across every watcher poll, batched poll, and
        # stream frame (the store binding merges the native body ring's
        # hit/miss counters into the exposed numbers)
        self.event_cache = EventEncodeCache(store=self.store)
        # server spans: one per request through the _track_span seam,
        # joined to client spans via the propagated traceparent; drained
        # by the telemetry exporter, browsable at /trace
        from ..tracing import Tracer

        self.tracer = Tracer(max_spans=8192)
        self.collector = None
        if collector:
            from ..telemetry.collector import Collector

            self.collector = Collector()
        # durable-store observability: the WAL's fsync histogram +
        # segment/byte/snapshot-age gauges ride this server's /metrics
        # (a memory-only store exposes nothing)
        wal_sources: tuple = ()
        if getattr(self.store, "persistent", False):
            wal_text = getattr(self.store, "wal_metrics_text", None)
            if callable(wal_text):
                wal_sources = (wal_text,)

        def _event_cache_metrics() -> str:
            stats = self.event_cache.stats_by_codec()
            lines = [
                "# HELP apiserver_watch_event_encodings_total Watch event "
                "wire serializations by outcome and codec (hit = cached "
                "bytes reused across watchers).\n"
                "# TYPE apiserver_watch_event_encodings_total counter\n"
            ]
            for c in sorted(stats):
                h, m = stats[c]
                lines.append(
                    "apiserver_watch_event_encodings_total"
                    f"{{result=\"hit\",codec=\"{c}\"}} {h}\n"
                    "apiserver_watch_event_encodings_total"
                    f"{{result=\"miss\",codec=\"{c}\"}} {m}\n"
                )
            return "".join(lines)

        def _list_lag_metrics() -> str:
            # bounded-staleness read plane: the replication lag (records)
            # the last rv=0 list was served at. Emitted ONLY on a live
            # follower — unreplicated/leader servers omit the series, so
            # the sentinel's list-lag rule stays dormant there (same
            # contract as store_replication_lag_records)
            if not getattr(self.store, "follower", False):
                return ""
            lag = self.metrics.list_lag_last
            if lag is None:
                return ""
            return (
                "# HELP store_list_lag_records Replication records the "
                "last rv=0 (bounded-staleness) list trailed the leader "
                "by.\n"
                "# TYPE store_list_lag_records gauge\n"
                f"store_list_lag_records {lag}\n"
            )

        # embedded anomaly sentinel: watches THIS server's own scrape
        # (request histograms + the WAL fsync set) on a cadence thread
        self.sentinel = None
        if sentinel:
            from ..telemetry.sentinel import Sentinel

            self.sentinel = (
                sentinel if isinstance(sentinel, Sentinel) else Sentinel()
            )
            bundle_sources: dict = {}
            wal_stats = getattr(self.store, "wal_stats", None)
            if callable(wal_stats):
                bundle_sources["wal"] = wal_stats
            bundle_sources["event_cache"] = self.event_cache.stats_by_codec
            self.sentinel.bind(
                metrics_fn=self.metrics_text,
                tracer=self.tracer,
                bundle_sources=bundle_sources,
                process="apiserver",
                component="apiserver",
            )
        sentinel_sources: tuple = ()
        if self.sentinel is not None:
            sentinel_sources = (self.sentinel.metrics_text,)
        # the replication role's gauges (lag/epoch/applied) ride this
        # server's /metrics — the sentinel's replication_lag rule and the
        # telemetry exporter both read them from here
        self.replication = replication
        rep_sources: tuple = ()
        if replication is not None:
            rep_text = getattr(replication, "metrics_text", None)
            if callable(rep_text):
                rep_sources = (rep_text,)
        self._metrics_sources = (
            _event_cache_metrics, _list_lag_metrics, *wal_sources,
            *rep_sources, *sentinel_sources, *metrics_sources,
        )
        handler = type("BoundHandler", (_Handler,), {
            "store": self.store, "registry": self.registry,
            "metrics": self.metrics, "health": self.health,
            "event_cache": self.event_cache,
            "tracer": self.tracer,
            "collector": self.collector,
            "sentinel": self.sentinel,
            "replication": self.replication,
            "wire_enabled": wire == "binary",
            "metrics_sources": self._metrics_sources,
            # responses are small; Nagle + the client's delayed ACK would
            # stall every keep-alive request ~40 ms (a handler-class knob:
            # socketserver.StreamRequestHandler.disable_nagle_algorithm)
            "disable_nagle_algorithm": True,
        })

        class _Server(ThreadingHTTPServer):
            # streaming watch handlers hold connections open (bounded by
            # their own deadlines + the `closing` flag, checked every ≤1 s);
            # close() must not block on them
            daemon_threads = True
            block_on_close = False
            closing = False

            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self._conn_lock = threading.Lock()
                self._conns: set = set()

            def get_request(self):
                sock, addr = super().get_request()
                with self._conn_lock:
                    self._conns.add(sock)
                return sock, addr

            def shutdown_request(self, request):
                with self._conn_lock:
                    self._conns.discard(request)
                super().shutdown_request(request)

            def sever(self) -> None:
                """Half-close every live connection: a handler blocked on
                the next keep-alive request reads EOF and exits cleanly,
                so a closed server is DOWN for clients that already held a
                connection — without this, keep-alive handler threads
                outlive close() and a 'killed' leader keeps serving its
                replication feed (failover never sees the death)."""
                import socket as _socket

                with self._conn_lock:
                    conns = list(self._conns)
                for sock in conns:
                    try:
                        sock.shutdown(_socket.SHUT_RDWR)
                    except OSError:
                        pass

            def handle_error(self, request, client_address):
                if not self.closing:
                    super().handle_error(request, client_address)

        self._httpd = _Server((host, port), handler)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )

    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def attach_replication(self, replication) -> None:
        """Bind a replication role AFTER construction — the leader's lease
        identity is its own URL, which exists only once the listener is
        bound (``--port 0``). Must run before ``start()``: mounts the
        /replication/* endpoints, the follower write redirect, and the
        role's metrics, exactly as the constructor param would."""
        self.replication = replication
        self._httpd.RequestHandlerClass.replication = replication
        rep_text = getattr(replication, "metrics_text", None)
        if callable(rep_text) and rep_text not in self._metrics_sources:
            # keep the constructor's source order: the role's gauges sit
            # right after the store/WAL set, before the sentinel's
            self._metrics_sources = (
                *self._metrics_sources[:1], rep_text,
                *self._metrics_sources[1:],
            )
            self._httpd.RequestHandlerClass.metrics_sources = (
                self._metrics_sources
            )

    def metrics_text(self) -> str:
        """The same Prometheus text GET /metrics serves (request set +
        event-cache counters + WAL set + extra sources) — the telemetry
        exporter's snapshot source."""
        chunks = [self.metrics.expose()]
        for source in self._metrics_sources:
            chunks.append(source())
        return "".join(chunks)

    def start(self) -> "APIServer":
        self._thread.start()
        if self.replication is not None:
            # leader: take the writer lease before serving writes;
            # follower: start the tail (the listener is already up, so a
            # peer's status probe can reach us during bootstrap)
            self.replication.start()
        if self.sentinel is not None:
            # thread-served owner: the sentinel runs its own cadence
            # (the scheduler instead evaluates at its cycle boundary)
            self.sentinel.start()
        return self

    def close(self) -> None:
        if self.replication is not None:
            # stop the renew/tail thread while the store and peers are
            # still reachable (a leader releases the writer lease here)
            self.replication.close()
        if self.sentinel is not None:
            self.sentinel.close()
        self._httpd.closing = True
        self._httpd.shutdown()
        self._httpd.server_close()
        self._httpd.sever()
        self._thread.join(timeout=5)
        # AFTER the listener is down (no request can append mid-close):
        # flush + fsync + close an OWNED store's WAL, so a graceful stop
        # never leaves a torn tail for the next boot's recovery to
        # truncate. A caller-provided store stays open — its durability
        # and lifecycle are the caller's (writes after OUR close must not
        # silently stop reaching its log)
        if self._owns_store:
            close_store = getattr(self.store, "close", None)
            if callable(close_store):
                close_store()
