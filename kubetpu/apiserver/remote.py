"""RemoteStore — the store protocol over the API server's REST + watch.

The client-go side of the process boundary: a RemoteStore exposes the SAME
surface the in-process MemStore does (get/list/create/update/delete/watch),
so ``Reflector``/``SchedulerInformers``/``StoreClient`` and every
controller run unchanged against a remote API server — scheduler and
control plane in separate processes, exactly the reference's deployment
shape (components talk only to the apiserver, SURVEY §1).

Watch is the pull form: ``RemoteWatcher.poll`` GETs
``?watch=1&resourceVersion=<cursor>`` with a short long-poll; HTTP 410 maps
back to ``CompactedError`` so the reflector's relist path fires.

WIRE NEGOTIATION (kubetpu.api.codec): with ``wire="binary"`` (the default)
every request carries ``Accept: application/x-kubetpu-bin; v=…;
schema=<fp>``; the server replies binary only when the fingerprint matches
its own, and the first binary-typed response CONFIRMS the dialect — only
then do request bodies switch to binary (a body is never sent in a format
the server has not proven it decodes). A 415 at any point (schema drift, a
JSON-only server) drops this client to JSON permanently and re-issues the
request once — mixed-version client/server pairs keep working in both
directions. Responses always decode by their Content-Type, so the two
sides never have to agree in advance.
"""

from __future__ import annotations

import http.client
import threading
import time
from typing import Any

from ..api import codec
from ..store.memstore import CompactedError, ConflictError, WatchEvent

BULK_SUFFIX = ":bulk"
#: the batched watch poll's query parameter that asks for bind deltas
BIND_DELTAS_PARAM = "bindDeltas"


class RemoteStoreError(Exception):
    pass


class RemoteUnavailableError(ConnectionError):
    """Transient transport failure (connection refused/reset, timeout):
    derives from ConnectionError so pump loops can catch-and-retry it the
    way client-go's ListAndWatch retries — one apiserver restart must not
    kill a component process."""


class RemoteStore:
    #: watch-path reconnect policy (see ``_watch_request``): capped
    #: jittered exponential backoff with a retry budget — a restarting
    #: apiserver is a BOUNDED stall for the informer pump, not informer
    #: death, and not an unthrottled hammer on the returning server
    WATCH_RETRY_BUDGET = 6
    BACKOFF_BASE_S = 0.05
    BACKOFF_CAP_S = 2.0
    BACKOFF_JITTER = 0.25       # +/- fraction of the delay
    #: default LIST page size: every relist is a limit/continue walk of
    #: N bounded RPCs instead of one unbounded reply — at 50k nodes the
    #: unpaged body is tens of MB in one read, the paged walk is ~100
    #: requests that each fit in a socket buffer. 0 disables paging.
    LIST_PAGE_LIMIT = 500
    #: per-PAGE retry budget (the watch path's policy applied to each
    #: page GET): a page is an idempotent snapshot-pinned read, so the
    #: capped-jitter retry that hardens watch polls is safe here too
    LIST_RETRY_BUDGET = 6

    def __init__(self, base_url: str, timeout_s: float = 30.0,
                 wire: str = "binary", traceparent: bool = False,
                 tracer=None) -> None:
        """``traceparent=True`` stamps a W3C-style trace context on every
        RPC — the ``traceparent`` header on the JSON wire, the ``tp``
        media-type parameter on the binary envelope (both through the
        codec seam, so a 415/JSON fallback carries the SAME value in the
        other slot) — and, with a ``tracer`` bound, records one client
        span per request so the apiserver's server span joins it. False
        (the default, ``--telemetry off``) is byte-identical to the
        pre-telemetry wire: no header, no parameter, no span."""
        if wire not in ("binary", "json"):
            raise ValueError(f"wire must be binary|json, got {wire!r}")
        self.base = base_url.rstrip("/")
        self.timeout_s = timeout_s
        self._traceparent = traceparent
        self._tracer = tracer
        # persistent per-THREAD connections (client-go's transport reuse):
        # a fresh TCP handshake per request would dominate the bind path
        self._local = threading.local()
        # negotiation state: None = undetermined (Accept advertises binary,
        # bodies still ride JSON), True = server confirmed our dialect
        # (bodies go binary), False = JSON only (wire="json", or a 415
        # dropped us there permanently). Plain attribute: worst case two
        # threads re-confirm/re-fall-back — both idempotent.
        self._wire_ok: "bool | None" = None if wire == "binary" else False
        # replicated read plane: when ``base_url`` is a FOLLOWER apiserver
        # its 307 names the leader — writes retarget there (and stay
        # there), reads/watches keep riding the follower. Cleared when the
        # leader stops answering (failover: the next 307 re-learns it).
        self._write_base: "str | None" = None
        # apiserver_client_reconnects_total{reason}: every watch-path
        # retry taken after a transport failure, by failure class, plus
        # every list-page retry under reason="list" — the
        # restart-visibility counter (guarded: watcher threads + a
        # diagnostics scrape share it)
        self._reconnect_lock = threading.Lock()
        self.reconnect_counts: dict[str, int] = {}
        # paged-relist evidence: cumulative totals plus the last walk's
        # shape (pages, wire bytes, largest page)
        self.relist_stats: dict[str, int] = {
            "relists": 0, "pages": 0, "bytes": 0, "max_page_bytes": 0,
        }
        self.last_relist: "dict[str, int] | None" = None
        # the decode clock: what ``codec.loads`` of response bodies costs
        # this process, one ``perf_counter`` pair a RESPONSE. A cell
        # ``[seconds, bytes, seconds of watch_bulk replies alone]`` per
        # thread, written by its thread alone (no lock a response), summed
        # per caller at scrape time: "loop" is the thread that built this
        # client (in ``kubetpu scheduler`` the one that runs the loop),
        # "worker" any other (the dispatcher's)
        self._owner_thread = threading.get_ident()
        self._decode_cells: list[tuple[str, list]] = []

    # ------------------------------------------------- reconnect policy
    @staticmethod
    def _failure_reason(e: Exception) -> str:
        """Coarse failure class for the reconnect counter's label."""
        msg = str(e).lower()
        if "refused" in msg:
            return "refused"
        if "reset" in msg or "disconnected" in msg or "aborted" in msg:
            return "reset"
        if "timed out" in msg or "timeout" in msg:
            return "timeout"
        return "other"

    def _count_reconnect(self, reason: str) -> None:
        with self._reconnect_lock:
            self.reconnect_counts[reason] = (
                self.reconnect_counts.get(reason, 0) + 1
            )

    def reconnect_metrics_text(self) -> str:
        """Prometheus text for the reconnect counter — mountable as a
        diagnostics metrics source next to the scheduler set."""
        with self._reconnect_lock:
            counts = dict(self.reconnect_counts)
        lines = [
            "# HELP apiserver_client_reconnects_total Watch/long-poll "
            "retries taken after a transport failure, by failure class "
            "(list-page retries ride reason=\"list\").\n"
            "# TYPE apiserver_client_reconnects_total counter\n"
        ]
        for reason in sorted(counts):
            lines.append(
                "apiserver_client_reconnects_total"
                f"{{reason=\"{reason}\"}} {counts[reason]}\n"
            )
        return "".join(lines)

    # ------------------------------------------------------- decode clock
    def _decode_cell(self) -> list:
        """This thread's ``[seconds, bytes, watch seconds]``."""
        cell = getattr(self._local, "decode", None)
        if cell is None:
            cell = self._local.decode = [0.0, 0, 0.0]
            caller = (
                "loop" if threading.get_ident() == self._owner_thread
                else "worker"
            )
            with self._reconnect_lock:
                self._decode_cells.append((caller, cell))
        return cell

    def _decode(self, raw: bytes, resp_ct: "str | None") -> Any:
        """One response body → its tree, by its Content-Type, on the
        decode clock. (On the JSON wire this is ``json.loads``: the typed
        decode happens in ``codec.as_object``, outside the clock.)"""
        cell = self._decode_cell()
        t0 = time.perf_counter()
        try:
            return codec.loads(
                raw or b"{}", codec.codec_for_content_type(resp_ct)
            )
        finally:
            cell[0] += time.perf_counter() - t0
            cell[1] += len(raw)

    @property
    def watch_decode_s(self) -> float:
        """Decode seconds of ``watch_bulk`` replies alone (the informers'
        poll), every thread's."""
        with self._reconnect_lock:
            return sum(cell[2] for _caller, cell in self._decode_cells)

    def decode_metrics_text(self) -> str:
        """Prometheus text for the decode clock, a diagnostics metrics
        source beside the reconnect counter. The watch family carries the
        scheduler's name: its informers' pump is ``watch_bulk``'s one
        caller."""
        with self._reconnect_lock:
            cells = list(self._decode_cells)
        seconds = {"loop": 0.0, "worker": 0.0}
        size = {"loop": 0, "worker": 0}
        watch_s = 0.0
        for caller, (s, n, w) in cells:
            seconds[caller] += s
            size[caller] += n
            watch_s += w
        lines = [
            "# HELP apiserver_client_decode_seconds_total Seconds this "
            "client spent decoding response bodies (codec.loads), by the "
            "thread that decoded: loop built the client, worker is any "
            "other.\n"
            "# TYPE apiserver_client_decode_seconds_total counter\n"
        ]
        lines += [
            f'apiserver_client_decode_seconds_total{{caller="{c}"}} '
            f"{seconds[c]:.6f}\n" for c in seconds
        ]
        lines.append(
            "# HELP apiserver_client_decoded_bytes_total Bytes of the "
            "response bodies decoded, by the same threads.\n"
            "# TYPE apiserver_client_decoded_bytes_total counter\n"
        )
        lines += [
            f'apiserver_client_decoded_bytes_total{{caller="{c}"}} '
            f"{size[c]}\n" for c in size
        ]
        lines.append(
            "# HELP scheduler_watch_decode_seconds_total Seconds the "
            "informers' pump spent decoding watch_bulk replies: the "
            "decode's part of the loop's pump_rpc phase.\n"
            "# TYPE scheduler_watch_decode_seconds_total counter\n"
            f"scheduler_watch_decode_seconds_total "
            f"{watch_s:.6f}\n"
        )
        return "".join(lines)

    def _retried_get(self, path: str, budget: int, reason_for):
        """One idempotent GET hardened for apiserver restarts: a
        transient transport failure (past ``_request``'s single provably-
        safe retry) backs off — capped, jittered, exponential — and
        retries within ``budget``, counting each retry under
        ``reason_for(exc)``. Safe only for reads whose effect does not
        move on failure (watch polls: the cursor only advances on a
        delivered reply; list pages: snapshot-pinned by the continue
        token). A budget exhausted raises the last
        RemoteUnavailableError — the caller's catch-and-retry keeps the
        component alive at its own cadence."""
        import random

        for attempt in range(budget + 1):
            if attempt:
                delay = min(
                    self.BACKOFF_BASE_S * (2 ** (attempt - 1)),
                    self.BACKOFF_CAP_S,
                )
                delay *= 1.0 + random.uniform(
                    -self.BACKOFF_JITTER, self.BACKOFF_JITTER
                )
                time.sleep(delay)
            try:
                return self._request("GET", path)
            except RemoteUnavailableError as e:
                if attempt >= budget:
                    raise       # budget spent: no retry follows, no count
                self._count_reconnect(reason_for(e))

    def _watch_request(self, path: str):
        """Watch/long-poll GET with the reconnect policy, counted by
        failure class (``_failure_reason``)."""
        return self._retried_get(
            path, self.WATCH_RETRY_BUDGET, self._failure_reason
        )

    def _list_page_request(self, path: str):
        """One LIST page GET with the same capped-jitter policy the
        watch path rides, counted under reason="list" — a 50k relist is
        N bounded, individually-retried RPCs, not one unbounded GET
        whose mid-transfer failure restarts the whole transfer."""
        return self._retried_get(
            path, self.LIST_RETRY_BUDGET, lambda _e: "list"
        )

    @property
    def wire_codec(self) -> str:
        """The codec request BODIES currently ride ("binary" only after
        the server confirmed the dialect) — a result's wire_codec tag."""
        return codec.BINARY if self._wire_ok else codec.JSON

    # ------------------------------------------------------------ plumbing
    def _connection(self, base: "str | None" = None):
        """→ (conn, reused): ``reused`` marks a kept-alive socket — the
        idle-close race (server dropped it between our requests) is the one
        failure where resending is provably safe for any verb. One
        persistent connection per (thread, base): the write-redirect path
        talks to the leader without tearing down the follower's socket."""
        import socket
        from urllib.parse import urlsplit

        target = base or self.base
        conns = getattr(self._local, "conns", None)
        if conns is None:
            conns = self._local.conns = {}
        conn = conns.get(target)
        if conn is not None:
            return conn, True
        u = urlsplit(target)
        conn = http.client.HTTPConnection(
            u.hostname, u.port, timeout=self.timeout_s
        )
        conn.connect()
        # request bodies are small: without TCP_NODELAY, Nagle +
        # delayed-ACK stalls every keep-alive request ~40 ms
        conn.sock.setsockopt(
            socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
        )
        conns[target] = conn
        return conn, False

    def _drop_connection(self, base: "str | None" = None) -> None:
        target = base or self.base
        conns = getattr(self._local, "conns", None)
        conn = conns.pop(target, None) if conns else None
        if conn is not None:
            try:
                conn.close()
            except OSError:
                pass

    def _request(self, method: str, path: str, body: Any = None):
        """One request through the wire seam. ``body`` is the reply-shaped
        TREE (may contain live registered dataclasses) — the negotiated
        codec encodes it here, so no caller pre-serializes. A 415 response
        means the server cannot decode our binary dialect: fall back to
        JSON permanently and re-issue once (the mixed-version path)."""
        # ONE trace context per logical request: the 415/JSON re-issue
        # below carries the SAME value back in the header envelope, so
        # the two attempts correlate as one trace
        ctx = self._trace_context()
        # writes ride the learned leader base (replicated read plane);
        # reads/watches always ride self.base — that IS the offload
        base = self._write_base if method != "GET" else None
        for _redirect in range(3):
            try:
                for _wire_attempt in range(2):
                    status, raw, resp_ct = self._request_transport(
                        method, path, body, ctx, base=base
                    )
                    if status == 415 and self._wire_ok is not False:
                        self._wire_ok = False
                        continue
                    break
            except RemoteUnavailableError:
                if base is not None:
                    # the learned leader stopped answering (failover):
                    # forget it — the next 307 from our replica names the
                    # new one
                    self._write_base = None
                raise
            if status != 307:
                break
            # follower write redirect: the reply body names the leader
            payload = {}
            try:
                payload = self._decode(raw, resp_ct)
            except Exception:  # noqa: BLE001 — fall through to the error below
                pass
            leader = (payload.get("leader") or "").rstrip("/")
            if not leader or leader == (base or self.base):
                raise RemoteStoreError(
                    "follower apiserver redirected a write but named no "
                    "usable leader"
                )
            self._write_base = base = leader
        if status < 400:
            try:
                return self._decode(raw, resp_ct)
            except codec.UnsupportedWireError as e:
                raise RemoteStoreError(f"undecodable response: {e}") \
                    from None
        payload = {}
        try:
            payload = self._decode(raw, resp_ct)
        except Exception:
            pass
        reason = payload.get("error", f"HTTP {status}")
        if status == 409:
            raise ConflictError(reason)
        if status == 410:
            raise CompactedError(reason)
        if status == 404:
            raise KeyError(reason)
        if status in (400, 422):
            # 400: malformed request (bad selector); 422: strategy
            # validation rejected the object (admission.py)
            raise ValueError(reason)
        if status == 403:
            # validating admission hook vetoed the write
            raise PermissionError(reason)
        raise RemoteStoreError(f"{status}: {reason}")

    def set_tracer(self, tracer) -> None:
        """Bind the span recorder client rpc spans land in (the owning
        component's Tracer) — split from __init__ because the scheduler
        that owns the tracer is constructed around this store."""
        self._tracer = tracer

    def _trace_context(self):
        """A fresh per-request trace context when propagation is on
        (telemetry); None otherwise — and None means the request's bytes
        are identical to a pre-telemetry client's."""
        if not self._traceparent:
            return None
        from ..telemetry.context import TraceContext, new_span_id, new_trace_id

        return TraceContext(new_trace_id(), new_span_id())

    def _request_headers(self, wire_out: str, ctx=None) -> dict:
        tp = None
        if ctx is not None:
            from ..telemetry.context import format_traceparent

            tp = format_traceparent(ctx)
        if wire_out == codec.BINARY:
            # binary envelope: the traceparent rides the media type next
            # to the schema fingerprint (codec.TRACEPARENT_PARAM)
            headers = {
                "Content-Type": codec.content_type_for(wire_out, tp)
            }
        else:
            headers = {"Content-Type": codec.content_type_for(wire_out)}
            if tp:
                headers[codec.TRACEPARENT_HEADER] = tp
        if self._wire_ok is not False:
            # advertise our binary dialect (media type + schema
            # fingerprint); a server that matches replies binary and
            # thereby confirms it
            headers["Accept"] = codec.binary_content_type()
        return headers

    def _note_response_ct(self, resp_ct: "str | None") -> None:
        """First binary-typed response confirms the dialect — request
        bodies switch to binary from here on."""
        if (
            self._wire_ok is None and resp_ct
            and codec.CT_BINARY in resp_ct
        ):
            self._wire_ok = True

    def _request_transport(self, method: str, path: str, body: Any,
                           ctx=None, base: "str | None" = None):
        """The transport half with ONE safe retry. Blindly resending a
        non-idempotent verb after a transport error could double-apply it
        (a create whose response was lost resends → 409 for a create that
        SUCCEEDED), so the retry is limited to failures that prove the
        server never processed the request: a send-phase error, or the
        keep-alive idle-close race (RemoteDisconnected on a REUSED socket —
        the server dropped the idle connection before reading). GETs retry
        on any transport error; everything else surfaces as
        RemoteUnavailableError for the caller to decide. Returns
        (status, raw body, response content type)."""
        wire_out = codec.BINARY if self._wire_ok else codec.JSON
        data = codec.dumps(body, wire_out) if body is not None else None
        # ``ctx`` is the caller's per-LOGICAL-request trace context: the
        # provably-safe retry below and _request's 415/JSON re-issue both
        # re-send with the same trace + span ids
        headers = self._request_headers(wire_out, ctx)
        t_span = time.perf_counter() if ctx is not None else 0.0
        last: Exception | None = None
        for attempt in range(2):
            try:
                conn, reused = self._connection(base)
                conn.request(method, path, body=data, headers=headers)
            except (ConnectionError, TimeoutError, OSError,
                    http.client.HTTPException) as e:
                # connect or send never completed: the server never saw
                # the request, safe to retry any verb once
                self._drop_connection(base)
                last = e
                continue
            try:
                resp = conn.getresponse()
                status, raw = resp.status, resp.read()
                # per-THREAD last-response size: the paged list walk
                # reads it back per page for the bytes/relist evidence
                self._local.last_raw_len = len(raw)
                resp_ct = resp.getheader("Content-Type")
                self._note_response_ct(resp_ct)
                if ctx is not None and self._tracer is not None:
                    # the client half of the cross-process join: the
                    # server span opened for this request carries the
                    # same trace id + this span id as its parent
                    self._tracer.record(
                        f"rpc.{method}", start=t_span,
                        end=time.perf_counter(), per_item=True,
                        path=path.partition("?")[0], status=status,
                        trace_id=ctx.trace_id, span_id=ctx.span_id,
                    )
                return status, raw, resp_ct
            except (ConnectionError, TimeoutError, OSError,
                    http.client.HTTPException) as e:
                self._drop_connection(base)
                last = e
                idle_close = reused and isinstance(
                    e, (http.client.RemoteDisconnected, ConnectionResetError)
                )
                if attempt == 0 and (method == "GET" or idle_close):
                    continue
                raise RemoteUnavailableError(str(e)) from None
        raise RemoteUnavailableError(str(last)) from None

    # ------------------------------------------------------ store protocol
    def get(self, kind: str, key: str):
        try:
            res = self._request("GET", f"/apis/{kind}/{key}")
        except KeyError:
            return None, 0
        return codec.as_object(res["object"]), res["resourceVersion"]

    def list(
        self, kind: str,
        label_selector: str = "", field_selector: str = "",
        limit: "int | None" = None,
    ):
        """Full LIST as a limit/continue PAGED WALK (``limit=None`` →
        ``LIST_PAGE_LIMIT``; 0 forces the legacy single unpaged GET).
        Every page is snapshot-pinned by the server's continue token and
        individually retried within ``LIST_RETRY_BUDGET``
        (``_list_page_request``); the returned resourceVersion is the
        walk's pinned snapshot rv, so a watch opened from it replays
        exactly the mid-walk delta. A mid-walk 410 (token outlived the
        event-log compaction window) restarts ONE fresh walk; the walk's
        shape lands in ``relist_stats``/``last_relist``."""
        page_limit = self.LIST_PAGE_LIMIT if limit is None else limit
        sel = _sel_qs("&", label_selector, field_selector)
        restarts = 0
        while True:
            try:
                return self._list_walk(kind, sel, page_limit)
            except CompactedError:
                if page_limit <= 0 or restarts >= 1:
                    raise
                restarts += 1

    def _list_walk(self, kind: str, sel: str, page_limit: int):
        """One attempted walk (or the one unpaged GET when
        ``page_limit`` ≤ 0). Raises CompactedError if a continue token
        expires mid-walk — ``list`` restarts fresh."""
        items: list = []
        rv = 0
        cont = ""
        pages = total_bytes = max_page = 0
        while True:
            if page_limit > 0:
                path = (
                    f"/apis/{kind}?limit={page_limit}"
                    + (f"&continue={cont}" if cont else "") + sel
                )
            else:
                path = f"/apis/{kind}" + (("?" + sel[1:]) if sel else "")
            res = self._list_page_request(path)
            page_bytes = getattr(self._local, "last_raw_len", 0)
            pages += 1
            total_bytes += page_bytes
            max_page = max(max_page, page_bytes)
            items.extend(
                (i["key"], codec.as_object(i["object"]))
                for i in res["items"]
            )
            rv = res["resourceVersion"]
            cont = res.get("continue", "")
            if not cont:
                break
        with self._reconnect_lock:
            self.relist_stats["relists"] += 1
            self.relist_stats["pages"] += pages
            self.relist_stats["bytes"] += total_bytes
            self.relist_stats["max_page_bytes"] = max(
                self.relist_stats["max_page_bytes"], max_page
            )
            self.last_relist = {
                "pages": pages, "bytes": total_bytes,
                "max_page_bytes": max_page,
            }
        return items, rv

    def create(self, kind: str, key: str, obj: Any) -> int:
        res = self._request("POST", f"/apis/{kind}/{key}", obj)
        return res["resourceVersion"]

    def update(
        self, kind: str, key: str, obj: Any, expect_rv: int | None = None
    ) -> int:
        q = f"?resourceVersion={expect_rv}" if expect_rv is not None else ""
        res = self._request("PUT", f"/apis/{kind}/{key}{q}", obj)
        return res["resourceVersion"]

    def delete(self, kind: str, key: str) -> int:
        res = self._request("DELETE", f"/apis/{kind}/{key}")
        return res["resourceVersion"]

    def bulk(self, kind: str, ops: list[dict]) -> list[dict]:
        """POST /apis/<kind>:bulk — N ops, ONE round trip, positional
        per-op results (``MemStore.bulk``'s shape: {"status",
        "resourceVersion", "error"?, "object"?}, objects decoded). Per-op
        failures ride the result list — only transport / whole-request
        errors raise. The one-safe-retry discipline applies per BATCH
        (``_request``'s send-phase / idle-close rules), so a batch is
        never double-applied."""
        wire = []
        for op in ops:
            w = {"op": op["op"], "key": op["key"]}
            if "object" in op:
                w["object"] = op["object"]    # live; the codec encodes it
            if op.get("expect_rv") is not None:
                w["resourceVersion"] = op["expect_rv"]
            for field in ("uid", "node"):     # a bind op's
                if field in op:
                    w[field] = op[field]
            wire.append(w)
        res = self._request("POST", f"/apis/{kind}{BULK_SUFFIX}",
                            {"ops": wire})
        out = []
        for r in res["results"]:
            if r.get("object") is not None:
                r = dict(r, object=codec.as_object(r["object"]))
            out.append(r)
        return out

    def watch_bulk(
        self, cursors: dict[str, int], timeout_s: float = 0.0,
        bind_deltas: bool = False,
    ) -> dict:
        """Batched watch poll: every kind's cursor drained in ONE request
        (GET /apis/?watch=1&buckets=…). Returns {kind: (events, cursor)}
        with a CompactedError VALUE for a compacted kind (the caller
        relists just that kind — the other buckets' deliveries still
        land). ``bind_deltas`` asks for a pods bind op's event as its
        delta: a ``WatchEvent`` with ``obj`` None and ``bind`` (uid,
        node), for a caller that holds the pods (``SharedInformer``
        rebuilds them); a server that does not know the parameter sends
        whole events, taken as ever."""
        qs = ",".join(f"{k}:{rv}" for k, rv in cursors.items())
        if bind_deltas:
            qs += f"&{BIND_DELTAS_PARAM}=1"
        cell = self._decode_cell()
        decode_s0 = cell[0]
        res = self._watch_request(
            f"/apis/?watch=1&buckets={qs}&timeoutSeconds={timeout_s}",
        )
        cell[2] += cell[0] - decode_s0
        out: dict = {}
        for kind, bucket in res["buckets"].items():
            if bucket.get("code") == 410:
                out[kind] = CompactedError(bucket.get("error", "compacted"))
                continue
            out[kind] = (
                [_watch_event(kind, e) for e in bucket["events"]],
                bucket["resourceVersion"],
            )
        return out

    def watch(
        self, kind: str | None, since_rv: int,
        label_selector: str = "", field_selector: str = "",
        stream: bool = False,
    ):
        if kind is None:
            raise RemoteStoreError("remote watch requires a kind")
        if stream:
            return RemoteStreamWatcher(
                self, kind, since_rv, label_selector, field_selector
            )
        return RemoteWatcher(
            self, kind, since_rv,
            label_selector=label_selector, field_selector=field_selector,
        )


def _watch_event(kind: str, e: dict) -> WatchEvent:
    """One decoded event of a batched poll: whole, or a bind delta."""
    bind = e.get("bind")
    if bind is not None:
        return WatchEvent(e["type"], kind, e["key"], None,
                          e["resourceVersion"], (bind["uid"], bind["node"]))
    return WatchEvent(e["type"], kind, e["key"], codec.as_object(e["object"]),
                      e["resourceVersion"])


def _sel_qs(prefix: str, label_selector: str, field_selector: str) -> str:
    from urllib.parse import quote

    parts = []
    if label_selector:
        parts.append(f"labelSelector={quote(label_selector)}")
    if field_selector:
        parts.append(f"fieldSelector={quote(field_selector)}")
    if not parts:
        return ""
    return prefix + "&".join(parts)


class RemoteWatcher:
    """Pull watcher over the REST watch endpoint (Watcher protocol)."""

    def __init__(
        self, store: RemoteStore, kind: str, since_rv: int,
        poll_timeout_s: float = 0.0,
        label_selector: str = "", field_selector: str = "",
    ) -> None:
        self._store = store
        self._kind = kind
        self._rv = since_rv
        self._sel = _sel_qs("&", label_selector, field_selector)
        # 0 = non-blocking poll (loop-pump shape); raise for long-polling
        self.poll_timeout_s = poll_timeout_s

    @property
    def resource_version(self) -> int:
        return self._rv

    @property
    def bulk_pollable(self) -> bool:
        """Eligible for the informer bundle's batched multi-kind poll —
        only an unscoped watcher (the batched endpoint carries no
        selector state)."""
        return not self._sel

    def advance(self, cursor: int) -> None:
        """Move the cursor after a batched poll delivered this kind's
        events out-of-band."""
        self._rv = cursor

    def poll(self) -> list[WatchEvent]:
        # the long-poll must stay under the transport timeout or a quiet
        # bucket reads as a (retryable) timeout every poll; the backoff-
        # hardened watch request rides out an apiserver restart
        wait = min(self.poll_timeout_s, max(self._store.timeout_s - 5.0, 0.0))
        res = self._store._watch_request(
            f"/apis/{self._kind}?watch=1&resourceVersion={self._rv}"
            f"&timeoutSeconds={wait}{self._sel}",
        )
        self._rv = res["resourceVersion"]
        return [
            WatchEvent(
                type=e["type"], kind=self._kind, key=e["key"],
                obj=codec.as_object(e["object"]),
                resource_version=e["resourceVersion"],
            )
            for e in res["events"]
        ]


class RemoteStreamWatcher:
    """STREAMING watcher: one chunked ndjson connection held open by the
    server (?watch=1&stream=1), a blocking reader thread decoding events as
    lines arrive (a non-blocking line read over a buffered socket could
    tear a line) — the reference's watch-stream shape. ``poll()`` stays
    non-blocking (drains the decoded queue), so the Reflector pump loop
    runs unchanged; a dropped/expired connection re-opens transparently
    from the cursor on the next poll; an in-stream 410 raises
    CompactedError (relist)."""

    def __init__(
        self, store: RemoteStore, kind: str, since_rv: int,
        label_selector: str = "", field_selector: str = "",
        stream_timeout_s: float = 120.0,
    ) -> None:
        import collections
        self._store = store
        self._kind = kind
        self._rv = since_rv
        self._sel = _sel_qs("&", label_selector, field_selector)
        self._stream_timeout_s = stream_timeout_s
        self._lock = threading.Lock()
        self._queue: collections.deque = collections.deque()
        self._thread: threading.Thread | None = None
        self._sock = None
        self._closed = False
        self.reconnects = 0

    @property
    def resource_version(self) -> int:
        return self._rv

    def _reader(self, start_rv: int) -> None:
        """One connection's lifetime: connect, decode frames, enqueue.
        The stream's framing follows the response Content-Type — ndjson
        lines, or u32-length-prefixed binary frames when the server
        negotiated our binary dialect (the Accept header below). Ends on
        EOF/error; poll() restarts it from the current cursor."""
        from urllib.parse import urlsplit

        conn = resp = None
        try:
            u = urlsplit(self._store.base)
            conn = http.client.HTTPConnection(
                u.hostname, u.port,
                timeout=self._stream_timeout_s + self._store.timeout_s,
            )
            headers = {}
            if self._store._wire_ok is not False:
                headers["Accept"] = codec.binary_stream_content_type()
            ctx = self._store._trace_context()
            if ctx is not None:
                from ..telemetry.context import format_traceparent

                # a stream GET carries no body, so the header is the
                # envelope on both wires
                headers[codec.TRACEPARENT_HEADER] = format_traceparent(ctx)
            conn.request(
                "GET",
                f"/apis/{self._kind}?watch=1&stream=1"
                f"&resourceVersion={start_rv}"
                f"&timeoutSeconds={self._stream_timeout_s}{self._sel}",
                headers=headers,
            )
            resp = conn.getresponse()
            self._sock = conn.sock   # close() shutdowns this to wake us
            if resp.status != 200:
                body = resp.read()
                self._queue.append((
                    "error",
                    CompactedError(body.decode(errors="replace"))
                    if resp.status == 410
                    else RemoteStoreError(f"{resp.status}: {body[:200]!r}"),
                ))
                return
            ct = resp.getheader("Content-Type") or ""
            if codec.CT_BINARY in ct:
                self._read_binary_frames(resp)
            else:
                self._read_ndjson(resp)
        except (ConnectionError, TimeoutError, OSError,
                http.client.HTTPException,
                AttributeError, ValueError):
            # stream died (or close() tore the socket out from under a
            # buffered read): next poll reconnects from the cursor
            pass
        finally:
            self._sock = None
            sock = conn.sock if conn is not None else None
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass

    def _enqueue(self, msg: dict) -> bool:
        """One decoded frame → the queue; False ends the stream (410)."""
        if msg.get("code") == 410:
            self._queue.append(
                ("error", CompactedError(msg.get("error", "compacted")))
            )
            return False
        self._queue.append(("event", msg))
        return True

    def _read_ndjson(self, resp) -> None:
        for raw in resp:
            line = raw.strip()
            if not line:
                continue
            try:
                msg = codec.loads(line, codec.JSON)
            except codec.UnsupportedWireError:
                continue
            if not self._enqueue(msg):
                return

    def _read_binary_frames(self, resp) -> None:
        """u32-LE length prefix + one self-contained binary value per
        frame (codec.stream_frame's negotiated form)."""
        def read_exact(n: int) -> bytes:
            chunks = []
            while n:
                got = resp.read(n)
                if not got:
                    return b""
                chunks.append(got)
                n -= len(got)
            return b"".join(chunks)

        while True:
            head = read_exact(4)
            if len(head) < 4:
                return                      # EOF between frames
            body = read_exact(int.from_bytes(head, "little"))
            if not body:
                return
            try:
                msg = codec.loads(body, codec.BINARY)
            except codec.UnsupportedWireError:
                return                      # torn frame: reconnect
            if not self._enqueue(msg):
                return

    def poll(self) -> list[WatchEvent]:
        out: list[WatchEvent] = []
        while self._queue:
            tag, payload = self._queue.popleft()
            if tag == "error":
                raise payload
            self._rv = payload["resourceVersion"]
            out.append(WatchEvent(
                type=payload["type"], kind=self._kind, key=payload["key"],
                obj=codec.as_object(payload["object"]),
                resource_version=payload["resourceVersion"],
            ))
        if not self._closed and (
            self._thread is None or not self._thread.is_alive()
        ):
            with self._lock:
                if self._thread is None or not self._thread.is_alive():
                    self.reconnects += 1
                    self._thread = threading.Thread(
                        target=self._reader, args=(self._rv,), daemon=True,
                    )
                    self._thread.start()
        return out

    def close(self) -> None:
        """Tear the stream down NOW: a plain conn.close() would try to
        drain the unfinished chunked body (blocking up to the stream
        deadline) and would not wake the reader's blocked recv — a socket
        shutdown does both."""
        import socket as _socket

        self._closed = True
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
