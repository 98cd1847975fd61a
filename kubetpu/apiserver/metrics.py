"""API server request metrics — the apiserver/pkg/endpoints/metrics slice.

Reference names and shapes (metrics.go):

- ``apiserver_request_duration_seconds{verb, resource, code}`` — the
  reference's requestLatencies bucket list, 5 ms … 60 s
- ``apiserver_request_total{verb, resource, code}``
- ``apiserver_current_inflight_requests{request_kind}`` — readOnly vs
  mutating, the max-in-flight filter's gauge; long-running requests
  (watch streams) are EXCLUDED (the reference's longrunning predicate)
  and counted on
- ``apiserver_longrunning_requests{verb, resource}`` instead

Beside them, kubetpu's own: ``apiserver_bulk_ops_total{resource, path}``,
the ops of ``:bulk`` requests by the path the request took, and
``apiserver_pod_binds_total{result}``, the pods ``:bulk`` verb's bind ops
by what they did.
"""

from __future__ import annotations

import collections
import re
import time
from contextlib import contextmanager
from typing import Callable

from ..metrics.registry import Registry

# apiserver/pkg/endpoints/metrics/metrics.go requestLatencies buckets
REQUEST_DURATION_BUCKETS = [
    0.005, 0.025, 0.05, 0.1, 0.2, 0.4, 0.6, 0.8, 1.0, 1.25, 1.5,
    2, 3, 4, 5, 6, 8, 10, 15, 20, 30, 45, 60,
]

READ_VERBS = frozenset({"GET", "LIST", "WATCH"})

#: a bind op's result by its status: the pod took its node, it was bound
#: already (or recreated under another uid), it was not there
BIND_RESULTS = ("bound", "conflict", "gone")
_BIND_RESULT_OF = {200: "bound", 409: "conflict", 404: "gone"}

#: distinct resource label values admitted before folding into "other" —
#: the resource segment is CLIENT-supplied path text, and every unseen
#: label tuple mints new metric children, so an unbounded scanner would
#: otherwise grow the registry without limit (the reference only records
#: recognized resources)
MAX_RESOURCE_LABELS = 64

#: resource path segments are CLIENT text; only lowercase-DNS-label names
#: (the shape of every real resource: "pods", "poddisruptionbudgets") may
#: ever become a label value — anything else folds to "other" before it
#: can reach the exposition
_RESOURCE_RE = re.compile(r"[a-z0-9]([a-z0-9-]{0,61}[a-z0-9])?$")

#: verbs whose 2xx proves the resource kind really exists: a write decoded
#: through the scheme, or a keyed GET that found an object. LIST/WATCH of
#: an unknown kind "succeed" with an empty result, so their 200s admit
#: nothing — the list handler admits explicitly once it returns items.
_PROVING_VERBS = frozenset({"GET", "CREATE", "UPDATE", "PATCH", "DELETE"})


class APIServerMetrics:
    """Owns a Registry with the apiserver request metric set; the handler
    observes through ``track``."""

    def __init__(self, registry: Registry | None = None) -> None:
        import threading

        r = registry if registry is not None else Registry()
        self.registry = r
        self._resources: set[str] = set()
        self._resources_lock = threading.Lock()
        self.request_duration = r.histogram(
            "apiserver_request_duration_seconds",
            "Response latency distribution in seconds for each verb and "
            "resource.",
            labels=("verb", "resource", "code"),
            buckets=REQUEST_DURATION_BUCKETS,
        )
        self.request_total = r.counter(
            "apiserver_request_total",
            "Counter of apiserver requests broken out for each verb, "
            "resource and HTTP response code.",
            labels=("verb", "resource", "code"),
        )
        self.inflight = r.gauge(
            "apiserver_current_inflight_requests",
            "Maximal number of currently used inflight request limit of "
            "this apiserver per request kind in last second.",
            labels=("request_kind",),
        )
        self.longrunning = r.gauge(
            "apiserver_longrunning_requests",
            "Gauge of all active long-running apiserver requests "
            "(watch streams).",
            labels=("verb", "resource"),
        )
        # the wire-protocol evidence counter: payload bytes by codec and
        # direction (request bodies in, reply/stream bodies out) — the
        # perf harness's wire_bytes_per_pod numerator
        self.wire_bytes = r.counter(
            "apiserver_wire_bytes_total",
            "Request and response wire payload bytes by codec and "
            "direction.",
            labels=("codec", "direction"),
            declared={
                "codec": ("json", "binary"),
                "direction": ("in", "out"),
            },
        )

        # the read plane's pagination evidence: one increment per LIST
        # reply, split by whether the limit/continue walk served it
        # (ListScaling's pages/relist reads from here)
        self.list_pages = r.counter(
            "apiserver_list_pages_total",
            "LIST replies served, by pagination mode (paged = a "
            "limit/continue page, full = the unpaged monolithic reply).",
            labels=("mode",),
            declared={"mode": ("paged", "full")},
        )
        # which of the bulk verb's two paths a request's ops took: OPS, not
        # requests, so the one-lock share of a kind's writes is one division
        self.bulk_ops = r.counter(
            "apiserver_bulk_ops_total",
            "Ops of :bulk requests, by the path the request took: one_lock "
            "(one store lock, one WAL commit, one watch wake-up for the "
            "batch) or sequential (each op through the single-verb "
            "admission chain).",
            labels=("resource", "path"),
            declared={"path": ("one_lock", "sequential")},
        )
        # the binding subresource's outcomes, on both paths of the verb: Δ
        # bound ÷ pods bound says every bind of a window took the op
        self.pod_binds = r.counter(
            "apiserver_pod_binds_total",
            "Bind ops of the pods :bulk verb, by result: bound (the pod "
            "took its node), conflict (already bound, or recreated under "
            "another uid) or gone.",
            labels=("result",),
            declared={"result": BIND_RESULTS},
        )
        # replication-feed egress by path — the chained-shipping
        # acceptance (leader egress ~= one follower's worth) reads the
        # leader's log-path delta
        self.replication_bytes = r.counter(
            "apiserver_replication_bytes_total",
            "Replication feed payload bytes served, by path.",
            labels=("path",),
            declared={"path": ("log", "snapshot")},
        )
        # lag (records) the last rv=0 bounded-staleness list trailed the
        # leader by; None until one is served. Exposed as
        # store_list_lag_records by the follower's metrics source only —
        # unreplicated/leader servers omit the series so the sentinel's
        # list-lag rule stays dormant there
        self.list_lag_last: int | None = None

    def count_wire(self, codec: str, direction: str, n: int) -> None:
        """Record ``n`` payload bytes moving through the wire seam."""
        if n:
            self.wire_bytes.labels(codec, direction).inc(n)

    def count_bulk_ops(self, resource: str, path: str, n: int) -> None:
        """Record the ``n`` ops of one :bulk request under the path it
        took; ``resource`` folds like a failed request's (only a kind some
        2xx has proved gets a label of its own)."""
        if n:
            self.bulk_ops.labels(
                self._resource_label(resource, succeeded=False), path
            ).inc(n)

    def count_pod_binds(self, statuses) -> None:
        """Record the results of one request's bind ops by their statuses
        (a malformed op's 400 or 422 is none of the three)."""
        for result, n in collections.Counter(
            _BIND_RESULT_OF.get(s) for s in statuses
        ).items():
            if result is not None:
                self.pod_binds.labels(result).inc(n)

    def count_replication(self, path: str, n: int) -> None:
        """Record ``n`` replication-feed payload bytes served."""
        if n:
            self.replication_bytes.labels(path).inc(n)

    def replication_bytes_total(self, path: str | None = None) -> int:
        """Lifetime replication-feed egress bytes, optionally by path —
        the leader-egress probe of a chained-shipping run."""
        total = 0
        for key, child in self.replication_bytes._children_snapshot():
            if path is not None and key[0] != path:
                continue
            total += int(child.value)
        return total

    def wire_bytes_total(self, codec: str | None = None,
                         direction: str | None = None) -> int:
        """Lifetime wire payload bytes, optionally filtered by codec
        and/or direction — the perf harness's wire-traffic numerator."""
        total = 0
        for key, child in self.wire_bytes._children_snapshot():
            c, d = key
            if codec is not None and c != codec:
                continue
            if direction is not None and d != direction:
                continue
            total += int(child.value)
        return total

    def admit_resource(self, resource: str) -> str:
        """Admit ``resource`` as a label value once the caller has PROOF
        the kind exists (a keyed read/write succeeded, or a list returned
        items). Malformed names and overflow beyond MAX_RESOURCE_LABELS
        fold to "other"."""
        if not _RESOURCE_RE.fullmatch(resource):
            return "other"
        with self._resources_lock:
            if resource in self._resources:
                return resource
            if len(self._resources) < MAX_RESOURCE_LABELS:
                self._resources.add(resource)
                return resource
        return "other"

    def _resource_label(self, resource: str, succeeded: bool) -> str:
        """Admission is gated on a response that PROVES the kind exists:
        a scanner's junk paths fail (404/400) or prove nothing (empty
        LIST) and fold into "other", so they can never squat the slots
        real resources need."""
        if succeeded:
            return self.admit_resource(resource)
        if not _RESOURCE_RE.fullmatch(resource):
            return "other"
        with self._resources_lock:
            if resource in self._resources:
                return resource
        return "other"

    @contextmanager
    def track(self, verb: str, resource: str, status: Callable[[], int],
              long_running: bool = False):
        """Instrument one request: in-flight (or long-running) gauge for
        the request's lifetime, duration + total observed at completion
        with the status ``status()`` reports then."""
        kind = "readOnly" if verb in READ_VERBS else "mutating"
        gauge = (
            # gauge label resolves on entry: already-admitted resources
            # keep their name, never-seen ones ride "other" until a
            # success admits them
            self.longrunning.labels(
                verb, self._resource_label(resource, succeeded=False)
            )
            if long_running else self.inflight.labels(kind)
        )
        gauge.inc()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            gauge.dec()
            code = status()
            label = self._resource_label(
                resource,
                succeeded=(verb in _PROVING_VERBS and 200 <= code < 400),
            )
            self.request_duration.labels(verb, label, str(code)).observe(
                time.perf_counter() - t0
            )
            self.request_total.labels(verb, label, str(code)).inc()

    def total_requests(self) -> int:
        """Lifetime completed-request count across every verb/resource/code
        — the perf harness's numerator for API round trips per scheduled
        pod (watch long-polls complete per poll, so they count; a held-open
        stream counts once at close)."""
        return int(sum(
            child.value
            for _key, child in self.request_total._children_snapshot()
        ))

    def expose(self) -> str:
        return self.registry.expose()
