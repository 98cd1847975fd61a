"""Event recorder — the client-go EventBroadcaster/recorder analog.

Reference: ``staging/src/k8s.io/client-go/tools/events`` — components
record Events against the objects they act on; a broadcaster sinks them to
the API server, and repeats of the same (object, reason, note) aggregate
into a series (count + lastTimestamp bump) instead of new objects
(``events_cache``'s EventAggregator). The scheduler's events are the
canonical ones: ``Scheduled`` on bind, ``FailedScheduling`` on an
unschedulable attempt (schedule_one.go's recorder.Eventf calls).

The recorder here writes through the STORE protocol ("events" bucket) so
events flow to whatever backs the component — the in-process MemStore or
a remote apiserver — and ``kubetpu get events`` lists them. Writes are
best-effort (an event must never fail the operation it describes) and
aggregated client-side by (regarding, reason, note).
"""

from __future__ import annotations

import collections
import hashlib
import time
from typing import Callable

from ..api import types as t

EVENTS = "events"
#: entries the aggregation cache keeps (client-go's events_cache
#: maxLruCacheEntries): the scheduler records one ``Scheduled`` per pod,
#: so an unbounded cache grows for as long as the cluster binds pods
MAX_SEEN = 4096


class EventRecorder:
    """One component's recorder. Thread-compatible with the pump-driven
    loops (callers serialize); aggregation state is per-recorder, like the
    reference's per-broadcaster cache."""

    def __init__(
        self, store, controller: str,
        clock: Callable[[], float] | None = None,
    ) -> None:
        self.store = store
        self.controller = controller
        self.clock = clock if clock is not None else time.time
        # (regarding, reason, note) -> event key: the aggregation cache,
        # least recently used first, at most MAX_SEEN entries. A lost entry
        # only means that a repeat starts a new series
        self._seen: "collections.OrderedDict[tuple[str, str, str], str]" = (
            collections.OrderedDict()
        )
        self.dropped = 0   # store-write failures (best-effort contract)

    def event(
        self, regarding: str, reason: str, note: str,
        type: str = "Normal",
    ) -> None:
        """Record one occurrence; repeats bump count/lastTimestamp."""
        now = self.clock()
        sig = (regarding, reason, note)
        key = self._seen.get(sig)
        try:
            if key is not None:
                self._seen.move_to_end(sig)
                current, rv = self.store.get(EVENTS, key)
                if current is not None:
                    import dataclasses

                    self.store.update(EVENTS, key, dataclasses.replace(
                        current,
                        count=current.count + 1,
                        last_timestamp=now,
                    ))
                    return
                self._seen.pop(sig, None)
            digest = hashlib.sha1(
                "\x1f".join((regarding, reason, note, self.controller)).encode()
            ).hexdigest()[:10]
            ns = regarding.split("/")[1] if regarding.count("/") >= 2 else "default"
            name = f"{regarding.rsplit('/', 1)[-1]}.{digest}"
            ev = t.Event(
                name=name, namespace=ns, regarding=regarding,
                reason=reason, note=note, type=type,
                reporting_controller=self.controller,
                count=1, first_timestamp=now, last_timestamp=now,
            )
            self.store.update(EVENTS, ev.key, ev)   # upsert
            self._seen[sig] = ev.key
            if len(self._seen) > MAX_SEEN:
                self._seen.popitem(last=False)
        except Exception:
            # an event write must never break the action it annotates
            self.dropped += 1

    def metrics_text(self) -> str:
        """``kubetpu_events_dropped_total{controller=...}`` — the
        best-effort contract made visible: mounted on the OWNING
        component's /metrics (the scheduler folds it into its scrape),
        where the sentinel's events-dropped rule watches it."""
        from ..metrics.registry import Registry

        r = Registry()
        c = r.counter(
            "kubetpu_events_dropped_total",
            "Best-effort Event store-writes that failed, by recording "
            "controller.",
            labels=("controller",),
        )
        c.labels(self.controller).inc(self.dropped)
        return r.expose()
