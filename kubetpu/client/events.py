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

One way to the store. ``events()`` takes the occurrences a caller
gathered — the scheduler hands over what a drain or a cycle recorded,
before that call returns — and writes them in ONE ``store.bulk`` request
(two when a repeat has to be read first); ``event()`` is a batch of one,
stamped now. It is synchronous: no thread, and nothing is held past the
call. Three counts go to the owner's /metrics (``metrics_text``):
``written`` (occurrences the store acknowledged), ``dropped`` (occurrences
it did not: a request that raised, or answered with anything but one
result an op, drops all it carried, a failed op its own) and ``requests``
(store round trips made for them). written + dropped = occurrences
recorded.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import time
from typing import Callable, Iterable

from ..api import types as t

EVENTS = "events"
#: entries the aggregation cache keeps (client-go's events_cache
#: maxLruCacheEntries): the scheduler records one ``Scheduled`` per pod,
#: so an unbounded cache grows for as long as the cluster binds pods
MAX_SEEN = 4096

#: one occurrence as ``events()`` takes it: (regarding, reason, note, type,
#: timestamp) — ``event()``'s arguments and the moment it was recorded
Occurrence = tuple[str, str, str, str, float]


@dataclasses.dataclass
class _Series:
    """What one batch does to one Event key."""

    #: the series as this batch alone would start it (count = its
    #: occurrences here), then, once the store's object has been read,
    #: that object continued
    event: t.Event
    #: the signature was in the cache: the store's object, if it is still
    #: there, is continued and not replaced
    known: bool
    #: occurrences this op stands for (written or dropped together)
    occurrences: int


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
        # only means that a repeat starts a new series; one whose write
        # failed, that the repeat finds nothing and starts one too
        self._seen: "collections.OrderedDict[tuple[str, str, str], str]" = (
            collections.OrderedDict()
        )
        self.written = 0    # occurrences the store acknowledged
        self.dropped = 0    # occurrences whose write failed (best effort)
        self.requests = 0   # store round trips made for either

    def _new_series(
        self, regarding: str, reason: str, note: str, type: str, now: float
    ) -> t.Event:
        digest = hashlib.sha1(
            "\x1f".join((regarding, reason, note, self.controller)).encode()
        ).hexdigest()[:10]
        ns = regarding.split("/")[1] if regarding.count("/") >= 2 else "default"
        return t.Event(
            name=f"{regarding.rsplit('/', 1)[-1]}.{digest}", namespace=ns,
            regarding=regarding, reason=reason, note=note, type=type,
            reporting_controller=self.controller,
            count=1, first_timestamp=now, last_timestamp=now,
        )

    def _remember(self, sig: tuple[str, str, str], key: str) -> None:
        self._seen[sig] = key
        if len(self._seen) > MAX_SEEN:
            self._seen.popitem(last=False)

    def event(
        self, regarding: str, reason: str, note: str,
        type: str = "Normal",
    ) -> None:
        """Record one occurrence now; repeats bump count/lastTimestamp."""
        self.events([(regarding, reason, note, type, self.clock())])

    def events(self, batch: Iterable[Occurrence]) -> None:
        """Record the occurrences of ``batch``, in order: a new signature
        becomes one upsert, a signature the cache knows continues the
        store's object (read in one bulk of gets first; gone, it starts
        anew), and one that recurs inside the batch is folded into one op.
        All the writes travel in ONE ``store.bulk`` request. Never raises:
        what the store did not acknowledge is counted as dropped."""
        batch = list(batch)
        written0, dropped0 = self.written, self.dropped
        try:
            self._write(batch)
        except Exception:
            # an event write must never break the action it annotates
            pass
        self.dropped += (
            len(batch) - (self.written - written0) - (self.dropped - dropped0)
        )

    def _write(self, batch: list[Occurrence]) -> None:
        # the cache moves occurrence by occurrence; the store is not
        # touched yet
        plan: dict[str, _Series] = {}
        for regarding, reason, note, type, now in batch:
            sig = (regarding, reason, note)
            key = self._seen.get(sig)
            if key is not None:
                self._seen.move_to_end(sig)
                series = plan.get(key)
                if series is not None:
                    series.event = dataclasses.replace(
                        series.event, count=series.event.count + 1,
                        last_timestamp=now,
                    )
                    series.occurrences += 1
                    continue
            ev = self._new_series(regarding, reason, note, type, now)
            # the cache forgot the signature between two of its occurrences
            # (a batch beyond MAX_SEEN): the later one starts the series
            # again; the earlier ones are still accounted for with this op
            forgotten = plan.get(ev.key)
            plan[ev.key] = _Series(
                ev, known=key is not None,
                occurrences=1 + (forgotten.occurrences if forgotten else 0),
            )
            if key is None:
                self._remember(sig, ev.key)
        known = [s for s in plan.values() if s.known]
        if known:
            results = self._bulk(
                [{"op": "get", "key": s.event.key} for s in known]
            )
            for series, res in zip(known, results):
                current = res.get("object")
                if res.get("status", 500) < 400 and isinstance(
                    current, t.Event
                ):
                    series.event = dataclasses.replace(
                        current,
                        count=current.count + series.event.count,
                        last_timestamp=series.event.last_timestamp,
                    )
                elif res.get("status") != 404:
                    # the read failed (or brought no Event back): its
                    # occurrences are dropped, the stored count is not
                    # overwritten
                    del plan[series.event.key]
        if not plan:
            return
        writes = list(plan.values())
        results = self._bulk([
            {"op": "update", "key": s.event.key, "object": s.event}
            for s in writes
        ])
        for series, res in zip(writes, results):
            if res.get("status", 500) < 400:
                self.written += series.occurrences

    def _bulk(self, ops: list[dict]) -> list[dict]:
        """One round trip. A request that fails as a whole — it raised, or
        its answer is not one result an op — answers every op as failed,
        and is never retried op by op: that would bring the per-Event stall
        back exactly when the apiserver is struggling."""
        self.requests += 1
        try:
            results = self.store.bulk(EVENTS, ops)
            if len(results) == len(ops) and all(
                isinstance(res, dict) for res in results
            ):
                return results
        except Exception:
            pass
        return [{"status": 500}] * len(ops)

    def metrics_text(self) -> str:
        """The best-effort contract made visible, mounted on the OWNING
        component's /metrics (the scheduler folds it into its scrape):
        ``kubetpu_events_dropped_total{controller}`` (the sentinel's
        events-dropped rule watches it), ``kubetpu_events_written_total``
        and ``kubetpu_event_write_requests_total`` — occurrences the store
        acknowledged, and the store round trips made for them."""
        from ..metrics.registry import Registry

        r = Registry()
        for name, help_, value in (
            ("kubetpu_events_dropped_total",
             "Best-effort Event store-writes that failed, by recording "
             "controller.", self.dropped),
            ("kubetpu_events_written_total",
             "Event occurrences the store acknowledged, by recording "
             "controller.", self.written),
            ("kubetpu_event_write_requests_total",
             "Store round trips made to write Events (one bulk request "
             "carries a whole batch), by recording controller.",
             self.requests),
        ):
            r.counter(name, help_, labels=("controller",)).labels(
                self.controller
            ).inc(value)
        return r.expose()
