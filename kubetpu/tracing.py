"""Latency tracing — the utiltrace + component-base/tracing analog.

Reference surfaces:
- ``k8s.io/utils/trace`` (utiltrace): ``schedulePod`` opens a trace and
  logs its step breakdown when the cycle exceeds 100 ms
  (schedule_one.go:566-567). Mirrored by ``Tracer.span`` + the
  over-threshold log hook.
- ``component-base/tracing`` (OTel, utils.go:79-85): ratio-sampled spans
  with attributes exported off-process. Mirrored structurally: spans carry
  ids/parents/attributes and land in a bounded in-memory buffer an exporter
  can drain (``Tracer.drain``); the scheduler joins device + host work by
  cycle id, the OTel-span-per-cycle design SURVEY §5 prescribes.

Single-owner like the scheduler loop: span entry/exit runs on the loop
thread, so the parent stack is a plain list (no contextvars in the hot
path). Opening a span costs two ``perf_counter`` calls, two reads of the
thread's CPU clock (``cpu_s`` in its attributes: of the span's duration, the
part the thread was on a core; the rest it waited, for the GIL, for a core,
for a socket or for the chip), one ``Span`` and an
append to one of TWO bounded rings: the loop's ring holds what is recorded
once per cycle or per loop iteration, the per-item ring what is recorded
once per pod or per request (``Tracer.record(..., per_item=True)``: the
``bind`` span of every pod, the client's ``rpc.*``), so a burst of a
thousand binds cannot evict the cycle that caused them. Readers see both,
ordered by start.

Beside the spans runs the ``PhaseClock``: the loop thread's wall time AND
its CPU time, each partitioned into named phases that sum to the elapsed
time by construction (three dictionary additions and two clock reads per
switch, the wall clock and the thread's CPU clock, always on). The spans
say WHEN something ran; the phase counters say how much of every second
each part of the loop took, and how much of that the thread ran, idle
iterations included, which the spans leave out on purpose.

The CPU clock is Linux's per-thread one. ``thread_cpu`` reads the calling
thread's (the spans, the dispatcher's workers and the diagnostics
listener's request threads time themselves with it); the ``PhaseClock``
reads the LOOP thread's by its clock id, which any thread may read, because
its scrape runs elsewhere. Where the platform keeps no such clock
``thread_cpu`` is None and nothing is recorded under a CPU name.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable

#: the calling thread's CPU seconds (user + system); None where the platform
#: keeps no per-thread CPU clock
thread_cpu: Callable[[], float] | None = getattr(time, "thread_time", None)


def _cpu_clock_of(ident: int) -> int | None:
    """The id of thread ``ident``'s CPU clock, which ``time.clock_gettime``
    reads from ANY thread of the process; None where there is no such
    clock."""
    try:
        clock_id = time.pthread_getcpuclockid(ident)
        time.clock_gettime(clock_id)
    except (AttributeError, OSError):
        return None
    return clock_id


@dataclass
class Span:
    name: str
    span_id: int
    parent_id: int | None
    start: float
    end: float = 0.0
    attrs: dict[str, Any] = field(default_factory=dict)
    # recorded out-of-stack (Tracer.record): overlaps loop spans and other
    # off-stack spans, so the Chrome-trace exporter lays it out on its own
    # non-overlapping lane (tid >= 2)
    off_stack: bool = False
    # a zero-duration marker (Tracer.instant) — exported as a Chrome-trace
    # instant ("i") event instead of a complete span
    instant: bool = False
    # set inside a ``with tracer.span(...)`` block to drop the span when it
    # closes: an iteration, pump or drain that found nothing to do records
    # nothing, or an idle 20 Hz loop would evict every real cycle
    discard: bool = False

    @property
    def duration_s(self) -> float:
        return max(self.end - self.start, 0.0)


class Tracer:
    """Bounded in-memory span recorder with utiltrace threshold logging."""

    def __init__(
        self,
        enabled: bool = True,
        max_spans: int = 4096,
        threshold_s: float = 0.1,
        log: Callable[[str], None] | None = None,
        clock: Callable[[], float] = time.perf_counter,
    ) -> None:
        self.enabled = enabled
        self.threshold_s = threshold_s
        self._clock = clock
        self._log = log
        # the loop's ring, and the ring of spans recorded once per pod or
        # per request (record(..., per_item=True)), of the same size
        self._spans: collections.deque[Span] = collections.deque(
            maxlen=max_spans
        )
        self._item_spans: collections.deque[Span] = collections.deque(
            maxlen=max_spans
        )
        self._stack: list[Span] = []
        self._ids = itertools.count(1)

    @property
    def current_id(self) -> int | None:
        """The id of the innermost open span on the loop thread's stack:
        the parent for a loop-owned span recorded after the fact."""
        return self._stack[-1].span_id if self._stack else None

    @contextmanager
    def span(self, name: str, *, log_long: bool = True, **attrs):
        """Open a span; yields it so steps can attach attributes (or set
        ``discard``). A TOP-LEVEL span exceeding ``threshold_s`` logs its
        child breakdown (utiltrace's LogIfLong) unless ``log_long`` is
        off — the served loop's iteration envelope is long by design.
        The span closes with ``cpu_s`` among its attributes: the calling
        thread's CPU time between open and close."""
        if not self.enabled:
            yield None
            return
        parent = self._stack[-1] if self._stack else None
        sp = Span(
            name=name,
            span_id=next(self._ids),
            parent_id=parent.span_id if parent else None,
            start=self._clock(),
            attrs=dict(attrs),
        )
        cpu0 = thread_cpu() if thread_cpu is not None else None
        self._stack.append(sp)
        try:
            yield sp
        finally:
            sp.end = self._clock()
            if cpu0 is not None:
                sp.attrs["cpu_s"] = round(thread_cpu() - cpu0, 6)
            self._stack.pop()
            if not sp.discard:
                self._spans.append(sp)
                if (
                    log_long and parent is None
                    and sp.duration_s >= self.threshold_s
                ):
                    self._log_long(sp)

    def record(
        self,
        name: str,
        start: float,
        end: float,
        parent_id: int | None = None,
        off_stack: bool = True,
        per_item: bool = False,
        **attrs,
    ) -> Span | None:
        """Record a span whose timing happened OFF the loop thread's span
        stack (an async bind measured dispatch→completion): the caller
        supplies start/end on this tracer's clock; the span lands in the
        buffer like any other but never touches the parent stack.
        ``off_stack=False`` places it on the loop lane (tid 1) in the
        Chrome-trace export — for loop-owned phases whose start/end bracket
        other calls (the serial scheduling cycle, recorded where it ends
        around its snapshot, encode, explain and bind-dispatch spans),
        provided the caller guarantees proper nesting with the lane's other
        spans; ``parent_id=tracer.current_id`` then hangs it under the
        open span (the loop iteration). ``per_item=True`` is for what
        is recorded once per POD or per REQUEST: it lands in the per-item
        ring, so its volume never evicts the loop's spans."""
        if not self.enabled:
            return None
        sp = Span(
            name=name,
            span_id=next(self._ids),
            parent_id=parent_id,
            start=start,
            end=end,
            attrs=dict(attrs),
            off_stack=off_stack,
        )
        (self._item_spans if per_item else self._spans).append(sp)
        return sp

    def instant(self, name: str, **attrs) -> Span | None:
        """Record a zero-duration marker at 'now' (an event, not a phase —
        e.g. an encode-cache invalidation). Lands in the buffer like any
        span; the Chrome-trace export renders it as an instant event."""
        if not self.enabled:
            return None
        now = self._clock()
        sp = Span(
            name=name,
            span_id=next(self._ids),
            parent_id=None,
            start=now,
            end=now,
            attrs=dict(attrs),
            off_stack=True,
            instant=True,
        )
        self._spans.append(sp)
        return sp

    # ---- inspection ------------------------------------------------------
    @staticmethod
    def _copy(ring: "collections.deque[Span]") -> list[Span]:
        """Copy one ring tolerating concurrent appends: a diagnostics
        HTTP thread snapshots while the loop thread records (deque appends
        are atomic, but iterating during an append raises RuntimeError —
        retry instead of locking the hot path)."""
        while True:
            try:
                return list(ring)
            except RuntimeError:
                continue

    def _snapshot_spans(self) -> list[Span]:
        """Both rings, ordered by start (ties by id: the order recorded)."""
        out = self._copy(self._spans) + self._copy(self._item_spans)
        out.sort(key=lambda s: (s.start, s.span_id))
        return out

    def recent(self, n: int = 100) -> list[Span]:
        return self._snapshot_spans()[-n:]

    def drain(self) -> list[Span]:
        """Hand the buffered spans to an exporter and remove EXACTLY those
        spans from the buffer. A bare ``clear()`` here would erase spans
        recorded between the snapshot and the clear (the loop thread
        records while an exporter drains) — those must survive for the
        next drain and for concurrent readers (``/trace``, the flight
        recorder), so only the snapshotted prefix is popped, ring by
        ring."""
        out = self._snapshot_spans()
        drained = {id(s) for s in out}
        for ring in (self._spans, self._item_spans):
            while True:
                try:
                    head = ring[0]
                except IndexError:
                    break
                if id(head) not in drained:
                    break        # a newer span reached the head: stop
                ring.popleft()
        return out

    # ---- export ----------------------------------------------------------
    def chrome_trace(self, spans: list[Span] | None = None) -> dict:
        """The buffered spans as Chrome-trace-format JSON (Perfetto /
        chrome://tracing loadable): one complete ("X") event per span,
        µs timestamps on the tracer's monotonic clock, span/parent ids and
        attributes (incl. the cycle id the device-side counter records
        join on) under ``args``. Non-destructive — ``drain`` separately to
        clear the buffer."""
        src = self._snapshot_spans() if spans is None else spans
        events = []
        # off-stack spans (async binds) overlap the loop's spans AND each
        # other; complete events on one tid must nest properly or Perfetto
        # misnests/drops them, so each off-stack span takes the first free
        # LANE (tid >= 2) whose previous span already ended
        lane_ends: list[float] = []
        for sp in sorted(src, key=lambda s: s.start):
            if sp.instant:
                # marker events take no lane — process-scoped instants
                events.append({
                    "name": sp.name,
                    "cat": "kubetpu",
                    "ph": "i",
                    "s": "p",
                    "ts": sp.start * 1e6,
                    "pid": 1,
                    "tid": 1,
                    "args": {"span_id": sp.span_id, **sp.attrs},
                })
                continue
            if sp.off_stack:
                for lane, end in enumerate(lane_ends):
                    if end <= sp.start:
                        lane_ends[lane] = sp.end
                        break
                else:
                    lane = len(lane_ends)
                    lane_ends.append(sp.end)
                tid = 2 + lane
            else:
                tid = 1
            events.append({
                "name": sp.name,
                "cat": "kubetpu",
                "ph": "X",
                "ts": sp.start * 1e6,
                "dur": sp.duration_s * 1e6,
                "pid": 1,
                "tid": tid,
                "args": {
                    "span_id": sp.span_id,
                    "parent_id": sp.parent_id,
                    **sp.attrs,
                },
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump_chrome_trace(
        self, path: str, spans: list[Span] | None = None
    ) -> str:
        """Write ``chrome_trace`` to ``path``; returns the path."""
        import json

        with open(path, "w") as f:
            json.dump(self.chrome_trace(spans), f)
        return path

    def children_of(self, span: Span) -> list[Span]:
        # filter first, order after: the LogIfLong hook calls this on the
        # loop thread, which should not sort two full rings for it
        rings = self._copy(self._spans) + self._copy(self._item_spans)
        return sorted(
            (s for s in rings if s.parent_id == span.span_id),
            key=lambda s: (s.start, s.span_id),
        )

    # ---- threshold logging ----------------------------------------------
    def _log_long(self, sp: Span) -> None:
        steps = "; ".join(
            f"{c.name} {c.duration_s * 1000:.1f}ms"
            for c in self.children_of(sp)
        )
        attrs = ",".join(f"{k}={v}" for k, v in sp.attrs.items())
        msg = (
            f"Trace[{sp.name}] ({attrs}): {sp.duration_s * 1000:.1f}ms"
            + (f" — steps: {steps}" if steps else "")
        )
        if self._log is not None:
            self._log(msg)
        else:  # pragma: no cover - default sink
            import logging

            logging.getLogger("kubetpu.trace").warning(msg)


#: the phases of the served loop (``cli.py`` ``cmd_scheduler``): the ONLY
#: legal values of the {phase} label on scheduler_loop_phase_*_total
LOOP_PHASES = (
    "pump_rpc",         # blocked on the watch poll, decoding its reply
    "pump_apply",       # delivering events into queue and cache; relists
    "cycle",            # schedule_batch, less explain and bind_dispatch
    "explain",          # the flight recorder's note_cycle
    "bind_dispatch",    # assume, Reserve/Permit, handing binds over
    "drain",            # bind results back into cache and queue
    "events",           # the bulk Event writes of a drain or a cycle
                        # (EventRecorder.events); entries count Events
    "sleep",            # the loop's period sleep and its back-off
    "other",            # leader and membership checks, loop overhead
)


class PhaseClock:
    """The loop thread's wall time and its CPU time, each partitioned into
    ``LOOP_PHASES``.

    Every instant belongs to exactly one phase — the one ``switch`` named
    last — so the phases are SELF times and sum to the elapsed time by
    construction: ``seconds`` to the wall, ``cpu_seconds`` to the CPU time
    of the thread that switches. CPU is at most wall in every phase, to the
    clocks' grain; what is missing the thread spent off a core: asleep,
    blocked on a socket or on the chip, or waiting for the GIL. Switched
    only from the loop thread; ``snapshot`` and ``cpu_snapshot`` may be
    called from any thread (the /metrics scrape) and take no lock."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self.seconds: dict[str, float] = dict.fromkeys(LOOP_PHASES, 0.0)
        self.cpu_seconds: dict[str, float] = dict.fromkeys(LOOP_PHASES, 0.0)
        self.entries: dict[str, int] = dict.fromkeys(LOOP_PHASES, 0)
        #: completed iterations of the served loop (``_make_loop``)
        self.iterations = 0
        # the thread whose CPU clock is read: the one that switches. Taken
        # to be the builder until ``switch`` finds another
        self._owner = threading.get_ident()
        cpu_id = _cpu_clock_of(self._owner)
        # (phase, the moment it began, the id of the owner's CPU clock or
        # None, that clock when the phase began): ONE reference, so a reader
        # on another thread never pairs one phase with another's start, nor
        # one thread's clock with another's reading
        self._running: tuple[str, float, int | None, float] = (
            "other", clock(), cpu_id,
            time.clock_gettime(cpu_id) if cpu_id is not None else 0.0,
        )

    @property
    def current(self) -> str:
        return self._running[0]

    def switch(self, phase: str, entries: int = 1) -> str:
        """End the running phase and begin ``phase``; returns the phase
        that ended. Two clock reads: the wall, and the CPU clock of the
        calling thread. ``entries`` is what the visit counts for: 0 resumes
        an interrupted phase, the ``events`` phase counts the Events it
        writes."""
        now = self._clock()
        prev, since, cpu_id, cpu_since = self._running
        cpu = 0.0
        if cpu_id is not None:
            ident = threading.get_ident()
            if ident != self._owner:
                # another thread has taken the loop over (a test's, or a
                # loop started after its scheduler was built): its clock
                # from here on. The phase that changes hands keeps its wall
                # and gains no CPU: the two clocks share no origin
                self._owner = ident
                cpu_id = _cpu_clock_of(ident)
                if cpu_id is not None:
                    cpu = cpu_since = time.clock_gettime(cpu_id)
            else:
                cpu = time.clock_gettime(cpu_id)
        # the new phase first, the old one's seconds second: a scrape in
        # between reads a little too LITTLE, never a counter that later
        # steps back
        self._running = (phase, now, cpu_id, cpu)
        self.seconds[prev] += now - since
        self.cpu_seconds[prev] += cpu - cpu_since
        self.entries[phase] += entries
        return prev

    def iteration_done(self) -> None:
        """One more completed iteration of the served loop."""
        self.iterations += 1

    @contextmanager
    def phase(self, name: str, entries: int = 1):
        """Run the block as ``name``, then resume the phase it interrupted
        (also when the block raises)."""
        prev = self.switch(name, entries)
        try:
            yield
        finally:
            self.switch(prev, entries=0)

    def snapshot(self) -> tuple[dict[str, float], dict[str, int], int]:
        """(seconds, entries, iterations) with the running phase's elapsed
        part added, so that a scrape in the middle of a long phase does
        not lose it. Lock-free: a read torn by a concurrent ``switch`` is
        off by one phase slice at most and is made good at the next."""
        phase, since = self._running[:2]
        seconds = dict(self.seconds)
        seconds[phase] += max(self._clock() - since, 0.0)
        return seconds, dict(self.entries), self.iterations

    def cpu_snapshot(self) -> dict[str, float] | None:
        """``cpu_seconds`` with the running phase's part added, read from
        the LOOP thread's clock whichever thread calls; None where the
        platform keeps no per-thread CPU clock. Lock-free like
        ``snapshot``."""
        phase, _, cpu_id, cpu_since = self._running
        if cpu_id is None:
            return None
        cpu = dict(self.cpu_seconds)
        try:
            cpu[phase] += max(time.clock_gettime(cpu_id) - cpu_since, 0.0)
        except OSError:
            pass        # the thread has ended, and its clock with it
        return cpu
