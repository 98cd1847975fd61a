"""Async API dispatcher — mergeable call queue off the scheduling hot loop.

Analog of ``pkg/scheduler/backend/api_dispatcher/`` (api_dispatcher.go:32
``APIDispatcher``, call_queue.go:71 mergeable queue): API writes (binds,
status patches) are enqueued by the scheduling loop and executed by worker
threads against a client, so the device-batched hot loop never blocks on I/O.
Two calls for the same (object, call type) merge — the newer call absorbs the
older, which is resolved as skipped (the reference's ``merge``/relevance
machinery).

``workers=0`` runs calls inline at ``add`` time — deterministic mode for
tests and single-threaded harnesses.

Bulk mode (the reference's opportunistic cycle batching,
framework/runtime/batch.go, riding the same pending-map machinery): calls
accumulate across a scheduling cycle and ``flush`` drains them into
per-call-type bulk RPCs — see the APIDispatcher docstring.
"""

from __future__ import annotations

import queue as _queue
import threading
import time as _time
from dataclasses import dataclass, field
from typing import Any, Callable, Protocol

from ..api import types as t
from ..tracing import thread_cpu


class CallSkipped(Exception):
    """Passed to a superseded call's ``on_done``: the call never executed
    because a newer call for the same (object, type) absorbed it — distinct
    from success (None) and from an execution error."""


def is_bind_conflict(err: BaseException | None) -> bool:
    """Classify an API-write failure as a CAS-bind conflict: the store's
    409 (``ConflictError``, single-op or positional in a bulk reply), the
    client's already-bound/gone refusals (``"bind conflict"``), or a
    federation partition-lease fence rejection (``StaleOwnerError``).
    Conflicts are the EXPECTED arbitration outcome when N scheduler
    replicas overlap — accounted separately from transport errors so the
    conflict/throughput curve is measurable."""
    if err is None:
        return False
    try:
        from ..store.memstore import ConflictError

        if isinstance(err, ConflictError):
            return True
    except Exception:  # pragma: no cover — store layer absent
        pass
    name = type(err).__name__
    return name == "StaleOwnerError" or "bind conflict" in str(err)


class APICall(Protocol):
    """One queued API write (the reference's fwk.APICall)."""

    call_type: str
    object_key: str

    def execute(self, client: Any) -> None: ...

    def merge(self, older: "APICall") -> None: ...


@dataclass
class BindCall:
    """POST pods/<name>/binding (DefaultBinder,
    framework/plugins/defaultbinder/default_binder.go). ``on_done(err)`` fires
    after execution — the scheduler's binding-cycle epilogue (finish_binding
    on success, forget+requeue on failure). ``pre``/``post`` carry the
    binding cycle's PreBind / PostBind plugin runs (schedule_one.go:391
    bindingCycle order: WaitOnPermit → PreBind → Bind → PostBind); a raising
    ``pre`` fails the bind, ``post`` is informational."""

    pod: t.Pod
    node_name: str
    on_done: Callable[[Exception | None], None] | None = None
    pre: Callable[[], None] | None = None
    post: Callable[[], None] | None = None
    # overrides the client's bind — an interested binder EXTENDER owns the
    # bind API call for its pods (schedule_one.go extendersBinding)
    bind_fn: Callable[[t.Pod, str], None] | None = None
    # staged-latency stamp (sched.flightrecorder): perf_counter at API-phase
    # start, set by execute_api on the worker thread — splits the bind span
    # into dispatch (micro-batch queue wait) and bind_rtt (the round trip)
    t_exec: float = field(default=0.0, compare=False)
    call_type: str = field(default="bind", init=False)

    @property
    def object_key(self) -> str:
        return f"{self.pod.namespace}/{self.pod.name}"

    def execute(self, client: Any) -> None:
        if self.pre is not None:
            self.pre()
        self.execute_api(client)
        if self.post is not None:
            self.post()

    def execute_api(self, client: Any) -> None:
        """Just the API write — the slice a bulk micro-batch replaces
        (``pre``/``post`` run per-call around it either way, so PreBind
        plugin effects are never re-applied by a bulk fallback)."""
        if not self.t_exec:
            self.t_exec = _time.perf_counter()
        if self.bind_fn is not None:
            self.bind_fn(self.pod, self.node_name)
        else:
            client.bind(self.pod, self.node_name)

    def merge(self, older: "BindCall") -> None:
        # a second bind for the same pod supersedes the first
        if older.on_done is not None:
            older.on_done(CallSkipped())


@dataclass
class StatusPatchCall:
    """PATCH pod status (condition PodScheduled=False with the failure
    message — framework/api_calls/ pod_status_patch)."""

    pod: t.Pod
    reason: str
    message: str = ""
    on_done: Callable[[Exception | None], None] | None = None
    call_type: str = field(default="status_patch", init=False)

    @property
    def object_key(self) -> str:
        return f"{self.pod.namespace}/{self.pod.name}"

    def execute(self, client: Any) -> None:
        client.patch_status(self.pod, self.reason, self.message)

    def merge(self, older: "StatusPatchCall") -> None:
        if older.on_done is not None:
            older.on_done(CallSkipped())


@dataclass
class DeleteVictimCall:
    """DELETE a preemption victim (preemption Executor's
    ``actuatePodPreemption`` — framework/preemption/executor.go issues the
    victim deletions, optionally clearing competing nominations first)."""

    pod: t.Pod
    preemptor_key: str = ""
    on_done: Callable[[Exception | None], None] | None = None
    call_type: str = field(default="delete_victim", init=False)

    @property
    def object_key(self) -> str:
        return f"{self.pod.namespace}/{self.pod.name}"

    def execute(self, client: Any) -> None:
        client.delete_pod(self.pod, reason="preempted by " + self.preemptor_key)

    def merge(self, older: "DeleteVictimCall") -> None:
        if older.on_done is not None:
            older.on_done(CallSkipped())


@dataclass
class NominateCall:
    """PATCH the preemptor's status.nominatedNodeName. Distinct call_type
    from StatusPatchCall: the dispatcher merges by (call_type, object_key)
    and each call executes only its own write, so sharing the type would let
    a later condition patch silently cancel a pending nomination (the
    reference's pod_status_patch instead merges both fields into one patch)."""

    pod: t.Pod
    node_name: str
    on_done: Callable[[Exception | None], None] | None = None
    call_type: str = field(default="nominate", init=False)

    @property
    def object_key(self) -> str:
        return f"{self.pod.namespace}/{self.pod.name}"

    def execute(self, client: Any) -> None:
        client.nominate(self.pod, self.node_name)

    def merge(self, older: "NominateCall") -> None:
        if older.on_done is not None:
            older.on_done(CallSkipped())


_CLOSE = object()


@dataclass
class _BatchJob:
    """One flushed micro-batch: every pending call of one call type,
    handed to a worker as a single work item."""

    call_type: str
    calls: list


#: call_type → (client bulk method name, call → bulk-op argument). A client
#: exposing the named method gets the whole micro-batch in ONE invocation
#: (e.g. StoreClient.bulk_bind turns a cycle's binds into ONE bulk RPC of
#: bind ops);
#: clients without it fall back to per-call execution unchanged.
_BULK_ADAPTERS: dict[str, tuple] = {
    "bind": ("bulk_bind", lambda c: (c.pod, c.node_name)),
    "status_patch": (
        "bulk_status_patch", lambda c: (c.pod, c.reason, c.message)
    ),
    "delete_victim": (
        "bulk_delete_victim", lambda c: (c.pod, c.preemptor_key)
    ),
}


def _bulkable(call: APICall) -> bool:
    """Only the standard API write may merge into a bulk RPC: a call whose
    bind is owned by an extender webhook (``bind_fn``) executes per-call.
    Host-side ``pre``/``post`` hooks do NOT disqualify — the batch runs
    them per-call around the bulked API phase (``execute_api``)."""
    return getattr(call, "bind_fn", None) is None


class APIDispatcher:
    """See module docstring.

    ``bulk=True`` turns on opportunistic micro-batching: ``add`` only
    accumulates into the mergeable pending map, and ``flush`` — called by
    the scheduler at cycle boundaries (and by ``sync``/``close``) — drains
    it into per-call-type batch jobs. A worker executes a whole batch
    through the client's ``bulk_<call_type>`` method when it has one
    (a cycle's 128 BindCalls become one bulk request); per-op failures,
    a missing bulk method, or calls carrying host hooks fall back to
    per-call ``execute``, so every pod's error path is exactly the
    non-bulk path's. ``bulk=False`` is byte-for-byte the previous
    dispatch behavior (the ``--bulk off`` escape hatch)."""

    def __init__(
        self, client: Any, workers: int = 2, bulk: bool = False,
        tracer=None,
    ) -> None:
        """``tracer``: an optional span recorder (the owning scheduler's
        Tracer) — every executed call type records one ``api.<type>``
        span (graftcheck TR003 pins the seam), carrying the pod's
        attribution id so the cross-process timeline includes the
        dispatch leg. None (or a disabled tracer) costs nothing."""
        self._client = client
        self._workers = workers
        self._bulk = bulk
        self._tracer = tracer
        self._pending: dict[tuple[str, str], APICall] = {}
        self._lock = threading.Lock()
        self._q: _queue.Queue = _queue.Queue()
        self._threads: list[threading.Thread] = []
        self._added = 0
        self._executed = 0
        self._errors = 0
        self._conflicts = 0        # errors that were CAS-bind conflicts
        #                            (bulk partial-409s land here per op)
        self._batches = 0          # bulk RPCs issued
        self._batched_calls = 0    # calls that rode a bulk RPC
        # call_type -> [wall, CPU] seconds the WORKERS spent executing what
        # they took from the queue (worker_clock)
        self._worker_s: dict[str, list[float]] = {}
        self._closed = False
        if workers > 0:
            for i in range(workers):
                th = threading.Thread(
                    target=self._worker, name=f"api-dispatcher-{i}", daemon=True
                )
                th.start()
                self._threads.append(th)

    @property
    def client(self) -> Any:
        """The API client the dispatcher writes through — the public handle
        lifecycle plugins use for their own API writes (PreBind's PV/claim
        status patches)."""
        return self._client

    def add(self, call: APICall) -> None:
        if self._closed or (self._workers == 0 and not self._bulk):
            self._execute(call)  # inline: no pool, or pool already drained
            return
        with self._lock:
            key = (call.call_type, call.object_key)
            older = self._pending.get(key)
            if older is not None:
                call.merge(older)
                older_skipped = True
            else:
                older_skipped = False
            self._pending[key] = call
            self._added += 1
            if not self._bulk and not older_skipped:
                self._q.put(key)

    def flush(self) -> None:
        """Drain the pending map into per-call-type batch jobs (the
        micro-batch window closes here — the scheduler calls this at cycle
        boundaries). No-op without ``bulk``: per-call dispatch already
        queued everything at ``add`` time."""
        if not self._bulk:
            return
        with self._lock:
            if not self._pending:
                return
            pending = list(self._pending.values())
            self._pending.clear()
        groups: dict[str, list] = {}
        for call in pending:
            groups.setdefault(call.call_type, []).append(call)
        for call_type, calls in groups.items():
            if self._workers == 0 or self._closed:
                self._execute_batch(call_type, calls)
            else:
                self._q.put(_BatchJob(call_type, calls))

    def _pop(self, key: tuple[str, str]) -> APICall | None:
        with self._lock:
            return self._pending.pop(key, None)

    def _finish(self, call: APICall, err: Exception | None) -> None:
        # counters under the lock: workers resolve calls concurrently and a
        # bare read-modify-write tears (the stats()/metrics reader would
        # see undercounts forever)
        with self._lock:
            self._executed += 1
            if err is not None:
                self._errors += 1
                if is_bind_conflict(err):
                    # per-dispatcher (= per-replica) conflict accounting:
                    # a bulk bind's partial 409s fall back through
                    # _execute_api and resolve here one by one, so the
                    # count is per-op exact either way
                    self._conflicts += 1
        on_done = getattr(call, "on_done", None)
        if on_done is not None:
            try:
                on_done(err)
            except Exception:
                pass

    def _record_call_span(self, call: APICall, t0: float,
                          err: Exception | None) -> None:
        """THE dispatcher span seam: one ``api.<call_type>`` span per
        executed call, off-stack (worker threads record concurrently),
        linked to the pod's cross-process timeline by its attribution id."""
        tr = self._tracer
        if tr is None:
            return
        pod = getattr(call, "pod", None)
        tr.record(
            f"api.{call.call_type}", start=t0, end=_time.perf_counter(),
            per_item=True, key=call.object_key,
            status="error" if err is not None else "ok",
            pod_trace=getattr(pod, "trace_id", "") or "",
        )

    def _execute(self, call: APICall) -> None:
        err: Exception | None = None
        t0 = _time.perf_counter()
        try:
            call.execute(self._client)
        except Exception as e:  # noqa: BLE001 — surfaced via on_done
            err = e
        self._record_call_span(call, t0, err)
        self._finish(call, err)

    def _execute_api(self, call: APICall) -> None:
        """Per-call fallback AFTER a bulk attempt: the call's ``pre`` hook
        already ran (PreBind effects must not re-apply), so only the API
        phase + ``post`` re-execute — exactly the single-op path's
        remainder."""
        err: Exception | None = None
        t0 = _time.perf_counter()
        try:
            api = getattr(call, "execute_api", None)
            if api is not None:
                api(self._client)
            else:
                call.execute(self._client)
            post = getattr(call, "post", None)
            if post is not None:
                post()
        except Exception as e:  # noqa: BLE001 — surfaced via on_done
            err = e
        self._record_call_span(call, t0, err)
        self._finish(call, err)

    def _execute_batch(self, call_type: str, calls: list) -> None:
        """One micro-batch: bulk-eligible calls ride the client's
        ``bulk_<call_type>`` in ONE invocation, their ``pre``/``post``
        hooks still running per-call around the bulked API phase;
        everything else — and any op the bulk response failed — executes
        per-call, so per-pod error semantics (bind-error → forget-assumed
        → requeue) are identical to the non-bulk path."""
        spec = _BULK_ADAPTERS.get(call_type)
        fn = getattr(self._client, spec[0], None) if spec else None
        eligible: list = []
        singles: list = []
        for call in calls:
            (eligible if fn is not None and _bulkable(call)
             else singles).append(call)
        if len(eligible) < 2:
            # nothing to amortize: a lone call pays less as a single op
            singles = calls
            eligible = []
        ready: list = []
        for call in eligible:
            pre = getattr(call, "pre", None)
            if pre is not None:
                try:
                    pre()
                except Exception as e:  # noqa: BLE001 — surfaced via on_done
                    # a failing PreBind aborts before the API write — the
                    # same resolution order as the single-op execute
                    self._finish(call, e)
                    continue
            ready.append(call)
        if len(ready) >= 2:
            t_bulk = _time.perf_counter()
            cpu_bulk = thread_cpu() if thread_cpu is not None else None
            for call in ready:
                # the bulk RPC IS these calls' API phase: stamp its start
                # (the per-call fallback restamps nothing — first write wins)
                if getattr(call, "t_exec", None) == 0.0:
                    call.t_exec = t_bulk
            try:
                errs = fn([spec[1](c) for c in ready])
                if len(errs) != len(ready):
                    raise RuntimeError("bulk result length mismatch")
            except Exception:
                # the whole batch failed to go bulk (no transport, missing
                # verb, malformed reply): per-call fallback for everything
                # (pre already ran — resume at the API phase)
                for call in ready:
                    self._execute_api(call)
            else:
                tr = self._tracer
                if tr is not None:
                    # one span for the whole micro-batch's API phase (the
                    # per-op fallbacks below record their own); pod
                    # attribution rides as a capped id list like the
                    # apiserver's bulk request span. cpu_s: of that phase,
                    # what this thread ran (the encode and decode of two
                    # bulk requests); the rest it waited for the apiserver
                    # or for the GIL
                    cpu = ({} if cpu_bulk is None else
                           {"cpu_s": round(thread_cpu() - cpu_bulk, 6)})
                    tr.record(
                        f"api.{call_type}.bulk", start=t_bulk,
                        end=_time.perf_counter(), n=len(ready), **cpu,
                        pod_traces=[
                            tid for c in ready
                            if (tid := getattr(
                                getattr(c, "pod", None), "trace_id", ""
                            ))
                        ][:64],
                    )
                with self._lock:
                    self._batches += 1
                    self._batched_calls += len(ready)
                for call, err in zip(ready, errs):
                    if err is not None:
                        # partial failure: re-run just this op per-call so
                        # its error (or late success) is exactly what the
                        # single-op path would have produced
                        self._execute_api(call)
                        continue
                    post_err: Exception | None = None
                    post = getattr(call, "post", None)
                    if post is not None:
                        try:
                            post()
                        except Exception as e:  # noqa: BLE001
                            post_err = e
                    self._finish(call, post_err)
        else:
            for call in ready:
                self._execute_api(call)
        for call in singles:
            self._execute(call)

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is _CLOSE:
                self._q.task_done()  # keep join() balanced after close
                return
            # one wall pair and one CPU pair around each item: this thread
            # runs under the loop's GIL, and nothing else times it
            t0 = _time.perf_counter()
            cpu0 = thread_cpu() if thread_cpu is not None else 0.0
            if isinstance(item, _BatchJob):
                call_type = item.call_type
                self._execute_batch(call_type, item.calls)
            else:
                call_type = item[0]
                call = self._pop(item)
                if call is not None:
                    self._execute(call)
            wall = _time.perf_counter() - t0
            cpu = thread_cpu() - cpu0 if thread_cpu is not None else 0.0
            with self._lock:
                cell = self._worker_s.setdefault(call_type, [0.0, 0.0])
                cell[0] += wall
                cell[1] += cpu
            self._q.task_done()

    def sync(self) -> None:
        """Barrier: wait until every queued call has executed (tests and
        harness measurement boundaries). Flushes the micro-batch window
        first so a pending bulk batch cannot outlive the barrier."""
        self.flush()
        if self._workers > 0:
            self._q.join()

    def close(self) -> None:
        if self._closed:
            return
        # flush + drain regardless of worker count: a workers=0 bulk
        # dispatcher still holds a pending micro-batch window, and a close
        # that skipped the flush would silently drop the final cycle's
        # calls (later add()s execute inline once _closed is set)
        self.sync()
        self._closed = True
        if self._workers > 0:
            for _ in self._threads:  # one sentinel per worker, each acked
                self._q.put(_CLOSE)
            for th in self._threads:
                th.join(timeout=5)

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "added": self._added,
                "executed": self._executed,
                "errors": self._errors,
                "conflicts": self._conflicts,
                "batches": self._batches,
                "batched_calls": self._batched_calls,
            }

    def worker_clock(self) -> dict[str, tuple[float, float | None]]:
        """call_type -> (wall seconds, CPU seconds) the worker threads
        spent executing what they took from the queue, since the dispatcher
        was built. Wall less CPU is the workers' own wait: for the
        apiserver, for the GIL. With several workers the wall can pass the
        elapsed time. A call executed INLINE (``workers=0``, a closed
        dispatcher) ran on its caller's thread and is in neither. CPU is
        None where the platform keeps no per-thread clock."""
        with self._lock:
            return {
                call_type: (wall, cpu if thread_cpu is not None else None)
                for call_type, (wall, cpu) in self._worker_s.items()
            }
