"""Versioned in-memory object store with watch — layer 0 of the stack.

Reference semantics mirrored (storage is host-side by design, SURVEY §2.9 —
the device-resident tensors are the hot store; THIS layer is the source of
truth every component watches):

- etcd3 store (apiserver/pkg/storage/etcd3/store.go): every write bumps one
  monotonically increasing resourceVersion; Create fails on exists (:269),
  ``GuaranteedUpdate`` does optimistic CAS on resourceVersion (:458);
  GetList returns the store's current revision (:733).
- Watch cache (apiserver/pkg/storage/cacher/cacher.go:263): one ring buffer
  of events fans out to N watchers; a watcher asking for a revision older
  than the buffer gets "too old" (HTTP 410 Gone) and must relist —
  ``CompactedError`` here, consumed by the Reflector's relist loop
  (client-go reflector.go ListAndWatch).

Two interchangeable CORES behind one locking wrapper (the reference's
storage engine is native code — etcd; kubetpu.native/memstore_core.cpp is
this framework's equivalent):

- the C++ ``StoreCore`` (kubetpu.native), compiled on first use, and
- ``_PyCore``, the pure-Python fallback (``KUBETPU_NO_NATIVE=1`` or no
  compiler).

Both expose the same micro-interface and exception mapping; the wrapper
owns the Condition lock (serializing every call — the native core is
single-writer by construction) and the blocking ``wait_for``.

Watchers are PULL-based (``Watcher.poll``): the schedulers/controllers in
this framework fold their pumps into their loops (same shape as the queue's
flush timers); ``wait_for`` provides the blocking form for threads.
"""

from __future__ import annotations

import collections
import os
import threading
from dataclasses import dataclass
from typing import Any, Callable

from . import faultpoints

ADDED = "ADDED"
MODIFIED = "MODIFIED"
DELETED = "DELETED"

_EVENT_TYPES = (ADDED, MODIFIED, DELETED)

_WIRE_ENCODERS: dict[str, Any] = {}


def _wire_ids() -> dict:
    """codec name → dense slot id in the cores' per-event body ring.
    ONE authoritative Python-side table (kubetpu.api.codec.WIRE_CODEC_IDS
    — the native Event struct's fixed kNumCodecs array must stay aligned
    with it), imported lazily so layer 0 imports stay light."""
    from ..api.codec import WIRE_CODEC_IDS

    return WIRE_CODEC_IDS


def _wire_encoder(codec: str):
    """(encoder, codec id) for the body ring's miss path."""
    got = _WIRE_ENCODERS.get(codec)
    if got is None:
        from ..api.codec import event_body_encoder

        got = (event_body_encoder(codec), _wire_ids()[codec])
        _WIRE_ENCODERS[codec] = got
    return got


class CompactedError(Exception):
    """The requested resourceVersion predates the event buffer (the watch
    cache's 'too old resource version' / HTTP 410 — relist required)."""


class ConflictError(Exception):
    """CAS failure: the object moved past the expected resourceVersion, or
    Create hit an existing object."""


class FollowerWriteError(Exception):
    """A local write reached a replication FOLLOWER store. Followers are
    read-only replicas — every mutation must land on the leader (the
    apiserver answers with a redirect carrying the leader's URL); the only
    paths that may move a follower's core are ``apply_replicated`` and
    ``load_replica_snapshot`` (graftcheck RP001 pins the seam)."""


class ReplicationGapError(Exception):
    """The replication feed skipped revisions — a shipped record's rv is
    not contiguous with the follower's store. The follower must resync
    from a leader snapshot (the live-replay twin of recovery's WALError
    'replay gap'; never silently applied out of order)."""


def bulk_result_error(res: dict) -> Exception | None:
    """Map one bulk-op result (the ``{"status": …, "error": …}`` dicts
    ``MemStore.bulk``/``RemoteStore.bulk`` return) to the exception the
    matching single-op verb would have raised — one mapping for both
    deployment shapes, so callers of either surface handle conflicts and
    absences identically."""
    status = res.get("status", 500)
    if status < 400:
        return None
    reason = res.get("error", f"status {status}")
    if status == 409:
        return ConflictError(reason)
    if status == 404:
        return KeyError(reason)
    if status in (400, 422):
        return ValueError(reason)
    if status == 403:
        return PermissionError(reason)
    return RuntimeError(f"{status}: {reason}")


def bind_refusal(key: str, current: Any, uid: str = "") -> dict | None:
    """Why pod ``key`` may not be bound as it is stored (``current``), as
    a bulk-op result, or None: the binding subresource's checks. Gone is
    a 404; a different ``uid`` (a recreated pod of the same name) and ANY
    node already set, the same node included, are 409s — federation's
    race mode depends on a losing replica's same-node bind reading as a
    conflict, not a win."""
    if current is None:
        status, why = 404, "is gone"
    elif uid and current.uid != uid:
        status, why = 409, f"was recreated (uid {current.uid!r}, not {uid!r})"
    elif current.node_name:
        status, why = 409, f"already on {current.node_name}"
    else:
        return None
    return {
        "status": status, "resourceVersion": 0,
        "error": f"bind conflict: pod {key} {why}",
    }


@dataclass(frozen=True)
class WatchEvent:
    type: str              # ADDED | MODIFIED | DELETED
    kind: str              # resource bucket ("nodes", "pods", …)
    key: str
    obj: Any               # the object AFTER the change (before, for DELETED)
    resource_version: int
    # a bind delta of the batched watch poll: (uid, node) of a pods ``bind``
    # op, with ``obj`` None; the informer rebuilds the object it holds
    bind: "tuple[str, str] | None" = None


def _deletes_on_update(obj: Any) -> bool:
    """An update that leaves the object terminating with no finalizers
    completes its deletion (the finalizer gate of ``_update_locked``)."""
    return (
        getattr(obj, "deletion_timestamp", None) is not None
        and not getattr(obj, "finalizers", ())
    )


class _PyCore:
    """Pure-Python core: the same micro-interface as the native StoreCore
    (create/update/delete/get/count/list/events_since[+bulk]/
    event_bodies_since[+bulk]/resource_version), same exception types
    (KeyError/ValueError/LookupError — mapped by the wrapper).

    Ring entries are 6-slot lists — the 6th slot is the per-event WIRE
    BODY cache ({codec id: bytes}, the serialize-once body ring): an
    event's wire encoding is immutable (store writes replace objects,
    never mutate them), so a cached body can never go stale and dies with
    its ring entry."""

    def __init__(self, history: int = 8192) -> None:
        self._rv = 0
        # (obj, rv, seq) — seq is the insertion order (stable across
        # updates), the paged list walk's cursor axis; matches the native
        # core's Entry.seq
        self._objects: dict[tuple[str, str], tuple[Any, int, int]] = {}
        # live objects per kind, kept at every insert/pop of ``_objects``:
        # what ``count`` answers without a walk
        self._kind_counts: collections.Counter = collections.Counter()
        self._seq = 0
        self._events: collections.deque = collections.deque(maxlen=history)
        self._compacted_through = 0
        self._body_hits = [0, 0]      # per codec id (0 json, 1 binary)
        self._body_misses = [0, 0]

    def _emit(self, ev_type: int, kind: str, key: str, obj: Any) -> None:
        if len(self._events) == self._events.maxlen:
            self._compacted_through = self._events[0][4]
        self._events.append([ev_type, kind, key, obj, self._rv, {}])

    def create(self, kind: str, key: str, obj: Any) -> int:
        if (kind, key) in self._objects:
            raise KeyError(f"{kind}/{key} already exists")
        self._rv += 1
        self._seq += 1
        self._objects[(kind, key)] = (obj, self._rv, self._seq)
        self._kind_counts[kind] += 1
        self._emit(0, kind, key, obj)
        return self._rv

    def update(self, kind: str, key: str, obj: Any, expect: int = -1) -> int:
        got = self._objects.get((kind, key))
        if expect >= 0:
            have = got[1] if got is not None else -1
            if got is None or have != expect:
                raise ValueError(
                    f"{kind}/{key}: expected rv {expect}, have "
                    f"{have if got is not None else 'absent'}"
                )
        self._rv += 1
        if got is None:
            self._seq += 1
            seq = self._seq
            self._kind_counts[kind] += 1
        else:
            seq = got[2]                 # updates do not reorder
        self._objects[(kind, key)] = (obj, self._rv, seq)
        self._emit(0 if got is None else 1, kind, key, obj)
        return self._rv

    def delete(self, kind: str, key: str) -> int:
        got = self._objects.pop((kind, key), None)
        if got is None:
            raise KeyError(f"{kind}/{key} not found")
        self._kind_counts[kind] -= 1
        self._rv += 1
        self._emit(2, kind, key, got[0])
        return self._rv

    def get(self, kind: str, key: str):
        got = self._objects.get((kind, key))
        return (None, 0) if got is None else (got[0], got[1])

    def count(self, kind: str) -> int:
        """Live objects of ``kind``, O(1): a lookup, never a walk."""
        return self._kind_counts[kind]

    def list(self, kind: str, label_terms: tuple = (),
             field_terms: tuple = ()):
        items = [
            (key, obj)
            for (k, key), (obj, _rv, _seq) in self._objects.items()
            if k == kind
        ]
        if label_terms or field_terms:
            from ..api.selectors import object_matches_selectors

            items = [
                (k, o) for k, o in items
                if object_matches_selectors(o, label_terms, field_terms)
            ]
        return items, self._rv

    def list_page(self, kind: str, label_terms: tuple = (),
                  field_terms: tuple = (), limit: int = 0,
                  after_seq: int = 0, through_seq: int = 0):
        """One bounded page of the seq-ordered list walk — the pagination
        primitive behind ``MemStore._list_page_locked``; returns
        ``(items [(key, obj, rv)], store_rv, next_seq, has_more,
        through_seq)``. Seq order is insertion order and updates never
        reorder, so a walk resumed at ``next_seq`` can neither duplicate
        nor skip an object that existed across the whole walk.
        ``through_seq`` caps the walk at a seq bound so objects CREATED
        mid-walk never splice into later pages (the snapshot-cut half of
        the continue-token contract); ``through_seq <= 0`` captures the
        current max seq and echoes it back for the caller's token.
        ``limit <= 0`` is unbounded (the full-list form).
        Selector-filtered candidates still advance ``next_seq`` (a
        filtered walk always makes progress); ``has_more`` reports
        whether any in-bound candidate of the kind remains past this
        page."""
        matcher = None
        if label_terms or field_terms:
            from ..api.selectors import object_matches_selectors

            matcher = object_matches_selectors
        bound = through_seq if through_seq > 0 else self._seq
        items: list = []
        next_seq = after_seq
        has_more = False
        # dict insertion order IS seq order (updates keep both), so no sort
        for (k, key), (obj, rv, seq) in self._objects.items():
            if k != kind or seq <= after_seq or seq > bound:
                continue
            if limit > 0 and len(items) >= limit:
                has_more = True
                break
            if matcher is None or matcher(obj, label_terms, field_terms):
                items.append((key, obj, rv))
            next_seq = seq
        return items, self._rv, next_seq, has_more, bound

    def _collect_since(self, kind: str | None, rv: int):
        """Ring entries newer than ``rv`` for ``kind`` + the new cursor
        (oldest first)."""
        if not self._events or self._events[-1][4] <= rv:
            return [], rv
        cursor = self._events[-1][4]
        out = []
        for e in reversed(self._events):
            if e[4] <= rv:
                break
            if kind is None or e[1] == kind:
                out.append(e)
        out.reverse()
        return out, cursor

    def events_since(self, kind: str | None, rv: int):
        if rv < self._compacted_through:
            raise LookupError(
                f"rv {rv} compacted (through {self._compacted_through})"
            )
        hits, cursor = self._collect_since(kind, rv)
        return [tuple(e[:5]) for e in hits], cursor

    def events_since_bulk(self, cursors: dict):
        """Every kind's cursor drained in one call (None marks a
        compacted kind); second value is the revision at the drain."""
        out: dict = {}
        for kind, rv in cursors.items():
            if rv < self._compacted_through:
                out[kind] = None
                continue
            out[kind] = self.events_since(kind, rv)
        return out, self._rv

    def _event_body(self, e: list, codec_id: int, encoder) -> bytes:
        body = e[5].get(codec_id)
        if body is not None:
            self._body_hits[codec_id] += 1
            return body
        body = encoder(e[0], e[2], e[3], e[4])
        e[5][codec_id] = body
        self._body_misses[codec_id] += 1
        return body

    def event_bodies_since(self, kind: str | None, rv: int,
                           codec_id: int, encoder):
        """The serialize-once fan-out path: cached wire bodies for every
        event newer than ``rv`` (encoded once per event per codec via
        ``encoder(type_id, key, obj, rv) -> bytes`` on first sight)."""
        if rv < self._compacted_through:
            raise LookupError(
                f"rv {rv} compacted (through {self._compacted_through})"
            )
        hits, cursor = self._collect_since(kind, rv)
        return (
            [self._event_body(e, codec_id, encoder) for e in hits],
            cursor,
        )

    def event_bodies_since_bulk(self, cursors: dict, codec_id: int,
                                encoder):
        out: dict = {}
        for kind, rv in cursors.items():
            if rv < self._compacted_through:
                out[kind] = None
                continue
            out[kind] = self.event_bodies_since(kind, rv, codec_id, encoder)
        return out, self._rv

    def clear_event_bodies(self) -> None:
        """Drop every cached wire body (the ring events stay) — the
        registry-generation flush: binary bodies embed schema-table ids
        that shift when a kind registers late."""
        for e in self._events:
            e[5].clear()

    def body_cache_stats(self) -> dict:
        return {
            cid: (self._body_hits[cid], self._body_misses[cid])
            for cid in (0, 1)
        }

    def resource_version(self) -> int:
        return self._rv

    def compacted_through(self) -> int:
        return self._compacted_through

    # ------------------------------------------------- durability surface
    def dump(self):
        """Every object as (kind, key, obj, rv) in insertion order — the
        compaction snapshot's input (and the recovery tests' parity
        probe). Insertion order matters: ``load_snapshot`` must rebuild
        the same list() ordering both cores guarantee."""
        return [
            (kind, key, obj, rv)
            for (kind, key), (obj, rv, _seq) in self._objects.items()
        ]

    def load_snapshot(self, items, rv: int) -> None:
        """Reset to a snapshot: objects with their per-object rvs (CAS
        survives recovery), store revision ``rv``, event ring EMPTY with
        the compaction horizon at ``rv`` — a watcher cursor below the
        snapshot predates everything replayable and must 410 into a full
        relist; the replayed WAL tail then repopulates the ring."""
        self._objects = {
            (kind, key): (obj, obj_rv, seq)
            for seq, (kind, key, obj, obj_rv) in enumerate(items, start=1)
        }
        self._kind_counts = collections.Counter(
            kind for kind, _key in self._objects
        )
        self._seq = len(self._objects)
        self._rv = rv
        self._events.clear()
        self._compacted_through = rv


class MemStore:
    """See module docstring. Thread-safe; writes are serialized under one
    Condition, which also backs the blocking ``wait_for``."""

    def __init__(self, history: int = 8192, native: bool | None = None,
                 persistence: "str | None" = None,
                 wal_wire: str = "binary", wal_fsync: bool = True,
                 compact_every: int = 65536,
                 follower: bool = False) -> None:
        """``persistence``: a directory path turns on the write-ahead log
        + snapshot durability (kubetpu.store.wal) — recover-on-start
        replays snapshot+tail into the core, every committed write is
        logged-then-applied, and compaction runs automatically every
        ``compact_every`` records. None (the default, ``--persistence
        off``) is byte-identical to the memory-only store. ``wal_wire``
        picks the record codec (binary default — the compact wire the
        body ring speaks); ``wal_fsync=False`` is the benchmark escape
        hatch (flush-to-OS only). ``follower`` makes this store a
        replication replica: local writes raise FollowerWriteError and
        the core moves ONLY through ``apply_replicated`` /
        ``load_replica_snapshot`` (kubetpu.store.replication tails the
        leader's log into this seam) until ``promote()``."""
        if follower and persistence:
            raise ValueError(
                "a follower store is a memory replica — its durability is "
                "the leader's WAL (bootstrap loads a snapshot the local "
                "log never saw, so a follower-side WAL could not recover)"
            )
        self._follower = follower
        self._applying = False      # True only inside the replication seam
        self._lock = threading.Condition()
        core_cls = None
        if native is not False and not os.environ.get("KUBETPU_NO_NATIVE"):
            from ..native import store_core

            core_cls = store_core()
        if native is True and core_cls is None:
            raise RuntimeError("native store core unavailable")
        self._core = core_cls(history) if core_cls is not None else _PyCore(history)
        self.native = core_cls is not None
        # list-walk continuity domain: seqs are only comparable within one
        # of these. Snapshot loads (crash recovery below, replica
        # bootstrap/resync) renumber seqs densely, so a continue token
        # minted before a load could silently skip or duplicate entries
        # where deletions had left gaps — the token carries this stamp and
        # the server 410s on mismatch. Random (not monotonic) so a token
        # that survives a process restart also misses.
        self._list_gen = int.from_bytes(os.urandom(4), "big") or 1
        # scheme-registry generation the cached wire bodies were encoded
        # under (None until the first body drain); a move flushes the ring
        self._body_gen: "int | None" = None
        # the events that pods ``bind`` ops committed, by revision: rv →
        # [key, uid, node, delta body per codec id]. Beside the ring and
        # pruned as it compacts, so a record dies with its event; the ring,
        # the WAL and the event's whole body are what any update writes
        self._binds: dict[int, list] = {}
        self._bind_rvs: collections.deque = collections.deque()
        self._wal = None
        self._wal_closed = False
        self._wal_lock = None
        self.recovery_info = None
        if persistence:
            from .wal import DirLock, WriteAheadLog, recover_into

            # single-writer guard FIRST (a concurrent opener would rotate
            # + truncate the live log), then recover (torn tails
            # truncated, snapshot+tail replayed into the core with rv
            # continuity), then open a fresh append segment; a replay
            # longer than the compaction interval compacts immediately so
            # boot chains stay bounded
            os.makedirs(persistence, exist_ok=True)
            self._wal_lock = DirLock(persistence)
            try:
                self.recovery_info = recover_into(self._core, persistence)
                self._wal = WriteAheadLog(
                    persistence, wire=wal_wire, fsync=wal_fsync,
                    compact_every=compact_every,
                    base_rv=self._core.resource_version(),
                )
                if self.recovery_info.replayed >= compact_every:
                    self._wal.snapshot(
                        self._core.dump(), self._core.resource_version()
                    )
            except BaseException:
                self._wal_lock.release()
                raise

    # ------------------------------------------------------------- writes
    # THE WAL append seam: every core mutation — single verbs, the bulk
    # verb, the finalizer/soft-delete sub-writes — routes through
    # ``_commit_locked``, which appends the write's record to the WAL
    # (flushed, write-AHEAD) before the core applies it. graftcheck WL001
    # pins this: a core mutation outside the seam is a durability hole.

    def _commit_locked(self, verb: str, kind: str, key: str,
                       obj: Any = None, expect: int = -1) -> int:
        """Apply ONE write to the core, WAL-logged first when persistence
        is on. The peek mirrors the core's own failure rules exactly so a
        doomed write raises the CANONICAL core error without ever being
        logged (a logged-but-failed write would corrupt the replay
        chain); caller holds the store lock."""
        if self._follower and not self._applying:
            # the follower guard sits at THE choke point every mutation
            # routes through (WL001's seam), so no write verb — present or
            # future — can slip a local write into a replica
            raise FollowerWriteError(
                "store is a replication follower — writes must go to the "
                "leader apiserver"
            )
        if self._wal_closed:
            # the WAL was flushed and closed (graceful shutdown): an ack'd
            # write from here on would be silently non-durable — refuse
            # loudly instead of punching a hole in the recovery chain
            raise RuntimeError(
                "persistent store is closed — writes after close() would "
                "never reach the WAL"
            )
        core = self._core
        wal = self._wal
        if wal is not None:
            cur, cur_rv = core.get(kind, key)
            if verb == "create":
                if cur is not None:
                    return core.create(kind, key, obj)   # canonical raise
                ev = 0
            elif verb == "update":
                if expect >= 0 and (cur is None or cur_rv != expect):
                    return core.update(kind, key, obj, expect)
                ev = 0 if cur is None else 1
            else:                                        # delete
                if cur is None:
                    return core.delete(kind, key)        # canonical raise
                ev, obj = 2, cur
            wal.append(ev, kind, key, obj, core.resource_version() + 1)
            faultpoints.fire("wal-post-append-pre-apply")
        if verb == "create":
            return core.create(kind, key, obj)
        if verb == "update":
            return core.update(kind, key, obj, expect)
        return core.delete(kind, key)

    def _wal_commit_locked(self) -> None:
        """Group commit at the end of one lock round — fsync everything
        appended (one write = one fsync; a bulk batch shares one), BEFORE
        any caller is acked/notified — then compact when the record
        budget since the last snapshot is spent."""
        wal = self._wal
        if wal is None:
            return
        wal.commit()
        if wal.wants_compaction:
            wal.snapshot(self._core.dump(), self._core.resource_version())

    def create(self, kind: str, key: str, obj: Any) -> int:
        with self._lock:
            try:
                rv = self._commit_locked("create", kind, key, obj)
            except KeyError as e:
                raise ConflictError(str(e).strip("'\"")) from None
            self._wal_commit_locked()
            self._lock.notify_all()
            return rv

    def update(
        self, kind: str, key: str, obj: Any, expect_rv: int | None = None
    ) -> int:
        """GuaranteedUpdate: CAS when ``expect_rv`` is given; upsert when the
        object is absent and no CAS was requested.

        Finalizer gate (registry/store.go deleteForEmptyFinalizers): an
        update that leaves a TERMINATING object (deletion_timestamp set)
        with no finalizers completes the deletion — the object is removed
        and a DELETED event fires instead of MODIFIED."""
        with self._lock:
            rv = self._update_locked(kind, key, obj, expect_rv)
            self._wal_commit_locked()
            self._lock.notify_all()
            return rv

    def _update_locked(
        self, kind: str, key: str, obj: Any, expect_rv: int | None
    ) -> int:
        """The update body, caller holds the lock (shared by the single-op
        verb and ``bulk``; the caller notifies)."""
        if _deletes_on_update(obj):
            current, have_rv = self._core.get(kind, key)
            if current is None:
                raise ConflictError(f"{kind}/{key}: gone")
            if expect_rv is not None and have_rv != expect_rv:
                raise ConflictError(
                    f"{kind}/{key}: expected rv {expect_rv}, have {have_rv}"
                )
            return self._commit_locked("delete", kind, key)
        try:
            return self._commit_locked(
                "update", kind, key, obj,
                -1 if expect_rv is None else expect_rv,
            )
        except ValueError as e:
            raise ConflictError(str(e)) from None

    def delete(self, kind: str, key: str) -> int:
        """Remove the object. GRACEFUL path (pkg/registry/core/pod —
        pods delete via deletionTimestamp): an object carrying finalizers
        is soft-deleted — ``deletion_timestamp`` is stamped and the object
        retained (MODIFIED event) until every finalizer is cleared; a
        repeat delete of a terminating object is a no-op returning the
        current revision."""
        with self._lock:
            rv = self._delete_locked(kind, key)
            self._wal_commit_locked()
            self._lock.notify_all()
            return rv

    def _delete_locked(self, kind: str, key: str) -> int:
        """The delete body, caller holds the lock (shared by the single-op
        verb and ``bulk``; the caller notifies)."""
        current, rv = self._core.get(kind, key)
        if current is not None and getattr(current, "finalizers", ()):
            import dataclasses
            import time as _time

            if getattr(current, "deletion_timestamp", None) is not None:
                return self._core.resource_version()   # already going
            doomed = dataclasses.replace(
                current, deletion_timestamp=_time.time()
            )
            return self._commit_locked("update", kind, key, doomed, -1)
        return self._commit_locked("delete", kind, key)  # KeyError propagates

    # --------------------------------------------------------------- bulk
    def bulk(self, kind: str, ops: list[dict],
             guard: Callable[[], bool] | None = None) -> list[dict] | None:
        """Apply a list of create/update/delete/get/bind ops under ONE lock
        acquisition (the bulk verb's storage half: N writes pay one lock
        round instead of N). Ops are dicts ``{"op": "create|update|delete|
        get", "key": …, "object": …, "expect_rv": …}``; the result list is
        positional, one ``{"status", "resourceVersion", "error"?,
        "object"?}`` per op with the SAME per-object conflict/absence
        semantics as the single-op verbs (a mid-batch conflict fails only
        its own op — later ops still apply).

        ``{"op": "bind", "key", "uid"?, "node"}`` is the pods binding
        subresource: no object crosses in or out. The stored pod, checked
        by ``bind_refusal``, is committed with ``node_name`` set through
        the update body, so the WAL record, the watch event and the
        resourceVersion are what a get + CAS update of the same pod write.
        The event's revision is recorded as a bind of (key, uid, node),
        which the batched watch poll may send in place of the whole pod
        (``events_body_since_bulk``).

        ``guard`` is asked once, under the SAME lock acquisition that
        applies the batch (the lock is reentrant, so it may read the
        store): when it answers False nothing is applied and None comes
        back. That is how the apiserver's bulk verb decides on its one-lock
        pass without a window: whatever the store committed before this
        batch's first write, the guard has seen."""
        out: list[dict] = []
        with self._lock:
            if guard is not None and not guard():
                return None
            for op in ops:
                verb, key = op.get("op"), op.get("key")
                try:
                    if verb == "create":
                        try:
                            rv = self._commit_locked(
                                "create", kind, key, op["object"]
                            )
                        except KeyError as e:
                            raise ConflictError(
                                str(e).strip("'\"")
                            ) from None
                        out.append({"status": 201, "resourceVersion": rv})
                    elif verb == "update":
                        rv = self._update_locked(
                            kind, key, op["object"], op.get("expect_rv")
                        )
                        out.append({"status": 200, "resourceVersion": rv})
                    elif verb == "delete":
                        rv = self._delete_locked(kind, key)
                        out.append({"status": 200, "resourceVersion": rv})
                    elif verb == "bind" and kind == "pods":
                        current, rv = self._core.get(kind, key)
                        refused = bind_refusal(
                            key, current, op.get("uid") or ""
                        )
                        if refused is not None:
                            out.append(refused)
                            continue
                        bound = current.with_node(op["node"])
                        rv = self._update_locked(kind, key, bound, rv)
                        if not _deletes_on_update(bound):
                            self._note_bind_locked(
                                rv, key, current.uid, op["node"]
                            )
                        out.append({"status": 200, "resourceVersion": rv})
                    elif verb == "get":
                        obj, rv = self._core.get(kind, key)
                        if obj is None:
                            out.append({
                                "status": 404, "resourceVersion": 0,
                                "error": f"{kind}/{key} not found",
                            })
                        else:
                            out.append({
                                "status": 200, "resourceVersion": rv,
                                "object": obj,
                            })
                    else:
                        out.append({
                            "status": 400, "resourceVersion": 0,
                            "error": f"unknown bulk op {verb!r}",
                        })
                except ConflictError as e:
                    out.append({
                        "status": 409, "resourceVersion": 0, "error": str(e),
                    })
                except KeyError as e:
                    out.append({
                        "status": 404, "resourceVersion": 0,
                        "error": str(e).strip("'\""),
                    })
            # one fsync for the whole batch (group commit), before any
            # caller sees the results
            self._wal_commit_locked()
            self._lock.notify_all()
        return out

    def _note_bind_locked(self, rv: int, key: str, uid: str,
                          node: str) -> None:
        """Record the event at ``rv`` as a bind of (key, uid, node), after
        dropping the records whose events the ring has compacted: there
        are never more records than events in the ring."""
        compacted = self._core.compacted_through()
        rvs, binds = self._bind_rvs, self._binds
        while rvs and rvs[0] <= compacted:
            del binds[rvs.popleft()]
        rvs.append(rv)
        binds[rv] = [key, uid, node, None, None]

    def _splice_bind_deltas_locked(self, raw: dict, cursors: dict,
                                   codec: str, cid: int) -> None:
        """In the pods bucket of a body drain (``raw``, the core's answer
        under this same lock round), put each bind op's delta body in the
        place of its whole-pod body. The delta is encoded once per codec
        and kept in the record."""
        got = raw.get("pods")
        rvs = self._bind_rvs
        if not got or not rvs or rvs[-1] <= cursors["pods"]:
            return
        from ..api.codec import bind_delta_wire_bytes

        bodies = got[0]
        meta, _cursor = self._core.events_since("pods", cursors["pods"])
        binds = self._binds
        for i, ev in enumerate(meta):
            rec = binds.get(ev[4])
            if rec is None:
                continue
            body = rec[3 + cid]
            if body is None:
                body = rec[3 + cid] = bind_delta_wire_bytes(
                    rec[0], rec[1], rec[2], ev[4], codec
                )
            bodies[i] = body

    def events_since_bulk(
        self, cursors: dict[str, int]
    ) -> tuple[dict, int]:
        """Drain several kinds' watch cursors under ONE lock acquisition
        AND one core call (the server half of the batched watch poll):
        per kind, the same (events, new cursor) a ``_events_since`` would
        return — or a CompactedError value (not raised: one compacted
        kind relists, the others' deliveries still land). The second
        return value is the store's revision AT THE DRAIN, captured under
        the same lock — the long-poll must wait on this, not on a
        revision read afterwards, or a write landing between drain and
        wait stalls for the full timeout."""
        with self._lock:
            raw, drain_rv = self._core.events_since_bulk(cursors)
            compacted = self._core.compacted_through()
        out: dict[str, Any] = {}
        for kind, res in raw.items():
            if res is None:
                out[kind] = CompactedError(
                    f"rv {cursors[kind]} compacted (through {compacted})"
                )
                continue
            events, cursor = res
            out[kind] = (
                [
                    WatchEvent(_EVENT_TYPES[t], k, key, obj, erv)
                    for (t, k, key, obj, erv) in events
                ],
                cursor,
            )
        return out, drain_rv

    # --------------------------------------------- serialize-once bodies
    # The fan-out hot path: pre-encoded event WIRE BODIES straight off the
    # core's per-event body ring — the apiserver's unscoped watch paths
    # splice these into reply envelopes without ever materializing a
    # WatchEvent (kubetpu.api.codec's splice-safe encoding). Bodies are
    # encoded ON MISS under the store lock — once per event per codec,
    # against an encoder that never re-enters the store — so steady-state
    # fan-out is all hits.

    def _check_body_gen_locked(self) -> None:
        """Binary bodies embed schema-table ids derived from the scheme
        registry — a kind registered AFTER bodies were cached shifts the
        ids (and the negotiated fingerprint), so a generation move
        flushes every cached body before the next drain can splice a
        stale encoding into a new-fingerprint reply."""
        from ..api.scheme import registry_generation

        gen = registry_generation()
        if self._body_gen != gen:
            if self._body_gen is not None:
                self._core.clear_event_bodies()
            self._body_gen = gen

    def events_body_since(
        self, kind: str | None, rv: int, codec: str = "json"
    ) -> tuple[list[bytes], int]:
        enc, cid = _wire_encoder(codec)
        with self._lock:
            self._check_body_gen_locked()
            try:
                return self._core.event_bodies_since(kind, rv, cid, enc)
            except LookupError as e:
                raise CompactedError(str(e)) from None

    def events_body_since_bulk(
        self, cursors: dict[str, int], codec: str = "json",
        bind_deltas: bool = False,
    ) -> tuple[dict, int]:
        """Bulk form: ({kind: (bodies, cursor) | CompactedError}, drain
        revision) — the batched watch poll's one-lock-round body drain.
        ``bind_deltas``: each event a pods ``bind`` op committed comes as
        its delta (``codec.bind_delta_wire_bytes``: key, uid, node, no
        object) in place of the whole pod, for a client that holds the
        pods and rebuilds them; every other event is its cached body."""
        enc, cid = _wire_encoder(codec)
        with self._lock:
            self._check_body_gen_locked()
            raw, drain_rv = self._core.event_bodies_since_bulk(
                cursors, cid, enc
            )
            compacted = self._core.compacted_through()
            if bind_deltas:
                self._splice_bind_deltas_locked(raw, cursors, codec, cid)
        out: dict[str, Any] = {}
        for kind, res in raw.items():
            out[kind] = (
                CompactedError(
                    f"rv {cursors[kind]} compacted (through {compacted})"
                )
                if res is None else res
            )
        return out, drain_rv

    def body_cache_stats(self) -> dict:
        """{codec name: (hits, misses)} from the core's body ring."""
        with self._lock:
            stats = self._core.body_cache_stats()
        names = {v: k for k, v in _wire_ids().items()}
        return {names[cid]: tuple(hm) for cid, hm in stats.items()}

    # -------------------------------------------------------------- reads
    def get(self, kind: str, key: str):
        with self._lock:
            return self._core.get(kind, key)

    def count(self, kind: str) -> int:
        """How many live objects of ``kind`` the store holds, O(1): both
        cores keep the number at create / delete / snapshot load (WAL
        recovery and a follower's apply go through the same verbs), so
        "is there any ResourceQuota" costs a lookup where ``list`` walks
        every object of every kind."""
        with self._lock:
            return self._core.count(kind)

    @staticmethod
    def _parse_selectors(label_selector: str, field_selector: str):
        lt: tuple = ()
        ft: tuple = ()
        if label_selector or field_selector:
            from ..api.selectors import parse_simple_selector

            lt = parse_simple_selector(label_selector)
            ft = parse_simple_selector(field_selector)
        return lt, ft

    def _list_page_locked(self, kind: str, lt: tuple, ft: tuple,
                          limit: int, after_seq: int,
                          through_seq: int = 0):
        """THE pagination seam: every full-store list materialization —
        paged or not — walks the core through here (graftcheck LS001 pins
        it: a ``core.list``/``core.list_page`` call anywhere else in the
        apiserver/store modules is an unbounded read the continue-token
        protocol cannot see). Caller holds the store lock. Returns
        ``(items [(key, obj, rv)], store_rv, next_seq, has_more,
        through_seq)``."""
        return self._core.list_page(kind, lt, ft, limit, after_seq,
                                    through_seq)

    def list(
        self, kind: str,
        label_selector: str = "", field_selector: str = "",
    ):
        """GetList: items + the revision the list is consistent at.
        ``label_selector``/``field_selector`` are the reference's list
        options (``k=v,k2!=v2`` strings) applied server-side — an informer
        with a selector never receives the objects it filtered out.
        Selector matching runs INSIDE the core (the native list filter):
        the terms are parsed here (a malformed selector 400s before the
        lock) and evaluated per object in the core's list walk."""
        lt, ft = self._parse_selectors(label_selector, field_selector)
        with self._lock:
            items, rv, _seq, _more, _bound = self._list_page_locked(
                kind, lt, ft, 0, 0
            )
        return [(key, obj) for key, obj, _rv in items], rv

    def list_page(
        self, kind: str,
        label_selector: str = "", field_selector: str = "",
        limit: int = 0, after_seq: int = 0, through_seq: int = 0,
    ):
        """One bounded page of the list walk (the apiserver's
        ``limit``/``continue`` serving path): ``(items [(key, obj, rv)],
        store_rv, next_seq, has_more, through_seq)``. A walk resumed at
        ``next_seq`` with the echoed ``through_seq`` bound neither
        duplicates nor skips an object present across the whole walk AND
        never splices in an object created after the walk's first page
        (the bound is the snapshot cut); per-item rvs feed the
        serialize-once list-item encode cache."""
        lt, ft = self._parse_selectors(label_selector, field_selector)
        with self._lock:
            return self._list_page_locked(kind, lt, ft, limit, after_seq,
                                          through_seq)

    @property
    def resource_version(self) -> int:
        with self._lock:
            return self._core.resource_version()

    @property
    def compacted_through(self) -> int:
        """The event ring's compaction horizon — the continue-token
        expiry watermark: a paged walk pinned to a snapshot rv below this
        can no longer promise a gapless watch-from-snapshot resume, so
        the server 410s the token into a fresh walk."""
        with self._lock:
            return self._core.compacted_through()

    @property
    def list_generation(self) -> int:
        """The seq-continuity domain stamp continue tokens carry. A
        snapshot load (crash recovery, replica bootstrap/resync)
        renumbers seqs densely, so a cursor from before the load is
        meaningless even when its snapshot rv clears the compaction
        horizon — the server 410s a token whose stamp mismatches."""
        with self._lock:
            return self._list_gen

    # -------------------------------------------------------------- watch
    def watch(
        self, kind: str | None, since_rv: int,
        label_selector: str = "", field_selector: str = "",
    ) -> "Watcher":
        """A pull watcher for events AFTER ``since_rv`` (``kind`` None =
        all buckets). Raises CompactedError immediately when the start
        revision predates the buffer (an O(1) watermark check — no event
        materialization; the first poll() fetches them). With selectors,
        non-matching ADDED/MODIFIED events are rewritten to DELETED
        tombstones (the watch cache's selector watchers: an object leaving
        the selection must vanish from the client's cache; one that never
        matched makes the tombstone a no-op)."""
        with self._lock:
            compacted = self._core.compacted_through()
        if since_rv < compacted:
            raise CompactedError(
                f"rv {since_rv} compacted (through {compacted})"
            )
        return Watcher(self, kind, since_rv, label_selector, field_selector)

    def _events_since(
        self, kind: str | None, rv: int
    ) -> tuple[list[WatchEvent], int]:
        """Returns ``(matching events, new cursor)`` — the cursor covers
        every event examined (matching or not), so a kind-filtered watcher
        never re-scans other kinds' events."""
        with self._lock:
            try:
                raw, cursor = self._core.events_since(kind, rv)
            except LookupError as e:
                raise CompactedError(str(e)) from None
        return (
            [
                WatchEvent(_EVENT_TYPES[t], k, key, obj, erv)
                for (t, k, key, obj, erv) in raw
            ],
            cursor,
        )

    def wait_for(self, rv: int, timeout: float | None = None) -> bool:
        """Block until the store moves past ``rv`` (thread form)."""
        with self._lock:
            return self._lock.wait_for(
                lambda: self._core.resource_version() > rv, timeout=timeout
            )

    # -------------------------------------------------------- replication
    # Log-shipping (kubetpu.store.replication): the leader serves ordered
    # (kind, wire body) records straight off the serialize-once body ring;
    # a follower replays them through apply_replicated — the live twin of
    # WAL recovery's rv-gated replay, routed through _commit_locked so the
    # follower's ring/rv continuity is identical to having taken the
    # writes itself.

    @property
    def follower(self) -> bool:
        return self._follower

    def replication_records(
        self, rv: int, codec: str = "binary"
    ) -> tuple[list[tuple[str, bytes]], int]:
        """Ordered ``(kind, event wire body)`` for every event after
        ``rv`` + the new cursor — the leader's ship feed. Bodies come off
        the core's serialize-once ring (shared with watch fan-out: one
        encode serves watchers AND replication); kinds ride the ring
        metadata from the SAME lock round, so the two walks pair 1:1.
        Raises CompactedError when ``rv`` predates the ring — the
        follower must bootstrap from a snapshot instead."""
        enc, cid = _wire_encoder(codec)
        with self._lock:
            self._check_body_gen_locked()
            try:
                meta, cursor = self._core.events_since(None, rv)
                bodies, _ = self._core.event_bodies_since(None, rv, cid, enc)
            except LookupError as e:
                raise CompactedError(str(e)) from None
        return [(m[1], b) for m, b in zip(meta, bodies)], cursor

    def _apply_replicated_locked(self, ev_type: int, kind: str, key: str,
                                 obj: Any, rv: int) -> bool:
        """One shipped record into the core — rv-gated exactly like WAL
        replay (at-or-below: idempotent skip; a gap: loud resync error),
        routed through _commit_locked under the ``_applying`` flag so the
        follower guard stands for every other caller."""
        have = self._core.resource_version()
        if rv <= have:
            return False                     # double ship / re-fetch
        if rv != have + 1:
            raise ReplicationGapError(
                f"shipped record rv {rv} after store rv {have} — "
                "resync from a leader snapshot required"
            )
        self._applying = True
        try:
            if ev_type == 2:
                got = self._commit_locked("delete", kind, key)
            else:
                got = self._commit_locked("update", kind, key, obj, -1)
        finally:
            self._applying = False
        if got != rv:
            raise ReplicationGapError(
                f"replicated {kind}/{key} applied at rv {got}, "
                f"record said {rv}"
            )
        return True

    def apply_replicated(self, ev_type: int, kind: str, key: str,
                         obj: Any, rv: int) -> bool:
        """Apply ONE shipped record (``ev_type`` is the ring id: 0 ADDED /
        1 MODIFIED / 2 DELETED). True when applied, False when rv-gated
        away. Follower-only."""
        with self._lock:
            if not self._follower:
                raise RuntimeError(
                    "apply_replicated on a non-follower store"
                )
            applied = self._apply_replicated_locked(
                ev_type, kind, key, obj, rv
            )
            if applied:
                self._lock.notify_all()
            return applied

    def apply_replicated_batch(self, records) -> int:
        """A shipped batch under ONE lock round (the tail-follow hot
        path: a write storm's batch pays one lock acquisition and one
        notify, like ``bulk`` on the leader). ``records`` yields
        (ev_type, kind, key, obj, rv); returns how many applied."""
        applied = 0
        with self._lock:
            if not self._follower:
                raise RuntimeError(
                    "apply_replicated on a non-follower store"
                )
            for ev_type, kind, key, obj, rv in records:
                if self._apply_replicated_locked(ev_type, kind, key, obj, rv):
                    applied += 1
            if applied:
                self._lock.notify_all()
        return applied

    def load_replica_snapshot(self, items, rv: int) -> None:
        """Bootstrap/resync: reset the replica to a leader snapshot
        (objects + per-object rvs, store revision ``rv``, event ring
        empty with the compaction horizon at ``rv`` — a watcher holding
        an older cursor takes the bounded 410 relist, exactly recovery's
        contract)."""
        with self._lock:
            if not self._follower:
                raise RuntimeError(
                    "load_replica_snapshot on a non-follower store"
                )
            self._core.load_snapshot(list(items), rv)
            self._binds.clear()         # their events left with the ring
            self._bind_rvs.clear()
            # the load renumbered seqs — invalidate every outstanding
            # continue token (they 410 into a fresh walk)
            self._list_gen = int.from_bytes(os.urandom(4), "big") or 1
            self._lock.notify_all()

    def promote(self) -> int:
        """Failover: flip the replica into a writable leader store at its
        replayed position (no recovery replay — the state is already
        live). Returns the revision the new leader starts serving at."""
        with self._lock:
            self._follower = False
            self._lock.notify_all()
            return self._core.resource_version()

    def demote(self) -> None:
        """The inverse of ``promote`` — an election candidate that
        promoted but lost the writer-lease CAS steps back down before
        any local write could land."""
        with self._lock:
            self._follower = True

    # --------------------------------------------------------- durability
    @property
    def persistent(self) -> bool:
        return self._wal is not None

    def dump(self) -> list:
        """Every object as (kind, key, obj, rv), insertion order — the
        recovery tests' parity probe and ``compact``'s snapshot input."""
        with self._lock:
            return self._core.dump()

    def dump_with_rv(self) -> tuple[list, int]:
        """(dump, store revision) from ONE lock round — the consistent
        pair a replication bootstrap snapshot needs (a dump and a
        revision read separately could straddle a write)."""
        with self._lock:
            return self._core.dump(), self._core.resource_version()

    def compact(self) -> "str | None":
        """Force a compaction snapshot now (snapshot at the current rv,
        segment rotation, truncation of superseded files). No-op without
        persistence. Returns the snapshot path."""
        with self._lock:
            if self._wal is None:
                return None
            return self._wal.snapshot(
                self._core.dump(), self._core.resource_version()
            )

    def close(self) -> None:
        """Flush + fsync + close the WAL — the graceful-shutdown path
        (apiserver close, perf-runner finally): a clean stop never leaves
        a torn tail for the next boot's recovery to truncate. A
        persistent store refuses writes after close (they could never be
        logged); a memory-only store is unaffected."""
        with self._lock:
            if self._wal is not None:
                self._wal.close()
                self._wal = None
                self._wal_closed = True
            if self._wal_lock is not None:
                self._wal_lock.release()
                self._wal_lock = None

    def wal_stats(self) -> "dict | None":
        """Append-side counters for metrics and the debug bundle (None when
        off)."""
        import math

        with self._lock:
            wal = self._wal
            if wal is None:
                return None
            p50 = wal.fsync_hist.quantile(0.50)
            p99 = wal.fsync_hist.quantile(0.99)
            return {
                "records_appended": wal.records_appended,
                "bytes_appended": wal.bytes_appended,
                "fsyncs": wal.fsyncs,
                "records_since_snapshot": wal.records_since_snapshot,
                # the p99 group-commit fsync in ms (None before the first
                # fsync).
                # p50 rides along as the sentinel bundle's WAL stat feed —
                # a stall diagnosis needs the baseline next to the tail
                "fsync_p50_ms": (
                    None if math.isnan(p50) else round(p50 * 1000.0, 3)
                ),
                "fsync_p99_ms": (
                    None if math.isnan(p99) else round(p99 * 1000.0, 3)
                ),
            }

    def wal_metrics_text(self) -> str:
        """The durable store's Prometheus text — mounted on the owning
        apiserver's /metrics: the ``store_wal_fsync_duration_seconds``
        histogram plus segment/byte/snapshot-age gauges. Empty without
        persistence (a memory-only scrape stays byte-identical)."""
        import time as _time

        from ..metrics.registry import Registry
        from .wal import list_segments

        with self._lock:
            wal = self._wal
            if wal is None:
                return ""
            hist = wal.fsync_hist
            dirpath = wal.dirpath
            bytes_total = wal.bytes_appended
            snap_age = max(_time.time() - wal.last_snapshot_wall, 0.0)
        # directory I/O and exposition both OUTSIDE the store lock: a 1 s
        # exporter cadence must never park every store write behind an
        # os.listdir (the histogram carries its own lock; dirpath is
        # immutable for the WAL's lifetime)
        try:
            segments = len(list_segments(dirpath))
        except OSError:
            segments = 0        # dir vanished under a concurrent close
        r = Registry()
        r.register(hist)
        r.gauge(
            "store_wal_segments",
            "WAL segment files currently on disk (compaction truncates).",
        ).set(segments)
        r.counter(
            "store_wal_bytes_total",
            "Bytes appended to the write-ahead log since open.",
        ).inc(bytes_total)
        r.gauge(
            "store_snapshot_age_seconds",
            "Seconds since the newest compaction snapshot was written.",
        ).set(round(snap_age, 3))
        return r.expose()


class SelectorView:
    """Stateful selector filter for ONE watch stream (the watch cache's
    per-watcher selector view): matching events pass and mark the key
    delivered; an event LEAVING the selection becomes one DELETED
    tombstone; further events for a key the client provably does not hold
    are dropped outright — so a kubelet watching ``spec.nodeName=<self>``
    pays one tombstone per foreign pod, not one per foreign event.

    An event for an UNKNOWN non-matching key still tombstones once: the
    client's initial (selector-scoped) list may contain objects that left
    the selection before their first watch event, and the view cannot
    distinguish them from never-matched objects."""

    def __init__(self, label_selector: str, field_selector: str) -> None:
        from ..api.selectors import parse_simple_selector

        self._lt = parse_simple_selector(label_selector)
        self._ft = parse_simple_selector(field_selector)
        self._matched: set[str] = set()     # keys delivered as matching
        self._tombstoned: set[str] = set()  # foreign keys already tombstoned

    def filter(self, events: list[WatchEvent]) -> list[WatchEvent]:
        from ..api.selectors import object_matches_selectors

        out: list[WatchEvent] = []
        for e in events:
            if e.type == DELETED:
                if e.key in self._tombstoned:
                    self._tombstoned.discard(e.key)
                    continue               # client never held it
                self._matched.discard(e.key)
                out.append(e)
                continue
            if object_matches_selectors(e.obj, self._lt, self._ft):
                self._matched.add(e.key)
                self._tombstoned.discard(e.key)
                out.append(e)
                continue
            if e.key in self._tombstoned:
                continue                   # repeat foreign event: dropped
            self._matched.discard(e.key)
            self._tombstoned.add(e.key)
            out.append(
                WatchEvent(DELETED, e.kind, e.key, e.obj, e.resource_version)
            )
        return out


class Watcher:
    """One watch stream: ``poll()`` drains events after the cursor."""

    def __init__(
        self, store: MemStore, kind: str | None, since_rv: int,
        label_selector: str = "", field_selector: str = "",
    ) -> None:
        self._store = store
        self._kind = kind
        self._rv = since_rv
        self._view = (
            SelectorView(label_selector, field_selector)
            if (label_selector or field_selector) else None
        )

    @property
    def resource_version(self) -> int:
        return self._rv

    def poll(self) -> list[WatchEvent]:
        """New events since the cursor; raises CompactedError when the
        cursor fell behind the ring buffer (caller relists)."""
        events, self._rv = self._store._events_since(self._kind, self._rv)
        if self._view is not None:
            events = self._view.filter(events)
        return events
