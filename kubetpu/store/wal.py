"""Write-ahead log + compacted snapshots + crash recovery for the store.

The reference's layer 0 is durable by construction (etcd: raft WAL +
boltdb snapshots under ``storage.Interface``); the MemStore was the
control plane's last single point of failure — one apiserver crash lost
the cluster. This module closes that gap with the same shape:

- **WAL**: one checksummed, length-prefixed record per committed write
  (create / update / delete / bind — a bind IS a CAS update), appended
  and flushed BEFORE the core applies it and fsync'd before the store
  acks (group commit: a bulk batch's records share one fsync). The
  record payload is the event wire body the serialize-once seam already
  defines (``kubetpu.api.codec.event_wire_bytes`` — byte-identical to
  what the store's body ring caches for watch fan-out), framed with the
  record's kind; the segment header pins the codec and the schema
  fingerprint so a record can never be mis-decoded by a drifted build.
- **Snapshots + truncation**: ``compact()`` writes the full object map
  (with per-object resourceVersions — CAS survives recovery) at revision
  R to a temp file, atomically renames it in, rotates the active
  segment, and deletes every segment/snapshot the new snapshot
  supersedes. The registry generation is re-checked per append: a kind
  registered after the segment opened rotates the segment (binary
  bodies embed schema-table ids — one segment, one schema).
- **Recovery**: ``recover_into(core, dir)`` loads the newest valid
  snapshot (objects + per-object rvs + store rv, compacted_through = R)
  and replays the WAL tail IN ORDER through the core's own write verbs —
  so the event ring repopulates with the tail and resourceVersion
  continuity holds exactly: a watcher reconnecting with a pre-crash
  cursor >= R takes a bounded relist (just the tail events), only a
  cursor older than the compaction horizon 410s into a full relist.
  Replay is rv-gated (records at-or-below the core's revision are
  skipped), which makes double replay — and the mid-truncate crash's
  leftover segments — idempotent. A torn tail on the ACTIVE segment
  (half-written final record: short frame or checksum mismatch) is
  detected and truncated; corruption anywhere else is a loud WALError,
  never a silent partial store.

Fault points (kubetpu.store.faultpoints) instrument every boundary the
claims above depend on; tests/test_wal.py kills-and-recovers at each.

File layout under the persistence dir::

    wal-<seq 16 hex>.log      segments, replayed in seq order
    snap-<rv 16 hex>.snap     compaction snapshots (newest valid wins)

Wire framing (little-endian):

    segment header:  b"KTWL" | u8 version | u8 codec_id | u8 fp_len |
                     fp bytes (ascii schema fingerprint) | u64 base_rv
    snapshot header: b"KTSN" | u8 version | u8 codec_id | u8 fp_len |
                     fp | u64 store_rv | u32 entry_count
    record frame:    u32 payload_len | u32 crc32(payload) | payload
    WAL payload:     u8 kind_len | kind | event wire body
                     (codec.event_wire_bytes: type/key/object/rv)
    snap payload:    u8 kind_len | kind | u64 object_rv | object body
                     (codec.dumps(obj))
"""

from __future__ import annotations

import os
import struct
import time
import zlib
from dataclasses import dataclass
from typing import Any

from ..api import codec
from ..metrics.registry import Histogram, exponential_buckets
from . import faultpoints

SEGMENT_MAGIC = b"KTWL"
SNAPSHOT_MAGIC = b"KTSN"
FORMAT_VERSION = 1

_u32 = struct.Struct("<I")
_u64 = struct.Struct("<Q")

#: sanity cap on one framed payload (a torn length prefix must never make
#: recovery try to allocate gigabytes)
_MAX_RECORD = 1 << 30

_EV_NAMES = codec.EVENT_TYPE_NAMES           # ("ADDED","MODIFIED","DELETED")
_EV_IDS = {n: i for i, n in enumerate(_EV_NAMES)}


class WALError(Exception):
    """Unrecoverable persistence-dir problem: mid-log corruption, a schema
    the running build cannot decode, an rv gap in the replay chain."""


def _codec_id(name: str) -> int:
    try:
        return codec.WIRE_CODEC_IDS[name]
    except KeyError:
        raise WALError(f"unknown WAL codec {name!r}") from None


def _codec_name(cid: int) -> str:
    for name, i in codec.WIRE_CODEC_IDS.items():
        if i == cid:
            return name
    raise WALError(f"unknown WAL codec id {cid}")


def _frame(payload: bytes) -> bytes:
    return _u32.pack(len(payload)) + _u32.pack(
        zlib.crc32(payload) & 0xFFFFFFFF
    ) + payload


def _segment_path(dirpath: str, seq: int) -> str:
    return os.path.join(dirpath, f"wal-{seq:016x}.log")


def _snapshot_path(dirpath: str, rv: int) -> str:
    return os.path.join(dirpath, f"snap-{rv:016x}.snap")


def list_segments(dirpath: str) -> list[tuple[int, str]]:
    """(seq, path) of every segment, seq order."""
    out = []
    for name in os.listdir(dirpath):
        if name.startswith("wal-") and name.endswith(".log"):
            try:
                seq = int(name[4:-4], 16)
            except ValueError:
                continue
            out.append((seq, os.path.join(dirpath, name)))
    return sorted(out)


def list_snapshots(dirpath: str) -> list[tuple[int, str]]:
    """(rv, path) of every snapshot file, rv order (temp files excluded)."""
    out = []
    for name in os.listdir(dirpath):
        if name.startswith("snap-") and name.endswith(".snap"):
            try:
                rv = int(name[5:-5], 16)
            except ValueError:
                continue
            out.append((rv, os.path.join(dirpath, name)))
    return sorted(out)


def _fsync_dir(dirpath: str) -> None:
    """Make renames/unlinks in ``dirpath`` durable (POSIX: directory
    entries have their own durability)."""
    try:
        fd = os.open(dirpath, os.O_RDONLY)
    except OSError:
        return                              # platform without dir-fsync
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


class DirLock:
    """Single-writer guard on a persistence dir (``flock`` on a lock
    file): a second live opener — a concurrent ``store compact``, a
    second apiserver on the same dir — would rotate the segment chain and
    truncate the live writer's active segment out from under it, silently
    losing every write acked afterwards. The lock dies with the holder's
    file descriptor, so a crashed (or abandoned) store never needs stale-
    lock cleanup; on platforms without ``fcntl`` the guard degrades to
    advisory-nothing rather than blocking the store."""

    def __init__(self, dirpath: str) -> None:
        self.path = os.path.join(dirpath, "wal.lock")
        self._f = open(self.path, "a+")
        try:
            import fcntl
        except ImportError:                 # non-POSIX: no guard
            return
        try:
            fcntl.flock(self._f.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            self._f.close()
            self._f = None
            raise WALError(
                f"{dirpath} is locked by another live process — a second "
                "writer would truncate the live log (stop the apiserver "
                "before compact/recovery)"
            ) from None
        self._f.seek(0)
        self._f.truncate()
        self._f.write(str(os.getpid()))
        self._f.flush()

    def release(self) -> None:
        if self._f is not None:
            self._f.close()                 # closing the fd drops the flock
            self._f = None


@dataclass
class RecoveryInfo:
    """What one recovery did — surfaced by fsck."""

    snapshot_rv: int = 0
    snapshot_objects: int = 0
    replayed: int = 0
    skipped: int = 0            # rv-gated (already covered) records
    segments: int = 0
    pruned_segments: int = 0    # empty (header-only) segments deleted
    truncated_bytes: int = 0    # torn tail removed from the active segment
    truncated_segment: str = ""
    resource_version: int = 0

    def to_json(self) -> dict:
        return {k: v for k, v in self.__dict__.items()}


# --------------------------------------------------------------- the log

class WriteAheadLog:
    """Append side. NOT thread-safe by itself — the owning MemStore calls
    under its store lock (same single-writer contract as the cores)."""

    def __init__(self, dirpath: str, wire: str = codec.BINARY,
                 fsync: bool = True, compact_every: int = 65536,
                 base_rv: int = 0) -> None:
        """``base_rv``: the store revision at open (the owner's recovered
        rv) — stamped into each segment header so a reader can skip whole
        segments without decoding a record."""
        if wire not in codec.WIRE_CODEC_IDS:
            raise WALError(f"wire must be one of "
                           f"{sorted(codec.WIRE_CODEC_IDS)}, got {wire!r}")
        os.makedirs(dirpath, exist_ok=True)
        self.dirpath = dirpath
        self.wire = wire
        self.fsync = fsync
        self.compact_every = compact_every
        self._encoder = codec.event_body_encoder(wire)
        self._f = None
        self._seq = 0
        self._seg_fp: str | None = None     # fingerprint the segment pinned
        self._dirty = False                 # appended-but-not-fsynced bytes
        self._last_rv = base_rv             # highest rv this log has seen
        # counters for /metrics and MemStore.wal_stats
        self.records_appended = 0
        self.bytes_appended = 0
        self.fsyncs = 0
        self.records_since_snapshot = 0
        # store_wal_fsync_duration_seconds: the durability tax per group
        # commit (10 µs … ~1.3 s — a battery-backed controller acks in
        # tens of µs, a contended spindle can take hundreds of ms); the
        # apiserver mounts it on /metrics and wal_stats reports its p99
        self.fsync_hist = Histogram(
            "store_wal_fsync_duration_seconds",
            "WAL group-commit fsync latency in seconds.",
            buckets=exponential_buckets(0.00001, 2, 18),
        )
        # store_snapshot_age_seconds anchor: the newest on-disk snapshot's
        # mtime (a dir that has never compacted ages from open time)
        snaps = list_snapshots(dirpath)
        self.last_snapshot_wall = (
            os.path.getmtime(snaps[-1][1]) if snaps else time.time()
        )
        self._open_segment()

    # ------------------------------------------------------------ segments
    def _next_seq(self) -> int:
        segs = list_segments(self.dirpath)
        return (segs[-1][0] + 1) if segs else 1

    def _open_segment(self) -> None:
        """Start a FRESH segment (boot and rotation both do — appending to
        a recovered segment would re-open the torn-tail question the
        recovery just settled)."""
        if self._f is not None:
            self._close_file()
        self._seq = self._next_seq()
        self._seg_fp = (
            codec.schema_fingerprint() if self.wire == codec.BINARY else ""
        )
        fp = self._seg_fp.encode()
        path = _segment_path(self.dirpath, self._seq)
        self._f = open(path, "xb")
        self._f.write(
            SEGMENT_MAGIC + bytes((FORMAT_VERSION, _codec_id(self.wire),
                                   len(fp))) + fp
            + _u64.pack(self._last_rv)
        )
        self._f.flush()
        self._sync_file()
        _fsync_dir(self.dirpath)

    def _close_file(self) -> None:
        try:
            self._f.flush()
            self._sync_file()
        finally:
            self._f.close()
            self._f = None

    def _sync_file(self) -> None:
        if self.fsync and self._f is not None:
            t0 = time.perf_counter()
            os.fsync(self._f.fileno())
            self.fsync_hist.observe(time.perf_counter() - t0)
            self.fsyncs += 1
        self._dirty = False

    def _check_generation(self) -> None:
        """Binary bodies embed schema-table ids; a kind registered after
        this segment opened would make its later records undecodable under
        the header's fingerprint — one segment, one schema, so rotate."""
        if self.wire != codec.BINARY:
            return
        if codec.schema_fingerprint() != self._seg_fp:
            self._open_segment()

    # ------------------------------------------------------------- append
    def append(self, ev_type: int, kind: str, key: str, obj: Any,
               rv: int) -> None:
        """Frame + write + flush ONE committed write's record (to the OS;
        durability lands at the next ``commit``). ``ev_type`` is the ring
        id (0 ADDED / 1 MODIFIED / 2 DELETED); ``rv`` is the revision the
        core WILL assign — the caller appends before applying
        (write-ahead), so a post-append crash replays the write whose ack
        was lost."""
        self._check_generation()
        faultpoints.fire("wal-pre-append")
        body = self._encoder(ev_type, key, obj, rv)
        kind_b = kind.encode()
        if len(kind_b) > 255:
            raise WALError(f"kind too long for the WAL frame: {kind!r}")
        rec = _frame(bytes((len(kind_b),)) + kind_b + body)
        if faultpoints.due("wal-mid-record"):
            # the torn write: half the frame reaches the OS, then death
            self._f.write(rec[: max(1, len(rec) // 2)])
            self._f.flush()
            faultpoints.crash("wal-mid-record")
        self._f.write(rec)
        self._f.flush()
        self._dirty = True
        self._last_rv = rv
        self.records_appended += 1
        self.records_since_snapshot += 1
        self.bytes_appended += len(rec)

    def commit(self) -> None:
        """Group commit: fsync everything appended since the last commit —
        the store calls this once per lock round (one write = one fsync, a
        bulk batch = one fsync for the batch), BEFORE any caller is
        acked. A round that appended nothing (read-only bulk, all-conflict
        batch) costs nothing."""
        if self._dirty:
            self._sync_file()

    @property
    def wants_compaction(self) -> bool:
        return self.records_since_snapshot >= self.compact_every

    # ----------------------------------------------------------- snapshot
    def snapshot(self, items: "list[tuple[str, str, Any, int]]",
                 rv: int) -> str:
        """Write a compaction snapshot of the full object map at revision
        ``rv`` (atomic: temp + rename), rotate the active segment, then
        delete every superseded segment and snapshot. ``items`` is the
        core's dump — (kind, key, obj, object_rv) in insertion order."""
        self._check_generation()
        path = _snapshot_path(self.dirpath, rv)
        tmp = f"{path}.tmp.{os.getpid()}"
        fp = (
            codec.schema_fingerprint() if self.wire == codec.BINARY else ""
        ).encode()
        half = len(items) // 2
        with open(tmp, "wb") as f:
            f.write(
                SNAPSHOT_MAGIC + bytes((FORMAT_VERSION,
                                        _codec_id(self.wire), len(fp))) + fp
                + _u64.pack(rv) + _u32.pack(len(items))
            )
            for i, (kind, key, obj, obj_rv) in enumerate(items):
                if i == half and faultpoints.due("wal-mid-snapshot"):
                    f.flush()   # the half-written temp file is the debris
                    faultpoints.crash("wal-mid-snapshot")
                kind_b = kind.encode()
                body = self._encoder(0, key, obj, obj_rv)
                f.write(_frame(
                    bytes((len(kind_b),)) + kind_b + _u64.pack(obj_rv)
                    + body
                ))
            f.flush()
            if self.fsync:
                os.fsync(f.fileno())
        os.replace(tmp, path)
        _fsync_dir(self.dirpath)
        self.last_snapshot_wall = time.time()
        # the snapshot is durable: everything at-or-below rv is redundant
        self._last_rv = max(self._last_rv, rv)
        self._open_segment()
        self.records_since_snapshot = 0
        self._truncate_through(rv, keep_snapshot=path)
        return path

    def _truncate_through(self, rv: int, keep_snapshot: str) -> None:
        """Delete segments older than the active one and snapshots older
        than ``keep_snapshot``. A crash midway (fault point) leaves extra
        files recovery skips idempotently — never a hole."""
        doomed = [
            p for seq, p in list_segments(self.dirpath) if seq < self._seq
        ] + [
            p for srv, p in list_snapshots(self.dirpath)
            if p != keep_snapshot and srv <= rv
        ]
        half = len(doomed) // 2
        for i, p in enumerate(doomed):
            if i == half and faultpoints.due("wal-mid-truncate"):
                faultpoints.crash("wal-mid-truncate")
            try:
                os.unlink(p)
            except OSError:
                pass
        _fsync_dir(self.dirpath)

    def close(self) -> None:
        """Flush + fsync + close — the graceful-shutdown path: a clean
        stop NEVER leaves a torn tail for recovery to truncate."""
        if self._f is not None:
            self._close_file()


# ------------------------------------------------------------- read side

def _read_exact(f, n: int) -> bytes:
    data = f.read(n)
    return data if data is not None else b""


def _read_header(f, magic: bytes, path: str):
    """→ (codec_name, fingerprint). Raises WALError on a file too
    short/foreign to even carry a header."""
    head = _read_exact(f, len(magic) + 3)
    if len(head) < len(magic) + 3 or head[: len(magic)] != magic:
        raise WALError(f"{path}: bad or missing header magic")
    version, cid, fp_len = head[len(magic):]
    if version != FORMAT_VERSION:
        raise WALError(f"{path}: format version {version} unsupported")
    fp = _read_exact(f, fp_len).decode("ascii", errors="replace")
    return _codec_name(cid), fp


def _check_fingerprint(wire: str, fp: str, path: str) -> None:
    if wire == codec.BINARY and fp != codec.schema_fingerprint():
        raise WALError(
            f"{path}: binary schema fingerprint {fp!r} != this build's "
            f"{codec.schema_fingerprint()!r} — the log cannot be decoded "
            "by a drifted registry (recover with the writing build, or "
            "discard the persistence dir and full-resync)"
        )


def _iter_frames(f, path: str):
    """Yield (offset, payload) for each well-formed frame; stop at EOF.
    A torn frame (short prefix/payload or crc mismatch) yields a final
    ("torn", offset) marker instead of raising — the caller decides
    whether that position is a truncatable tail."""
    while True:
        offset = f.tell()
        head = _read_exact(f, 8)
        if not head:
            return
        if len(head) < 8:
            yield ("torn", offset)
            return
        (length,) = _u32.unpack(head[:4])
        (crc,) = _u32.unpack(head[4:])
        # length 0 is the zero-fill crash artifact (file size extended,
        # data blocks never written): crc32(b"") == 0, so an all-NUL tail
        # would otherwise parse as an endless run of "valid" empty frames
        # — no real record is ever empty (the payload carries at least
        # the kind-length byte), so treat it as torn
        if length == 0 or length > _MAX_RECORD:
            yield ("torn", offset)
            return
        payload = _read_exact(f, length)
        if len(payload) < length or (
            zlib.crc32(payload) & 0xFFFFFFFF
        ) != crc:
            yield ("torn", offset)
            return
        yield (offset, payload)


def _decode_wal_payload(payload: bytes, wire: str, path: str):
    """→ (ev_type_id, kind, key, obj, rv)."""
    try:
        kind_len = payload[0]
        kind = payload[1: 1 + kind_len].decode()
        body = payload[1 + kind_len:]
        msg = codec.loads(body, wire)
    except (codec.UnsupportedWireError, IndexError,
            UnicodeDecodeError) as e:
        raise WALError(f"{path}: undecodable record body: {e}") from None
    ev = _EV_IDS.get(msg.get("type"))
    if ev is None:
        raise WALError(f"{path}: record carries no event type")
    return ev, kind, msg["key"], codec.as_object(msg.get("object")), \
        msg["resourceVersion"]


def _read_snapshot_stream(f, path: str):
    """The snapshot format's ONE reader (file or shipped bytes): header +
    entry frames → (rv, [(kind, key, obj, obj_rv), …]). Raises WALError
    on anything short, torn, undecodable, or count-mismatched."""
    wire, fp = _read_header(f, SNAPSHOT_MAGIC, path)
    _check_fingerprint(wire, fp, path)
    tail = _read_exact(f, 12)
    if len(tail) < 12:
        raise WALError(f"{path}: truncated snapshot header")
    (rv,) = _u64.unpack(tail[:8])
    (count,) = _u32.unpack(tail[8:])
    items = []
    for entry in _iter_frames(f, path):
        if entry[0] == "torn":
            raise WALError(f"{path}: torn snapshot entry")
        _off, payload = entry
        kind_len = payload[0]
        kind = payload[1: 1 + kind_len].decode()
        (obj_rv,) = _u64.unpack(payload[1 + kind_len: 9 + kind_len])
        body = payload[9 + kind_len:]
        try:
            msg = codec.loads(body, wire)
        except codec.UnsupportedWireError as e:
            raise WALError(f"{path}: undecodable snapshot entry: {e}") \
                from None
        items.append((kind, msg["key"],
                      codec.as_object(msg.get("object")), obj_rv))
    if len(items) != count:
        raise WALError(
            f"{path}: snapshot carries {len(items)} entries, "
            f"header promised {count}"
        )
    return rv, items


def load_snapshot_items(path: str):
    """→ (rv, [(kind, key, obj, obj_rv), …]) or raises WALError."""
    with open(path, "rb") as f:
        return _read_snapshot_stream(f, path)


# ------------------------------------------------- replication streaming
# The log-shipping wire (kubetpu.store.replication) IS the WAL format:
# shipped records are the exact frames `append` writes, the bootstrap
# snapshot is the exact byte layout `snapshot` writes — one copy of the
# format rules, so a drifted build refuses a ship the same way it refuses
# a foreign persistence dir (the fingerprint check above).

def frame_record(kind: str, body: bytes) -> bytes:
    """Frame ONE record from a kind + an event wire body
    (``codec.event_wire_bytes`` — what the store's body ring caches) —
    byte-identical to what ``WriteAheadLog.append`` writes."""
    kind_b = kind.encode()
    if len(kind_b) > 255:
        raise WALError(f"kind too long for the WAL frame: {kind!r}")
    return _frame(bytes((len(kind_b),)) + kind_b + body)


def iter_log_stream(data: bytes, wire: str,
                    source: str = "<replication>"):
    """Decode a shipped run of record frames (a /replication/log body):
    yields (ev_type_id, kind, key, obj, rv) in order. A torn frame is a
    loud WALError — HTTP delivers the body whole or not at all, so unlike
    a crashed segment there is no truncatable-tail policy here."""
    import io

    for entry in _iter_frames(io.BytesIO(data), source):
        if entry[0] == "torn":
            raise WALError(f"{source}: torn replication frame")
        yield _decode_wal_payload(entry[1], wire, source)


def encode_snapshot_stream(items, rv: int, wire: str = codec.BINARY) -> bytes:
    """A full object map in the WAL snapshot format, as bytes — the
    leader's /replication/snapshot body (follower bootstrap). ``items``
    is a core dump: (kind, key, obj, obj_rv) in insertion order."""
    import io

    encoder = codec.event_body_encoder(wire)
    fp = (
        codec.schema_fingerprint() if wire == codec.BINARY else ""
    ).encode()
    f = io.BytesIO()
    f.write(
        SNAPSHOT_MAGIC + bytes((FORMAT_VERSION, _codec_id(wire), len(fp)))
        + fp + _u64.pack(rv) + _u32.pack(len(items))
    )
    for kind, key, obj, obj_rv in items:
        kind_b = kind.encode()
        body = encoder(0, key, obj, obj_rv)
        f.write(_frame(
            bytes((len(kind_b),)) + kind_b + _u64.pack(obj_rv) + body
        ))
    return f.getvalue()


def decode_snapshot_stream(data: bytes,
                           source: str = "<replication>"):
    """→ (rv, items) from an ``encode_snapshot_stream`` body — the same
    walk (and the same fingerprint refusal) as ``load_snapshot_items``."""
    import io

    return _read_snapshot_stream(io.BytesIO(data), source)


def iter_segment(path: str):
    """ONE copy of the segment format rules, consumed by both recovery
    and fsck (their policies differ — apply vs report — but the walk must
    never drift). Yields, in order: ``("base", base_rv)`` once, then per
    frame either ``("record", (offset, ev_type, kind, key, obj, rv))`` or
    a final ``("torn", offset)``. Header, fingerprint, and crc-valid-but-
    undecodable problems raise WALError."""
    with open(path, "rb") as f:
        wire, fp = _read_header(f, SEGMENT_MAGIC, path)
        _check_fingerprint(wire, fp, path)
        base = _read_exact(f, 8)
        if len(base) < 8:
            raise WALError(f"{path}: truncated segment header")
        yield ("base", _u64.unpack(base)[0])
        for entry in _iter_frames(f, path):
            if entry[0] == "torn":
                yield ("torn", entry[1])
                return
            offset, payload = entry
            yield (
                "record",
                (offset, *_decode_wal_payload(payload, wire, path)),
            )


def _latest_valid_snapshot(dirpath: str):
    """Newest snapshot that loads cleanly (an older valid one shadows a
    newer corrupt one — a mid-snapshot crash before the atomic rename can
    only leave temp debris, but belt-and-braces). Returns (rv, items,
    path) or (0, [], ""); with NO usable snapshot the replay chain's
    rv-gap check decides loudly whether the segments alone suffice."""
    for rv, path in reversed(list_snapshots(dirpath)):
        try:
            srv, items = load_snapshot_items(path)
            return srv, items, path
        except WALError:
            continue
    return 0, [], ""


def recover_into(core, dirpath: str,
                 truncate_torn_tail: bool = True) -> RecoveryInfo:
    """Rebuild ``core`` (a store core — native or the Python twin, the
    same micro-interface) from the persistence dir: newest valid snapshot
    loaded wholesale (objects + per-object rvs, store rv, compaction
    horizon), then every WAL segment replayed in order through the core's
    own write verbs so the event ring and resourceVersion continuity come
    back exactly. Torn tail on the final segment is truncated (the
    crash's half-record); corruption elsewhere raises WALError."""
    info = RecoveryInfo()
    if not os.path.isdir(dirpath):
        return info
    # sweep mid-snapshot crash debris: half-written temp files were never
    # renamed in (the atomic-rename protocol), so they are dead weight —
    # one full-object-map-sized orphan per crash otherwise accretes
    for name in os.listdir(dirpath):
        if ".tmp." in name:
            try:
                os.unlink(os.path.join(dirpath, name))
            except OSError:
                pass
    snap_rv, items, _snap_path = _latest_valid_snapshot(dirpath)
    if snap_rv:
        core.load_snapshot(items, snap_rv)
        info.snapshot_rv = snap_rv
        info.snapshot_objects = len(items)
    segments = list_segments(dirpath)
    info.segments = len(segments)
    empty: list[str] = []
    for idx, (_seq, path) in enumerate(segments):
        last = idx == len(segments) - 1
        records_here = 0
        for tag, payload in iter_segment(path):
            if tag == "base":
                continue
            if tag == "torn":
                offset = payload
                if not (last and truncate_torn_tail):
                    raise WALError(
                        f"{path}: torn record at offset {offset} in a "
                        "non-final segment — mid-log corruption"
                    )
                size = os.path.getsize(path)
                with open(path, "r+b") as tf:
                    tf.truncate(offset)
                _fsync_dir(dirpath)
                info.truncated_bytes = size - offset
                info.truncated_segment = os.path.basename(path)
                break
            _off, ev, kind, key, obj, rv = payload
            records_here += 1
            have = core.resource_version()
            if rv <= have:
                info.skipped += 1           # double replay / leftover seg
                continue
            if rv != have + 1:
                raise WALError(
                    f"{path}: replay gap — record rv {rv} after store "
                    f"rv {have} (a segment is missing)"
                )
            if ev == 2:
                got = core.delete(kind, key)
            else:
                got = core.update(kind, key, obj, -1)
            if got != rv:
                raise WALError(
                    f"{path}: replay applied {kind}/{key} at rv {got}, "
                    f"record said {rv}"
                )
            info.replayed += 1
        if records_here == 0:
            empty.append(path)
    # prune header-only segments: every boot rotates to a fresh segment,
    # so a restart loop would otherwise accrete one empty file per boot
    # forever (they carry nothing — deleting them cannot touch the chain;
    # segments with rv-covered records stay until a compaction folds them)
    for path in empty:
        try:
            os.unlink(path)
            info.pruned_segments += 1
        except OSError:
            pass
    if empty:
        _fsync_dir(dirpath)
    info.resource_version = core.resource_version()
    return info


# ------------------------------------------------------------------ fsck

def fsck(dirpath: str) -> dict:
    """Offline integrity report for a persistence dir — what recovery
    WOULD do, without mutating anything (except nothing): per-snapshot
    validity, per-segment record counts, torn-tail position, replay-chain
    continuity. ``ok`` is False on anything recovery would refuse."""
    report: dict[str, Any] = {
        "dir": dirpath, "ok": True, "snapshots": [], "segments": [],
        "errors": [],
    }
    if not os.path.isdir(dirpath):
        report["ok"] = False
        report["errors"].append("not a directory")
        return report
    best_rv = 0
    for rv, path in list_snapshots(dirpath):
        entry = {"file": os.path.basename(path), "rv": rv}
        try:
            srv, items = load_snapshot_items(path)
            entry.update(valid=True, objects=len(items))
            best_rv = max(best_rv, srv)
        except WALError as e:
            entry.update(valid=False, error=str(e))
            report["ok"] = False
        report["snapshots"].append(entry)
    segments = list_segments(dirpath)
    chain_rv = best_rv
    for idx, (seq, path) in enumerate(segments):
        last = idx == len(segments) - 1
        entry: dict[str, Any] = {
            "file": os.path.basename(path), "seq": seq, "records": 0,
        }
        try:
            # same walk as recovery (iter_segment — one copy of the
            # format rules), report-don't-apply policy
            for tag, payload in iter_segment(path):
                if tag == "base":
                    entry["base_rv"] = payload
                    continue
                if tag == "torn":
                    entry["torn_at"] = payload
                    if not last:
                        report["ok"] = False
                        report["errors"].append(
                            f"{os.path.basename(path)}: torn record in "
                            "a non-final segment"
                        )
                    break
                _off, _ev, _kind, _key, _obj, rv = payload
                entry["records"] += 1
                if rv <= chain_rv:
                    continue
                if rv != chain_rv + 1:
                    report["ok"] = False
                    report["errors"].append(
                        f"{os.path.basename(path)}: replay gap "
                        f"({chain_rv} -> {rv})"
                    )
                chain_rv = rv
        except WALError as e:
            entry["error"] = str(e)
            report["ok"] = False
            report["errors"].append(str(e))
        report["segments"].append(entry)
    report["resource_version"] = chain_rv
    return report
