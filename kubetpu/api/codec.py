"""Wire codec seam — one place that turns API objects into bytes.

Two codecs behind one surface, negotiated per request via content type
(the reference's NegotiatedSerializer, apimachinery runtime/serializer):

- ``json`` — the original kind-tagged JSON (``kubetpu.api.scheme``), the
  compatibility + debugging format; and
- ``binary`` — a compact msgpack/CBOR-style binary format ("ktpb"),
  self-describing at the value level (every value carries a type tag) and
  SPARSE at the object level: a registered dataclass is written as a kind
  id plus only its non-default fields, referenced through a schema table
  both sides derive deterministically from the scheme registry. The
  schema's fingerprint rides the negotiated content type
  (``application/x-kubetpu-bin; v=1; schema=<fp>``) so a client and
  server built from different registries can NEVER mis-decode each other:
  the mismatch 415s and the client falls back to JSON (remote.py).

Why sparse matters: the JSON encoding spells every field of every object
— a bench pod is ~30 fields of defaults around ~7 real values — so the
binary form cuts both wire bytes (the ≥60% reduction the fullstack
ladder measures) and encode/decode work (only present fields are walked,
no intermediate dict tree is ever built: encode packs straight off the
dataclass, decode constructs the dataclass straight from the buffer).

Splice-safe by construction: every encoded value is self-contained (no
cross-value state like string interning), so the serialize-once caches —
the apiserver's EventEncodeCache and the native store's per-event body
ring — can concatenate cached event bodies into reply envelopes with the
header helpers here (``events_envelope``/``buckets_envelope``) without
re-encoding a single event.

Value tags (all little-endian):

    0x00-0x7f  posfixint            0xa7/a8/a9  str8/16/32 (len + utf-8)
    0x80-0x9f  fixstr (len 0-31)    0xaa/ab     list8/32 (count + items)
    0xa0/a1/a2 None/False/True      0xac/ad     map8/32 (count + k,v …)
    0xa3/a5/a4 int16/int32/int64    0xae        object (see below)
    0xa6       float64              0xaf        bigint (|i64| overflow)
    0xe0-0xff  negfixint (-32..-1)

    object: 0xae, kind_id u8, n_present u8, then n × (field_id u16 LE,
    value). kind_id indexes the sorted kind-name table; field_id indexes
    the global sorted field-name table — both derived from the scheme
    registry and pinned by the negotiated schema fingerprint.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import struct
from typing import Any, Callable

from . import scheme

JSON = "json"
BINARY = "binary"

#: negotiated wire format version (part of the content type AND the
#: schema fingerprint — bump on any tag-layout change)
WIRE_VERSION = 1

CT_JSON = "application/json"
CT_BINARY = "application/x-kubetpu-bin"
#: the streaming-watch frame form of the binary codec (u32-length-prefixed
#: frames instead of ndjson lines)
CT_BINARY_STREAM = "application/x-kubetpu-bin-seq"
CT_NDJSON = "application/x-ndjson"


class UnsupportedWireError(ValueError):
    """The peer speaks a binary dialect we do not (missing/mismatched
    schema fingerprint, undecodable body) — the HTTP 415 of the
    negotiation, consumed by the client's fall-back-to-JSON path."""


# --------------------------------------------------------------- schema

class _KindPlan:
    """Per-kind encode/decode plan: ordered fields with their global
    name ids and defaults (MISSING = required, always encoded); for the
    decode, each field id's name and compiled coercer (the scheme's own
    closures: one set of strict rules for both codecs) and the kind's
    defaulting hook."""

    __slots__ = ("kind_id", "kind", "cls", "fields", "by_fid", "defaulter")

    def __init__(self, kind_id: int, kind: str, cls: type,
                 name_ids: dict[str, int]) -> None:
        self.kind_id = kind_id
        self.kind = kind
        self.cls = cls
        coercers = scheme.field_coercers(cls)
        self.defaulter = scheme.defaulter_for(cls)
        self.fields: list[tuple[int, str, Any]] = []
        self.by_fid: dict[int, tuple[str, Callable[[Any], Any]]] = {}
        for f in dataclasses.fields(cls):
            if f.default is not dataclasses.MISSING:
                default = f.default
            elif f.default_factory is not dataclasses.MISSING:  # type: ignore[misc]
                default = f.default_factory()  # type: ignore[misc]
            else:
                default = dataclasses.MISSING
            fid = name_ids[f.name]
            self.fields.append((fid, f.name, default))
            self.by_fid[fid] = (f.name, coercers[f.name])


class _Tables:
    """The negotiated schema: kind table, field-name table, per-kind
    plans, and the fingerprint that pins all of it."""

    def __init__(self) -> None:
        kinds = scheme.kind_registry()
        self.kind_names: list[str] = sorted(kinds)
        if len(self.kind_names) > 255:
            raise scheme.SchemeError("binary codec: >255 registered kinds")
        names: set[str] = set()
        for kind in self.kind_names:
            for f in dataclasses.fields(kinds[kind]):
                names.add(f.name)
        self.field_names: list[str] = sorted(names)
        self.name_ids: dict[str, int] = {
            n: i for i, n in enumerate(self.field_names)
        }
        self.plans_by_kind: dict[str, _KindPlan] = {}
        self.plans_by_cls: dict[type, _KindPlan] = {}
        self.plans_by_id: list[_KindPlan] = []
        for kid, kind in enumerate(self.kind_names):
            plan = _KindPlan(kid, kind, kinds[kind], self.name_ids)
            self.plans_by_kind[kind] = plan
            self.plans_by_cls[kinds[kind]] = plan
            self.plans_by_id.append(plan)
        # the fingerprint covers everything decode depends on: the wire
        # version, the kind table, and each kind's (field, default) set —
        # a default change alters what an ABSENT field decodes to, so it
        # is a schema change. MISSING (required field) gets a FIXED token:
        # repr(MISSING) embeds a memory address, which would make the
        # fingerprint process-specific — two identical builds could never
        # negotiate binary across a process boundary, and a WAL written
        # by one process would refuse to decode in any other
        spec = [WIRE_VERSION, self.field_names]
        for kind in self.kind_names:
            plan = self.plans_by_kind[kind]
            spec.append([
                kind,
                [
                    (name, "<required>" if default is dataclasses.MISSING
                     else repr(default))
                    for _fid, name, default in plan.fields
                ],
            ])
        self.fingerprint = hashlib.sha1(
            repr(spec).encode()
        ).hexdigest()[:12]


_TABLES: _Tables | None = None
_TABLES_GEN = -1


def tables() -> _Tables:
    """The schema tables for the CURRENT scheme registry (rebuilt when a
    kind registration lands after import)."""
    global _TABLES, _TABLES_GEN
    gen = scheme.registry_generation()
    if _TABLES is None or _TABLES_GEN != gen:
        _TABLES = _Tables()
        _TABLES_GEN = gen
    return _TABLES


def schema_fingerprint() -> str:
    return tables().fingerprint


def binary_content_type() -> str:
    return f"{CT_BINARY}; v={WIRE_VERSION}; schema={schema_fingerprint()}"


def binary_stream_content_type() -> str:
    return f"{CT_BINARY_STREAM}; v={WIRE_VERSION}; schema={schema_fingerprint()}"


#: the W3C trace-context header (JSON wire) and its binary-envelope twin:
#: on the binary content type the traceparent rides as a media-type
#: parameter (``tp=00-…``) next to the schema fingerprint — one envelope,
#: negotiated and parsed by the same seam, so a 415/JSON fallback simply
#: moves the SAME value back to the header. Both are ABSENT when telemetry
#: is off (byte-identical wire).
TRACEPARENT_HEADER = "traceparent"
TRACEPARENT_PARAM = "tp"


def content_type_for(codec: str, traceparent: str | None = None) -> str:
    """The request/reply Content-Type for ``codec``. ``traceparent``
    attaches the trace context to a BINARY envelope (the ``tp`` media-type
    parameter); the JSON wire carries it in the ``traceparent`` header
    instead (see ``traceparent_from_headers``)."""
    if codec == BINARY:
        ct = binary_content_type()
        if traceparent:
            ct += f"; {TRACEPARENT_PARAM}={traceparent}"
        return ct
    return CT_JSON


def traceparent_from_headers(headers) -> str | None:
    """Extract a propagated traceparent from one request's headers,
    whichever envelope carried it: the binary Content-Type's ``tp``
    parameter wins (the binary envelope field), else the W3C
    ``traceparent`` header (the JSON wire). Returns the RAW value —
    validation (malformed → ignored, never fatal) is the parser's job
    (kubetpu.telemetry.context.parse_traceparent)."""
    _media, params = parse_content_type(headers.get("Content-Type"))
    tp = params.get(TRACEPARENT_PARAM)
    if tp:
        return tp
    return headers.get(TRACEPARENT_HEADER)


def parse_content_type(value: str | None) -> tuple[str, dict[str, str]]:
    """``type/subtype; k=v; …`` → (media type, params). Tolerant: an
    absent header reads as JSON (the pre-binary wire)."""
    if not value:
        return CT_JSON, {}
    parts = [p.strip() for p in value.split(";")]
    params: dict[str, str] = {}
    for p in parts[1:]:
        k, sep, v = p.partition("=")
        if sep:
            params[k.strip().lower()] = v.strip().strip('"')
    return parts[0].lower(), params


def codec_for_content_type(value: str | None) -> str:
    """The codec a BODY with this content type is encoded in. Raises
    UnsupportedWireError for a binary type whose schema fingerprint does
    not match ours (the 415 path — decoding would be garbage)."""
    media, params = parse_content_type(value)
    if media in (CT_BINARY, CT_BINARY_STREAM):
        if params.get("schema") != schema_fingerprint():
            raise UnsupportedWireError(
                f"binary schema {params.get('schema')!r} != local "
                f"{schema_fingerprint()!r} (negotiate JSON)"
            )
        return BINARY
    return JSON


def accepts_binary(accept_header: str | None) -> bool:
    """True when the Accept header names OUR binary dialect (media type
    + matching schema fingerprint). Anything else — absent header, JSON,
    a foreign fingerprint — negotiates JSON: replying a dialect the
    client cannot decode is never an option, so mismatch degrades
    instead of erroring."""
    if not accept_header or CT_BINARY not in accept_header:
        return False
    for part in accept_header.split(","):
        media, params = parse_content_type(part)
        if (
            media in (CT_BINARY, CT_BINARY_STREAM)
            and params.get("schema") == schema_fingerprint()
        ):
            return True
    return False


# --------------------------------------------------------------- encode

_pack_h = struct.Struct("<h").pack
_pack_i = struct.Struct("<i").pack
_pack_q = struct.Struct("<q").pack
_pack_d = struct.Struct("<d").pack
_pack_H = struct.Struct("<H").pack
_unpack_h = struct.Struct("<h").unpack_from
_unpack_i = struct.Struct("<i").unpack_from
_unpack_q = struct.Struct("<q").unpack_from
_unpack_d = struct.Struct("<d").unpack_from
_unpack_H = struct.Struct("<H").unpack_from

_I16 = 1 << 15
_I32 = 1 << 31
_I64 = 1 << 63


def _pack_int(out: bytearray, v: int) -> None:
    if 0 <= v < 0x80:
        out.append(v)
    elif -32 <= v < 0:
        out.append(0x100 + v)
    elif -_I16 <= v < _I16:
        out.append(0xA3)
        out += _pack_h(v)
    elif -_I32 <= v < _I32:
        out.append(0xA5)
        out += _pack_i(v)
    elif -_I64 <= v < _I64:
        out.append(0xA4)
        out += _pack_q(v)
    else:
        raw = repr(v).encode()
        if len(raw) > 255:
            raise scheme.SchemeError("int too large for the wire")
        out.append(0xAF)
        out.append(len(raw))
        out += raw


def _pack_str(out: bytearray, v: str) -> None:
    raw = v.encode()
    n = len(raw)
    if n < 32:
        out.append(0x80 | n)
    elif n < 256:
        out.append(0xA7)
        out.append(n)
    elif n < 65536:
        out.append(0xA8)
        out += _pack_H(n)
    else:
        out.append(0xA9)
        out += _pack_i(n)
    out += raw


def list_header(n: int) -> bytes:
    """The envelope splicers build lists around pre-encoded bodies."""
    if n < 256:
        return bytes((0xAA, n))
    return bytes((0xAB,)) + _pack_i(n)


def map_header(n: int) -> bytes:
    if n < 256:
        return bytes((0xAC, n))
    return bytes((0xAD,)) + _pack_i(n)


def _pack(out: bytearray, v: Any, t: _Tables) -> None:
    if v is None:
        out.append(0xA0)
    elif v is True:
        out.append(0xA2)
    elif v is False:
        out.append(0xA1)
    elif isinstance(v, str):        # str-enums land here (their value)
        _pack_str(out, v)
    elif isinstance(v, int):
        _pack_int(out, v)
    elif isinstance(v, float):
        out.append(0xA6)
        out += _pack_d(v)
    elif isinstance(v, (list, tuple)):
        out += list_header(len(v))
        for x in v:
            _pack(out, x, t)
    elif isinstance(v, dict):
        out += map_header(len(v))
        for k, x in v.items():
            _pack(out, k, t)
            _pack(out, x, t)
    else:
        plan = t.plans_by_cls.get(type(v))
        if plan is None:
            raise scheme.SchemeError(
                f"cannot binary-encode {type(v).__name__} "
                "(not a registered kind)"
            )
        present: list[tuple[int, Any]] = []
        for fid, name, default in plan.fields:
            val = getattr(v, name)
            if val is default or val == default:
                continue
            present.append((fid, val))
        if len(present) > 255:
            raise scheme.SchemeError(f"{plan.kind}: >255 present fields")
        out.append(0xAE)
        out.append(plan.kind_id)
        out.append(len(present))
        for fid, val in present:
            out += _pack_H(fid)
            _pack(out, val, t)


def pack_value(v: Any) -> bytes:
    """One self-contained binary value (objects may appear anywhere in
    the tree) — the unit the serialize-once caches store and the
    envelope helpers splice."""
    out = bytearray()
    _pack(out, v, tables())
    return bytes(out)


# --------------------------------------------------------------- decode

def _unpack(buf: bytes, pos: int, t: _Tables) -> tuple[Any, int]:
    tag = buf[pos]
    pos += 1
    if tag < 0x80:
        return tag, pos
    if tag >= 0xE0:
        return tag - 0x100, pos
    if tag < 0xA0:                      # fixstr
        n = tag & 0x1F
        return buf[pos:pos + n].decode(), pos + n
    if tag == 0xA0:
        return None, pos
    if tag == 0xA1:
        return False, pos
    if tag == 0xA2:
        return True, pos
    if tag == 0xA3:
        return _unpack_h(buf, pos)[0], pos + 2
    if tag == 0xA5:
        return _unpack_i(buf, pos)[0], pos + 4
    if tag == 0xA4:
        return _unpack_q(buf, pos)[0], pos + 8
    if tag == 0xA6:
        return _unpack_d(buf, pos)[0], pos + 8
    if tag in (0xA7, 0xA8, 0xA9):       # str8/16/32
        if tag == 0xA7:
            n = buf[pos]
            pos += 1
        elif tag == 0xA8:
            n = _unpack_H(buf, pos)[0]
            pos += 2
        else:
            n = _unpack_i(buf, pos)[0]
            pos += 4
        return buf[pos:pos + n].decode(), pos + n
    if tag in (0xAA, 0xAB):             # list
        if tag == 0xAA:
            n = buf[pos]
            pos += 1
        else:
            n = _unpack_i(buf, pos)[0]
            pos += 4
        out = []
        for _ in range(n):
            v, pos = _unpack(buf, pos, t)
            out.append(v)
        return out, pos
    if tag in (0xAC, 0xAD):             # map
        if tag == 0xAC:
            n = buf[pos]
            pos += 1
        else:
            n = _unpack_i(buf, pos)[0]
            pos += 4
        m = {}
        for _ in range(n):
            k, pos = _unpack(buf, pos, t)
            v, pos = _unpack(buf, pos, t)
            m[k] = v
        return m, pos
    if tag == 0xAE:                     # object
        kid = buf[pos]
        nf = buf[pos + 1]
        pos += 2
        if kid >= len(t.plans_by_id):
            raise UnsupportedWireError(f"unknown kind id {kid}")
        plan = t.plans_by_id[kid]
        by_fid = plan.by_fid
        kwargs: dict[str, Any] = {}
        for _ in range(nf):
            fid = _unpack_H(buf, pos)[0]
            pos += 2
            raw, pos = _unpack(buf, pos, t)
            got = by_fid.get(fid)
            if got is None:
                raise scheme.SchemeError(
                    f"{plan.kind}: unknown field id {fid} "
                    "(strict decoding)"
                )
            name, coerce = got
            kwargs[name] = coerce(raw)
        obj = plan.cls(**kwargs)
        if plan.defaulter is not None:
            obj = plan.defaulter(obj)
        return obj, pos
    if tag == 0xAF:                     # bigint
        n = buf[pos]
        pos += 1
        return int(buf[pos:pos + n]), pos + n
    raise UnsupportedWireError(f"bad wire tag 0x{tag:02x}")


def unpack_value(data: bytes) -> Any:
    try:
        v, pos = _unpack(data, 0, tables())
    except (IndexError, struct.error, UnicodeDecodeError) as e:
        raise UnsupportedWireError(f"truncated/garbled binary body: {e}") \
            from None
    if pos != len(data):
        raise UnsupportedWireError(
            f"{len(data) - pos} trailing bytes after binary value"
        )
    return v


# ----------------------------------------------------------- the seam

def jsonify(tree: Any) -> Any:
    """Registered objects anywhere in ``tree`` → their kind-tagged JSON
    form (``scheme.encode`` recursion; plain values pass through)."""
    return scheme.encode(tree)


def dumps(tree: Any, codec: str = JSON) -> bytes:
    """One wire body. ``tree`` may contain live registered dataclasses —
    both codecs encode them in place, so no handler pre-serializes."""
    if codec == BINARY:
        return pack_value(tree)
    return json.dumps(jsonify(tree), separators=(",", ":")).encode()


def loads(data: bytes, codec: str = JSON) -> Any:
    """The inverse. Binary bodies come back with registered objects
    MATERIALIZED (dataclasses, defaults applied); JSON bodies come back
    as the plain tree — normalize nested objects with ``as_object``."""
    if codec == BINARY:
        return unpack_value(data)
    try:
        return json.loads(data or b"{}")
    except ValueError as e:
        raise UnsupportedWireError(f"bad JSON body: {e}") from None


def as_object(value: Any) -> Any:
    """One decoded "object" slot → the typed object, whichever codec
    carried it: binary already materialized it; JSON left the kind-tagged
    dict. None passes through (tombstones)."""
    if value is None or not isinstance(value, (dict, list)):
        return value
    return scheme.decode(value)


def event_wire_bytes(
    ev_type: str, key: str, obj: Any, resource_version: int,
    codec: str = JSON,
) -> bytes:
    """One watch event's wire body — the unit the serialize-once caches
    hold. ``obj`` None is the scoped DELETED tombstone (no body)."""
    if codec == BINARY:
        return pack_value({
            "type": ev_type, "key": key, "object": obj,
            "resourceVersion": resource_version,
        })
    return json.dumps({
        "type": ev_type, "key": key,
        "object": None if obj is None else scheme.encode(obj),
        "resourceVersion": resource_version,
    }, separators=(",", ":")).encode()


def bind_delta_wire_bytes(
    key: str, uid: str, node: str, resource_version: int,
    codec: str = JSON,
) -> bytes:
    """A pods ``bind`` op's MODIFIED event as the batched watch poll sends
    it to a client that asked for deltas: the pod's key, its uid and the
    node, no object. The client rebuilds the object from the pod it holds
    (``SharedInformer``), as the store did. The bytes are what ``dumps``
    gives for ``{"type": "MODIFIED", "key", "resourceVersion", "bind":
    {"uid", "node"}}``, built from fixed parts: the apiserver makes 1024 a
    cycle under its store lock."""
    if codec == BINARY:
        out = bytearray(_DELTA_HEAD)
        _pack_str(out, key)
        out += _DELTA_RV
        _pack_int(out, resource_version)
        out += _DELTA_UID
        _pack_str(out, uid)
        out += _DELTA_NODE
        _pack_str(out, node)
        return bytes(out)
    return b'{"type":"MODIFIED","key":%s,"resourceVersion":%d,' \
        b'"bind":{"uid":%s,"node":%s}}' % (
            json.dumps(key).encode(), resource_version,
            json.dumps(uid).encode(), json.dumps(node).encode())


def _packed_strs(*words: str) -> bytes:
    out = bytearray()
    for w in words:
        _pack_str(out, w)
    return bytes(out)


#: the fixed parts of a binary bind delta, around its key, rv, uid and node
_DELTA_HEAD = map_header(4) + _packed_strs("type", "MODIFIED", "key")
_DELTA_RV = _packed_strs("resourceVersion")
_DELTA_UID = _packed_strs("bind") + map_header(2) + _packed_strs("uid")
_DELTA_NODE = _packed_strs("node")


def events_envelope(parts: list[bytes], cursor: int, codec: str = JSON) -> bytes:
    """The watch-poll reply ``{"events": […], "resourceVersion": N}``
    assembled by SPLICING pre-encoded event bodies — no event is ever
    re-encoded on the fan-out path."""
    if codec == BINARY:
        out = bytearray(map_header(2))
        _pack_str(out, "events")
        out += list_header(len(parts))
        for p in parts:
            out += p
        _pack_str(out, "resourceVersion")
        _pack_int(out, cursor)
        return bytes(out)
    return (
        b'{"events":[' + b",".join(parts)
        + b'],"resourceVersion":' + str(cursor).encode() + b"}"
    )


def list_item_wire_bytes(key: str, obj: Any, codec: str = JSON) -> bytes:
    """One LIST item's wire body ``{"key": …, "object": …}`` — the unit
    the apiserver's list-item encode cache holds and ``items_envelope``
    splices. Byte-identical to the item's slice of the pre-pagination
    monolithic reply, so a spliced page decodes through the same client
    path."""
    if codec == BINARY:
        return pack_value({"key": key, "object": obj})
    return json.dumps(
        {"key": key, "object": scheme.encode(obj)}, separators=(",", ":")
    ).encode()


def items_envelope(
    parts: list[bytes], resource_version: int, codec: str = JSON,
    cont: str | None = None,
) -> bytes:
    """The (paged) LIST reply ``{"items": […], "resourceVersion": N
    [, "continue": tok]}`` assembled by SPLICING pre-encoded item bodies
    — a 50k-node page re-encodes nothing that the list-item cache
    already holds. ``cont`` (the opaque continue token) is present only
    when the walk has more pages."""
    if codec == BINARY:
        out = bytearray(map_header(3 if cont else 2))
        _pack_str(out, "items")
        out += list_header(len(parts))
        for p in parts:
            out += p
        _pack_str(out, "resourceVersion")
        _pack_int(out, resource_version)
        if cont:
            _pack_str(out, "continue")
            _pack_str(out, cont)
        return bytes(out)
    tail = b'],"resourceVersion":' + str(resource_version).encode()
    if cont:
        tail += b',"continue":' + json.dumps(cont).encode()
    return b'{"items":[' + b",".join(parts) + tail + b"}"


def encode_continue(snapshot_rv: int, after_seq: int,
                    generation: int = 0, through_seq: int = 0) -> str:
    """The LIST continue token: opaque to clients (they hand it back
    verbatim), pinned to the resourceVersion snapshot the walk started
    at plus the seq cursor the next page resumes after and the seq BOUND
    the walk may not cross (objects created after the first page have
    higher seqs — the bound is what keeps them out of later pages),
    stamped with the store's list generation (seqs renumber on snapshot
    loads — crash recovery, replica resync — so a cursor is only
    meaningful within one generation). URL-safe — it rides a query
    parameter."""
    import base64

    raw = f"v1:{snapshot_rv}:{after_seq}:{generation}:{through_seq}".encode()
    return base64.urlsafe_b64encode(raw).decode().rstrip("=")


def decode_continue(token: str) -> tuple[int, int, int, int]:
    """(snapshot_rv, after_seq, generation, through_seq) from a continue
    token; raises ValueError on garbage (the server 400s — distinct from
    the 410 an EXPIRED but well-formed token earns)."""
    import base64

    try:
        raw = base64.urlsafe_b64decode(
            (token + "=" * (-len(token) % 4)).encode()
        ).decode()
        version, rv, seq, gen, bound = raw.split(":")
        if version != "v1":
            raise ValueError(version)
        return int(rv), int(seq), int(gen), int(bound)
    except (ValueError, UnicodeDecodeError) as e:
        raise ValueError(f"malformed continue token: {e}") from None


def buckets_envelope(parts: list[tuple[str, bytes]], codec: str = JSON) -> bytes:
    """The batched-poll reply ``{"buckets": {kind: body, …}}`` spliced
    from per-kind pre-assembled bodies (an events envelope or a 410
    error body per kind)."""
    if codec == BINARY:
        out = bytearray(map_header(1))
        _pack_str(out, "buckets")
        out += map_header(len(parts))
        for kind, body in parts:
            _pack_str(out, kind)
            out += body
        return bytes(out)
    return (
        b'{"buckets":{'
        + b",".join(
            json.dumps(kind).encode() + b":" + body for kind, body in parts
        )
        + b"}}"
    )


def stream_frame(body: bytes, codec: str = JSON) -> bytes:
    """One streaming-watch frame: ndjson line (json) or u32-length-
    prefixed binary body (the negotiated frame stream)."""
    if codec == BINARY:
        return len(body).to_bytes(4, "little") + body
    return body + b"\n"


#: wire-body slots in the native store's per-event ring (must stay dense
#: small ints — they index a fixed array in memstore_core.cpp)
WIRE_CODEC_IDS: dict[str, int] = {JSON: 0, BINARY: 1}

#: ring event-type ids → wire names (the store cores carry the int)
EVENT_TYPE_NAMES = ("ADDED", "MODIFIED", "DELETED")


def event_body_encoder(codec: str) -> Callable[[int, str, Any, int], bytes]:
    """The body ring's miss-path encoder: ``(type id, key, obj, rv) →
    wire bytes``. Called by the store core under its lock — it must (and
    does) never re-enter the store."""
    def _enc(ev_type: int, key: str, obj: Any, rv: int) -> bytes:
        return event_wire_bytes(EVENT_TYPE_NAMES[ev_type], key, obj, rv,
                                codec)
    return _enc
