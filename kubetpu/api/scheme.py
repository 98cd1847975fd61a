"""Scheme + serializers — the apimachinery runtime.Scheme analog.

Reference: ``staging/src/k8s.io/apimachinery`` — ``runtime.Scheme`` maps
GroupVersionKinds to Go types and back; serializers encode objects with a
``kind``/``apiVersion`` tag so any component can round-trip any registered
object. Here the registry maps **kind names to dataclasses** and the codec
round-trips the typed scheduling envelope (dataclasses, enums, tuples,
nested objects) through plain JSON with a ``"kind"`` tag — the wire format
of the apiserver layer (kubetpu.apiserver) and anything else that ships
typed objects across a process boundary.

Unknown kinds and unknown fields fail loudly (strict decoding — the
reference's strict serializer mode); None round-trips as null; tuples of
nested dataclasses are reconstructed from the field's type annotation.

GVK VERSIONING (apimachinery runtime.Scheme's group/version surface):
objects may carry an ``apiVersion`` tag. The registered dataclasses are
the HUB (internal) types; per-(kind, apiVersion) CONVERTERS decode other
versions into the hub — and the load-bearing registration is the real
Kubernetes ``v1`` wire format: a genuine upstream Pod/Node manifest
(``apiVersion: v1``) decodes through the bridge codecs
(kubetpu.bridge.convert), so ``kubetpu apply -f`` accepts reference
manifests verbatim. ``encode_versioned`` is the reverse conversion.
Unknown apiVersions fail loudly. Per-kind DEFAULTING hooks
(``register_defaults`` — the reference's zz_generated.defaults funcs)
run after construction on every decode path.
"""

from __future__ import annotations

import dataclasses
import enum
import types
import typing
from typing import Any

from . import types as t

# kind name -> dataclass. The registered surface is every wire-visible
# object of the framework (the "API types" layer).
_KINDS: dict[str, type] = {}

# bumped on every registration: the binary codec (kubetpu.api.codec)
# derives its schema tables from this registry and caches them per
# generation, so a late registration rebuilds the tables (and changes
# the negotiated schema fingerprint) instead of silently missing a kind
_GENERATION = 0


def register(cls: type, kind: str | None = None) -> type:
    global _GENERATION
    _KINDS[kind or cls.__name__] = cls
    _GENERATION += 1
    return cls


def kind_registry() -> dict[str, type]:
    """The live kind → dataclass map (read-only view for the codec's
    schema-table derivation)."""
    return _KINDS


def registry_generation() -> int:
    return _GENERATION


for _cls in (
    t.Node, t.Pod, t.Taint, t.Toleration, t.Affinity, t.NodeAffinity,
    t.PodAffinity, t.PodAffinityTerm, t.WeightedPodAffinityTerm,
    t.PreferredSchedulingTerm, t.NodeSelector, t.NodeSelectorTerm,
    t.Requirement, t.LabelSelector, t.TopologySpreadConstraint,
    t.ContainerPort, t.PodVolume, t.PersistentVolume,
    t.PersistentVolumeClaim, t.StorageClass, t.Service, t.Namespace,
    t.PodDisruptionBudget, t.PodGroup, t.GangPolicy, t.ImageState,
    t.ReplicaSet, t.DeviceClass, t.CELSelector, t.ResourceSlice, t.Device,
    t.DeviceRequest, t.DeviceSubRequest, t.DeviceConstraint,
    t.ResourceClaim, t.ClaimAllocation, t.DeviceResult, t.PodResourceClaim,
    t.NodeHeartbeat, t.LeaderElectionRecord, t.Deployment, t.Job,
    t.StatefulSet, t.ResourceClaimTemplate, t.DaemonSet, t.Event,
    t.CronJob, t.ResourceQuota,
):
    register(_cls)


class SchemeError(ValueError):
    pass


# the hub version every plain "kind"-tagged object implicitly carries
HUB_VERSION = "kubetpu/v1"

# (kind, apiVersion) -> converter(raw dict) -> hub object
_CONVERTERS: dict[tuple[str, str], Any] = {}
# hub class -> defaulting fn(obj) -> obj (zz_generated.defaults analog)
_DEFAULTERS: dict[type, Any] = {}


def register_conversion(kind: str, api_version: str, fn) -> None:
    """Decode ``apiVersion``-tagged wire objects of ``kind`` into the hub
    type (runtime.Scheme.AddConversionFunc's role)."""
    _CONVERTERS[(kind, api_version)] = fn


def register_defaults(cls: type, fn) -> None:
    """Run ``fn(obj) -> obj`` after every decode of ``cls``. The binary
    codec's plan holds the hook per kind, so a registration rebuilds its
    tables as a kind's does (the fingerprint does not move: a hook is no
    part of the wire)."""
    global _GENERATION
    _DEFAULTERS[cls] = fn
    _GENERATION += 1


def _apply_defaults(obj: Any) -> Any:
    fn = _DEFAULTERS.get(type(obj))
    return fn(obj) if fn is not None else obj


def encode_versioned(obj: Any, api_version: str = HUB_VERSION) -> Any:
    """Encode into a SPECIFIC version's wire format (the reverse
    conversion). The hub version is the plain kind-tagged envelope;
    ``v1`` Pods/Nodes emit the real Kubernetes JSON."""
    if api_version == HUB_VERSION:
        out = encode(obj)
        if isinstance(out, dict):
            out["apiVersion"] = HUB_VERSION
        return out
    kind = type(obj).__name__
    if api_version == "v1" and kind == "Pod":
        from ..bridge.convert import pod_to_v1

        wire = pod_to_v1(obj)
        wire.setdefault("apiVersion", "v1")
        wire.setdefault("kind", "Pod")
        return wire
    raise SchemeError(
        f"no conversion from {kind} to apiVersion {api_version!r}"
    )


def encode(obj: Any) -> Any:
    """Object → JSON-safe value. Dataclasses carry a "kind" tag."""
    if obj is None or isinstance(obj, (str, int, float, bool)):
        if isinstance(obj, enum.Enum):   # str-enums are str instances
            return obj.value
        return obj
    if isinstance(obj, enum.Enum):
        return obj.value
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        kind = type(obj).__name__
        if kind not in _KINDS:
            raise SchemeError(f"kind {kind!r} is not registered")
        out: dict[str, Any] = {"kind": kind}
        for f in dataclasses.fields(obj):
            out[f.name] = encode(getattr(obj, f.name))
        return out
    if isinstance(obj, (list, tuple)):
        return [encode(x) for x in obj]
    if isinstance(obj, dict):
        return {str(k): encode(v) for k, v in obj.items()}
    raise SchemeError(f"cannot encode {type(obj).__name__}")


def _resolve_hints(cls: type) -> dict[str, Any]:
    # evaluated lazily + cached on the class (postponed annotations)
    cached = cls.__dict__.get("__kubetpu_hints__")
    if cached is None:
        cached = typing.get_type_hints(cls, vars(t))
        setattr(cls, "__kubetpu_hints__", cached)
    return cached


# ------------------------------------------------------ compiled coercion
#
# A field's annotation never changes between two objects, so what it asks
# of a value is worked out ONCE: ``_coercer(hint)`` turns the annotation
# into a closure tree (union arms, tuple items, enums, nested dataclasses,
# dict values, the strict primitive leaves) and every decode of every
# object of the kind calls the closure. Both decode paths go through it:
# ``_decode_into`` (JSON, manifests, the v1 conversions) and the binary
# codec's per-kind plan (``field_coercers``), so the two codecs cannot
# drift on what a field accepts.
#
# What every closure holds, whatever the annotation (the rules the
# interpreting decoder applied before it looked at the hint): ``None``
# passes; an already-typed object passes (the binary codec materializes
# nested objects before coercion: its object tag carries the kind); a
# kind-tagged dict decodes by its tag. A closure tries the annotated shape
# first, which those three can never have, and falls back to them.

_UNION_ORIGINS = (typing.Union, types.UnionType)
_NOT_ANNOTATED = object()

#: annotation -> closure; annotations hash by value, so two kinds that
#: spell ``tuple[str, ...]`` share one closure
_COERCERS: dict[Any, Any] = {}


def _untyped(value: Any) -> Any:
    """The passes that do not depend on the annotation, else
    ``_NOT_ANNOTATED``."""
    if value is None:
        return None
    if hasattr(type(value), "__dataclass_fields__"):
        return value
    if isinstance(value, dict) and "kind" in value:
        return decode(value)
    return _NOT_ANNOTATED


def _leaf_coercer(hint: type, exact: tuple[type, ...], accepts) -> Any:
    """Primitive leaves are type-checked against the annotation — strict
    decoding covers field types, not just unknown kinds/fields. A bool is
    an int to ``isinstance`` and is refused as one; int is accepted where
    float is annotated (JSON has one number type)."""
    name = hint.__name__
    refuse_bool = hint is not bool

    def coerce(value: Any) -> Any:
        if type(value) in exact:
            return value
        got = _untyped(value)
        if got is not _NOT_ANNOTATED:
            return got
        if not isinstance(value, accepts) or (
            refuse_bool and isinstance(value, bool)
        ):
            raise SchemeError(f"expected {name}, got {value!r}")
        return value
    return coerce


def _passthrough(value: Any) -> Any:
    got = _untyped(value)
    return value if got is _NOT_ANNOTATED else got


def _build_coercer(hint: Any) -> Any:
    origin = typing.get_origin(hint)
    args = typing.get_args(hint)
    if origin in _UNION_ORIGINS:
        arms = tuple(_coercer(a) for a in args if a is not type(None))

        def coerce_union(value: Any) -> Any:
            if value is None:
                return None
            if isinstance(value, dict) and "kind" in value:
                return decode(value)    # its own errors, not an arm's
            for arm in arms:
                try:
                    return arm(value)
                except (SchemeError, TypeError, ValueError):
                    continue
            raise SchemeError(f"no union arm of {hint} accepts {value!r}")
        return coerce_union
    if origin is tuple:
        if len(args) == 2 and args[1] is Ellipsis:
            item = _coercer(args[0])

            def coerce_items(value: list) -> tuple:
                return tuple([item(v) for v in value])
        elif args:
            items = tuple(_coercer(a) for a in args)
            n = len(items)

            def coerce_items(value: list) -> tuple:
                return tuple([items[i % n](v) for i, v in enumerate(value)])
        else:
            coerce_items = tuple

        def coerce_tuple(value: Any) -> Any:
            if isinstance(value, list):
                return coerce_items(value)
            got = _untyped(value)
            if got is _NOT_ANNOTATED:
                raise SchemeError(f"expected array for {hint}, got {value!r}")
            return got
        return coerce_tuple
    if isinstance(hint, type) and issubclass(hint, enum.Enum):
        def coerce_enum(value: Any) -> Any:
            if type(value) is not str:
                got = _untyped(value)
                if got is not _NOT_ANNOTATED:
                    return got
            return hint(value)
        return coerce_enum
    if isinstance(hint, type) and dataclasses.is_dataclass(hint):
        def coerce_object(value: Any) -> Any:
            if type(value) is hint:
                return value
            got = _untyped(value)
            if got is not _NOT_ANNOTATED:
                return got
            if isinstance(value, dict):
                return _decode_into(hint, value)
            raise SchemeError(
                f"expected object for {hint.__name__}, got {value!r}"
            )
        return coerce_object
    if origin is dict:
        item = _coercer(args[1]) if args else None

        def coerce_dict(value: Any) -> Any:
            if isinstance(value, dict):
                if "kind" in value:
                    return decode(value)
                if item is None:
                    return value
                return {str(k): item(v) for k, v in value.items()}
            got = _untyped(value)
            if got is _NOT_ANNOTATED:
                raise SchemeError(f"expected object for {hint}, got {value!r}")
            return got
        return coerce_dict
    if hint is bool:
        return _leaf_coercer(bool, (bool,), bool)
    if hint is int:
        return _leaf_coercer(int, (int,), int)
    if hint is float:
        return _leaf_coercer(float, (float, int), (int, float))
    if hint is str:
        return _leaf_coercer(str, (str,), str)
    return _passthrough


def _coercer(hint: Any) -> Any:
    fn = _COERCERS.get(hint)
    if fn is None:
        fn = _COERCERS[hint] = _build_coercer(hint)
    return fn


def coerce_value(value: Any, hint: Any) -> Any:
    """One value against one annotation, by the field-coercion rules
    (tuple rebuild, enum reconstruction, strict primitive checks)."""
    return _coercer(hint)(value)


def field_coercers(cls: type) -> dict[str, Any]:
    """Field name -> compiled coercer for a registered class: built on
    the class's first decode and kept on it beside its resolved hints.
    The binary codec's per-kind plan holds these same closures."""
    cached = cls.__dict__.get("__kubetpu_coercers__")
    if cached is None:
        hints = _resolve_hints(cls)
        cached = {
            f.name: _coercer(hints[f.name]) for f in dataclasses.fields(cls)
        }
        setattr(cls, "__kubetpu_coercers__", cached)
    return cached


def defaulter_for(cls: type) -> Any:
    """The kind's registered defaulting hook, or None (every decode path —
    JSON and binary — must apply the same defaults)."""
    return _DEFAULTERS.get(cls)


def type_hints(cls: type) -> dict[str, Any]:
    """Resolved field annotations for a registered class (cached)."""
    return _resolve_hints(cls)


def _decode_into(cls: type, data: dict) -> Any:
    coercers = field_coercers(cls)
    kwargs: dict[str, Any] = {}
    for key, raw in data.items():
        if key in ("kind", "apiVersion"):
            continue
        coerce = coercers.get(key)
        if coerce is None:
            raise SchemeError(
                f"{cls.__name__}: unknown field {key!r} (strict decoding)"
            )
        kwargs[key] = coerce(raw)
    return cls(**kwargs)


def decode(data: Any) -> Any:
    """JSON value → typed object (requires the "kind" tag on objects).
    An ``apiVersion`` other than the hub's routes through the registered
    conversion (e.g. real Kubernetes ``v1`` Pod/Node manifests)."""
    if isinstance(data, dict):
        kind = data.get("kind")
        if kind is None:
            raise SchemeError("object has no 'kind' tag")
        version = data.get("apiVersion", HUB_VERSION)
        if version != HUB_VERSION:
            converter = _CONVERTERS.get((kind, version))
            if converter is None:
                raise SchemeError(
                    f"no conversion registered for {kind!r} "
                    f"apiVersion {version!r}"
                )
            return _apply_defaults(converter(data))
        cls = _KINDS.get(kind)
        if cls is None:
            raise SchemeError(
                f"kind {kind!r} is not registered "
                f"(known: {sorted(_KINDS)})"
            )
        return _apply_defaults(_decode_into(cls, data))
    if isinstance(data, list):
        return [decode(x) for x in data]
    return data


def _register_v1_conversions() -> None:
    """The real Kubernetes v1 wire format as a scheme version: upstream
    Pod/Node manifests decode via the bridge codecs."""

    def _pod_v1(raw: dict) -> Any:
        from ..bridge.convert import pod_from_v1

        return pod_from_v1(raw)

    def _node_v1(raw: dict) -> Any:
        from ..bridge.convert import node_from_v1

        return node_from_v1(raw)

    register_conversion("Pod", "v1", _pod_v1)
    register_conversion("Node", "v1", _node_v1)


_register_v1_conversions()


def _default_pod(pod: Any) -> Any:
    """pkg/apis/core/v1 defaulting slice: an empty schedulerName becomes
    "default-scheduler" (SetDefaults_PodSpec)."""
    if not pod.scheduler_name:
        import dataclasses

        return dataclasses.replace(pod, scheduler_name="default-scheduler")
    return pod


register_defaults(t.Pod, _default_pod)
