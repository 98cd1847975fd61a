"""The wire decode's compiled plan (ISSUE 35): a field's annotation is turned
into a closure once, per kind and per annotation, and both codecs decode
through it.

Held here: every registered kind, with every optional field set and with
none, comes back ``==`` from the binary and from the JSON wire; every strict
failure the interpreting decoder raised still raises, by its exception type,
binary and JSON alike; what it let pass still passes; and after a kind's
first object no decode looks at an annotation again.
"""

import dataclasses
import enum
import json
import types
import typing

import pytest

pytest.importorskip("jax")

from kubetpu.api import codec, scheme
from kubetpu.api import types as t
from kubetpu.api.wrappers import make_pod

KINDS = sorted(scheme.kind_registry())


# ---------------------------------------------------------------- samples

def sample(hint, name: str, depth: int = 0, avoid=None):
    """A value of the annotated shape that differs from every default
    (``avoid`` is the field's, where an enum's members are all there is
    to choose from)."""
    origin, args = typing.get_origin(hint), typing.get_args(hint)
    if origin in (typing.Union, types.UnionType):
        return sample([a for a in args if a is not type(None)][0], name,
                      depth, avoid)
    if origin is tuple:
        if len(args) == 2 and args[1] is Ellipsis:
            return (sample(args[0], name, depth + 1),)
        return tuple(sample(a, f"{name}{i}", depth + 1)
                     for i, a in enumerate(args))
    if origin is dict:
        return {f"k-{name}": sample(args[1], name, depth + 1)}
    if isinstance(hint, type) and issubclass(hint, enum.Enum):
        return [m for m in hint if m is not avoid][-1]
    if isinstance(hint, type) and dataclasses.is_dataclass(hint):
        return instance(hint, full=depth < 3, depth=depth + 1)
    # ``object`` (a device attribute: any scalar) takes a string
    return {bool: True, int: 7, float: 2.5}.get(hint, f"x-{name}")


def instance(cls, full: bool, depth: int = 0):
    """``full``: every field set, optional ones too, nested objects as
    full as the depth allows; else the required fields alone."""
    hints = scheme.type_hints(cls)
    kwargs = {}
    for f in dataclasses.fields(cls):
        required = (f.default is dataclasses.MISSING
                    and f.default_factory is dataclasses.MISSING)
        if full or required:
            kwargs[f.name] = sample(hints[f.name], f.name, depth, f.default)
    return cls(**kwargs)


@pytest.mark.parametrize("fill", ["every-field", "required-only"])
@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_round_trips_both_wires(kind, fill):
    cls = scheme.kind_registry()[kind]
    obj = instance(cls, full=fill == "every-field")
    assert kind in codec.tables().plans_by_kind
    if fill == "every-field":
        # the sample is worth its name: no field was left at its default
        assert all(getattr(obj, f.name) != f.default
                   for f in dataclasses.fields(cls))
    via_binary = codec.loads(codec.dumps(obj, codec.BINARY), codec.BINARY)
    via_json = codec.as_object(
        codec.loads(codec.dumps(obj, codec.JSON), codec.JSON))
    hook = scheme.defaulter_for(cls)
    want = hook(obj) if hook is not None else obj
    assert via_binary == want and type(via_binary) is cls
    assert via_json == want and type(via_json) is cls


# --------------------------------------------------------- strict failures

def wire_binary(obj):
    """Through the binary wire. The encoder packs what the object holds
    (a dataclass checks no type), so a wrong value reaches the decoder."""
    return codec.loads(codec.dumps(obj, codec.BINARY), codec.BINARY)


def wire_json(obj):
    return codec.as_object(
        codec.loads(codec.dumps(obj, codec.JSON), codec.JSON))


def raw_object(kind: str, fields: bytes, n: int) -> bytes:
    return bytes((0xAE, codec.tables().plans_by_kind[kind].kind_id, n)) \
        + fields


def unknown_field_binary():
    fid = len(codec.tables().field_names)          # one past the table
    return codec.loads(
        raw_object("Taint", fid.to_bytes(2, "little") + b"\x01", 1),
        codec.BINARY)


def unknown_kind_binary():
    return codec.loads(bytes((0xAE, len(KINDS), 0)), codec.BINARY)


def union_order(wire):
    """``Pod.nonzero`` is ``tuple[tuple[str, int], ...] | None``: a bare
    string is no array, the one arm refuses and the union says so."""
    return wire(dataclasses.replace(make_pod("p"), nonzero="cpu"))


FAILURES = {
    "unknown-field": (
        scheme.SchemeError, "unknown field",
        unknown_field_binary,
        lambda: scheme.decode({"kind": "Taint", "key": "k", "bogus": 1})),
    "unknown-kind": (
        (codec.UnsupportedWireError, scheme.SchemeError), "kind",
        unknown_kind_binary,
        lambda: scheme.decode({"kind": "Frob"})),
    "bool-for-int": (
        scheme.SchemeError, "expected int",
        lambda: wire_binary(t.ContainerPort(host_port=True)),
        lambda: wire_json(t.ContainerPort(host_port=True))),
    "str-for-int": (
        scheme.SchemeError, "expected int",
        lambda: wire_binary(t.ContainerPort(host_port="eighty")),
        lambda: wire_json(t.ContainerPort(host_port="eighty"))),
    "int-for-str": (
        scheme.SchemeError, "expected str",
        lambda: wire_binary(t.Namespace(name=7)),
        lambda: wire_json(t.Namespace(name=7))),
    "int-for-bool": (
        scheme.SchemeError, "expected bool",
        lambda: wire_binary(t.Node(name="n", unschedulable=1)),
        lambda: wire_json(t.Node(name="n", unschedulable=1))),
    "str-for-float": (
        scheme.SchemeError, "no union arm",
        lambda: wire_binary(t.Toleration(key="k", toleration_seconds="5")),
        lambda: wire_json(t.Toleration(key="k", toleration_seconds="5"))),
    "non-array-for-tuple": (
        scheme.SchemeError, "expected array",
        lambda: wire_binary(t.Requirement(
            key="k", operator=t.Operator.IN, values="a")),
        lambda: wire_json(t.Requirement(
            key="k", operator=t.Operator.IN, values="a"))),
    "bad-item-in-tuple": (
        scheme.SchemeError, "expected str",
        lambda: wire_binary(t.Requirement(
            key="k", operator=t.Operator.IN, values=("a", 3))),
        lambda: wire_json(t.Requirement(
            key="k", operator=t.Operator.IN, values=("a", 3)))),
    "no-union-arm": (
        scheme.SchemeError, "no union arm",
        lambda: union_order(wire_binary), lambda: union_order(wire_json)),
    "non-object-for-nested": (
        scheme.SchemeError, "no union arm",
        lambda: wire_binary(dataclasses.replace(make_pod("p"), affinity=3)),
        lambda: wire_json(dataclasses.replace(make_pod("p"), affinity=3))),
    "unknown-enum-value": (
        ValueError, "not a valid",
        lambda: wire_binary(t.Taint(key="k", effect="Sometimes")),
        lambda: wire_json(t.Taint(key="k", effect="Sometimes"))),
}


@pytest.mark.parametrize("wire", ["binary", "json"])
@pytest.mark.parametrize("case", sorted(FAILURES))
def test_strict_decoding_still_refuses(case, wire):
    exc, match, binary, as_json = FAILURES[case]
    with pytest.raises(exc, match=match):
        (binary if wire == "binary" else as_json)()


def test_union_arms_are_tried_in_the_annotation_s_order():
    first = scheme.coerce_value(3, int | float | str)
    assert first == 3 and type(first) is int
    assert scheme.coerce_value("3", int | float | str) == "3"
    # bool is refused by int AND by float, and passes the bool arm behind
    assert scheme.coerce_value(True, int | float | bool) is True
    with pytest.raises(scheme.SchemeError, match="no union arm"):
        scheme.coerce_value(True, int | float)
    # an array goes to the first arm that takes arrays
    assert scheme.coerce_value(["a"], str | tuple[str, ...]) == ("a",)
    # no API type holds a map today; the rule is kept for one that will
    assert scheme.coerce_value({"a": 1, 2: 3}, dict[str, int]) == \
        {"a": 1, "2": 3}
    with pytest.raises(scheme.SchemeError, match="expected int"):
        scheme.coerce_value({"a": "1"}, dict[str, int])
    with pytest.raises(scheme.SchemeError, match="expected object"):
        scheme.coerce_value(["a"], dict[str, int])


PASSES = {
    "int-where-float": lambda: t.Toleration(
        key="k", operator=t.TolerationOperator.EXISTS, toleration_seconds=5),
    "none-anywhere": lambda: t.Toleration(key=None, value=None),
    "already-typed-nested": lambda: dataclasses.replace(
        make_pod("p"), topology_spread_constraints=(
            t.TopologySpreadConstraint(
                max_skew=5, topology_key="zone",
                when_unsatisfiable=(
                    t.UnsatisfiableConstraintAction.SCHEDULE_ANYWAY),
                selector=t.LabelSelector.of({"color": "blue"})),)),
    "fixed-tuple-pairs": lambda: t.Namespace(
        name="ns", labels=(("a", "b"), ("c", "d"))),
    "pairs-of-str-and-object": lambda: dataclasses.replace(
        instance(t.Node, full=False),
        images=(("img:v1", t.ImageState(size_bytes=1 << 28)),)),
}


@pytest.mark.parametrize("wire", [wire_binary, wire_json])
@pytest.mark.parametrize("case", sorted(PASSES))
def test_what_passed_still_passes(case, wire):
    obj = PASSES[case]()
    assert wire(obj) == obj


def test_a_kind_tagged_dict_inside_a_binary_body_decodes():
    sel = t.LabelSelector.of({"a": "b"})
    tagged = scheme.encode(sel)
    assert tagged["kind"] == "LabelSelector"
    # as a field's raw value, under any annotation that could hold it
    for hint in (t.LabelSelector, t.LabelSelector | None, typing.Any,
                 dict[str, str]):
        assert scheme.coerce_value(tagged, hint) == sel
    # and as what the binary decoder hands the field's coercer: a map on
    # the wire where an object tag would be
    ids = codec.tables().name_ids
    body = raw_object(
        "ReplicaSet",
        ids["name"].to_bytes(2, "little") + codec.dumps("web", codec.BINARY)
        + ids["selector"].to_bytes(2, "little")
        + codec.dumps(tagged, codec.BINARY), 2)
    assert codec.loads(body, codec.BINARY) == \
        t.ReplicaSet(name="web", selector=sel)
    with pytest.raises(scheme.SchemeError, match="not registered"):
        scheme.coerce_value({"kind": "Frob"}, t.LabelSelector | None)


def test_defaults_run_from_the_plan_and_follow_a_late_registration():
    cls = t.Namespace
    assert scheme.defaulter_for(cls) is None
    body = codec.dumps(t.Namespace(name="ns"), codec.BINARY)
    fingerprint = codec.schema_fingerprint()
    try:
        scheme.register_defaults(cls, lambda ns: dataclasses.replace(
            ns, labels=(("defaulted", "yes"),)))
        assert codec.loads(body, codec.BINARY).labels == \
            (("defaulted", "yes"),)
        assert scheme.decode({"kind": "Namespace", "name": "ns"}).labels \
            == (("defaulted", "yes"),)
        # a hook is no part of the wire
        assert codec.schema_fingerprint() == fingerprint
    finally:
        scheme._DEFAULTERS.pop(cls)
        scheme._GENERATION += 1
    assert codec.loads(body, codec.BINARY) == t.Namespace(name="ns")
    pod = codec.loads(codec.dumps(
        dataclasses.replace(make_pod("p"), scheduler_name=""),
        codec.BINARY), codec.BINARY)
    assert pod.scheduler_name == "default-scheduler"


# ------------------------------------------------------- built once a kind

def spread_pod(i: int) -> t.Pod:
    return dataclasses.replace(
        make_pod(f"pod-{i}", namespace="sched-1", cpu_milli=100,
                 memory=500 * 1024 ** 2, labels={"color": "blue"}),
        uid=f"uid-{i}", trace_id=f"{i:016x}", ingest_ts=1234.5 + i,
        topology_spread_constraints=(t.TopologySpreadConstraint(
            max_skew=5, topology_key="topology.kubernetes.io/zone",
            when_unsatisfiable=t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE,
            selector=t.LabelSelector.of({"color": "blue"})),),
    )


@pytest.mark.parametrize("wire", [codec.BINARY, codec.JSON])
def test_the_plan_is_built_once(monkeypatch, wire):
    """Decoding 1,000 pods after the first looks at no annotation: none of
    what the interpreting decoder called for every value of every pod."""
    pods = [spread_pod(i) for i in range(1001)]
    bodies = [codec.dumps(p, wire) for p in pods]
    first = codec.as_object(codec.loads(bodies[0], wire))
    assert first == pods[0]
    calls = []

    def counting(name, fn):
        def counted(*a, **kw):
            calls.append(name)
            return fn(*a, **kw)
        return counted

    for mod, name in ((typing, "get_origin"), (typing, "get_args"),
                      (typing, "get_type_hints"),
                      (dataclasses, "is_dataclass"),
                      (dataclasses, "fields")):
        monkeypatch.setattr(mod, name, counting(name, getattr(mod, name)))
    got = [codec.as_object(codec.loads(b, wire)) for b in bodies[1:]]
    assert calls == []
    assert got == pods[1:]
    # the closures are the class's own, shared by both codecs
    plan = codec.tables().plans_by_kind["Pod"]
    by_name = dict(plan.by_fid.values())
    assert by_name == scheme.field_coercers(t.Pod)
    assert scheme.field_coercers(t.Pod) is scheme.field_coercers(t.Pod)


def test_the_json_body_of_the_first_pod_is_plain_json():
    # the JSON wire spells every field; the plan reads it all the same
    tree = json.loads(codec.dumps(spread_pod(0), codec.JSON))
    assert tree["kind"] == "Pod" and tree["node_name"] == ""
    assert scheme.decode(tree) == spread_pod(0)
