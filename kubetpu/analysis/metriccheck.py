"""Metrics-registry consistency checkers (MR001–MR004).

The registry raises on duplicate registration at RUNTIME — but only when
the two registrations land on the same Registry instance in the same
process, which a unit test may never arrange. And a `.labels()` call with
the wrong arity, or a bare `.inc()` on a labeled vector, fails (or worse,
silently updates a parent child no scrape exposes) only when that exact
line runs. These checkers move all three to parse time. MR004 adds the
declared-label-value contract: a metric registered with
``declared={"label": SOME_TUPLE}`` (the staged-latency ``{stage}``
histograms) may only ever be emitted with values from that tuple — the
registry enforces it at ``.labels()`` time, and MR004 enforces the same
set at parse time for literal call sites, so the declared set and the
emission sites cannot drift apart silently.
"""

from __future__ import annotations

import ast

from .astutil import dotted, terminal_attr
from .core import Checker, ModuleInfo, Violation, register

_REG_METHODS = {"counter", "gauge", "histogram"}
_EMIT_METHODS = {"inc", "dec", "set", "observe", "observe_n"}


def _module_str_tuples(tree: ast.AST) -> dict[str, tuple]:
    """Module-level ``NAME = ("a", "b", …)`` constants — the declared
    label-value sets MR004 resolves ``declared={"stage": NAME}`` against."""
    out: dict[str, tuple] = {}
    for node in ast.iter_child_nodes(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not isinstance(node.value, (ast.Tuple, ast.List)):
            continue
        vals = []
        ok = True
        for elt in node.value.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                vals.append(elt.value)
            else:
                ok = False
                break
        if not ok:
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                out[tgt.id] = tuple(vals)
    return out


def _declared_sets(call: ast.Call, consts: dict[str, tuple]):
    """The ``declared={…}`` keyword of a registration call resolved to
    {label_name: tuple_of_values}; None when absent or unresolvable."""
    for kw in call.keywords:
        if kw.arg != "declared" or not isinstance(kw.value, ast.Dict):
            continue
        out: dict[str, tuple] = {}
        for k, v in zip(kw.value.keys, kw.value.values):
            if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                return None
            if isinstance(v, ast.Name):
                vals = consts.get(v.id)
                if vals is None:
                    return None
            elif isinstance(v, (ast.Tuple, ast.List)):
                vals = []
                for elt in v.elts:
                    if isinstance(elt, ast.Constant) and isinstance(
                        elt.value, str
                    ):
                        vals.append(elt.value)
                    else:
                        return None
                vals = tuple(vals)
            else:
                return None
            out[k.value] = tuple(vals)
        return out
    return None


def _registrations(tree: ast.AST):
    """Yield (attr_or_None, metric_name, labels_tuple, lineno) for every
    ``X.counter("name", …, labels=(…))``-shaped call; ``attr`` is the
    ``self.Y`` the registration was assigned to, when it was."""
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Assign, ast.Expr)):
            continue
        value = node.value
        if not isinstance(value, ast.Call):
            continue
        m = terminal_attr(value.func) if isinstance(
            value.func, ast.Attribute
        ) else None
        if m not in _REG_METHODS:
            continue
        if not value.args or not isinstance(value.args[0], ast.Constant) \
                or not isinstance(value.args[0].value, str):
            continue
        name = value.args[0].value
        labels: tuple | None = ()
        for kw in value.keywords:
            if kw.arg == "labels":
                if isinstance(kw.value, (ast.Tuple, ast.List)):
                    vals = []
                    ok = True
                    for elt in kw.value.elts:
                        if isinstance(elt, ast.Constant) and isinstance(
                            elt.value, str
                        ):
                            vals.append(elt.value)
                        else:
                            ok = False
                    labels = tuple(vals) if ok else None
                else:
                    labels = None       # dynamic labels: unknown arity
        # positional labels (3rd positional arg of counter/gauge)
        if len(value.args) >= 3 and isinstance(
            value.args[2], (ast.Tuple, ast.List)
        ):
            vals = []
            ok = True
            for elt in value.args[2].elts:
                if isinstance(elt, ast.Constant) and isinstance(
                    elt.value, str
                ):
                    vals.append(elt.value)
                else:
                    ok = False
            labels = tuple(vals) if ok else None
        attr = None
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Attribute) and isinstance(
                    tgt.value, ast.Name
                ) and tgt.value.id == "self":
                    attr = tgt.attr
        yield attr, name, labels, value.args[0].lineno


@register
class MetricDuplicateRegistration(Checker):
    code = "MR001"
    title = "metric name registered twice with different label sets"
    rationale = (
        "One metric name must mean one series shape everywhere: the "
        "scheduler, TPU and workqueue sets share a single Registry on "
        "the diagnostics port, and two registrations of the same name "
        "with different label sets either throw at startup (same "
        "registry) or — worse — expose two incompatible series from two "
        "processes that dashboards silently aggregate wrong. Metric "
        "names are registered exactly once, with one label tuple."
    )

    def collect(self, mod: ModuleInfo):
        return [
            (attr, name, labels, line)
            for attr, name, labels, line in _registrations(mod.tree)
        ]

    def report(self, collected):
        seen: dict[str, tuple] = {}   # name -> (labels, relpath, line)
        out: list[Violation] = []
        for mod, regs in collected:
            for _attr, name, labels, line in regs:
                if labels is None:
                    continue
                prior = seen.get(name)
                if prior is None:
                    seen[name] = (labels, mod.relpath, line)
                    continue
                if prior[0] != labels:
                    out.append(Violation(
                        path=mod.relpath, line=line, code=self.code,
                        symbol=name,
                        message=(
                            f"metric {name!r} registered with labels "
                            f"{labels} here but {prior[0]} at "
                            f"{prior[1]}:{prior[2]}"
                        ),
                    ))
        return out


@register
class MetricLabelArity(Checker):
    code = "MR002"
    title = ".labels() arity does not match the registration"
    rationale = (
        "Counter.labels() raises ValueError at CALL time when the value "
        "count mismatches the registered label names — on an error path "
        "that may run once a week. The registration's label tuple is "
        "static; so is nearly every call site. Checked at parse time by "
        "matching the receiver's attribute name against every "
        "registration in the project (ambiguous names — same attribute, "
        "different arities in different classes — are skipped)."
    )

    def collect(self, mod: ModuleInfo):
        sites = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute) or f.attr != "labels":
                continue
            recv = f.value
            attr = terminal_attr(recv)
            if attr is None or isinstance(recv, ast.Call):
                continue
            if attr == "self":
                continue
            nargs = len(node.args)
            if any(isinstance(a, ast.Starred) for a in node.args):
                continue
            sites.append((attr, nargs, node.lineno))
        regs = [
            (attr, name, labels, line)
            for attr, name, labels, line in _registrations(mod.tree)
        ]
        return regs, sites

    def report(self, collected):
        arity: dict[str, set[int]] = {}
        metric_of: dict[str, str] = {}
        for _mod, (regs, _sites) in collected:
            for attr, name, labels, _line in regs:
                if attr is None or labels is None:
                    continue
                arity.setdefault(attr, set()).add(len(labels))
                metric_of[attr] = name
        out: list[Violation] = []
        for mod, (_regs, sites) in collected:
            for attr, nargs, line in sites:
                known = arity.get(attr)
                if known is None or len(known) != 1:
                    continue        # unknown receiver or ambiguous arity
                want = next(iter(known))
                if nargs != want:
                    out.append(Violation(
                        path=mod.relpath, line=line, code=self.code,
                        symbol=f"{attr}.labels",
                        message=(
                            f".labels() on {metric_of.get(attr, attr)!r} "
                            f"called with {nargs} values, registered "
                            f"with {want} label names"
                        ),
                    ))
        return out


@register
class MetricDeclaredLabelValues(Checker):
    code = "MR004"
    title = "label literal outside the metric's declared value set"
    rationale = (
        "The staged-latency histograms carry a CLOSED label contract: "
        "scheduler_e2e_scheduling_duration_seconds{stage} is registered "
        "with declared={'stage': E2E_STAGES}, and every dashboard and "
        "result field joins on exactly those stage "
        "names. The registry rejects unknown values at .labels() time, "
        "but that only fires when the emitting line runs — a typo'd "
        "stage on a rare path (bind_rtt vs bind_rt) would silently "
        "vanish from production scrapes until someone reads the raw "
        "text. This checker resolves each registration's declared tuple "
        "(a module-level constant or literal) and verifies every literal "
        ".labels() argument at that label's position is a member, at "
        "parse time."
    )

    def collect(self, mod: ModuleInfo):
        consts = _module_str_tuples(mod.tree)
        regs = []       # (attr, metric_name, labels, declared_dict)
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.Assign, ast.Expr)):
                continue
            value = node.value
            if not isinstance(value, ast.Call):
                continue
            m = terminal_attr(value.func) if isinstance(
                value.func, ast.Attribute
            ) else None
            if m not in _REG_METHODS:
                continue
            declared = _declared_sets(value, consts)
            if not declared:
                continue
            attr = None
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute) and isinstance(
                        tgt.value, ast.Name
                    ) and tgt.value.id == "self":
                        attr = tgt.attr
            labels: tuple = ()
            name = ""
            for reg_attr, reg_name, reg_labels, _line in _registrations(
                ast.Module(body=[node], type_ignores=[])
            ):
                name, labels = reg_name, reg_labels
                if attr is None:
                    attr = reg_attr
            if attr is None or labels is None:
                continue
            regs.append((attr, name, labels, declared))
        sites = []      # (attr, literal_args [str|None per position], line)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute) or f.attr != "labels":
                continue
            attr = terminal_attr(f.value)
            if attr is None or attr == "self" or isinstance(f.value, ast.Call):
                continue
            literals = [
                a.value if isinstance(a, ast.Constant) and isinstance(
                    a.value, str
                ) else None
                for a in node.args
            ]
            if any(isinstance(a, ast.Starred) for a in node.args):
                continue
            sites.append((attr, literals, node.lineno))
        return regs, sites

    def report(self, collected):
        # attr -> (metric name, labels, declared); ambiguous attrs skipped
        decl: dict[str, tuple] = {}
        ambiguous: set[str] = set()
        for _mod, (regs, _sites) in collected:
            for attr, name, labels, declared in regs:
                prior = decl.get(attr)
                if prior is not None and prior != (name, labels, declared):
                    ambiguous.add(attr)
                decl[attr] = (name, labels, declared)
        out: list[Violation] = []
        for mod, (_regs, sites) in collected:
            for attr, literals, line in sites:
                info = decl.get(attr)
                if info is None or attr in ambiguous:
                    continue
                name, labels, declared = info
                for label, allowed in declared.items():
                    try:
                        pos = labels.index(label)
                    except ValueError:
                        continue
                    if pos >= len(literals) or literals[pos] is None:
                        continue    # non-literal value: runtime check owns it
                    if literals[pos] not in allowed:
                        out.append(Violation(
                            path=mod.relpath, line=line, code=self.code,
                            symbol=f"{attr}.labels",
                            message=(
                                f"{name!r} emitted with {label}="
                                f"{literals[pos]!r}, outside the declared "
                                f"set {allowed}"
                            ),
                        ))
        return out


@register
class MetricUnlabeledEmission(Checker):
    code = "MR003"
    title = "bare emission on a labeled metric vector"
    rationale = (
        "Calling .inc()/.observe()/.set() directly on a metric "
        "registered WITH labels updates the parent object — whose value "
        "never appears in the exposition (samples() iterates children "
        "when label_names is non-empty). The increment is silently "
        "dropped from every scrape. Labeled vectors are always emitted "
        "through .labels(…)."
    )

    def collect(self, mod: ModuleInfo):
        sites = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            if f.attr not in _EMIT_METHODS:
                continue
            recv = f.value
            if isinstance(recv, ast.Call):
                continue            # .labels(...).inc() — the good path
            attr = terminal_attr(recv)
            if attr is None or attr == "self":
                continue
            sites.append((attr, f.attr, node.lineno))
        regs = [
            (attr, name, labels, line)
            for attr, name, labels, line in _registrations(mod.tree)
        ]
        return regs, sites

    def report(self, collected):
        labeled: dict[str, str] = {}      # attr -> metric name
        unlabeled_attrs: set[str] = set()
        for _mod, (regs, _sites) in collected:
            for attr, name, labels, _line in regs:
                if attr is None:
                    continue
                if labels:
                    labeled[attr] = name
                else:
                    unlabeled_attrs.add(attr)
        out: list[Violation] = []
        for mod, (_regs, sites) in collected:
            for attr, emit, line in sites:
                name = labeled.get(attr)
                if name is None or attr in unlabeled_attrs:
                    # unknown, or the attr name is also registered
                    # label-less somewhere (ambiguous) — skip
                    continue
                out.append(Violation(
                    path=mod.relpath, line=line, code=self.code,
                    symbol=f"{attr}.{emit}",
                    message=(
                        f".{emit}() called directly on labeled metric "
                        f"{name!r} — updates a parent no scrape exposes; "
                        f"go through .labels(…)"
                    ),
                ))
        return out
