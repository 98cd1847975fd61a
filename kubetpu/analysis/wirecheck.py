"""Wire-codec seam discipline checker (WP001).

The API plane serializes through ONE seam — ``kubetpu.api.codec`` — so
the wire format is negotiated per request (binary when the client's
schema fingerprint matches, JSON otherwise) and every watch body can ride
the serialize-once caches. A bare ``json.dumps``/``json.loads`` in an
apiserver/client/store hot-path module reintroduces exactly the bug class
PR 10 removed: a handler that hand-rolls JSON replies JSON to a client
that negotiated binary (an undecodable body), bypasses the
``apiserver_wire_bytes_total`` accounting, and re-serializes per watcher
what the event-encode cache and the store's body ring exist to encode
once. Diagnostics and CLI surfaces (human-facing text) are exempt — the
invariant covers the object wire, not log output.
"""

from __future__ import annotations

import ast
import posixpath

from .astutil import dotted
from .core import Checker, ModuleInfo, Violation, register

#: hot-path prefixes the invariant covers (repo-relative, forward
#: slashes): the apiserver, the client stack (informers/reflector/
#: events), the store, and the scheduler's API dispatcher — every module
#: that touches request/reply/watch BODIES
_SCOPE_PREFIXES = (
    "kubetpu/apiserver/",
    "kubetpu/client/",
    "kubetpu/store/",
)
_SCOPE_FILES = {
    "kubetpu/sched/api_dispatcher.py",
}

#: the seam itself encodes with the json module by design
_EXEMPT = {
    "kubetpu/api/codec.py",
}

_WIRE_FUNCS = {"dumps", "loads", "dump", "load"}


@register
class BareJsonOnWirePath(Checker):
    code = "WP001"
    title = "bare json.dumps/loads in a wire hot-path module"
    rationale = (
        "Every API body rides the negotiated wire seam "
        "(kubetpu.api.codec: dumps/loads/event_wire_bytes + the envelope "
        "splicers), so the codec is chosen per request from Accept/"
        "Content-Type and watch fan-out shares serialize-once caches. A "
        "bare json.dumps()/json.loads() in the apiserver, client stack, "
        "store, or API dispatcher hand-rolls one side of that protocol: "
        "the reply ignores what the client negotiated (a binary client "
        "gets undecodable JSON or — worse — a JSON client gets bytes it "
        "cannot parse), the payload escapes the "
        "apiserver_wire_bytes_total accounting, "
        "and per-watcher re-serialization silently returns to the fan-"
        "out path the EventEncodeCache/body ring exist to protect. "
        "Route object bodies through kubetpu.api.codec. Diagnostics "
        "endpoints and CLI/debug output (human-facing text, never "
        "negotiated) are exempt by scope."
    )

    def covers(self, relpath: str) -> bool:
        if relpath in _EXEMPT:
            return False
        base = posixpath.basename(relpath)
        if base.startswith("wire_") and base.endswith(".py"):
            return True     # the known-bad/known-good fixtures
        return relpath in _SCOPE_FILES or any(
            relpath.startswith(p) for p in _SCOPE_PREFIXES
        )

    def collect(self, mod: ModuleInfo):
        # resolve every way this module can reach the json serializers:
        # plain/aliased `import json` and from-imports of the functions
        # themselves — `import json as j` / `from json import loads as
        # jl` must not evade the gate
        module_aliases = set()
        from_imports: dict[str, str] = {}   # local name -> json.<func>
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "json":
                        module_aliases.add(a.asname or "json")
            elif isinstance(node, ast.ImportFrom) and node.module == "json":
                for a in node.names:
                    if a.name in _WIRE_FUNCS:
                        from_imports[a.asname or a.name] = f"json.{a.name}"
        if not module_aliases and not from_imports:
            return []
        out: list[Violation] = []
        parents: dict[int, str] = {}
        for fn in ast.walk(mod.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(fn):
                    parents.setdefault(id(sub), fn.name)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = ""
            if (
                isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id in module_aliases
                and f.attr in _WIRE_FUNCS
            ):
                name = dotted(f) or f"{f.value.id}.{f.attr}"
            elif isinstance(f, ast.Name) and f.id in from_imports:
                name = from_imports[f.id]
            if not name:
                continue
            out.append(Violation(
                path=mod.relpath, line=node.lineno, code=self.code,
                symbol=parents.get(id(node), ""),
                message=(
                    f"bare {name}() on the wire hot path — encode/decode "
                    "through kubetpu.api.codec (dumps/loads/"
                    "event_wire_bytes) so the negotiated codec, the "
                    "wire-byte accounting, and the serialize-once caches "
                    "all see this body"
                ),
            ))
        return out
