"""WAL append-seam discipline checker (WL001).

Durability holds only if EVERY committed store write is logged before it
is applied: ``MemStore._commit_locked`` is the one seam that appends the
record (write-ahead, peek-validated) and then mutates the core. A core
mutation called anywhere else — a new verb calling ``self._core.create``
directly, a helper that grabs ``core = self._core`` and updates through
the alias — commits state the WAL never saw: recovery silently loses the
write, the replay chain's rv check explodes one record later, and the
exactly-once binding parity the multi-process runs assert is gone. This
checker moves that invariant to parse time, alias-resolving like WP001:
any ``create``/``update``/``delete`` call whose receiver resolves to a
store core (``self._core``, or a local name assigned from one) outside
the blessed seam is a finding. Recovery's own replay (kubetpu.store.wal
— it IS the path that reconstructs the core from the log) and the core
implementations themselves are exempt by scope.
"""

from __future__ import annotations

import ast
import posixpath

from .core import Checker, ModuleInfo, Violation, register

#: the store wrapper — the only module that owns a core reference the
#: seam invariant governs
_SCOPE_FILES = {
    "kubetpu/store/memstore.py",
}

#: kubetpu.store.wal replays INTO a core by design (it is the durability
#: layer's read side); the cores themselves (native + _PyCore methods)
#: are the mutation primitives the seam wraps, not callers of it
_EXEMPT = {
    "kubetpu/store/wal.py",
}

#: the one function allowed to mutate a core directly: the WAL append
#: seam (log-then-apply, peek-validated)
_SEAM_FUNCS = {"_commit_locked"}

#: the classes whose methods ARE the core (self.<mutation> inside them is
#: the primitive, not a bypass)
_CORE_CLASSES = {"_PyCore"}

_MUTATIONS = {"create", "update", "delete"}


def _is_core_attr(node: ast.AST) -> bool:
    """``X._core`` for any X — the direct core reference shape."""
    return isinstance(node, ast.Attribute) and node.attr == "_core"


@register
class CoreMutationOutsideWalSeam(Checker):
    code = "WL001"
    title = "store-core mutation outside the WAL append seam"
    rationale = (
        "Every committed write must be WAL-logged BEFORE the core applies "
        "it (MemStore._commit_locked: peek-validate so doomed writes "
        "raise the canonical error unlogged, append the framed record, "
        "fire the post-append fault point, apply). A core "
        "create/update/delete called anywhere else — directly as "
        "self._core.update(...), or through an alias like core = "
        "self._core — commits state the log never saw: recovery loses "
        "the write AND the replay chain's rv-continuity check blows up "
        "on the next logged record, because the on-disk rv sequence now "
        "has a hole where the unlogged write bumped the revision. That "
        "is exactly how a future write verb (a patch subresource, a "
        "conditional-delete) silently punches a durability hole that no "
        "test notices until a crash lands in the window. Route the "
        "mutation through _commit_locked; reads (get/list/events_since/"
        "resource_version) are unrestricted. kubetpu.store.wal's replay "
        "and the core implementations themselves are exempt by scope."
    )

    def covers(self, relpath: str) -> bool:
        if relpath in _EXEMPT:
            return False
        base = posixpath.basename(relpath)
        if base.startswith("wal_") and base.endswith(".py"):
            return True     # the known-bad/known-good fixtures
        return relpath in _SCOPE_FILES

    def collect(self, mod: ModuleInfo):
        out: list[Violation] = []
        # map every node to its enclosing (class, function) context
        for cls_name, fn in self._functions(mod.tree):
            if cls_name in _CORE_CLASSES:
                continue        # the primitive itself, not a caller
            if fn.name in _SEAM_FUNCS:
                continue        # the seam is the one blessed mutator
            aliases = self._core_aliases(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if not (
                    isinstance(f, ast.Attribute) and f.attr in _MUTATIONS
                ):
                    continue
                recv = f.value
                if _is_core_attr(recv) or (
                    isinstance(recv, ast.Name) and recv.id in aliases
                ):
                    symbol = (
                        f"{cls_name}.{fn.name}" if cls_name else fn.name
                    )
                    out.append(Violation(
                        path=mod.relpath, line=node.lineno, code=self.code,
                        symbol=symbol,
                        message=(
                            f"core .{f.attr}() outside the WAL append "
                            "seam — this write commits without ever "
                            "reaching the log (recovery loses it and the "
                            "replay rv chain breaks); route it through "
                            "MemStore._commit_locked"
                        ),
                    ))
        return out

    @staticmethod
    def _functions(tree: ast.AST):
        """Yield (enclosing class name or '', function node) for every
        function, innermost functions included."""
        out = []

        def walk(node, cls_name):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    walk(child, child.name)
                elif isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    out.append((cls_name, child))
                    walk(child, cls_name)
                else:
                    walk(child, cls_name)
        walk(tree, "")
        return out

    @staticmethod
    def _core_aliases(fn: ast.AST) -> set:
        """Local names bound (anywhere in the function) from a core
        reference: ``core = self._core`` — assignment order is ignored
        on purpose (flow-insensitive, no false negatives)."""
        aliases: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _is_core_attr(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        aliases.add(tgt.id)
            elif isinstance(node, ast.AnnAssign) and (
                node.value is not None and _is_core_attr(node.value)
                and isinstance(node.target, ast.Name)
            ):
                aliases.add(node.target.id)
        return aliases
