"""Encode-cache invalidation-scope checker (EC001).

PR 14 scoped the encode cache's node-epoch invalidation: a node ADD
extends every cached row with the appended nodes' columns (O(templates ×
Δnodes)), a node DELETE compacts them down to the survivors' columns by
an old-index gather (the drain-wave twin, ROADMAP 5b), while only
updates and mixed waves pay the full-epoch flush — at 100k nodes under
an autoscaler wave, the difference is a per-event re-encode storm vs a
per-wave delta. That scoping only survives if the full flush stays
behind ONE seam: a bare ``invalidate_nodes()`` (or a raw ``node_epoch``
bump) sprinkled anywhere else silently reverts the hot path to
flush-per-event and no test notices — throughput decays, the cache
"works", and the 50k/100k admission p99s quietly blow their SLO.

EC001 pins two invariants across ``kubetpu/``:

- ``node_epoch`` is written only inside ``state/encode_cache.py`` (the
  cache owns its own versioning);
- a BARE ``invalidate_nodes()`` call — the full-epoch flush — appears
  only in the scheduler's node event handlers (``on_node_add``'s
  resync-duplicate branch, ``on_node_update``, ``on_node_delete``).
  Scoped calls (``invalidate_nodes(added=node)`` /
  ``invalidate_nodes(removed=node)``) are fine anywhere.
"""

from __future__ import annotations

import ast
import posixpath

from .core import Checker, ModuleInfo, Violation, register

#: the cache itself — the one module allowed to touch node_epoch
_OWNER = "kubetpu/state/encode_cache.py"

#: (file, function) pairs blessed to call the FULL-epoch flush
_BLESSED_FLUSH = {
    ("kubetpu/sched/scheduler.py", "on_node_add"),
    ("kubetpu/sched/scheduler.py", "on_node_update"),
    ("kubetpu/sched/scheduler.py", "on_node_delete"),
}


@register
class UnscopedEpochFlush(Checker):
    code = "EC001"
    title = "unscoped encode-cache epoch flush outside the blessed seam"
    rationale = (
        "The encode cache's node-epoch invalidation is SCOPED (PR 14): a "
        "node ADD extends cached rows with the appended nodes' columns — "
        "O(templates × Δnodes) — instead of clearing every node-dependent "
        "store; only updates/deletes (facts change at interior indices, "
        "or indices reindex) take the wholesale flush, and only through "
        "the scheduler's node event handlers. A bare invalidate_nodes() "
        "call added anywhere else — or a raw node_epoch assignment — "
        "silently reverts the 100k-node add-wave path to a full re-encode "
        "storm per event: nothing errors, the cache still 'works', and "
        "the scale-frontier admission p99s decay until a measured run "
        "notices. Call invalidate_nodes(added=node) for appends; route "
        "genuine full flushes through the blessed handlers so the scope "
        "decision stays reviewable in one place."
    )

    def covers(self, relpath: str) -> bool:
        if relpath == _OWNER:
            return False
        base = posixpath.basename(relpath)
        if base.startswith("epoch_") and base.endswith(".py"):
            return True     # the known-bad/known-good fixtures
        return relpath.startswith("kubetpu/") and relpath.endswith(".py")

    def collect(self, mod: ModuleInfo):
        out: list[Violation] = []
        parents: dict[int, str] = {}
        for fn in ast.walk(mod.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(fn):
                    parents.setdefault(id(sub), fn.name)
        is_fixture = posixpath.basename(mod.relpath).startswith("epoch_")
        for node in ast.walk(mod.tree):
            # raw node_epoch writes (assign / augassign) outside the owner
            targets: list[ast.expr] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            for tgt in targets:
                if (
                    isinstance(tgt, ast.Attribute)
                    and tgt.attr == "node_epoch"
                ):
                    out.append(Violation(
                        path=mod.relpath, line=node.lineno, code=self.code,
                        symbol=parents.get(id(node), ""),
                        message=(
                            "raw node_epoch write outside "
                            "state/encode_cache.py — the cache owns its "
                            "versioning; use invalidate_nodes(added=...) "
                            "or the blessed full-flush handlers"
                        ),
                    ))
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (
                isinstance(f, ast.Attribute)
                and f.attr == "invalidate_nodes"
            ):
                continue
            if node.args or node.keywords:
                continue    # scoped (added=...) call: fine anywhere
            where = (
                mod.relpath, parents.get(id(node), "")
            )
            if not is_fixture and where in _BLESSED_FLUSH:
                continue
            out.append(Violation(
                path=mod.relpath, line=node.lineno, code=self.code,
                symbol=parents.get(id(node), ""),
                message=(
                    "bare invalidate_nodes() — a FULL-epoch flush — "
                    "outside the blessed node-event seam: a node add "
                    "must pass added=<node> so the cache extends rows "
                    "instead of re-encoding the cluster per event"
                ),
            ))
        return out
