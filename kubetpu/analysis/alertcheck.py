"""Alert-rule threshold discipline checker (AL001).

PR 16's anomaly sentinel judges live series against a DECLARATIVE rule
table (``telemetry/rules.py``): every budget, burn threshold, outlier
trip point, and window lives on a ``Rule`` and nowhere else. That split
is what makes the alerting reviewable — one file answers "when does this
page?" — and what keeps the run-scaled variants honest:
``fast_rules()`` derives its windows from the SAME rows production
evaluates, so a threshold that drifts into an evaluator is invisible to
the table, untested by the scaled suite, and silently different between
``kubetpu scheduler --sentinel on`` and a perf-harness run.

AL001 pins the seam on the evaluation side (``telemetry/sentinel.py``):

- inside the evaluator functions (``evaluate*`` / ``_eval*``), numeric
  literals may not appear in comparison expressions — thresholds are
  read off ``rule.*``. Structural literals 0 / 1 / -1 (emptiness, index
  arithmetic) stay legal;
- nowhere in the evaluation module may a call smuggle a threshold past
  the table via a literal keyword (``threshold= / budget_ms= /
  slo_budget_ms= / burn_threshold= / mad_k= / ewma_alpha=``) — a
  ``replace(rule, burn_threshold=3.0)`` is a table edit hiding at an
  evaluation site.

The table itself (``rules.py``) is deliberately OUT of scope: it is the
one home those literals are supposed to have.
"""

from __future__ import annotations

import ast
import posixpath

from .core import Checker, ModuleInfo, Violation, register

#: the evaluation module the seam governs (the rules table is exempt —
#: it is the literals' one legitimate home)
_EVALUATION_MODULES = ("kubetpu/telemetry/sentinel.py",)

#: keyword names that ARE thresholds: a numeric literal passed under one
#: of these outside rules.py is a table row hiding at a call site
_THRESHOLD_KWARGS = frozenset({
    "threshold", "budget_ms", "slo_budget_ms", "burn_threshold",
    "mad_k", "ewma_alpha",
})

#: structural literals that never flag: emptiness/count checks and index
#: arithmetic are not thresholds
_STRUCTURAL = (0, 1, -1)


def _numeric_literal(node: ast.expr) -> "float | None":
    """The numeric value of a literal expression (including ``-x``),
    else None. Bools are not numbers here."""
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = _numeric_literal(node.operand)
        return None if inner is None else -inner
    if (
        isinstance(node, ast.Constant)
        and isinstance(node.value, (int, float))
        and not isinstance(node.value, bool)
    ):
        return float(node.value)
    return None


@register
class AlertThresholdLiteral(Checker):
    code = "AL001"
    title = "alert threshold literal at an evaluation site"
    rationale = (
        "The sentinel's alerting contract is a DECLARATIVE rule table "
        "(telemetry/rules.py): budgets, burn thresholds, outlier trip "
        "points and windows live on Rule rows and nowhere else, so one "
        "file answers 'when does this page?' and the run-scaled "
        "fast_rules() variants provably evaluate the same policy as "
        "production. A literal comparison inside an evaluator — "
        "`if burn > 6.0` instead of `if burn > rule.burn_threshold` — "
        "silently forks that policy: the table still reads 6x, reviews "
        "and scaled tests still trust it, and the live sentinel pages "
        "on a number nobody can find. Same for a threshold-named "
        "keyword carrying a literal (replace(rule, burn_threshold=3.0)) "
        "at an evaluation site: that is a table edit hiding in the "
        "evaluator. Read thresholds off the rule; change them in "
        "rules.py."
    )

    def covers(self, relpath: str) -> bool:
        base = posixpath.basename(relpath)
        if base.startswith("alert_") and base.endswith(".py"):
            return True     # the known-bad/known-good fixtures
        return relpath in _EVALUATION_MODULES

    def collect(self, mod: ModuleInfo):
        out: list[Violation] = []
        parents: dict[int, str] = {}
        for fn in ast.walk(mod.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(fn):
                    parents.setdefault(id(sub), fn.name)
        # 1) literal comparisons inside the evaluators
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not (
                fn.name.startswith("evaluate") or fn.name.startswith("_eval")
            ):
                continue
            for cmp_node in ast.walk(fn):
                if not isinstance(cmp_node, ast.Compare):
                    continue
                for sub in ast.walk(cmp_node):
                    val = (
                        None if not isinstance(sub, ast.Constant)
                        else _numeric_literal(sub)
                    )
                    if val is None or val in _STRUCTURAL:
                        continue
                    out.append(Violation(
                        path=mod.relpath, line=sub.lineno, code=self.code,
                        symbol=fn.name,
                        message=(
                            f"literal {sub.value!r} compared inside "
                            f"evaluator {fn.name}() — thresholds live on "
                            "the rule table (rules.py); read rule.<attr> "
                            "here"
                        ),
                    ))
        # 2) threshold-named keywords carrying literals, module-wide
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if kw.arg not in _THRESHOLD_KWARGS:
                    continue
                val = _numeric_literal(kw.value)
                if val is None:
                    continue
                out.append(Violation(
                    path=mod.relpath, line=kw.value.lineno, code=self.code,
                    symbol=parents.get(id(node), ""),
                    message=(
                        f"literal {kw.arg}={val:g} at an evaluation "
                        "site — a rule-table edit hiding in the "
                        "evaluator; declare it on the Rule in rules.py"
                    ),
                ))
        return out
