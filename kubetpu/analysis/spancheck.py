"""Trace-span balance checkers (TS001, TS002).

The tracer's spans close in ``Span.__exit__`` — but only when the span
was opened as a ``with`` context. A span opened by calling
``tracer.span(…)`` and entering it by hand leaks on any exception path:
the span never lands in the buffer, the parent stack is corrupted, and
every later span mis-parents — the whole Chrome-trace export (and the
perf harness numbers derived from it) silently skews. Same story for the
JAX profiler: ``start_trace`` without a ``finally: stop_trace`` leaves
the profiler running forever after one raise.
"""

from __future__ import annotations

import ast

from .astutil import dotted, terminal_attr
from .core import Checker, ModuleInfo, Violation, register

#: receivers that are tracers by project convention
_TRACER_NAMES = {"tracer", "_tracer", "trace", "tr"}


@register
class SpanWithoutWith(Checker):
    code = "TS001"
    title = "tracer span opened outside a with-statement"
    rationale = (
        "Tracer.span is a contextmanager: only __exit__ pops the parent "
        "stack and buffers the span. Calling .span() and driving it by "
        "hand (or storing the manager for later) leaks the span on any "
        "exception between open and close — the parent stack is then "
        "permanently misaligned and every subsequent span in the process "
        "mis-parents. Spans open with `with tracer.span(…):`, always; "
        "for timings measured off-stack use Tracer.record, which takes "
        "explicit start/end and cannot leak."
    )

    def collect(self, mod: ModuleInfo):
        with_calls: set[int] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.With):
                for item in node.items:
                    if isinstance(item.context_expr, ast.Call):
                        with_calls.add(id(item.context_expr))
        out: list[Violation] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute) or f.attr != "span":
                continue
            recv = terminal_attr(f.value)
            if recv not in _TRACER_NAMES:
                continue
            if id(node) in with_calls:
                continue
            out.append(Violation(
                path=mod.relpath, line=node.lineno, code=self.code,
                symbol=dotted(f) or "span",
                message=(
                    "tracer.span(…) not used as a `with` context — the "
                    "span leaks (and mis-parents every later span) on "
                    "any exception path; use `with tracer.span(…):` or "
                    "Tracer.record for off-stack timings"
                ),
            ))
        return out


@register
class ProfilerTraceBalance(Checker):
    code = "TS002"
    title = "jax profiler trace started without a finally-stop"
    rationale = (
        "jax.profiler.start_trace leaves the profiler capturing until "
        "stop_trace runs — an exception between the two keeps it "
        "recording for the life of the process, swamping the trace "
        "directory and skewing every later measurement. start_trace "
        "appears only with a stop_trace in a `finally` block of the "
        "same function."
    )

    def collect(self, mod: ModuleInfo):
        out: list[Violation] = []
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            starts = []
            has_finally_stop = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    name = dotted(node.func) or ""
                    if name.endswith("start_trace"):
                        starts.append(node.lineno)
                if isinstance(node, ast.Try):
                    for final_stmt in node.finalbody:
                        for sub in ast.walk(final_stmt):
                            if isinstance(sub, ast.Call) and (
                                dotted(sub.func) or ""
                            ).endswith("stop_trace"):
                                has_finally_stop = True
            for line in starts:
                if has_finally_stop:
                    continue
                out.append(Violation(
                    path=mod.relpath, line=line, code=self.code,
                    symbol=fn.name,
                    message=(
                        "jax.profiler.start_trace without a "
                        "stop_trace in a finally block of the same "
                        "function — the profiler runs forever after "
                        "one exception"
                    ),
                ))
        return out
