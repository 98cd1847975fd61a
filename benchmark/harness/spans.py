"""The program's own spans, read from the scheduler's /trace as an operator
would. The Tracer's ring holds 4096 spans and records one ``bind`` span per
pod, so it forgets within seconds: poll it once a second and merge by
``span_id``."""

from __future__ import annotations

import json

from benchmark.harness import intervals as iv
from benchmark.harness.promtext import fetch

#: which span covers an idle gap, first match wins: a span nested in another
#: stands before it (``encode-spread`` in ``encode``; ``explain`` and
#: ``bind-dispatch`` in ``scheduling-cycle``), the loop's own spans before
#: the asynchronous ``bind`` spans, which overlap them, and the
#: ``loop-iteration`` that holds them all comes last
PRIORITY = ("assign", "encode-spread", "encode", "snapshot", "extenders",
            "explain", "bind-dispatch", "scheduling-cycle", "drain", "pump",
            "bind", "loop-iteration")
LABEL = {"scheduling-cycle": "in a cycle but in no span",
         "loop-iteration": "in an iteration but in no span"}
OUTSIDE = "outside any cycle (pump, sleep, generator)"


class SpanLog:
    def __init__(self, base_url: str) -> None:
        self.url = base_url.rstrip("/") + "/trace"
        self.spans: dict[int, tuple[str, float, float]] = {}

    def poll(self) -> int:
        """Merge the ring's present contents; returns how many were new."""
        doc = json.loads(fetch(self.url))
        new = 0
        for ev in doc.get("traceEvents", ()):
            if ev.get("ph") != "X":
                continue
            sid = ev.get("args", {}).get("span_id")
            if sid is None or sid in self.spans:
                continue
            start = ev["ts"] / 1e6          # the Tracer's perf_counter clock
            self.spans[sid] = (ev["name"], start, start + ev["dur"] / 1e6)
            new += 1
        return new

    def by_name(self, offset_s: float = 0.0) -> dict[str, iv.Intervals]:
        """Span intervals per name, moved by ``offset_s`` onto another
        clock."""
        out: dict[str, list] = {}
        for name, s, e in self.spans.values():
            out.setdefault(name, []).append((s + offset_s, e + offset_s))
        return {name: iv.union(xs) for name, xs in out.items()}


def attribute_gaps(idle: iv.Intervals,
                   spans: dict[str, iv.Intervals]) -> dict[str, float]:
    """Seconds of device idleness by the program span that covers them."""
    out: dict[str, float] = {}
    left = idle
    for name in PRIORITY:
        cover = iv.intersect(left, spans.get(name, []))
        if cover:
            out[LABEL.get(name, name)] = iv.length(cover)
            left = iv.subtract(left, cover)
    if left:
        out[OUTSIDE] = iv.length(left)
    return out
