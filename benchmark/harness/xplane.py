"""The reduction from a profiler trace (``.xplane.pb``) to numbers, read
with ``jax.profiler.ProfileData`` and nothing else. Times are seconds on the
trace's own clock.

What a TPU trace holds (looked at by hand on the v5e, PR 22; the recorded
``tests/data/tpu_v5e_small.xplane.pb`` shows it): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per executed
program (``jit_<name>(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per HLO operation, named by its whole HLO text and nested where an
operation (a ``while``) holds others (``Async XLA Ops`` repeats the
asynchronous copies as start-to-done spans and is not read); and a host
plane (``/host:CPU``) with one line per thread, on which
``jax.profiler.TraceAnnotation`` events land under their own name. The
device's clock is not the host's: in the recorded trace the first program
is stamped 1.1 ms BEFORE the anchor that the host wrote before launching
it. Spans and device events are therefore aligned to a millisecond or two,
which is enough to attribute gaps between cycles of hundreds of ms.
"""

from __future__ import annotations

import glob
import os
import re

from benchmark.harness import intervals as iv

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter"
    r"|collective-broadcast", re.I)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    """A trace from the file ``jax.profiler.stop_trace`` wrote. A run reads
    its own trace as bytes (``phases._reduce_trace``); this and
    ``find_xplane`` serve the fixture's recorder and ``tools/``."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def short_name(text: str) -> str:
    """An operation's own name. The trace gives the whole HLO line
    (``%fusion.2 = f32[128]{0} fusion(f32[128]{0} %all-reduce.1), ...``);
    what it consumes must not be mistaken for what it is."""
    return text.split(" = ", 1)[0].lstrip("%")


def _events(line) -> list[tuple[float, float, str]]:
    return [(e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9,
             short_name(e.name)) for e in line.events]


def annotation(data, name: str) -> tuple[float, float] | None:
    """(start, end) of the first host event called ``name``."""
    best = None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    s = e.start_ns / 1e9
                    if best is None or s < best[0]:
                        best = (s, (e.start_ns + e.duration_ns) / 1e9)
    return best


def self_times(events: list[tuple[float, float, str]]) -> tuple[
        dict[str, float], list[tuple[float, float, str]]]:
    """From the nested events of one line: the time each name spent outside
    its children, and the leaf events (those that hold no other)."""
    by_name: dict[str, float] = {}
    leaves: list[tuple[float, float, str]] = []
    stack: list[list] = []          # [start, end, name, children seconds]

    def close(top: list) -> None:
        s, e, name, inner = top
        by_name[name] = by_name.get(name, 0.0) + max(e - s - inner, 0.0)
        if inner == 0.0:
            leaves.append((s, e, name))

    for s, e, name in sorted(events, key=lambda x: (x[0], -(x[1] - x[0]))):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, name, 0.0])
    while stack:
        close(stack.pop())
    return by_name, leaves


def reduce_trace(data, window: tuple[float, float] | None = None,
                 assign_program: str = "") -> dict:
    """Per chip and over the chips: seconds busy (the union of the intervals
    in which an operation ran), the programs run, operations by self time,
    collective time and its exposed part, and the idle gaps. ``window``
    clips everything to (start, end) on the trace's clock; without it the
    window is the extent of the device events."""
    chips = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None:
            continue
        lines = {line.name: line for line in plane.lines}
        ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        modules = _events(lines[MODULES_LINE]) \
            if MODULES_LINE in lines else []
        chips.append((int(m.group(1)), ops, modules))
    if not chips:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    chips.sort()
    if window is None:
        every = [e for _c, ops, mods in chips for e in ops + mods]
        if not every:
            raise ValueError("the trace holds no device event")
        window = (min(e[0] for e in every), max(e[1] for e in every))
    lo, hi = window
    per_chip = []
    op_self: dict[str, float] = {}
    module_s: dict[str, float] = {}
    assign_s, assign_runs = 0.0, 0
    for chip, ops, modules in chips:
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in ops
               if e > lo and s < hi]
        modules = [(max(s, lo), min(e, hi), n) for s, e, n in modules
                   if e > lo and s < hi]
        busy = iv.union((s, e) for s, e, _n in ops + modules)
        by_name, leaves = self_times(ops)
        coll = iv.union((s, e) for s, e, n in ops if COLLECTIVE.search(n))
        compute = iv.union((s, e) for s, e, n in leaves
                           if not COLLECTIVE.search(n))
        for name, secs in by_name.items():
            op_self[name] = op_self.get(name, 0.0) + secs
        for s, e, name in modules:
            module_s[name] = module_s.get(name, 0.0) + (e - s)
            if assign_program and assign_program in name:
                assign_s += e - s
                assign_runs += 1
        per_chip.append({
            "chip": chip, "busy_s": iv.length(busy),
            "idle_share": 1.0 - iv.length(busy) / (hi - lo),
            "collective_s": iv.length(coll),
            "collective_exposed_s": iv.length(iv.subtract(coll, compute)),
            "ops": len(ops), "programs": len(modules),
            "busy": busy,
        })
    n = len(per_chip)
    return {
        "window_s": hi - lo, "window": (lo, hi), "chips": per_chip,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "collective_s": sum(c["collective_s"] for c in per_chip) / n,
        "collective_exposed_s":
            sum(c["collective_exposed_s"] for c in per_chip) / n,
        "op_self_s": {k: v / n for k, v in op_self.items()},
        "module_s": {k: v / n for k, v in module_s.items()},
        "assign_s": assign_s / n, "assign_runs": assign_runs / n,
    }


def top(table: dict[str, float], k: int = 10) -> list[list]:
    return [[name, secs] for name, secs in
            sorted(table.items(), key=lambda kv: -kv[1])[:k]]


def describe(data) -> list[str]:
    """Planes and lines with their event counts: what to look at by hand
    before trusting the reduction on a new kind of trace."""
    out = []
    for plane in data.planes:
        for line in plane.lines:
            n = sum(1 for _ in line.events)
            out.append(f"{plane.name} | {line.name} | {n} events")
    return out
