"""The benchmark's arithmetic over time stamps. Pure functions of numbers:
no clock is read here, so the tests drive them with synthetic stamps."""

from __future__ import annotations

import math
from typing import Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; ``values`` need not be sorted."""
    if not values:
        raise ValueError("quantile of nothing")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tail_is_resolved(n: int, q: float, beyond: int = 10) -> bool:
    """A percentile is reported only with ``beyond`` samples past it."""
    return n * (1.0 - q) >= beyond


def interval_rate(batches: Sequence[tuple[float, int]], t0: float,
                  t1: float) -> tuple[float, int, float]:
    """Pods per second between the first and the last binding event inside
    [t0, t1]. ``batches`` are (arrival time, pods) of each delivery of the
    client's watch. The first delivery's own pods are left out: they were
    bound before its stamp, in time that the interval does not cover. So a
    cycle cut by either edge of the window does not quantise the rate.
    Returns (rate, pods counted, seconds between the two events)."""
    inside = sorted((t, n) for t, n in batches if t0 <= t <= t1 and n > 0)
    if len(inside) < 2:
        raise ValueError(
            f"{len(inside)} binding events inside the window: no interval")
    span = inside[-1][0] - inside[0][0]
    if span <= 0:
        raise ValueError("binding events inside the window share one stamp")
    pods = sum(n for _t, n in inside[1:])
    return pods / span, pods, span


def slope_rate(batches: Sequence[tuple[float, int]], t0: float,
               t1: float) -> tuple[float, int, int]:
    """Pods per second as the least-squares slope of the cumulative count
    of pods seen bound against the arrival time of each delivery inside
    [t0, t1]. It uses every delivery of the window, not its two outermost:
    where the binding comes in bursts with pauses between them (measured
    on the chip, PR 22: two cycles of 1024 every 4.5 s), ``interval_rate``
    credits the first burst's later deliveries to an interval that does
    not hold their time and reads 13% high, and a plain count over the
    window moves by a burst with the phase of its edges. The slope is
    unbiased on that pattern and spreads least (PERF.md, PR 22).
    Returns (rate, pods inside the window, deliveries)."""
    inside = sorted((t, n) for t, n in batches if t0 <= t <= t1 and n > 0)
    if len(inside) < 3:
        raise ValueError(
            f"{len(inside)} binding events inside the window: no slope")
    n = len(inside)
    mean_t = sum(t for t, _ in inside) / n
    cum, total = [], 0
    for _t, k in inside:
        total += k
        cum.append(total)
    mean_c = sum(cum) / n
    var = sum((t - mean_t) ** 2 for t, _ in inside)
    if var <= 0:
        raise ValueError("binding events inside the window share one stamp")
    cov = sum((t - mean_t) * (c - mean_c) for (t, _), c in zip(inside, cum))
    return cov / var, total, n


def due_latencies_ms(due: Sequence[float], bound_at: Sequence[float | None],
                     t0: float, t1: float) -> tuple[list[float], int]:
    """Latency of every pod DUE inside [t0, t1], from its due time on the
    generator's schedule (not from when it was really sent, which would hide
    a stall of the generator or of the server's admission) to the moment the
    client saw it bound. Returns (latencies in ms, pods due in the window
    that were never seen bound)."""
    out: list[float] = []
    missing = 0
    for d, b in zip(due, bound_at):
        if not t0 <= d <= t1:
            continue
        if b is None:
            missing += 1
        else:
            out.append((b - d) * 1e3)
    return out, missing


def backlog_at(sent: Sequence[float], bound_at: Sequence[float | None],
               t: float) -> int:
    """Pods sent by ``t`` and not yet seen bound at ``t``."""
    return sum(1 for s, b in zip(sent, bound_at)
               if s <= t and (b is None or b > t))
