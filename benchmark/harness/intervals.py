"""Arithmetic on sets of half-open time intervals, each a sorted list of
disjoint (start, end) pairs."""

from __future__ import annotations

from typing import Iterable

Intervals = list[tuple[float, float]]


def union(spans: Iterable[tuple[float, float]]) -> Intervals:
    out: Intervals = []
    for a, b in sorted(s for s in spans if s[1] > s[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(xs: Intervals) -> float:
    return sum(b - a for a, b in xs)


def intersect(xs: Intervals, ys: Intervals) -> Intervals:
    out: Intervals = []
    i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs: Intervals, ys: Intervals) -> Intervals:
    out: Intervals = []
    j = 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(xs: Intervals, lo: float, hi: float) -> Intervals:
    return intersect(xs, [(lo, hi)])


def gaps(xs: Intervals, lo: float, hi: float) -> Intervals:
    """The parts of [lo, hi] that ``xs`` does not cover."""
    return subtract([(lo, hi)], xs)
