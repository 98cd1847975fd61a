"""Prometheus text exposition, as far as the benchmark reads it: samples by
family name and labels, and the difference between two scrapes. The
``_sum`` and ``_count`` of a histogram are exact, so a delta between the
window's two edges gives seconds and events with no bucket error."""

from __future__ import annotations

import re
import urllib.request

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{.*\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


class Scrape:
    """One parsed /metrics page: ``samples[name]`` is a list of
    (labels dict, value)."""

    def __init__(self, text: str) -> None:
        self.samples: dict[str, list[tuple[dict, float]]] = {}
        for line in text.splitlines():
            if not line or line[0] == "#":
                continue
            m = _SAMPLE.match(line)
            if m is None:
                continue
            name, labels, value = m.groups()
            try:
                v = float(value)
            except ValueError:
                continue
            self.samples.setdefault(name, []).append(
                (dict(_LABEL.findall(labels or "")), v))

    def total(self, name: str, **labels: str) -> float:
        """Sum of the family's samples whose labels contain ``labels``."""
        return sum(v for have, v in self.samples.get(name, ())
                   if all(have.get(k) == w for k, w in labels.items()))

    def buckets(self, name: str, **labels: str) -> dict[float, float]:
        """Cumulative histogram buckets {upper bound: count}, summed over
        the series that match."""
        out: dict[float, float] = {}
        for have, v in self.samples.get(name + "_bucket", ()):
            if all(have.get(k) == w for k, w in labels.items()):
                le = float(have["le"].replace("+Inf", "inf"))
                out[le] = out.get(le, 0.0) + v
        return out


def fetch(url: str, timeout_s: float = 10.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return resp.read().decode()


def scrape(base_url: str) -> Scrape:
    return Scrape(fetch(base_url.rstrip("/") + "/metrics"))


class Delta:
    """What the counters did between two scrapes of one component."""

    def __init__(self, before: Scrape, after: Scrape) -> None:
        self.before, self.after = before, after

    def total(self, name: str, **labels: str) -> float:
        return (self.after.total(name, **labels)
                - self.before.total(name, **labels))

    def by_labels(self, name: str, *keys: str) -> dict[str, float]:
        """The family's increase, summed per value of the labels ``keys``
        (joined by a space), what did not move left out."""
        out: dict[str, float] = {}
        for scrape, sign in ((self.after, 1.0), (self.before, -1.0)):
            for have, v in scrape.samples.get(name, ()):
                key = " ".join(have.get(k, "") for k in keys)
                out[key] = out.get(key, 0.0) + sign * v
        return {k: v for k, v in out.items() if v}

    def histogram_quantile(self, name: str, q: float,
                           **labels: str) -> float | None:
        """The q-quantile of the observations made between the scrapes,
        interpolated inside its bucket as PromQL's histogram_quantile
        does. None when nothing was observed. It moves in steps of the
        bucket layout: a per-layer figure, not an end-to-end one."""
        b0 = self.before.buckets(name, **labels)
        b1 = self.after.buckets(name, **labels)
        bounds = sorted(b1)
        counts = [b1[le] - b0.get(le, 0.0) for le in bounds]
        if not counts or counts[-1] <= 0:
            return None
        rank = q * counts[-1]
        prev_bound, prev_count = 0.0, 0.0
        for le, c in zip(bounds, counts):
            if c >= rank:
                if le == float("inf"):
                    return prev_bound
                width = c - prev_count
                frac = (rank - prev_count) / width if width > 0 else 1.0
                return prev_bound + (le - prev_bound) * frac
            prev_bound, prev_count = le, c
        return prev_bound
