"""Prometheus-shaped metric primitives + registry + text exposition.

The analog of staging/src/k8s.io/component-base/metrics (which wraps
client_golang): Counter/Gauge/Histogram vectors keyed by label values, a
Registry for /metrics exposition (Prometheus text format 0.0.4), and
``exponential_buckets`` matching prometheus.ExponentialBuckets — the bucket
layouts in pkg/scheduler/metrics/metrics.go are reproduced exactly so
dashboards built for the reference read identically.

Histogram quantiles use the Prometheus histogram_quantile estimation
(linear interpolation within the bucket), so the perf harness's p99 numbers
come from the same math a PromQL query would produce.
"""

from __future__ import annotations

import bisect
import math
import threading
from dataclasses import dataclass, field


def exponential_buckets(start: float, factor: float, count: int) -> list[float]:
    """prometheus.ExponentialBuckets: count buckets, start * factor^i."""
    return [start * (factor ** i) for i in range(count)]


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = "", labels: tuple[str, ...] = (),
                 declared: dict[str, tuple] | None = None):
        self.name = name
        self.help = help
        self.label_names = tuple(labels)
        # label name -> tuple of the ONLY legal values (the staged-latency
        # {stage} contract): an unknown value raises at .labels() time, and
        # the graftcheck MR004 checker enforces the same set at parse time
        # for literal call sites — declared sets cannot drift silently.
        self.declared = {
            k: tuple(v) for k, v in (declared or {}).items()
        }
        self._children: dict[tuple, "_Metric"] = {}
        self._lock = threading.Lock()

    def labels(self, *values: str):
        """Child metric for one label-value combination (Vec semantics)."""
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name}: expected labels {self.label_names}, got {values}"
            )
        key = tuple(str(v) for v in values)
        for name, value in zip(self.label_names, key):
            allowed = self.declared.get(name)
            if allowed is not None and value not in allowed:
                raise ValueError(
                    f"{self.name}: label {name}={value!r} outside the "
                    f"declared set {allowed}"
                )
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._make_child()
                self._children[key] = child
            return child

    def _make_child(self):
        raise NotImplementedError

    def _children_snapshot(self) -> list[tuple]:
        """Stable view for iteration — labels() may insert concurrently
        (the scheduler thread observes while a /metrics scrape walks)."""
        with self._lock:
            return list(self._children.items())

    def samples(self):
        """Yield (suffix, label_values, extra_label_pairs, value)."""
        raise NotImplementedError


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name, help="", labels=(), declared=None):
        super().__init__(name, help, labels, declared)
        self.value = 0.0

    def _make_child(self):
        return Counter(self.name)

    def inc(self, amount: float = 1.0) -> None:
        # locked: apiserver handler threads and the scheduler loop mutate
        # concurrently (ThreadingHTTPServer); a bare += is a lost-update
        # race across threads
        with self._lock:
            self.value += amount

    def set_total(self, value: float) -> None:
        """For a counter whose OWNER keeps the monotonic total (the loop's
        phase clock, on a thread that must take no lock) and hands it over
        at scrape time."""
        with self._lock:
            self.value = value

    def samples(self):
        if self.label_names:
            for key, child in self._children_snapshot():
                yield "", key, child.value
        else:
            yield "", (), self.value


class Gauge(Counter):
    kind = "gauge"

    def _make_child(self):
        return Gauge(self.name)

    def set(self, value: float) -> None:
        with self._lock:
            self.value = value

    def dec(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value -= amount


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help="", labels=(), buckets=None, declared=None):
        super().__init__(name, help, labels, declared)
        self.buckets = list(buckets if buckets is not None
                            else exponential_buckets(0.001, 2, 15))
        self.counts = [0] * (len(self.buckets) + 1)   # +Inf tail
        self.total = 0
        self.sum = 0.0

    def _make_child(self):
        return Histogram(self.name, buckets=self.buckets)

    def observe(self, value: float) -> None:
        i = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self.counts[i] += 1
            self.total += 1
            self.sum += value

    def observe_n(self, value: float, n: int) -> None:
        """n identical observations in O(1) — batch cycles record one
        duration for every pod of the batch."""
        if n <= 0:
            return
        i = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self.counts[i] += n
            self.total += n
            self.sum += value * n

    def _consistent_state(self) -> tuple[list[int], int, float]:
        """counts/total/sum copied under the lock — a reader racing an
        observe() must not see counts moved but total not (a torn,
        non-monotonic histogram breaks histogram_quantile)."""
        with self._lock:
            return list(self.counts), self.total, self.sum

    def merged(self) -> "Histogram":
        """Aggregate across children (and self) — what a PromQL sum() over
        label dimensions sees."""
        out = Histogram(self.name, buckets=self.buckets)
        children = [c for _, c in self._children_snapshot()]
        sources = children or [self]
        if children and self.total:
            sources.append(self)
        for src in sources:
            counts, total, s = src._consistent_state()
            for i, c in enumerate(counts):
                out.counts[i] += c
            out.total += total
            out.sum += s
        return out

    def since(self, earlier: "Histogram") -> "Histogram":
        """The delta histogram vs an earlier ``merged()`` snapshot — scopes
        quantiles to a measurement window (the perf harness's per-workload
        p99)."""
        h = self.merged()
        out = Histogram(self.name, buckets=self.buckets)
        out.counts = [a - b for a, b in zip(h.counts, earlier.counts)]
        out.total = h.total - earlier.total
        out.sum = h.sum - earlier.sum
        return out

    def quantile(self, q: float) -> float:
        """histogram_quantile(q, …): linear interpolation inside the target
        bucket; NaN when empty; the last bucket's upper bound caps +Inf."""
        # merged() copies under the lock even without children, so a racing
        # observe() cannot tear the read
        h = self.merged()
        if h.total == 0:
            return float("nan")
        rank = q * h.total
        acc = 0
        for i, c in enumerate(h.counts):
            acc += c
            if acc >= rank and c > 0:
                lo = h.buckets[i - 1] if i > 0 else 0.0
                hi = h.buckets[i] if i < len(h.buckets) else h.buckets[-1]
                frac = (rank - (acc - c)) / c
                return lo + (hi - lo) * frac
        return h.buckets[-1]

    def samples(self):
        def rows(child, key):
            counts, total, s = child._consistent_state()
            acc = 0
            for i, ub in enumerate(child.buckets):
                acc += counts[i]
                yield "_bucket", key + (("le", _fmt(ub)),), acc
            yield "_bucket", key + (("le", "+Inf"),), total
            yield "_sum", key, s
            yield "_count", key, total

        if self.label_names:
            for key, child in self._children_snapshot():
                labeled = tuple(zip(self.label_names, key))
                yield from rows(child, labeled)
        else:
            yield from rows(self, ())


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == int(v):
        return str(int(v))
    return repr(v)


@dataclass
class Registry:
    """Named metric registry + Prometheus text exposition (the legacy
    registry + /metrics handler of component-base)."""

    metrics: dict[str, _Metric] = field(default_factory=dict)

    def register(self, metric: _Metric) -> _Metric:
        if metric.name in self.metrics:
            raise ValueError(f"metric {metric.name!r} already registered")
        self.metrics[metric.name] = metric
        return metric

    def counter(self, name, help="", labels=(), declared=None) -> Counter:
        return self.register(Counter(name, help, labels, declared))

    def gauge(self, name, help="", labels=(), declared=None) -> Gauge:
        return self.register(Gauge(name, help, labels, declared))

    def histogram(self, name, help="", labels=(), buckets=None,
                  declared=None) -> Histogram:
        return self.register(Histogram(name, help, labels, buckets, declared))

    def get(self, name: str) -> _Metric | None:
        return self.metrics.get(name)

    def expose(self) -> str:
        """Prometheus text format 0.0.4."""
        out: list[str] = []
        for name in sorted(self.metrics):
            m = self.metrics[name]
            out.append(f"# HELP {name} {m.help}")
            out.append(f"# TYPE {name} {m.kind}")
            for suffix, label_pairs, value in m.samples():
                if isinstance(label_pairs, tuple) and label_pairs and (
                    not isinstance(label_pairs[0], tuple)
                ):
                    # bare child key from a vec Counter/Gauge
                    label_pairs = tuple(zip(m.label_names, label_pairs))
                if label_pairs:
                    body = ",".join(
                        f'{k}="{_esc_label(v)}"' for k, v in label_pairs
                    )
                    out.append(f"{name}{suffix}{{{body}}} {_num(value)}")
                else:
                    out.append(f"{name}{suffix} {_num(value)}")
        return "\n".join(out) + "\n"


def _esc_label(v) -> str:
    """Exposition-format label-value escaping (text format 0.0.4): label
    values may carry any UTF-8, so backslash, double-quote, and newline
    must be escaped or one hostile value corrupts the whole scrape page."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _num(v) -> str:
    f = float(v)
    if f == int(f):
        return str(int(f))
    return repr(f)
