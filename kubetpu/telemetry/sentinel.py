"""In-process anomaly sentinel — the first ACTIVE layer of the
observability stack.

Every earlier telemetry layer is passive: spans, histograms, flight
records and perf results exist, but a blown admission SLO or an fsync
stall is only discovered post-hoc, after the evidence (queue state,
cache stats, the outlier cycle's trace slice) is gone. The sentinel
closes that loop in-process:

- it **subscribes to the live metric series** its owner already emits —
  it re-reads the owner's own ``/metrics`` text (``metrics_fn``) on an
  evaluation cadence and keeps a bounded per-rule history of cumulative
  counts, so every windowed rate/fraction is a delta between two
  scrapes of the same source of truth the operator sees;
- it **evaluates the declarative rule table** (rules.py): multi-window
  burn-rate SLO rules against declared budgets (``slo_budget_ms`` from
  the PR-14 trace profiles, or a fixed per-rule budget), windowed
  ratio/delta rules, and EWMA/MAD robust outlier rules for series
  without budgets;
- it runs the **full alert lifecycle**: pending → firing → resolved,
  deduped by fingerprint (a repeated spike re-fires the SAME alert,
  bumping its episode count, never duplicating it), visible at
  ``/debug/alerts`` and merged process-wide by the collector at
  ``/telemetry/alerts``;
- when a rule fires it captures a **diagnostic bundle** through ONE
  seam (``capture_bundle``): last-N cycle records, the queue snapshot
  with per-pod backoff deadlines, encode-cache/WAL stats (whatever
  ``bundle_sources`` the owner bound), per-thread py stacks, RSS, and
  the surrounding chrome-trace slice — served at ``/debug/bundle``,
  shipped to the collector, rendered by ``kubetpu bundle``.

Drive model: a loop-owned component (the scheduler) calls
``maybe_evaluate()`` at its cycle boundary — zero threads, overhead on
the owner's clock, where an on/off pair of runs can price it; a
thread-served component (the apiserver) calls ``start()`` for a cadence
thread.
Escape hatch by construction: a component without a sentinel performs
zero extra work.
"""

from __future__ import annotations

import hashlib
import json
import statistics
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Callable

from ..metrics.textparse import ParseError, parse_prometheus_text
from .rules import (
    BURN_RATE,
    DELTA,
    LEVEL,
    OUTLIER,
    RATIO,
    Rule,
    default_rules,
)

#: alert lifecycle states
PENDING = "pending"
FIRING = "firing"
RESOLVED = "resolved"

#: MAD → standard-deviation scale for a normal distribution
MAD_SCALE = 1.4826
#: robust-sigma floor as a fraction of the EWMA baseline — a perfectly
#: flat series (MAD 0) must not make every microscopic jitter infinite
SIGMA_FLOOR_FRAC = 0.05

#: per-rule history entries kept (hard cap; time-based pruning first)
MAX_HISTORY = 4096
#: outlier observation ring
MAX_OBSERVATIONS = 256
#: py-stack frames kept per thread in a bundle
STACK_FRAMES = 24
#: spans scanned for the bundle's trace slice
TRACE_SCAN_SPANS = 4096


class Alert:
    """One fingerprint's lifecycle record. Mutable by design: the same
    object survives pending → firing → resolved and re-fires on the next
    episode (dedup is identity, not append)."""

    def __init__(self, fingerprint: str, rule: Rule) -> None:
        self.fingerprint = fingerprint
        self.rule = rule.name
        self.series = rule.series
        self.severity = rule.severity
        self.state = PENDING
        self.value: float | None = None
        self.reason = ""
        self.since_wall = 0.0          # first breach of the current episode
        self.fired_at_wall: float | None = None
        self.resolved_at_wall: float | None = None
        self.breach_streak = 0
        self.clean_streak = 0
        self.fires = 0                 # firing episodes (dedup counter)
        self.bundle_id: int | None = None

    def to_json(self) -> dict:
        return {
            "fingerprint": self.fingerprint,
            "rule": self.rule,
            "series": self.series,
            "severity": self.severity,
            "state": self.state,
            "value": self.value,
            "reason": self.reason,
            "since_wall": self.since_wall,
            "fired_at_wall": self.fired_at_wall,
            "resolved_at_wall": self.resolved_at_wall,
            "fires": self.fires,
            "bundle_id": self.bundle_id,
        }


def _labels_match(sample, labels: tuple) -> bool:
    return all(sample.label(k) == v for k, v in labels)


def _rss_bytes() -> int | None:
    try:
        with open("/proc/self/status", encoding="ascii") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:  # noqa: BLE001 — RSS is advisory bundle context
        return None


def _py_stacks(max_frames: int = STACK_FRAMES) -> dict[str, list[str]]:
    """Every live thread's current stack, bounded — the "what was the
    process DOING" section of a bundle."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: dict[str, list[str]] = {}
    for tid, frame in sys._current_frames().items():
        stack = traceback.format_stack(frame)
        out[f"{names.get(tid, 'thread')}-{tid}"] = [
            line.rstrip() for line in stack[-max_frames:]
        ]
    return out


class AlertSink:
    """Out-of-process alert delivery — one record per lifecycle
    TRANSITION (fired / resolved), never per evaluation pass. Specs:

    - ``file:PATH``   — append-only ndjson, one line per transition
      (tail -f it, or point a log shipper at it);
    - ``webhook:URL`` — one POST per transition, JSON body.

    Best-effort by contract: a full disk or a dead webhook endpoint
    bumps ``errors`` and the lifecycle proceeds — delivery failure must
    never take the sentinel (or its owner) down with it."""

    def __init__(self, spec: str, timeout_s: float = 5.0) -> None:
        scheme, sep, target = spec.partition(":")
        if not sep or scheme not in ("file", "webhook") or not target:
            raise ValueError(
                f"alert sink spec {spec!r}: expected file:PATH or "
                f"webhook:URL"
            )
        self.spec = spec
        self.scheme = scheme
        self.target = target
        self.timeout_s = timeout_s
        self.delivered = 0
        self.errors = 0
        self._lock = threading.Lock()

    def deliver(self, transition: str, alert: dict,
                process: str = "") -> bool:
        record = {
            "transition": transition,
            "ts_wall": time.time(),
            "process": process,
            "alert": alert,
        }
        try:
            if self.scheme == "file":
                line = json.dumps(record, default=str) + "\n"
                with self._lock:
                    with open(self.target, "a", encoding="utf-8") as f:
                        f.write(line)
            else:
                import urllib.request

                req = urllib.request.Request(
                    self.target,
                    data=json.dumps(record, default=str).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(
                    req, timeout=self.timeout_s
                ) as resp:
                    resp.read()
        except Exception:  # noqa: BLE001 — failure-counted, never fatal
            with self._lock:
                self.errors += 1
            return False
        with self._lock:
            self.delivered += 1
        return True

    def stats(self) -> dict:
        with self._lock:
            return {
                "spec": self.spec,
                "delivered": self.delivered,
                "errors": self.errors,
            }


class Sentinel:
    """See module docstring. Thread-safe: the evaluation driver (owner
    loop or cadence thread), diagnostics readers and the exporter share
    state under one lock."""

    def __init__(
        self,
        metrics_fn: "Callable[[], str] | None" = None,
        rules: "tuple[Rule, ...] | None" = None,
        process: str = "",
        component: str = "",
        slo_budget_ms: "float | None" = None,
        interval_s: float = 1.0,
        clock: Callable[[], float] = time.monotonic,
        wall: Callable[[], float] = time.time,
        tracer=None,
        bundle_sources: "dict[str, Callable[[], Any]] | None" = None,
        max_bundles: int = 8,
        trace_window_s: float = 30.0,
        sink: "AlertSink | str | None" = None,
    ) -> None:
        self.metrics_fn = metrics_fn
        self.rules: tuple[Rule, ...] = (
            tuple(rules) if rules is not None else default_rules()
        )
        self.process = process
        self.component = component
        self.slo_budget_ms = slo_budget_ms
        self.interval_s = interval_s
        self.clock = clock
        self.wall = wall
        self.tracer = tracer
        self.bundle_sources: dict[str, Callable[[], Any]] = dict(
            bundle_sources or {}
        )
        self.trace_window_s = trace_window_s
        self.sink: "AlertSink | None" = (
            AlertSink(sink) if isinstance(sink, str) else sink
        )
        self._lock = threading.Lock()
        # rule.name -> deque[(t_mono, extract tuple)] of cumulative counts
        self._history: dict[str, deque] = {}
        # outlier state: rule.name -> (obs deque, ewma | None)
        self._obs: dict[str, deque] = {}
        self._ewma: dict[str, float] = {}
        self._alerts: dict[str, Alert] = {}
        self.bundles: deque = deque(maxlen=max(max_bundles, 1))
        self._bundle_seq = 0
        self._last_eval: float | None = None
        self.evaluations = 0
        self.eval_errors = 0
        self.fired_total = 0
        self.bundles_total = 0
        self.eval_wall_s = 0.0
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    # ----------------------------------------------------------------- bind
    def bind(
        self,
        metrics_fn: "Callable[[], str] | None" = None,
        tracer=None,
        bundle_sources: "dict[str, Callable[[], Any]] | None" = None,
        process: str = "",
        component: str = "",
    ) -> "Sentinel":
        """Late-bind the owner's sources: the perf runner constructs the
        sentinel (budget + rule table), the owning component binds its
        own metrics text, tracer and bundle sections."""
        if metrics_fn is not None:
            self.metrics_fn = metrics_fn
        if tracer is not None:
            self.tracer = tracer
        if bundle_sources:
            self.bundle_sources.update(bundle_sources)
        if process and not self.process:
            self.process = process
        if component and not self.component:
            self.component = component
        return self

    # ------------------------------------------------------------- sampling
    def _extract(self, rule: Rule, parsed) -> "tuple | None":
        """One rule's cumulative aggregate from one parsed scrape — the
        per-evaluation history entry windowed deltas are taken over."""
        if rule.kind == BURN_RATE:
            buckets: dict[float, float] = {}
            total = 0.0
            seen = False
            for s in parsed.samples(rule.series):
                if not _labels_match(s, rule.labels):
                    continue
                if s.name.endswith("_bucket"):
                    le = s.label("le")
                    if le is None:
                        continue
                    ub = float("inf") if le == "+Inf" else float(le)
                    buckets[ub] = buckets.get(ub, 0.0) + s.value
                elif s.name.endswith("_count"):
                    total += s.value
                    seen = True
            if not seen:
                return None
            return (total, tuple(sorted(buckets.items())))
        if rule.kind == RATIO:
            num = 0.0
            seen = False
            for s in parsed.samples(rule.series):
                if s.name == rule.series and _labels_match(s, rule.labels):
                    num += s.value
                    seen = True
            den = 0.0
            for family in rule.denominator:
                for s in parsed.samples(family):
                    if s.name == family:
                        den += s.value
                        seen = True
            return (num, den) if seen else None
        if rule.kind in (DELTA, LEVEL):
            total = 0.0
            seen = False
            for s in parsed.samples(rule.series):
                if s.name == rule.series and _labels_match(s, rule.labels):
                    total += s.value
                    seen = True
            return (total,) if seen else None
        if rule.kind == OUTLIER:
            total_sum = 0.0
            total_count = 0.0
            seen = False
            for s in parsed.samples(rule.series):
                if not _labels_match(s, rule.labels):
                    continue
                if s.name.endswith("_sum"):
                    total_sum += s.value
                    seen = True
                elif s.name.endswith("_count"):
                    total_count += s.value
            return (total_sum, total_count) if seen else None
        return None

    @staticmethod
    def _window_start(ring, now: float, window_s: float):
        """The newest entry at least ``window_s`` old (partial-window
        fallback: the oldest entry — min_events floors guard the noise
        this admits at startup)."""
        start = ring[0]
        for entry in reversed(ring):
            if now - entry[0] >= window_s:
                start = entry
                break
        return start

    # ------------------------------------------------------------ evaluation
    def maybe_evaluate(self) -> bool:
        """Owner-loop hook: evaluate iff a full interval has elapsed.
        Exceptions are counted, never propagated — an evaluator bug must
        not kill a scheduling loop."""
        now = self.clock()
        if self._last_eval is not None and (
            now - self._last_eval
        ) < self.interval_s:
            return False
        try:
            self.evaluate()
        except Exception:  # noqa: BLE001
            with self._lock:
                self.eval_errors += 1
                self._last_eval = now
        return True

    def evaluate(self, text: "str | None" = None) -> dict:
        """One evaluation pass: scrape → extract → judge every rule →
        advance alert lifecycles (capturing bundles on the pending →
        firing edge). Returns {"fired": [...], "resolved": [...]} of the
        transitions THIS pass made."""
        t0 = time.perf_counter()
        now = self.clock()
        if text is None:
            text = self.metrics_fn() if self.metrics_fn is not None else ""
        try:
            parsed = parse_prometheus_text(text)
        except ParseError:
            parsed = None
        fired: list[Alert] = []
        resolved: list[Alert] = []
        with self._lock:
            self._last_eval = now
            self.evaluations += 1
            for rule in self.rules:
                verdict = self._eval_rule(rule, parsed, now)
                if verdict is None:
                    continue
                breached, value, reason = verdict
                transition = self._advance_locked(
                    rule, breached, value, reason
                )
                if transition == FIRING:
                    fired.append(self._alerts[self._fingerprint(rule)])
                elif transition == RESOLVED:
                    resolved.append(self._alerts[self._fingerprint(rule)])
        # bundle capture OUTSIDE the lock: sources (queue walk, trace
        # slice) may take milliseconds and readers must not stall
        for al in fired:
            rule = self._rule_by_name(al.rule)
            if rule is not None and rule.capture_bundle:
                bundle = self.capture_bundle(trigger=al)
                al.bundle_id = bundle["id"]
        # sink delivery also outside the lock (a webhook may block for
        # timeout_s) and AFTER bundle capture so the record carries the
        # bundle_id an operator would fetch next
        if self.sink is not None:
            for al in fired:
                self.sink.deliver("fired", al.to_json(), self.process)
            for al in resolved:
                self.sink.deliver("resolved", al.to_json(), self.process)
        with self._lock:
            self.eval_wall_s += time.perf_counter() - t0
        return {
            "fired": [a.to_json() for a in fired],
            "resolved": [a.to_json() for a in resolved],
        }

    def _rule_by_name(self, name: str) -> "Rule | None":
        for r in self.rules:
            if r.name == name:
                return r
        return None

    def _eval_rule(self, rule: Rule, parsed, now: float):
        """Judge one rule against the history. Returns (breached, value,
        reason) or None when the rule has no data / no budget yet."""
        if parsed is None:
            return None
        extract = self._extract(rule, parsed)
        if extract is None:
            return None
        ring = self._history.setdefault(rule.name, deque(maxlen=MAX_HISTORY))
        ring.append((now, extract))
        horizon = max(rule.long_window_s, rule.window_s) + self.interval_s
        while ring and now - ring[0][0] > horizon and len(ring) > 1:
            ring.popleft()
        if rule.kind == LEVEL:
            # a gauge IS its judgment — no window, the first scrape counts
            return self._eval_level(rule, ring)
        if len(ring) <= 1:
            return None
        if rule.kind == BURN_RATE:
            return self._eval_burn(rule, ring, now)
        if rule.kind == RATIO:
            return self._eval_ratio(rule, ring, now)
        if rule.kind == DELTA:
            return self._eval_delta(rule, ring, now)
        if rule.kind == OUTLIER:
            return self._eval_outlier(rule, ring)
        return None

    def _budget_ms(self, rule: Rule) -> "float | None":
        return rule.budget_ms if rule.budget_ms is not None else (
            self.slo_budget_ms
        )

    @staticmethod
    def _bad_fraction(start, end, budget_s: float) -> "tuple[float, float]":
        """(bad_fraction, windowed_total) between two burn extracts —
        "bad" is every observation above the smallest bucket bound ≥ the
        budget (bucket-boundary conservative: an event inside the
        straddling bucket counts as good)."""
        d_total = end[0] - start[0]
        if d_total <= 0:
            return 0.0, 0.0
        start_buckets = dict(start[1])
        good_ub = None
        for ub, _cum in end[1]:
            if ub >= budget_s:
                good_ub = ub
                break
        if good_ub is None:
            return 0.0, d_total
        d_good = dict(end[1])[good_ub] - start_buckets.get(good_ub, 0.0)
        bad = max(d_total - max(d_good, 0.0), 0.0)
        return bad / d_total, d_total

    def _eval_burn(self, rule: Rule, ring, now: float):
        budget_ms = self._budget_ms(rule)
        if budget_ms is None:
            return None                      # no declared budget: dormant
        budget_s = budget_ms / 1000.0
        allowed = max(1.0 - rule.objective, 1e-9)
        end = ring[-1]
        burns = []
        for window_s in (rule.short_window_s, rule.long_window_s):
            start = self._window_start(ring, now, window_s)
            frac, total = self._bad_fraction(start[1], end[1], budget_s)
            if total < rule.min_events:
                return (False, 0.0, "insufficient events in window")
            burns.append(frac / allowed)
        value = burns[0]                     # the short (detection) window
        breached = all(b > rule.burn_threshold for b in burns)
        reason = (
            f"burn {burns[0]:.1f}x/{burns[1]:.1f}x of the "
            f"{budget_ms:.0f}ms p{rule.objective * 100:g} budget "
            f"(threshold {rule.burn_threshold:g}x on both windows)"
        )
        return breached, round(value, 3), reason

    def _eval_ratio(self, rule: Rule, ring, now: float):
        end = ring[-1]
        start = self._window_start(ring, now, rule.window_s)
        d_num = end[1][0] - start[1][0]
        d_den = end[1][1] - start[1][1]
        if d_den < rule.min_events:
            return (False, 0.0, "insufficient events in window")
        ratio = d_num / d_den
        if rule.direction == "below":
            breached = ratio < rule.threshold
        else:
            breached = ratio > rule.threshold
        reason = (
            f"windowed {rule.series} ratio {ratio:.3f} "
            f"{rule.direction} threshold {rule.threshold:g}"
        )
        return breached, round(ratio, 4), reason

    def _eval_delta(self, rule: Rule, ring, now: float):
        end = ring[-1]
        start = self._window_start(ring, now, rule.window_s)
        d = end[1][0] - start[1][0]
        if rule.direction == "below":
            breached = d < rule.threshold
        else:
            breached = d > rule.threshold
        reason = (
            f"{rule.series} moved {d:g} in {rule.window_s:g}s "
            f"({rule.direction} {rule.threshold:g})"
        )
        return breached, round(d, 4), reason

    def _eval_level(self, rule: Rule, ring):
        value = ring[-1][1][0]
        if rule.direction == "below":
            breached = value < rule.threshold
        else:
            breached = value > rule.threshold
        reason = (
            f"{rule.series} at {value:g} ({rule.direction} "
            f"trip {rule.threshold:g})"
        )
        return breached, round(value, 4), reason

    def _eval_outlier(self, rule: Rule, ring):
        end, prev = ring[-1], ring[-2]
        d_count = end[1][1] - prev[1][1]
        if d_count <= 0:
            return (False, 0.0, "no new observations")
        x = (end[1][0] - prev[1][0]) / d_count   # this interval's mean
        obs = self._obs.setdefault(rule.name, deque(maxlen=MAX_OBSERVATIONS))
        ewma = self._ewma.get(rule.name)
        breached = False
        reason = "baseline warming up"
        z = 0.0
        if ewma is not None and len(obs) >= rule.min_samples:
            med = statistics.median(obs)
            mad = statistics.median(abs(o - med) for o in obs)
            sigma = MAD_SCALE * mad
            sigma = max(sigma, SIGMA_FLOOR_FRAC * abs(ewma))
            if sigma > 0:
                z = (x - ewma) / sigma
                breached = z > rule.mad_k
            reason = (
                f"interval mean {x * 1000.0:.2f}ms vs EWMA "
                f"{ewma * 1000.0:.2f}ms (robust z {z:.1f}, "
                f"trip {rule.mad_k:g})"
            )
        obs.append(x)
        self._ewma[rule.name] = x if ewma is None else (
            rule.ewma_alpha * x + (1.0 - rule.ewma_alpha) * ewma
        )
        return breached, round(z, 2), reason

    # -------------------------------------------------------------- lifecycle
    def _fingerprint(self, rule: Rule) -> str:
        raw = "\x1f".join((
            rule.name, rule.series,
            ",".join(f"{k}={v}" for k, v in rule.labels),
            self.process,
        ))
        return hashlib.sha1(raw.encode()).hexdigest()[:12]

    def _advance_locked(self, rule: Rule, breached: bool, value, reason) -> (
        "str | None"
    ):
        """One lifecycle step for one rule's alert; caller holds
        ``self._lock``. Returns the state TRANSITIONED TO this step
        (FIRING/RESOLVED), else None."""
        fp = self._fingerprint(rule)
        al = self._alerts.get(fp)
        if breached:
            if al is None:
                al = self._alerts[fp] = Alert(fp, rule)
                al.since_wall = self.wall()
            elif al.state == RESOLVED:
                # the SAME alert re-enters pending: dedup by identity
                al.state = PENDING
                al.since_wall = self.wall()
                al.resolved_at_wall = None
                al.breach_streak = 0
            al.breach_streak += 1
            al.clean_streak = 0
            al.value = value
            al.reason = reason
            if al.state == PENDING and al.breach_streak >= (
                rule.for_intervals
            ):
                al.state = FIRING
                al.fired_at_wall = self.wall()
                al.fires += 1
                self.fired_total += 1
                return FIRING
            return None
        if al is None:
            return None
        al.clean_streak += 1
        al.breach_streak = 0
        if al.state == FIRING:
            if al.clean_streak >= rule.resolve_intervals:
                al.state = RESOLVED
                al.resolved_at_wall = self.wall()
                return RESOLVED
        elif al.state == PENDING:
            # recovered before firing: the episode never happened
            del self._alerts[fp]
        return None

    # ---------------------------------------------------------------- bundles
    def capture_bundle(self, trigger: "Alert | None" = None,
                       reason: str = "") -> dict:
        """THE diagnostic-bundle seam: every capture — alert-triggered or
        operator-forced — goes through here. Bounded point-in-time
        evidence: the bound ``bundle_sources`` sections (cycle records,
        queue snapshot, cache/WAL stats…), per-thread py stacks, RSS,
        and the chrome-trace slice covering the last
        ``trace_window_s``."""
        now_mono = self.clock()
        with self._lock:
            self._bundle_seq += 1
            bundle_id = self._bundle_seq
        bundle: dict[str, Any] = {
            "id": bundle_id,
            "process": self.process,
            "component": self.component,
            "captured_wall": self.wall(),
            "captured_mono": now_mono,
            "trigger": trigger.to_json() if trigger is not None else {
                "reason": reason or "manual capture"
            },
            "rss_bytes": _rss_bytes(),
            "py_stacks": _py_stacks(),
        }
        sections: dict[str, Any] = {}
        for name, fn in self.bundle_sources.items():
            try:
                sections[name] = fn()
            except Exception as e:  # noqa: BLE001 — one broken section
                # must not void the rest of the evidence
                sections[name] = {"error": f"{type(e).__name__}: {e}"}
        bundle["sections"] = sections
        if self.tracer is not None:
            try:
                cutoff = now_mono - self.trace_window_s
                spans = [
                    sp for sp in self.tracer.recent(TRACE_SCAN_SPANS)
                    if sp.end >= cutoff
                ]
                bundle["trace"] = self.tracer.chrome_trace(spans)
            except Exception as e:  # noqa: BLE001
                bundle["trace"] = {"error": f"{type(e).__name__}: {e}"}
        with self._lock:
            self.bundles.append(bundle)
            self.bundles_total += 1
        return bundle

    # ------------------------------------------------------------------ reads
    def alerts_json(self) -> dict:
        with self._lock:
            alerts = [a.to_json() for a in self._alerts.values()]
        alerts.sort(key=lambda a: (a["state"] != FIRING,
                                   a["state"] != PENDING,
                                   a["rule"]))
        return {
            "process": self.process,
            "component": self.component,
            "interval_s": self.interval_s,
            "evaluations": self.evaluations,
            "alerts": alerts,
            "firing": sum(a["state"] == FIRING for a in alerts),
            "pending": sum(a["state"] == PENDING for a in alerts),
            "resolved": sum(a["state"] == RESOLVED for a in alerts),
        }

    def bundles_json(self, query: "dict | None" = None) -> dict:
        """GET /debug/bundle[?id=N]: summaries without an id (the full
        bundle is big), the complete capture with one."""
        q = query or {}

        def one(name: str, default: str = "") -> str:
            v = q.get(name, default)
            return v[-1] if isinstance(v, list) else v

        with self._lock:
            bundles = list(self.bundles)
        want = one("id")
        if want:
            for b in bundles:
                if str(b["id"]) == want:
                    return {"bundle": b}
            return {"bundle": None, "error": f"no bundle id {want}"}
        return {
            "bundles": [{
                "id": b["id"],
                "process": b["process"],
                "rule": (b["trigger"] or {}).get("rule"),
                "severity": (b["trigger"] or {}).get("severity"),
                "captured_wall": b["captured_wall"],
                "sections": sorted((b.get("sections") or {})),
                "trace_events": len(
                    (b.get("trace") or {}).get("traceEvents", ())
                ),
                "rss_bytes": b.get("rss_bytes"),
            } for b in bundles],
            "count": len(bundles),
        }

    def bundles_payload(self) -> list[dict]:
        """Full retained bundles — the exporter ships these; the
        collector dedups by (process, id)."""
        with self._lock:
            return list(self.bundles)

    def stats(self) -> dict:
        """The perf runner's view (WorkloadResult.sentinel)."""
        with self._lock:
            alerts = list(self._alerts.values())
            out = {
                "evaluations": self.evaluations,
                "eval_errors": self.eval_errors,
                "eval_wall_s": round(self.eval_wall_s, 6),
                "fired_total": self.fired_total,
                "firing": sum(a.state == FIRING for a in alerts),
                "pending": sum(a.state == PENDING for a in alerts),
                "resolved": sum(a.state == RESOLVED for a in alerts),
                "bundles": self.bundles_total,
                "interval_s": self.interval_s,
            }
        if self.sink is not None:
            out["sink"] = self.sink.stats()
        return out

    def metrics_text(self) -> str:
        """The sentinel's own counters, mounted on the owner's /metrics
        (so the sentinel watches itself through the same pipe)."""
        from ..metrics.registry import Registry

        with self._lock:
            alerts = list(self._alerts.values())
            evaluations = self.evaluations
            fired = self.fired_total
            bundles = self.bundles_total
            wall = self.eval_wall_s
        r = Registry()
        r.counter(
            "kubetpu_sentinel_evaluations_total",
            "Sentinel rule-table evaluation passes.",
        ).inc(evaluations)
        r.counter(
            "kubetpu_sentinel_alerts_fired_total",
            "Alert firing episodes (pending→firing edges).",
        ).inc(fired)
        r.counter(
            "kubetpu_sentinel_bundles_total",
            "Diagnostic bundles captured.",
        ).inc(bundles)
        r.counter(
            "kubetpu_sentinel_eval_seconds_total",
            "Wall seconds spent evaluating the rule table.",
        ).inc(wall)
        g = r.gauge(
            "kubetpu_sentinel_alerts",
            "Alerts currently tracked, by lifecycle state.",
            labels=("state",),
        )
        for state in (PENDING, FIRING, RESOLVED):
            g.labels(state).set(sum(a.state == state for a in alerts))
        if self.sink is not None:
            st = self.sink.stats()
            r.counter(
                "kubetpu_sentinel_sink_delivered_total",
                "Alert transitions delivered to the out-of-process sink.",
            ).inc(st["delivered"])
            r.counter(
                "kubetpu_sentinel_sink_errors_total",
                "Alert-sink delivery failures (counted, never fatal).",
            ).inc(st["errors"])
        return r.expose()

    # ---------------------------------------------------------------- cadence
    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.evaluate()
            except Exception:  # noqa: BLE001 — a scrape/eval bug is a
                # gap in the watch, never sentinel death
                with self._lock:
                    self.eval_errors += 1

    def start(self) -> "Sentinel":
        """Cadence thread for thread-served owners (the apiserver);
        loop-owned components call ``maybe_evaluate()`` instead."""
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run,
                name=f"sentinel-{self.process or 'proc'}",
                daemon=True,
            )
            self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        th = self._thread
        if th is not None and th.is_alive():
            th.join(timeout=5)


def bundle_to_path(bundle: dict, path: str) -> str:
    """Dump one full bundle as JSON (``kubetpu bundle --out``)."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bundle, f, indent=2, default=str)
    return path
