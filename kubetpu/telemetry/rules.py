"""Declarative alert-rule table for the anomaly sentinel.

Every threshold the sentinel compares against lives HERE (or arrives as
a declared budget — a trace profile's ``slo_budget_ms`` in
``perf/workloads.py``), never as a literal at an evaluation site: the
AL001 checker (kubetpu.analysis.alertcheck) machine-enforces that split,
the same way EC001 pins encode-cache flush scope. A rule is a frozen
record naming WHAT series to watch and WHEN it is anomalous; the
sentinel (sentinel.py) owns HOW — windowed deltas over successive
/metrics scrapes and the pending → firing → resolved state machine.

Four rule kinds:

- ``burn_rate``  multi-window burn-rate over a latency histogram vs. an
  SLO budget (Google SRE's shape): the "bad-event" fraction is the share
  of windowed observations above the budget; burn = bad_frac / (1 −
  objective); the rule trips only when BOTH the short and the long
  window burn faster than ``burn_threshold`` — the short window gives
  detection latency, the long window kills flap. The budget is
  ``budget_ms`` when fixed (WAL fsync), or the sentinel's DECLARED
  per-run budget (``slo_budget_ms`` from the trace profile) when None —
  a run without a declared budget leaves the rule dormant.
- ``ratio``      windowed numerator/denominator rate (federation
  conflicts per attempt, encode-cache hit share) vs. a trip point, with
  a ``min_events`` floor so an idle process can't divide noise.
- ``delta``      windowed increase of one counter (collector span drops,
  event-write drops) vs. a trip point — "this should never move".
- ``outlier``    EWMA/MAD robust outlier detection for series with NO
  budget (cycle wall): each evaluation contributes the interval's mean;
  an observation is anomalous when it sits more than ``mad_k`` robust
  standard deviations (1.4826·MAD) above the EWMA baseline.
- ``level``      a gauge's CURRENT value vs. a trip point (replication
  lag): no windowing — the series is already a level, not a rate;
  ``for_intervals`` is the anti-flap. A process that never emits the
  series (an unreplicated apiserver, the leader) leaves the rule
  dormant.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

#: rule kinds (Rule.kind)
BURN_RATE = "burn_rate"
RATIO = "ratio"
DELTA = "delta"
OUTLIER = "outlier"
LEVEL = "level"

#: alert severities
WARNING = "warning"
CRITICAL = "critical"


@dataclass(frozen=True)
class Rule:
    """One declarative anomaly rule. Only the fields of its ``kind``
    matter; the rest keep their defaults."""

    name: str                   # stable id — part of the alert fingerprint
    kind: str                   # BURN_RATE | RATIO | DELTA | OUTLIER
    series: str                 # primary metric family sampled
    labels: tuple = ()          # ((key, value), ...) match on the series
    severity: str = WARNING
    description: str = ""
    # --- burn_rate ---------------------------------------------------
    objective: float = 0.99     # SLO: fraction of events within budget
    budget_ms: float | None = None   # fixed budget; None = declared budget
    short_window_s: float = 30.0
    long_window_s: float = 300.0
    burn_threshold: float = 6.0      # both windows must burn this fast
    # --- ratio / delta -----------------------------------------------
    denominator: tuple = ()     # families summed for the denominator
    threshold: float | None = None   # trip point (ratio value / delta count)
    direction: str = "above"    # "above" | "below"
    min_events: int = 10        # windowed denominator floor (ratio only)
    window_s: float = 30.0      # ratio/delta lookback
    # --- outlier ------------------------------------------------------
    ewma_alpha: float = 0.3
    mad_k: float = 8.0          # robust z-score trip point
    min_samples: int = 8        # observations before judging
    # --- lifecycle ----------------------------------------------------
    for_intervals: int = 1      # consecutive breach evals before firing
    resolve_intervals: int = 3  # consecutive clean evals before resolving
    capture_bundle: bool = True

    def scaled(self, time_scale: float) -> "Rule":
        """The same rule with every window shrunk by ``time_scale`` —
        a spike run is on the real wall clock and cannot wait five
        minutes for a long window to drain. Thresholds are untouched:
        only WHEN is scaled, never HOW MUCH."""
        return replace(
            self,
            short_window_s=self.short_window_s * time_scale,
            long_window_s=self.long_window_s * time_scale,
            window_s=self.window_s * time_scale,
        )


#: The default watch list — one rule per live series the control plane
#: already emits. Budgets/thresholds here are the ONLY place they live.
DEFAULT_RULES: tuple[Rule, ...] = (
    Rule(
        name="admission-slo-burn",
        kind=BURN_RATE,
        series="scheduler_e2e_scheduling_duration_seconds",
        labels=(("stage", "e2e"),),
        severity=CRITICAL,
        description="pod admission (queue→bound e2e) is burning its "
                    "declared slo_budget_ms faster than 6x on both the "
                    "30s and 300s windows",
        objective=0.99,
        budget_ms=None,           # the run's DECLARED budget (PR 14)
        short_window_s=30.0,
        long_window_s=300.0,
        burn_threshold=6.0,
        min_events=10,
        for_intervals=1,          # multi-window is the anti-flap; fire fast
        resolve_intervals=3,
    ),
    Rule(
        name="wal-fsync-stall",
        kind=BURN_RATE,
        series="store_wal_fsync_duration_seconds",
        severity=WARNING,
        description="group-commit fsyncs are exceeding the 50ms stall "
                    "budget too often — disk contention or a dying device",
        objective=0.99,
        budget_ms=50.0,
        short_window_s=30.0,
        long_window_s=300.0,
        burn_threshold=6.0,
        min_events=10,
        for_intervals=1,
        resolve_intervals=3,
    ),
    Rule(
        name="cycle-wall-outlier",
        kind=OUTLIER,
        series="scheduler_scheduling_algorithm_duration_seconds",
        severity=WARNING,
        description="the per-cycle scheduling wall jumped far above its "
                    "own recent baseline (no declared budget — robust "
                    "EWMA/MAD outlier)",
        ewma_alpha=0.3,
        mad_k=8.0,
        min_samples=8,
        for_intervals=2,
        resolve_intervals=3,
    ),
    Rule(
        name="packing-solver-iteration-spike",
        kind=OUTLIER,
        series="scheduler_packing_solver_iters",
        labels=(("engine", "packing"),),
        severity=WARNING,
        description="the packing engine's warm-started projection loop "
                    "suddenly needs far more iterations per cycle than "
                    "its own recent baseline — the cluster drifted away "
                    "from the carried dual prices (churn burst, shape "
                    "change) and cycles are paying cold-solve cost "
                    "(dormant on greedy/batched: only packing cycles "
                    "observe the series)",
        ewma_alpha=0.3,
        mad_k=8.0,
        min_samples=8,
        for_intervals=2,
        resolve_intervals=3,
    ),
    Rule(
        name="gang-admission-stall",
        kind=BURN_RATE,
        series="scheduler_gang_admission_duration_seconds",
        severity=WARNING,
        description="gang admission (quorum→fully-admitted) is burning "
                    "its declared slo_budget_ms faster than 6x on both "
                    "windows — pod groups are starving behind churn or "
                    "fragmentation (dormant when no pod groups admit: "
                    "the series is absent, and dormant without a "
                    "declared trace budget)",
        objective=0.99,
        budget_ms=None,           # the run's DECLARED budget, like
                                  # admission-slo-burn
        short_window_s=30.0,
        long_window_s=300.0,
        burn_threshold=6.0,
        min_events=5,             # gangs are rare events vs pods
        for_intervals=1,
        resolve_intervals=3,
    ),
    Rule(
        name="federation-conflict-storm",
        kind=RATIO,
        series="scheduler_federation_conflicts_total",
        denominator=("scheduler_schedule_attempts_total",),
        severity=WARNING,
        description="CAS bind conflicts per schedule attempt exceeded "
                    "25% over the last window — replica overlap is "
                    "burning cycles",
        threshold=0.25,
        direction="above",
        min_events=20,
        window_s=30.0,
        for_intervals=2,
        resolve_intervals=3,
    ),
    Rule(
        name="encode-cache-collapse",
        kind=RATIO,
        series="scheduler_encode_cache_hits_total",
        denominator=("scheduler_encode_cache_hits_total",
                     "scheduler_encode_cache_misses_total"),
        severity=WARNING,
        description="encode-cache hit share fell below 50% over the "
                    "last window — invalidation storm or template churn",
        threshold=0.50,
        direction="below",
        min_events=100,
        window_s=30.0,
        for_intervals=2,
        resolve_intervals=3,
        capture_bundle=False,     # cache stats ride every OTHER bundle
    ),
    Rule(
        name="replication-lag",
        kind=LEVEL,
        series="store_replication_lag_records",
        severity=WARNING,
        description="this follower's replication apply position is "
                    "trailing the leader's ship cursor by more than 500 "
                    "records — the read plane is serving stale state "
                    "(dormant on unreplicated/leader apiservers: the "
                    "series is absent there)",
        threshold=500.0,
        direction="above",
        for_intervals=2,
        resolve_intervals=3,
        capture_bundle=False,     # the evidence IS the replication status
    ),
    Rule(
        name="list-lag",
        kind=LEVEL,
        series="store_list_lag_records",
        severity=WARNING,
        description="rv=0 (bounded-staleness) lists on this follower are "
                    "being served more than 500 replication records "
                    "behind the leader — cached reads are stale beyond "
                    "the declared bound (dormant on unreplicated/leader "
                    "apiservers: the series is absent there)",
        threshold=500.0,
        direction="above",
        for_intervals=2,
        resolve_intervals=3,
        capture_bundle=False,     # the evidence IS the replication status
    ),
    Rule(
        name="collector-span-drops",
        kind=DELTA,
        series="kubetpu_collector_spans_dropped_total",
        severity=WARNING,
        description="the collector dropped spans this window — a ring "
                    "overflowed and the merged trace has holes",
        threshold=0.0,
        direction="above",
        window_s=30.0,
        for_intervals=1,
        resolve_intervals=3,
        capture_bundle=False,     # the drop is at the sink, not here
    ),
    Rule(
        name="events-dropped",
        kind=DELTA,
        series="kubetpu_events_dropped_total",
        severity=WARNING,
        description="best-effort Event writes failed this window "
                    "(kubetpu_events_dropped_total moved) — the store "
                    "is rejecting the annotation plane",
        threshold=0.0,
        direction="above",
        window_s=30.0,
        for_intervals=1,
        resolve_intervals=3,
        capture_bundle=False,
    ),
)


def default_rules() -> tuple[Rule, ...]:
    return DEFAULT_RULES


def fast_rules(time_scale: float = 0.05) -> tuple[Rule, ...]:
    """DEFAULT_RULES with windows scaled for a real-wall-clock perf or
    integration run (0.05 → 1.5s/15s burn windows). Same thresholds."""
    return tuple(r.scaled(time_scale) for r in DEFAULT_RULES)
