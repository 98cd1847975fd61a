"""Assignment engines — the replacement for the reference's per-pod argmax
(``selectHost``, pkg/scheduler/schedule_one.go:605) and its one-pod-at-a-time
outer loop (``ScheduleOne``, schedule_one.go:67).

- ``greedy``: device-resident per-pod loop with exact sequential-consistency
  semantics (each assignment updates node usage before the next pod is
  scored) — the ≥99%-parity reference mode.
- ``sinkhorn``: capacity-coupled batched assignment (LP-relaxed bin-pack via
  entropic OT) — the throughput mode; diffed against greedy by the parity
  harness.
- ``packing``: constraint-based packing (penalized LP-relaxation of the
  bin-pack, warm-started duals) — cluster-level objectives (nodes used,
  priority-weighted admission); hard constraints stay exact.
"""

from .greedy import greedy_assign, greedy_assign_device  # noqa: F401
