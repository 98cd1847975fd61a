"""scheduler_perf analog: op-list workloads driving the real scheduler loop
in one process (test/integration/scheduler_perf) — the tests' harness. A
speed is stated by the benchmark (``BENCHMARK.json``), not from here."""

from .runner import (
    WorkloadResult,
    run_workload,
    run_workload_trace,
)
from .workloads import (
    TEST_CASES,
    TRACE_PROFILES,
    TestCase,
    TraceProfile,
    Workload,
)

__all__ = [
    "TEST_CASES",
    "TRACE_PROFILES",
    "TestCase",
    "TraceProfile",
    "Workload",
    "WorkloadResult",
    "run_workload",
    "run_workload_trace",
]
