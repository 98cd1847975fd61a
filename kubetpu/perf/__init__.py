"""scheduler_perf analog: op-list workloads driving the real scheduler loop
(test/integration/scheduler_perf)."""

from .runner import (
    WorkloadResult,
    run_label,
    run_workload,
    run_workload_full_stack,
    run_workload_multiprocess,
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
    "run_label",
    "run_workload",
    "run_workload_full_stack",
    "run_workload_multiprocess",
    "run_workload_trace",
]
