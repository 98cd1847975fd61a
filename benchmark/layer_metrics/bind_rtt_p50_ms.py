"""Median round trip of the bind call of the pods of the window, from the
PR-8 stage histogram (bucketed: a layer's figure)."""

META = {"layer": "dispatch + bind", "unit": "ms",
        "source": "program_counter", "moves": "bind_latency_p50_ms"}
STAGES = "scheduler_e2e_scheduling_duration_seconds"


def read(run):
    q = run.scheduler.histogram_quantile(STAGES, 0.5, stage="bind_rtt")
    return None if q is None else q * 1e3
