"""Median wait in the scheduling queue of the pods of the window, from the
PR-8 stage histogram. Interpolated inside exponential buckets: it moves in
steps, which is fine for a layer's figure."""

META = {"layer": "queue", "unit": "ms", "source": "program_counter",
        "moves": "bind_latency_p50_ms"}
STAGES = "scheduler_e2e_scheduling_duration_seconds"


def read(run):
    q = run.scheduler.histogram_quantile(STAGES, 0.5, stage="queue_wait")
    return None if q is None else q * 1e3
