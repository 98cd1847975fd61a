"""Binds the scheduler's informers took as bind deltas of the batched watch
poll (``scheduler_watch_bind_deltas_total{result="applied"}``: key, uid and
node, the pod rebuilt from the one the informer held, no pod decoded), as a
share of the pods the scheduler placed in the window
(``scheduler_schedule_attempts_total{result="scheduled"}``). About 100 where
every bind came back as a delta."""

META = {"layer": "API plane", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
DELTAS = "scheduler_watch_bind_deltas_total"
ATTEMPTS = "scheduler_schedule_attempts_total"


def read(run):
    if DELTAS not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    placed = run.scheduler.total(ATTEMPTS, result="scheduled")
    if placed <= 0:
        return None
    return 100.0 * run.scheduler.total(DELTAS, result="applied") / placed
