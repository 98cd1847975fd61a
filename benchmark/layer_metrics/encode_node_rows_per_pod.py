"""Node rows the host encode wrote into the node tensors
(``scheduler_encode_node_rows_total``: in a refresh the rows whose cache
generation moved, in a build every row) per pod the scheduler placed in the
window (``scheduler_schedule_attempts_total{result="scheduled"}``). About 1
where a batch spread over as many nodes re-encodes each node once, for its
assume; about 2 where each bind confirmation re-encodes its node again; far
below 1 where a batch packs onto a few nodes."""

META = {"layer": "host encode", "unit": "rows/pod",
        "source": "program_counter", "moves": "pods_bound_per_s"}
ROWS = "scheduler_encode_node_rows_total"
ATTEMPTS = "scheduler_schedule_attempts_total"


def read(run):
    if ROWS not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    placed = run.scheduler.total(ATTEMPTS, result="scheduled")
    if placed <= 0:
        return None
    return run.scheduler.total(ROWS) / placed
