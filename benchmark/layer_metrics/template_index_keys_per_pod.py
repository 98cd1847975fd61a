"""Bound pods the encode cache's template-count index had to key
(``scheduler_encode_template_index_pods_total{result="keyed"}``: a pod new to
its node, or one whose template fields changed) per pod the scheduler placed
in the window (``scheduler_schedule_attempts_total{result="scheduled"}``).
About 1 where the index keys each bound pod once; an index that re-keyed
every pod of every node a cycle touched would read the pods a node holds."""

META = {"layer": "host encode", "unit": "keys/pod", "source": "program_counter",
        "moves": "pods_bound_per_s"}
INDEX = "scheduler_encode_template_index_pods_total"
ATTEMPTS = "scheduler_schedule_attempts_total"


def read(run):
    if INDEX not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    placed = run.scheduler.total(ATTEMPTS, result="scheduled")
    if placed <= 0:
        return None
    return run.scheduler.total(INDEX, result="keyed") / placed
