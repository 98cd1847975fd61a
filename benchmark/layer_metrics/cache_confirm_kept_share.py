"""Confirmations of assumed pods the scheduler cache kept
(``scheduler_cache_bind_confirmations_total{result="kept"}``: the bound pod
equal to the assumed one field for field, so the assume ends and no node's
generation moves) as a share of all it took (``kept`` and ``reapplied``).
About 100 where every bind confirms the pod the loop assumed; a confirmation
that reapplies re-clones and re-encodes its node."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
CONFIRMATIONS = "scheduler_cache_bind_confirmations_total"


def read(run):
    if CONFIRMATIONS not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    kept = run.scheduler.total(CONFIRMATIONS, result="kept")
    taken = kept + run.scheduler.total(CONFIRMATIONS, result="reapplied")
    if taken <= 0:
        return None
    return 100.0 * kept / taken
