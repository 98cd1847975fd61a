"""Pods of the window's cycles for which the inter-pod affinity SCORE had
work (at least one weighted count row: the pod's own preferred terms, or an
existing pod's preferred or required term that matches it), as a share of
the scheduling attempts made (all results): the proof that the traffic works
``ops/podaffinity.py`` ``affinity_score_pod`` in every scan step and in the
explain program. 100 where every pod of every cycle is scored for
affinity."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
PODS = "scheduler_podaffinity_pods_total"
ATTEMPTS = "scheduler_schedule_attempts_total"


def read(run):
    if PODS not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    attempts = run.scheduler.total(ATTEMPTS)
    if attempts <= 0:
        return None
    return 100.0 * run.scheduler.total(PODS, work="score") / attempts
