"""Pods of the window's cycles that carried or inherited a topology spread
constraint, as a share of the scheduling attempts made (all results): the
proof that the traffic works the spread path. 100 where every pod of every
cycle is constrained."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
CONSTRAINED = "scheduler_spread_constrained_pods_total"
ATTEMPTS = "scheduler_schedule_attempts_total"


def read(run):
    if CONSTRAINED not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    attempts = run.scheduler.total(ATTEMPTS)
    if attempts <= 0:
        return None
    return 100.0 * run.scheduler.total(CONSTRAINED) / attempts
