"""Pods of the window's cycles that carried or inherited at least one
ScheduleAnyway topology spread constraint, as a share of the scheduling
attempts made (all results): the proof that the traffic works the soft
spread score, the float64/int64 kernel of every scan step and of the explain
program. 100 where every pod of every cycle prefers a spread."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
SOFT = "scheduler_spread_soft_constrained_pods_total"
ATTEMPTS = "scheduler_schedule_attempts_total"


def read(run):
    if SOFT not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    attempts = run.scheduler.total(ATTEMPTS)
    if attempts <= 0:
        return None
    return 100.0 * run.scheduler.total(SOFT) / attempts
