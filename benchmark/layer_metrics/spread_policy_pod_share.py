"""Pods of the window's cycles whose topology spread constraint had nodes
left out of its per-domain counts by ``nodeTaintsPolicy: Honor`` (an
untolerated NoSchedule or NoExecute taint), as a share of the scheduling
attempts made (all results): the proof that the traffic works the taints
policy of ``state/spread.py`` ``encode_spread``, which keeps the tainted
nodes out of the minimum that every hard spread filter step compares with.
100 where every pod of every cycle is such a pod."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
PODS = "scheduler_spread_policy_pods_total"
ATTEMPTS = "scheduler_schedule_attempts_total"


def read(run):
    if PODS not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    attempts = run.scheduler.total(ATTEMPTS)
    if attempts <= 0:
        return None
    return 100.0 * run.scheduler.total(PODS, policy="taints") / attempts
