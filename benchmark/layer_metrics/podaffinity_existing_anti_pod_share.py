"""Pods of the window's cycles that an existing pod's required
anti-affinity term matches (at least one EA slot: upstream's
``satisfyExistingPodsAntiAffinity``, filtering.go), as a share of the
scheduling attempts made (all results): the proof that the traffic works the
existing pods' anti-affinity filter of ``ops/podaffinity.py``
``affinity_filter_pod`` in every cycle. 100 where every pod of every cycle
has such a slot."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
PODS = "scheduler_podaffinity_filter_pods_total"
ATTEMPTS = "scheduler_schedule_attempts_total"


def read(run):
    if PODS not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    attempts = run.scheduler.total(ATTEMPTS)
    if attempts <= 0:
        return None
    return (100.0 * run.scheduler.total(PODS, term="existing_anti_affinity")
            / attempts)
