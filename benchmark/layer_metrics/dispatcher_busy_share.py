"""Wall time the API dispatcher's worker threads spent executing what they
took from the queue, every call type, as a share of the window (two workers:
it can pass 100). Less ``dispatcher_cpu_share`` it is the workers' own wait:
for the apiserver's replies, and for the GIL."""

META = {"layer": "dispatch + bind", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_api_dispatcher_worker_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # no worker has a clock (or none ran anything)
    return 100.0 * run.scheduler.total(SECONDS) / run.window_s
