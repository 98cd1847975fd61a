"""CPU time of the API dispatcher's worker threads inside the window, every
call type, as a share of one core: the bulk binds' encode and decode and
their completions, Python run under the loop thread's GIL on threads the
phase clock does not see."""

META = {"layer": "dispatch + bind", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
CPU_SECONDS = "scheduler_api_dispatcher_worker_cpu_seconds_total"


def read(run):
    if CPU_SECONDS not in run.scheduler.after.samples:
        return None     # no worker has a clock (or none ran anything)
    return 100.0 * run.scheduler.total(CPU_SECONDS) / run.window_s
