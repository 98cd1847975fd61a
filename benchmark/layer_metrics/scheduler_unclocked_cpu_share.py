"""CPU time of the scheduler's process inside the window that NO thread's
clock accounts for, as a share of one core: ``process_cpu_seconds_total``
less the loop thread's, the dispatcher's workers' and the diagnostics
listener's request threads'. What is left ran on threads no Python clock
reaches: XLA's and the TPU runtime's pools, and in a benchmark run the
harness's own controller and pipe readers, which live in this process. With
it the process's core is accounted for thread by thread."""

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
PROCESS = "process_cpu_seconds_total"
LOOP = "scheduler_loop_phase_cpu_seconds_total"
#: absent where no worker ran anything, and then nothing to take away
CLOCKED = (LOOP, "scheduler_api_dispatcher_worker_cpu_seconds_total",
           "scheduler_diagnostics_request_cpu_seconds_total")


def read(run):
    have = run.scheduler.after.samples
    if PROCESS not in have or LOOP not in have:
        return None     # a program without the clocks
    clocked = sum(run.scheduler.total(name) for name in CLOCKED)
    return 100.0 * (run.scheduler.total(PROCESS) - clocked) / run.window_s
