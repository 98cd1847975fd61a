"""CPU time of the scheduler's LOOP thread inside the window, as a share of
one core: the sum over the nine phases of the phase clock's CPU counters (the
thread's own CPU clock, read beside the wall clock at every phase switch).
``scheduler_cpu_share`` is the whole process; this is the one thread whose
wall the ``loop_*`` shares partition. The rest of its wall it waited:
``loop_sleep_share``, ``loop_stall_share``, ``loop_blocked_share``."""

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
CPU_SECONDS = "scheduler_loop_phase_cpu_seconds_total"


def read(run):
    if CPU_SECONDS not in run.scheduler.after.samples:
        return None     # a program whose phase clock reads no CPU clock
    return 100.0 * run.scheduler.total(CPU_SECONDS) / run.window_s
