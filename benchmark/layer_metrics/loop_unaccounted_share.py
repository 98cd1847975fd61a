"""Share of the window that NO phase of the loop's clock accounts for: 100
less the sum of all nine phases. The phases partition the loop thread's time,
so this is the check that nothing is lost; what it reads is the distance
between the harness's window and its two scrapes of the scheduler."""

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return 100.0 * (1.0 - run.scheduler.total(SECONDS) / run.window_s)
