"""Completed iterations of the served loop (pump, ``schedule_batch``, drain,
sleep) a second, idle ones included. Each busy one is a ``loop-iteration``
span."""

META = {"layer": "entry point (cli.py loop)", "unit": "iterations/s",
        "source": "program_counter", "moves": "pods_bound_per_s"}
ITERATIONS = "scheduler_loop_iterations_total"


def read(run):
    if ITERATIONS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return run.scheduler.total(ITERATIONS) / run.window_s
