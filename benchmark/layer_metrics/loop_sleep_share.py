"""Share of the window the loop thread slept by design: the 50 ms period of
``_make_loop`` after every iteration, and its 2 s back-off. No span: it is
the gap between two ``loop-iteration`` spans."""

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return (100.0 * run.scheduler.total(SECONDS, phase="sleep")
            / run.window_s)
