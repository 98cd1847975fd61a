"""Share of the window the loop thread spent in the flight recorder's
``note_cycle``: the explain kernel on the device and its host half. Covered
by the ``explain`` span."""

META = {"layer": "flight recorder", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return (100.0 * run.scheduler.total(SECONDS, phase="explain")
            / run.window_s)
