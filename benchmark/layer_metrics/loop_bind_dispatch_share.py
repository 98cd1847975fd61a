"""Share of the window the loop thread spent assuming the cycle's pods, running
Reserve and Permit, and handing each bind to the dispatcher. Covered by the
``bind-dispatch`` span."""

META = {"layer": "dispatch + bind", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return (100.0 * run.scheduler.total(SECONDS, phase="bind_dispatch")
            / run.window_s)
