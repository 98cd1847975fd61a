"""Share of the window the loop thread spent taking bind results back into
cache, queue and flight recorder (``_drain_bind_completions``), less the
Event writes. Covered by the ``drain`` span, less its ``events_s``."""

META = {"layer": "dispatch + bind", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return (100.0 * run.scheduler.total(SECONDS, phase="drain")
            / run.window_s)
