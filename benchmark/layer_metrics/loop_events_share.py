"""Share of the window the loop thread spent writing Events
(``EventRecorder.event``: one blocking write to the apiserver per bound pod).
Covered by the ``drain`` span's ``events_s`` attribute; no span of its own."""

META = {"layer": "dispatch + bind", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return (100.0 * run.scheduler.total(SECONDS, phase="events")
            / run.window_s)
