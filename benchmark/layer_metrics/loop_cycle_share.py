"""Share of the window the loop thread spent in ``schedule_batch`` itself: pop,
snapshot, encode, extenders, dispatch, the wait for the device, failure
handling, the pod-group lane, the dispatcher's flush; less ``explain`` and
``bind_dispatch``. Covered by the ``scheduling-cycle`` span and its
children."""

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return (100.0 * run.scheduler.total(SECONDS, phase="cycle")
            / run.window_s)
