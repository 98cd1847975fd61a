"""Share of the window the loop thread spent in the informers' watch poll:
blocked on ``store.watch_bulk`` (one round trip to the apiserver for every
kind's cursor) and decoding its reply. Covered by the ``pump`` span, whose
``rpc_s`` attribute is this phase."""

META = {"layer": "API plane", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return (100.0 * run.scheduler.total(SECONDS, phase="pump_rpc")
            / run.window_s)
