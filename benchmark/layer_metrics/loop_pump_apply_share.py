"""Share of the window the loop thread spent delivering watch events into the
scheduler's queue and cache (``_apply_batch``, the ``on_pod_*`` and
``on_node_*`` handlers, a relist). Covered by the ``pump`` span, less its
``rpc_s``."""

META = {"layer": "API plane", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_loop_phase_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no phase clock
    return (100.0 * run.scheduler.total(SECONDS, phase="pump_apply")
            / run.window_s)
