"""Device time of the assign program for each of its runs in the traced
window: the durations of its events on the trace's ``XLA Modules`` line
(the configuration names the program, ``assign_program``)."""

META = {"layer": "device assign", "unit": "ms/cycle",
        "source": "device_trace", "moves": "pods_bound_per_s"}


def read(run):
    tr = run.device_trace
    if tr is None or not tr["assign_runs"]:
        return None
    return 1e3 * tr["assign_s"] / tr["assign_runs"]
