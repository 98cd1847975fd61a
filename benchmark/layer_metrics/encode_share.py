"""Share of the window the loop spent in the host encode (the ``PreFilter``
extension point, ``scheduler.py`` ``_launch_cycle``)."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
POINTS = "scheduler_framework_extension_point_duration_seconds_sum"


def read(run):
    secs = run.scheduler.total(POINTS, extension_point="PreFilter")
    return 100.0 * secs / run.window_s
