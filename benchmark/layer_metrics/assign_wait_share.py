"""Share of the window the loop waited for the assign program, from
dispatch to ``device_get`` (the ``Filter+Score`` extension point). Host
clock: it is what the loop waited, not device time."""

META = {"layer": "device assign, seen from the host", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
POINTS = "scheduler_framework_extension_point_duration_seconds_sum"


def read(run):
    secs = run.scheduler.total(POINTS, extension_point="Filter+Score")
    return 100.0 * secs / run.window_s
