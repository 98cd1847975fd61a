"""Share of the traced window in which no operation ran on the device: one
minus the union of the device's operation intervals over the window; on
several chips the mean over the chips (each chip's own is on an earlier
line of the output)."""

META = {"layer": "device", "unit": "%", "source": "device_trace",
        "moves": "pods_bound_per_s"}


def read(run):
    tr = run.device_trace
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
