"""Collective operations' share of the device's busy time, mean over the
chips. The part of it during which no compute ran on that chip is on an
earlier line of the output (``collective_exposed``)."""

META = {"layer": "mesh", "unit": "%", "source": "device_trace",
        "moves": "pods_bound_per_s"}


def read(run):
    tr = run.device_trace
    if tr is None or not tr["busy_s"]:
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]
