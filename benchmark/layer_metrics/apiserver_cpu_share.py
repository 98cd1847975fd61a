"""CPU time of the apiserver child inside the window, as a share of one core.
It is one Python process: near 100% it is bound by its interpreter lock,
whatever the host has free."""

META = {"layer": "API plane", "unit": "%", "source": "host_clock",
        "moves": "pods_bound_per_s"}


def read(run):
    if "apiserver" not in run.cpu_s:
        return None
    return 100.0 * run.cpu_s["apiserver"] / run.window_s
