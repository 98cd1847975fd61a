"""CPU time of the load generator child inside the window, as a share of one
core. Near 100% the generator, not the system, sets the pace: the run then
measures the harness."""

META = {"layer": "load generator (harness)", "unit": "%", "source": "host_clock",
        "moves": "pods_bound_per_s"}


def read(run):
    if "generator" not in run.cpu_s:
        return None
    return 100.0 * run.cpu_s["generator"] / run.window_s
