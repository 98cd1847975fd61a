"""CPU time of the scheduler's process inside the window (every thread: the
loop, the dispatcher's workers, XLA's pool), as a share of one core. Far
under 100% with an idle device means the loop waits on the API plane."""

META = {"layer": "entry point (cli.py loop)", "unit": "%", "source": "host_clock",
        "moves": "pods_bound_per_s"}


def read(run):
    if "scheduler" not in run.cpu_s:
        return None
    return 100.0 * run.cpu_s["scheduler"] / run.window_s
