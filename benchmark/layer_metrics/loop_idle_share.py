"""Share of the window in which the served loop ran no scheduling cycle:
the 50 ms sleep after every iteration (``cli.py`` ``_make_loop``), the
informer pump and the drain of bind completions."""

META = {"layer": "entry point (cli.py loop)", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
ACTIVE = "scheduler_scheduling_algorithm_duration_seconds_sum"


def read(run):
    return 100.0 * (1.0 - run.scheduler.total(ACTIVE) / run.window_s)
