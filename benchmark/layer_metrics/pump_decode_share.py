"""Share of the window the loop thread spent DECODING the informers' watch
poll replies (``codec.loads`` of every ``watch_bulk`` body, timed once a
response by the scheduler's own client): the decode's part of
``loop_pump_rpc_share``. That share less this one is the wait for the
apiserver and the read of the reply. 0 in a window with no reply."""

META = {"layer": "API plane", "unit": "%",
        "source": "program_counter", "moves": "pods_bound_per_s"}
SECONDS = "scheduler_watch_decode_seconds_total"


def read(run):
    if SECONDS not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    return 100.0 * run.scheduler.total(SECONDS) / run.window_s
