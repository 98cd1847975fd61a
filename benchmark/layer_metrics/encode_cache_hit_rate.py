"""Pod rows the encode cache gathered instead of building, in the window."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}


def read(run):
    hits = run.scheduler.total("scheduler_encode_cache_hits_total")
    misses = run.scheduler.total("scheduler_encode_cache_misses_total")
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
