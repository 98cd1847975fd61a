"""How late the generator sent: actual send minus due time, 99th percentile
over the pods due in the window. A starved generator must not be read as a
fast server: it guards both latency metrics."""

META = {"layer": "load generator (harness)", "unit": "ms",
        "source": "host_clock", "moves": "bind_latency_p50_ms"}


def read(run):
    return run.report.get("latency", {}).get("generator_late_p99_ms")
