"""Bytes on the apiserver child's wire in the window, both directions, for
each pod bound."""

META = {"layer": "API plane", "unit": "bytes/pod",
        "source": "program_counter", "moves": "pods_bound_per_s"}


def read(run):
    if not run.pods_bound:
        return None
    return run.apiserver.total("apiserver_wire_bytes_total") / run.pods_bound
