"""Requests the apiserver child served in the window, for each pod bound:
creates, informer polls, binds, status patches, the generator's watch."""

META = {"layer": "API plane", "unit": "rpcs/pod",
        "source": "program_counter", "moves": "pods_bound_per_s"}


def read(run):
    if not run.pods_bound:
        return None
    return run.apiserver.total("apiserver_request_total") / run.pods_bound
