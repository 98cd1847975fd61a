"""Share of the window the loop spent in the inter-pod affinity encode
(``state/podaffinity.py`` ``encode_pod_affinity``, timed where
``finalize_batch`` calls it): beside ``encode_share`` it says how much of
the host encode is the affinity path. A cycle in which the encoder did not
run, or found no term, observes nothing."""

META = {"layer": "host encode", "unit": "%", "source": "program_counter",
        "moves": "pods_bound_per_s"}
PLUGIN = "scheduler_plugin_execution_duration_seconds"
LABELS = {"plugin": "InterPodAffinity", "extension_point": "PreFilter"}


def read(run):
    if run.scheduler.after.total(PLUGIN + "_count", **LABELS) <= 0:
        return None     # a program that does not time the affinity encode
    return (100.0 * run.scheduler.total(PLUGIN + "_sum", **LABELS)
            / run.window_s)
