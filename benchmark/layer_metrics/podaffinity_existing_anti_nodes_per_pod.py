"""Nodes that existing pods' required anti-affinity refuses a pod, per pod
that has such a term against it, over the window's cycles: Δ
``scheduler_podaffinity_existing_anti_nodes_total`` ÷ Δ
``scheduler_podaffinity_filter_pods_total{term="existing_anti_affinity"}``.
The counts are the ones each cycle started from (pods the same batch places
earlier are not in it). Where every running pod with the term sits on a
node of its own, one node per such pod: a reading below that count means a
filter row lost nodes."""

META = {"layer": "host encode", "unit": "nodes/pod",
        "source": "program_counter", "moves": "pods_bound_per_s"}
NODES = "scheduler_podaffinity_existing_anti_nodes_total"
PODS = "scheduler_podaffinity_filter_pods_total"


def read(run):
    if NODES not in run.scheduler.after.samples:
        return None     # a program that has no such counter
    pods = run.scheduler.total(PODS, term="existing_anti_affinity")
    if pods <= 0:
        return None
    return run.scheduler.total(NODES) / pods
