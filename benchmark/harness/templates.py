"""Node and pod templates, by the names the configuration files use. The
benchmark's own copy of upstream's templates/*.yaml (as
``kubetpu/perf/workloads.py`` renders them), so that the program cannot move
the yardstick. A later PR adds a template with a new file-level table entry
in a module of its own and names it ``<module>:<function>`` in its
configuration."""

from __future__ import annotations

import importlib

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_node, make_pod, pod_affinity_term

ZONE_KEY = "topology.kubernetes.io/zone"
HOSTNAME_KEY = "kubernetes.io/hostname"
#: templates/pod-default.yaml: 100m / 500Mi
_POD_REQ = dict(cpu_milli=100, memory=500 * 1024 ** 2)


def node_default(i: int, zones: tuple[str, ...] = ()) -> t.Node:
    """templates/node-default.yaml: 4 cpu / 32Gi / 110 pods, the hostname
    label, and the zone label round-robin over ``zones``."""
    name = f"scheduler-perf-{i}"
    labels = {HOSTNAME_KEY: name}
    if zones:
        labels[ZONE_KEY] = zones[i % len(zones)]
    return make_node(name, cpu_milli=4000, memory=32 * 1024 ** 3, pods=110,
                     labels=labels)


def pod_default(name: str, namespace: str) -> t.Pod:
    """templates/pod-default.yaml."""
    return make_pod(name, namespace=namespace, **_POD_REQ)


def pod_with_pod_affinity(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-pod-affinity.yaml: color=blue, required zone
    affinity to color=blue across sched-0 and sched-1."""
    term = pod_affinity_term(ZONE_KEY, match_labels={"color": "blue"},
                             namespaces=("sched-1", "sched-0"))
    return make_pod(
        name, namespace=namespace, labels={"color": "blue"},
        affinity=t.Affinity(pod_affinity=t.PodAffinity(required=(term,))),
        **_POD_REQ)


NODE_TEMPLATES = {"node-default": node_default}
POD_TEMPLATES = {
    "pod-default": pod_default,
    "pod-with-pod-affinity": pod_with_pod_affinity,
}


def capacity(config: dict) -> int:
    """Pods of the measured template the configuration's cluster holds:
    nodes x the least of the node template's pod limit and each of its
    resources over the pod's request for it."""
    node = resolve(NODE_TEMPLATES, config["node_template"])(
        0, tuple(config.get("zones", ())))
    measured = config["measured_pods"]
    pod = resolve(POD_TEMPLATES, measured["template"])(
        "capacity", measured["namespace"])
    alloc = dict(node.allocatable)
    per_node = min([alloc["pods"]] + [alloc.get(name, 0) // request
                                      for name, request in pod.requests
                                      if request > 0])
    return config["nodes"] * per_node


def resolve(table: dict, name: str):
    """A template by its table name, or ``module:function`` for one a later
    PR keeps in a file of its own under benchmark/."""
    if name in table:
        return table[name]
    if ":" in name:
        module, func = name.split(":", 1)
        return getattr(importlib.import_module(module), func)
    raise KeyError(f"no template named {name!r}")
