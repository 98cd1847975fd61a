"""The pod template of upstream's TopologySpreading rows, in a file of its
own as ``templates.py`` asks: configurations name it
``benchmark.harness.templates_spread:pod_with_topology_spreading``. The
benchmark's own copy of templates/pod-with-topology-spreading.yaml, not an
import from ``kubetpu/perf/workloads.py``, so that the program cannot move
the yardstick."""

from __future__ import annotations

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_pod, spread_constraint

from benchmark.harness.templates import _POD_REQ, ZONE_KEY


def pod_with_topology_spreading(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-topology-spreading.yaml: color=blue, 100m / 500Mi,
    one constraint: maxSkew 5 over topology.kubernetes.io/zone,
    whenUnsatisfiable DoNotSchedule, labelSelector color=blue."""
    return make_pod(
        name, namespace=namespace, labels={"color": "blue"},
        spread=(spread_constraint(
            5, ZONE_KEY,
            when=t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE,
            match_labels={"color": "blue"}),),
        **_POD_REQ)
