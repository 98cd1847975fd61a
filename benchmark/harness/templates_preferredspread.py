"""The pod template of upstream's PreferredTopologySpreading rows, in a file
of its own as ``templates.py`` asks: configurations name it
``benchmark.harness.templates_preferredspread:pod_with_preferred_topology_spreading``.
The benchmark's own copy of
templates/pod-with-preferred-topology-spreading.yaml, not an import from
``kubetpu/perf/workloads.py``, so that the program cannot move the
yardstick. It is written out, not derived from ``templates_spread``'s hard
row, because upstream keeps two yaml files: a change to one row's template
must not move the other's cell (a test says the two differ in the action
alone today)."""

from __future__ import annotations

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_pod, spread_constraint

from benchmark.harness.templates import _POD_REQ, ZONE_KEY


def pod_with_preferred_topology_spreading(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-preferred-topology-spreading.yaml: color=blue,
    100m / 500Mi, one constraint: maxSkew 5 over
    topology.kubernetes.io/zone, whenUnsatisfiable ScheduleAnyway,
    labelSelector color=blue."""
    return make_pod(
        name, namespace=namespace, labels={"color": "blue"},
        spread=(spread_constraint(
            5, ZONE_KEY,
            when=t.UnsatisfiableConstraintAction.SCHEDULE_ANYWAY,
            match_labels={"color": "blue"}),),
        **_POD_REQ)
