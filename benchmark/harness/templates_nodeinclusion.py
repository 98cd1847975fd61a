"""The node and pod templates of upstream's SchedulingWithNodeInclusionPolicy
row, in a file of their own as ``templates.py`` asks: configurations name
them ``benchmark.harness.templates_nodeinclusion:node_with_taint_one_in_five``
and ``...:pod_with_node_inclusion_policy``. The benchmark's own copy of
templates/node-with-taint.yaml and templates/pod-with-node-inclusion-policy.yaml,
not an import from ``kubetpu/perf/workloads.py`` (which has no rendering of
them), so that the program cannot move the yardstick. Upstream's yaml as this
repo recalls it (``/root/reference`` is on no machine a session has had; what
the row holds beyond ``BASELINE.md:30`` is under ``assumed`` in the
configuration):

    # node-with-taint.yaml: node-default, plus
    spec:
      taints:
      - key: foo
        value: bar
        effect: NoSchedule

    # pod-with-node-inclusion-policy.yaml
    metadata:
      generateName: spreading-pod-
      labels:
        foo: bar
    spec:
      topologySpreadConstraints:
      - maxSkew: 1
        topologyKey: kubernetes.io/hostname
        whenUnsatisfiable: DoNotSchedule
        labelSelector:
          matchLabels:
            foo: bar
        nodeAffinityPolicy: Honor
        nodeTaintsPolicy: Honor
      containers:
      - resources:
          requests:
            cpu: 100m
            memory: 500Mi

Upstream's op list creates 4000 node-default nodes and 1000 node-with-taint
nodes in two createNodes ops; one generator that takes a node's index
interleaves them instead, one tainted node in five, so that every cut of the
node count (the CPU rehearsal's 256) keeps the ratio."""

from __future__ import annotations

import dataclasses

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_pod, spread_constraint

from benchmark.harness.templates import _POD_REQ, HOSTNAME_KEY, node_default

#: templates/node-with-taint.yaml's one taint
TAINT = t.Taint(key="foo", value="bar", effect=t.TaintEffect.NO_SCHEDULE)


def node_with_taint_one_in_five(i: int, zones: tuple[str, ...] = ()) -> t.Node:
    """node-default (4 cpu / 32Gi / 110 pods, hostname scheduler-perf-<i>),
    and node-with-taint's ``foo=bar:NoSchedule`` where ``i % 5 == 4``."""
    node = node_default(i, zones)
    if i % 5 == 4:
        return dataclasses.replace(node, taints=(TAINT,))
    return node


def pod_with_node_inclusion_policy(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-node-inclusion-policy.yaml: foo=bar, 100m / 500Mi,
    one constraint: maxSkew 1 over kubernetes.io/hostname, DoNotSchedule,
    labelSelector foo=bar, nodeAffinityPolicy and nodeTaintsPolicy Honor; no
    toleration and no node affinity. The policies are set on the
    constraint here and not through ``spread_constraint``'s own arguments
    (PR 40 added them), so that the parent of that PR runs the cell too."""
    constraint = spread_constraint(
        1, HOSTNAME_KEY, when=t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE,
        match_labels={"foo": "bar"})
    return make_pod(
        name, namespace=namespace, labels={"foo": "bar"},
        spread=(dataclasses.replace(constraint, node_affinity_policy="Honor",
                                    node_taints_policy="Honor"),),
        **_POD_REQ)
