"""The two pod templates of upstream's SchedulingPodMatchingAntiAffinity row,
in a file of their own as ``templates.py`` asks: configurations name them
``benchmark.harness.templates_podmatchinganti:pod_with_pod_anti_affinity``
and ``...:pod_with_pod_anti_affinity_label``. The benchmark's own copy of
templates/pod-with-pod-anti-affinity.yaml and
templates/pod-with-pod-anti-affinity-label.yaml as
``kubetpu/perf/workloads.py`` renders them (``pod_with_pod_anti_affinity``,
``pod_anti_affinity_label_only``), not an import from there, so that the
program cannot move the yardstick. The fields of upstream's yaml that this
repo renders (the yaml itself is not in the repo; what the row holds beyond
``BASELINE.md:32`` is under ``assumed`` in the configuration):

    # pod-with-pod-anti-affinity.yaml
    metadata:
      labels:
        color: green
    spec:
      affinity:
        podAntiAffinity:
          requiredDuringSchedulingIgnoredDuringExecution:
          - labelSelector:
              matchLabels:
                color: green
            topologyKey: kubernetes.io/hostname
            namespaces: ["sched-1", "sched-0"]
      containers:
      - resources:
          requests:
            cpu: 100m
            memory: 500Mi

    # pod-with-pod-anti-affinity-label.yaml
    metadata:
      labels:
        color: green
    spec:
      containers:
      - resources:
          requests:
            cpu: 100m
            memory: 500Mi

The init pods carry the term; the measured pods carry only the label it
selects, so every init pod's node refuses every measured pod, and the
measured pods do not constrain each other."""

from __future__ import annotations

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_pod, pod_affinity_term

from benchmark.harness.templates import _POD_REQ, HOSTNAME_KEY

#: the label the term selects and every pod of the row carries
GREEN = {"color": "green"}


def pod_with_pod_anti_affinity(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-pod-anti-affinity.yaml: color=green, 100m / 500Mi,
    one REQUIRED anti-affinity term over kubernetes.io/hostname, selector
    color=green, namespaces sched-1 and sched-0."""
    term = pod_affinity_term(HOSTNAME_KEY, match_labels=GREEN,
                             namespaces=("sched-1", "sched-0"))
    return make_pod(
        name, namespace=namespace, labels=GREEN,
        affinity=t.Affinity(pod_anti_affinity=t.PodAffinity(required=(term,))),
        **_POD_REQ)


def pod_with_pod_anti_affinity_label(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-pod-anti-affinity-label.yaml: color=green, 100m /
    500Mi, and no term of its own."""
    return make_pod(name, namespace=namespace, labels=GREEN, **_POD_REQ)
