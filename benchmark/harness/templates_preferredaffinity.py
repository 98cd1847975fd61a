"""The pod template of upstream's SchedulingPreferredPodAffinity rows, in a
file of its own as ``templates.py`` asks: configurations name it
``benchmark.harness.templates_preferredaffinity:pod_with_preferred_pod_affinity``.
The benchmark's own copy of templates/pod-with-preferred-pod-affinity.yaml,
not an import from ``kubetpu/perf/workloads.py``, so that the program cannot
move the yardstick. Upstream's yaml, as this repo renders it
(``/root/reference`` is on no machine a session has had; what the op list
holds beyond ``BASELINE.md:34`` is under ``assumed`` in the configuration):

    metadata:
      generateName: preferred-affinity-pod-
      labels:
        color: red
    spec:
      affinity:
        podAffinity:
          preferredDuringSchedulingIgnoredDuringExecution:
          - podAffinityTerm:
              labelSelector:
                matchLabels:
                  color: red
              topologyKey: kubernetes.io/hostname
              namespaces: ["sched-1", "sched-0"]
            weight: 1
      containers:
      - resources:
          requests:
            cpu: 100m
            memory: 500Mi

It is written out, not derived from ``templates.pod_with_pod_affinity``,
because upstream keeps two yaml files: they differ in the colour, in the
topology key (hostname, not zone) and in the term being preferred (weight 1)
and not required, and a change to one row's template must not move the
other's cell."""

from __future__ import annotations

from kubetpu.api import types as t
from kubetpu.api.wrappers import make_pod, pod_affinity_term

from benchmark.harness.templates import _POD_REQ, HOSTNAME_KEY


def pod_with_preferred_pod_affinity(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-preferred-pod-affinity.yaml: color=red,
    100m / 500Mi, one preferred pod-affinity term of weight 1 over
    kubernetes.io/hostname, labelSelector color=red, namespaces sched-1 and
    sched-0."""
    term = pod_affinity_term(HOSTNAME_KEY, match_labels={"color": "red"},
                             namespaces=("sched-1", "sched-0"))
    return make_pod(
        name, namespace=namespace, labels={"color": "red"},
        affinity=t.Affinity(pod_affinity=t.PodAffinity(
            preferred=(t.WeightedPodAffinityTerm(1, term),))),
        **_POD_REQ)
