"""scheduler_perf workload definitions — op lists + object templates.

Mirrors the reference harness's shape
(test/integration/scheduler_perf/scheduler_perf.go:756
RunBenchmarkPerfScheduling; ops in operations.go; per-topic
performance-config.yaml files): a *test case* is an op-list template
(createNodes/createNamespaces/createPods/churn/barrier) plus named
*workloads* binding the ``$param`` counts and the SchedulingThroughput
threshold asserted by CI. Templates reproduce the reference's YAML pod/node
templates (test/integration/scheduler_perf/templates/*.yaml) as factory
functions.

The measured metric is the reference's SchedulingThroughput: scheduled pods
per second over the collect-metrics phase (scheduler_perf.go:352-359 selects
``SchedulingThroughput / Average``; util.go:468 throughputCollector).
"""

from __future__ import annotations

import math

from dataclasses import dataclass, field, replace
from typing import Callable, Mapping

import numpy as np

from ..api import types as t
from ..api.wrappers import make_node, make_pod, pod_affinity_term, spread_constraint
from ..state.topology import RACK_KEY, SLICE_KEY

ZONE_KEY = "topology.kubernetes.io/zone"
HOSTNAME_KEY = "kubernetes.io/hostname"

# ---------------------------------------------------------------------------
# object templates (templates/*.yaml analogs)
# ---------------------------------------------------------------------------


def trace_topology_labels(name: str, slices: int) -> dict[str, str]:
    """The ONE rack/TPU-slice label grammar every node generator shares
    (initial fleet, autoscaler wave nodes, tests): a stable crc32 of the
    node name picks the slice — builtin hash() is salted per process,
    which would break the trace determinism contract — and racks group
    four slices each. ``slices <= 0`` means an unlabeled fleet (the
    ``--topology auto`` parity case)."""
    if slices <= 0:
        return {}
    import zlib

    s = zlib.crc32(name.encode()) % slices
    return {SLICE_KEY: f"slice-{s:03d}", RACK_KEY: f"rack-{s // 4:02d}"}


def node_default(
    i: int, zones: tuple[str, ...] = (), slices: int = 0
) -> t.Node:
    """templates/node-default.yaml: 4 cpu / 32Gi / 110 pods, plus the
    labelNodePrepareStrategy zone label (round-robin over ``zones``), the
    kubelet-maintained hostname label, and — when ``slices`` — the shared
    rack/TPU-slice grammar (trace_topology_labels)."""
    name = f"scheduler-perf-{i}"
    labels = {HOSTNAME_KEY: name}
    if zones:
        labels[ZONE_KEY] = zones[i % len(zones)]
    labels.update(trace_topology_labels(name, slices))
    return make_node(
        name, cpu_milli=4000, memory=32 * 1024**3, pods=110, labels=labels
    )


_POD_REQ = dict(cpu_milli=100, memory=500 * 1024**2)  # 100m / 500Mi


def pod_default(name: str, namespace: str) -> t.Pod:
    """templates/pod-default.yaml."""
    return make_pod(name, namespace=namespace, **_POD_REQ)


def pod_with_pod_affinity(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-pod-affinity.yaml: color=blue, required zone
    affinity to color=blue across sched-0/sched-1."""
    term = pod_affinity_term(
        ZONE_KEY, match_labels={"color": "blue"},
        namespaces=("sched-1", "sched-0"),
    )
    return make_pod(
        name, namespace=namespace, labels={"color": "blue"},
        affinity=t.Affinity(pod_affinity=t.PodAffinity(required=(term,))),
        **_POD_REQ,
    )


def pod_with_pod_anti_affinity(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-pod-anti-affinity.yaml: color=green, required
    hostname anti-affinity to color=green."""
    term = pod_affinity_term(
        HOSTNAME_KEY, match_labels={"color": "green"},
        namespaces=("sched-1", "sched-0"),
    )
    return make_pod(
        name, namespace=namespace, labels={"color": "green"},
        affinity=t.Affinity(pod_anti_affinity=t.PodAffinity(required=(term,))),
        **_POD_REQ,
    )


def pod_anti_affinity_label_only(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-pod-anti-affinity-label.yaml: carries color=green
    (matching the init pods' anti-affinity) but no constraint of its own."""
    return make_pod(
        name, namespace=namespace, labels={"color": "green"}, **_POD_REQ
    )


def pod_with_preferred_pod_affinity(name: str, namespace: str) -> t.Pod:
    term = pod_affinity_term(
        HOSTNAME_KEY, match_labels={"color": "red"},
        namespaces=("sched-1", "sched-0"),
    )
    return make_pod(
        name, namespace=namespace, labels={"color": "red"},
        affinity=t.Affinity(pod_affinity=t.PodAffinity(
            preferred=(t.WeightedPodAffinityTerm(1, term),)
        )),
        **_POD_REQ,
    )


def pod_with_preferred_pod_anti_affinity(name: str, namespace: str) -> t.Pod:
    term = pod_affinity_term(
        HOSTNAME_KEY, match_labels={"color": "yellow"},
        namespaces=("sched-1", "sched-0"),
    )
    return make_pod(
        name, namespace=namespace, labels={"color": "yellow"},
        affinity=t.Affinity(pod_anti_affinity=t.PodAffinity(
            preferred=(t.WeightedPodAffinityTerm(1, term),)
        )),
        **_POD_REQ,
    )


def pod_with_topology_spreading(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-topology-spreading.yaml: maxSkew 5 / zone /
    DoNotSchedule over color=blue."""
    return make_pod(
        name, namespace=namespace, labels={"color": "blue"},
        spread=(spread_constraint(
            5, ZONE_KEY,
            when=t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE,
            match_labels={"color": "blue"},
        ),),
        **_POD_REQ,
    )


def pod_with_preferred_topology_spreading(name: str, namespace: str) -> t.Pod:
    return make_pod(
        name, namespace=namespace, labels={"color": "blue"},
        spread=(spread_constraint(
            5, ZONE_KEY,
            when=t.UnsatisfiableConstraintAction.SCHEDULE_ANYWAY,
            match_labels={"color": "blue"},
        ),),
        **_POD_REQ,
    )


def pod_with_node_affinity(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-node-affinity.yaml: required zone In [zone1,zone2]."""
    from ..api.wrappers import node_affinity_required, req_in

    return make_pod(
        name, namespace=namespace,
        affinity=node_affinity_required(
            t.NodeSelectorTerm(match_expressions=(req_in(ZONE_KEY, "zone1", "zone2"),))
        ),
        **_POD_REQ,
    )


def pod_high_priority_large_cpu(name: str, namespace: str) -> t.Pod:
    """templates/pod-high-priority-large-cpu.yaml: priority 10, 9 cpu."""
    return make_pod(
        name, namespace=namespace, priority=10,
        cpu_milli=9000, memory=500 * 1024**2,
    )


def pod_low_priority(name: str, namespace: str) -> t.Pod:
    """templates/pod-low-priority.yaml: 900m/500Mi, priority 0 — four of
    them fill 3.6 of a node's 4 cpu (the PreemptionAsync setup)."""
    return make_pod(
        name, namespace=namespace, cpu_milli=900, memory=500 * 1024**2,
    )


def pod_high_priority_3cpu(name: str, namespace: str) -> t.Pod:
    """templates/pod-high-priority.yaml: priority 10, 3 cpu — must preempt
    3 of 4 low-priority pods to fit."""
    return make_pod(
        name, namespace=namespace, priority=10,
        cpu_milli=3000, memory=500 * 1024**2,
    )


def light_pod(name: str, namespace: str) -> t.Pod:
    """templates/light-pod.yaml: no resource requests."""
    return make_pod(name, namespace=namespace)


def gated_pod(name: str, namespace: str) -> t.Pod:
    """templates/gated-pod.yaml: held by a scheduling gate forever."""
    return make_pod(name, namespace=namespace, gates=("test.k8s.io/hold",))


def pod_with_label(name: str, namespace: str) -> t.Pod:
    """templates/pod-with-label.yaml: a labeled pod with no constraints of
    its own — exercises the profile's DEFAULT spread constraints path."""
    return make_pod(
        name, namespace=namespace, labels={"foo": "bar"}, **_POD_REQ,
    )


#: the bin-pack workload's deterministic 10-slot size/priority cycle,
#: keyed by the pod's trailing ``-{j}`` index: one 2-cpu latency pod
#: (priority 10), two 1-cpu services (priority 5), three 500m and four
#: 100m batch fillers (priority 0). One full cycle requests 5.9 cpu —
#: ~1.5 of a 4-cpu node when packed tight, but a spreading scorer happily
#: smears it over many part-empty nodes, which is exactly the frontier
#: the PackingComparison ladder measures.
_BINPACK_SLOTS: tuple[tuple[int, int], ...] = (
    (2000, 10),
    (1000, 5), (1000, 5),
    (500, 0), (500, 0), (500, 0),
    (100, 0), (100, 0), (100, 0), (100, 0),
)


def pod_binpack(name: str, namespace: str) -> t.Pod:
    """The skewed-size + priority-tier bin-pack template (PR 19): the
    pod's shape is a pure function of its trailing index, so the workload
    is identical across engines and runs — any nodes-used delta is the
    engine's doing, not the draw's."""
    try:
        j = int(name.rsplit("-", 1)[-1])
    except ValueError:
        j = 0
    cpu, priority = _BINPACK_SLOTS[j % len(_BINPACK_SLOTS)]
    return make_pod(
        name, namespace=namespace, priority=priority,
        cpu_milli=cpu, memory=500 * 1024**2,
    )


def node_with_extended_resource(i: int, zones: tuple[str, ...] = ()) -> t.Node:
    """templates/node-with-extended-resource.yaml: each node advertises ONE
    unit of a PER-NODE-UNIQUE extended resource (foo.com/bar-{i}) — the
    DRA-extended-resource scheduling shape."""
    return make_node(
        f"ext-node-{i}", cpu_milli=4000, memory=32 * 1024**3, pods=110,
        labels={"node-with-extended-resource": "true"},
        extended={f"foo.com/bar-{i}": 1},
    )


@dataclass(frozen=True)
class CreateExtendedResourcePodsOp:
    """createPods with templates/pod-with-extended-resource.yaml: pod i
    requests foo.com/bar-{i}: 1 — each pod fits exactly one node."""

    count_param: str = "measurePods"
    collect_metrics: bool = False
    namespace: str = "test"


DAEMONSET_NODE = "scheduler-perf-node"


def node_with_name(_: int = 0, zones: tuple[str, ...] = ()) -> t.Node:
    """templates/node-with-name.yaml: one named node with a 90000-pod
    allowance — the daemonset / gated cases funnel every pod onto it."""
    return make_node(
        DAEMONSET_NODE, cpu_milli=4000, memory=32 * 1024**3, pods=90000,
        labels={HOSTNAME_KEY: DAEMONSET_NODE},
    )


def daemonset_pod(name: str, namespace: str) -> t.Pod:
    """templates/daemonset-pod.yaml: required node affinity on
    matchFields metadata.name = scheduler-perf-node, no requests."""
    term = t.NodeSelectorTerm(match_fields=(
        t.Requirement("metadata.name", t.Operator.IN, (DAEMONSET_NODE,)),
    ))
    return make_pod(
        name, namespace=namespace,
        affinity=t.Affinity(node_affinity=t.NodeAffinity(
            required=t.NodeSelector(terms=(term,))
        )),
    )


def pod_preferred_anti_affinity_ns_selector(name: str, namespace: str) -> t.Pod:
    """templates/pod-preferred-anti-affinity-ns-selector.yaml: color=green,
    preferred hostname anti-affinity to color=green across namespaces
    labeled team=devops."""
    term = pod_affinity_term(
        HOSTNAME_KEY, match_labels={"color": "green"},
        namespace_selector=t.LabelSelector(match_labels=(("team", "devops"),)),
    )
    return make_pod(
        name, namespace=namespace, labels={"color": "green"},
        affinity=t.Affinity(pod_anti_affinity=t.PodAffinity(
            preferred=(t.WeightedPodAffinityTerm(1, term),)
        )),
        **_POD_REQ,
    )


# ---------------------------------------------------------------------------
# op list (operations.go analogs)
# ---------------------------------------------------------------------------

PodTemplate = Callable[[str, str], t.Pod]


@dataclass(frozen=True)
class CreateNodesOp:
    """operations.go:205 createNodesOp (+ labelNodePrepareStrategy).
    ``count`` > 0 overrides ``count_param`` (the YAML ``count:`` form);
    ``template`` overrides the default node factory (nodeTemplatePath)."""

    count_param: str = "initNodes"
    zones: tuple[str, ...] = ()
    count: int = 0
    template: Callable[[int, tuple[str, ...]], t.Node] | None = None


@dataclass(frozen=True)
class CreateNamespacesOp:
    """operations.go createNamespacesOp. ``labels`` models
    namespaceTemplatePath (templates/namespace-with-labels.yaml);
    ``count_param`` overrides ``count`` when set."""

    prefix: str = "sched"
    count: int = 2
    count_param: str = ""
    labels: tuple[tuple[str, str], ...] = ()


@dataclass(frozen=True)
class CreateServiceOp:
    """createAny with a Service template (templates/service.yaml:
    selector foo=bar) — feeds the DefaultSelector for default spread."""

    namespace: str = "service-ns"
    name: str = "service"
    selector: tuple[tuple[str, str], ...] = (("foo", "bar"),)


@dataclass(frozen=True)
class DeletePodsOp:
    """operations.go deletePodsOp: gradually delete the pods previously
    created in ``namespace`` at ``per_second``, while later ops run
    (skipWaitToCompletion) — each delete fires an AssignedPodDelete event
    through the queue."""

    namespace: str
    per_second: int = 50


@dataclass(frozen=True)
class CreatePodSetsOp:
    """operations.go createPodSetsOp: for i in 0..count: createPods into
    namespace ``{prefix}-{i}``."""

    count_param: str = "initNamespaces"
    pods_param: str = "initPodsPerNamespace"
    prefix: str = "init-ns"
    template: PodTemplate | None = None


@dataclass(frozen=True)
class CreatePodsOp:
    """operations.go:295 createPodsOp. ``skip_wait`` = the YAML
    skipWaitToCompletion (gated pods never schedule; don't settle)."""

    count_param: str = "initPods"
    template: PodTemplate | None = None     # None → case default
    collect_metrics: bool = False
    namespace: str | None = None            # None → unique per-op namespace
    skip_wait: bool = False


@dataclass(frozen=True)
class CreatePodGroupsOp:
    """operations.go createAny with a PodGroup template
    (podgroup/gangscheduling/performance-config.yaml:18 + its
    templates/podgroup.yaml: gangs gang-0..gang-(n-1), each with
    minCount = podsPerGroup)."""

    count_param: str = "initPodGroups"
    min_count_param: str = "podsPerGroup"
    prefix: str = "gang"


@dataclass(frozen=True)
class CreateGangPodsOp:
    """createPods with countMultiplierParam (performance-config.yaml:28 +
    templates/gang-pod.yaml): pod i references gang-(i // podsPerGroup);
    100m cpu / 100Mi, like the reference template."""

    count_param: str = "initPodGroups"
    multiplier_param: str = "podsPerGroup"
    prefix: str = "gang"
    collect_metrics: bool = True
    namespace: str = "gang-0"


@dataclass(frozen=True)
class CreatePodsWithPVsOp:
    """createPods with persistentVolumeTemplatePath /
    persistentVolumeClaimTemplatePath (volumes/performance-config.yaml:55
    SchedulingInTreePVs, :142 SchedulingCSIPVs): each pod gets its own
    bound PV+PVC pair (templates/pv-aws.yaml + templates/pvc.yaml —
    ReadOnlyMany, 1Gi, bind-completed)."""

    count_param: str = "measurePods"
    collect_metrics: bool = False
    driver: str = ""                        # CSI driver name ("" = in-tree)
    namespace: str | None = None


def node_with_dra(i: int, zones: tuple[str, ...] = ()) -> t.Node:
    """templates/node-with-dra-test-driver.yaml: a default node named to
    match the driver op's ``nodes: scheduler-perf-dra-*`` selector."""
    name = f"scheduler-perf-dra-{i}"
    return make_node(
        name, cpu_milli=4000, memory=32 * 1024**3, pods=110,
        labels={HOSTNAME_KEY: name},
    )


@dataclass(frozen=True)
class CreateResourceDriverOp:
    """operations.go createResourceDriverOp (dra/performance-config.yaml
    ``createResourceDriver``): publish the DRA driver's DeviceClass plus one
    ResourceSlice with ``maxClaimsPerNodeParam`` devices per node matching
    ``node_prefix`` (the reference's ``nodes: scheduler-perf-dra-*``
    selector; test driver shape: templates/deviceclass.yaml + per-node
    slices)."""

    driver: str = "test-driver.cdi.k8s.io"
    class_name: str = "test-class"
    max_claims_param: str = "maxClaimsPerNode"
    node_prefix: str = "scheduler-perf-dra-"


@dataclass(frozen=True)
class CreateClaimPodsOp:
    """createPods with a ResourceClaimTemplate
    (dra/performance-config.yaml SchedulingWithResourceClaimTemplate:
    templates/resourceclaimtemplate.yaml + pod-with-claim-template.yaml):
    each pod gets its OWN ResourceClaim instance — one request, one device
    of ``class_name`` — exactly what the resourceclaim controller stamps
    from the template."""

    count_param: str = "measurePods"
    class_name: str = "test-class"
    collect_metrics: bool = False
    namespace: str = "dra-test"


@dataclass(frozen=True)
class ChurnOp:
    """operations.go:518 churnOp — create (or recreate) interfering objects
    at an interval while the measured phase runs."""

    mode: str = "create"                    # create | recreate
    template: PodTemplate = pod_high_priority_large_cpu
    interval_ms: int = 500
    number: int = 0                         # recreate pool size (0 = unbounded)


@dataclass(frozen=True)
class BarrierOp:
    """operations.go:574 barrierOp — wait until all created pods scheduled."""


Op = object  # union of the five ops above


@dataclass(frozen=True)
class Workload:
    name: str
    params: Mapping[str, int]
    threshold: float | None = None          # SchedulingThroughput floor
    labels: tuple[str, ...] = ()
    # Documented derivation when ``threshold`` is NOT a verbatim reference
    # floor (the reduced-shape CPU-fallback workloads): how the floor was
    # scaled from the full-shape reference number, so ``vs_baseline`` is
    # never null and never silently flattering
    threshold_note: str = ""


@dataclass(frozen=True)
class TestCase:
    name: str
    ops: tuple
    workloads: tuple[Workload, ...]
    default_pod_template: PodTemplate = pod_default
    source: str = ""                        # reference config citation
    # per-case featureGates block (performance-config.yaml featureGates:)
    feature_gates: tuple[tuple[str, bool], ...] = ()


# ---------------------------------------------------------------------------
# registry — the BASELINE.md rows (thresholds from the reference configs)
# ---------------------------------------------------------------------------

TEST_CASES: dict[str, TestCase] = {}


def _case(tc: TestCase) -> TestCase:
    TEST_CASES[tc.name] = tc
    return tc


_case(TestCase(
    name="SchedulingBasic",
    source="misc/performance-config.yaml:20",
    ops=(
        CreateNodesOp("initNodes"),
        CreatePodsOp("initPods"),
        CreatePodsOp("measurePods", collect_metrics=True),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 500, "measurePods": 1000},
                 threshold=680, threshold_note=(
                     "5k floor kept verbatim: per-pod cost of the linear "
                     "workload is ~flat in node count (the reference "
                     "subsamples via numFeasibleNodesToFind), so its 500-"
                     "node throughput is >= the 5k floor")),
        Workload("5000Nodes_10000Pods",
                 {"initNodes": 5000, "initPods": 1000, "measurePods": 10000},
                 threshold=680, labels=("performance",)),
        # the wire-protocol fullstack ladder (ROADMAP item 2): 1k/2k/5k
        # nodes driven THROUGH the REST apiserver with heavy watch
        # fan-out — the control-plane-bound shapes the binary codec +
        # native body ring exist for. Thresholds keep the reference 5k
        # floor verbatim (the 500Nodes note: per-pod cost of the linear
        # workload is ~flat in node count).
        Workload("1000Nodes",
                 {"initNodes": 1000, "initPods": 300, "measurePods": 800},
                 threshold=680, threshold_note=(
                     "5k floor kept verbatim: per-pod cost of the linear "
                     "workload is ~flat in node count"),
                 labels=("wire",)),
        Workload("2000Nodes",
                 {"initNodes": 2000, "initPods": 300, "measurePods": 800},
                 threshold=680, threshold_note=(
                     "5k floor kept verbatim: per-pod cost of the linear "
                     "workload is ~flat in node count"),
                 labels=("wire",)),
        Workload("5000Nodes_1000Pods",
                 {"initNodes": 5000, "initPods": 300, "measurePods": 1000},
                 threshold=680, labels=("wire",)),
        # the mesh-sharded tier (ROADMAP item 1): a cluster one chip's HBM
        # and FLOPs can't hold comfortably — run with mesh on/off for the
        # ShardingComparison evidence (the reference config tops out at 5k;
        # the floor is kept verbatim, see the 500Nodes note)
        Workload("15000Nodes",
                 {"initNodes": 15000, "initPods": 1000, "measurePods": 5000},
                 threshold=680, threshold_note=(
                     "no reference row at 15k nodes; the 5k-node floor "
                     "(680) is kept verbatim — per-pod cost of the linear "
                     "workload is ~flat in node count"),
                 labels=("multichip",)),
    ),
))

_case(TestCase(
    name="SchedulingPodAntiAffinity",
    source="affinity/performance-config.yaml:20",
    default_pod_template=pod_with_pod_anti_affinity,
    ops=(
        CreateNodesOp("initNodes"),
        CreateNamespacesOp("sched", 2),
        CreatePodsOp("initPods", namespace="sched-0"),
        CreatePodsOp("measurePods", collect_metrics=True, namespace="sched-1"),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 100, "measurePods": 400}),
        Workload("5000Nodes_2000Pods",
                 {"initNodes": 5000, "initPods": 1000, "measurePods": 2000},
                 threshold=180, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingPodMatchingAntiAffinity",
    source="affinity/performance-config.yaml:60",
    default_pod_template=pod_with_pod_anti_affinity,
    ops=(
        CreateNodesOp("initNodes"),
        CreateNamespacesOp("sched", 2),
        CreatePodsOp("initPods", namespace="sched-0"),
        CreatePodsOp("measurePods", template=pod_anti_affinity_label_only,
                     collect_metrics=True, namespace="sched-1"),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 100, "measurePods": 400}),
        Workload("5000Nodes_5000Pods",
                 {"initNodes": 5000, "initPods": 1000, "measurePods": 5000},
                 threshold=540, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingPodAffinity",
    source="affinity/performance-config.yaml:96 (threshold 70 — the hardest quadratic workload)",
    default_pod_template=pod_with_pod_affinity,
    ops=(
        CreateNodesOp("initNodes", zones=("zone1",)),
        CreateNamespacesOp("sched", 2),
        CreatePodsOp("initPods", namespace="sched-0"),
        CreatePodsOp("measurePods", collect_metrics=True, namespace="sched-1"),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 500, "measurePods": 1000},
                 threshold=700, threshold_note=(
                     "70 pods/s 5k floor x10: the quadratic PreScore cost "
                     "scales ~linearly with node count, so at 1/10 the "
                     "nodes the reference would run ~10x its floor — the "
                     "scaled floor keeps vs_baseline conservative")),
        Workload("5000Nodes_5000Pods",
                 {"initNodes": 5000, "initPods": 5000, "measurePods": 5000},
                 threshold=70, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingNodeAffinity",
    source="affinity/performance-config.yaml SchedulingNodeAffinity",
    default_pod_template=pod_with_node_affinity,
    ops=(
        CreateNodesOp("initNodes", zones=("zone1",)),
        CreatePodsOp("initPods"),
        CreatePodsOp("measurePods", collect_metrics=True),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 500, "measurePods": 1000}),
        Workload("5000Nodes_10000Pods",
                 {"initNodes": 5000, "initPods": 1000, "measurePods": 10000},
                 threshold=540, labels=("performance",)),
    ),
))

_case(TestCase(
    name="TopologySpreading",
    source="topology_spreading/performance-config.yaml:19",
    ops=(
        CreateNodesOp("initNodes", zones=("moon-1", "moon-2", "moon-3")),
        CreatePodsOp("initPods", template=pod_default),
        CreatePodsOp("measurePods", template=pod_with_topology_spreading,
                     collect_metrics=True),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 1000, "measurePods": 1000},
                 threshold=4600, threshold_note=(
                     "460 pods/s 5k floor x10: segment-sum PreScore cost "
                     "scales ~linearly with node count (see "
                     "SchedulingPodAffinity scaling note)")),
        Workload("5000Nodes_5000Pods",
                 {"initNodes": 5000, "initPods": 5000, "measurePods": 5000},
                 threshold=460, labels=("performance",)),
    ),
))

_case(TestCase(
    name="PreferredTopologySpreading",
    source="topology_spreading/performance-config.yaml:64",
    ops=(
        CreateNodesOp("initNodes", zones=("moon-1", "moon-2", "moon-3")),
        CreatePodsOp("initPods", template=pod_default),
        CreatePodsOp("measurePods",
                     template=pod_with_preferred_topology_spreading,
                     collect_metrics=True),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 1000, "measurePods": 1000}),
        Workload("5000Nodes_5000Pods",
                 {"initNodes": 5000, "initPods": 5000, "measurePods": 5000},
                 threshold=340, labels=("performance",)),
    ),
))

_case(TestCase(
    name="MixedSchedulingBasePod",
    source="affinity/performance-config.yaml MixedSchedulingBasePod",
    ops=(
        CreateNodesOp("initNodes", zones=("zone1",)),
        CreateNamespacesOp("sched", 1),
        CreatePodsOp("initPods", namespace="sched-0"),
        CreatePodsOp("initPods", template=pod_with_pod_affinity,
                     namespace="sched-0"),
        CreatePodsOp("initPods", template=pod_with_pod_anti_affinity,
                     namespace="sched-0"),
        CreatePodsOp("initPods", template=pod_with_preferred_pod_affinity,
                     namespace="sched-0"),
        CreatePodsOp("initPods", template=pod_with_preferred_pod_anti_affinity,
                     namespace="sched-0"),
        CreatePodsOp("measurePods", collect_metrics=True),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 200, "measurePods": 1000}),
        Workload("5000Nodes_5000Pods",
                 {"initNodes": 5000, "initPods": 2000, "measurePods": 5000},
                 threshold=540, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingInTreePVs",
    source="volumes/performance-config.yaml:55 (threshold 290)",
    ops=(
        CreateNodesOp("initNodes"),
        CreatePodsWithPVsOp("initPods"),
        CreatePodsWithPVsOp("measurePods", collect_metrics=True),
    ),
    workloads=(
        Workload("5Nodes", {"initNodes": 5, "initPods": 5, "measurePods": 10}),
        Workload("5000Nodes_2000Pods",
                 {"initNodes": 5000, "initPods": 1000, "measurePods": 2000},
                 threshold=290, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingCSIPVs",
    source="volumes/performance-config.yaml:142 (threshold 100)",
    ops=(
        CreateNodesOp("initNodes"),
        CreatePodsWithPVsOp("initPods", driver="ebs.csi.aws.com"),
        CreatePodsWithPVsOp("measurePods", driver="ebs.csi.aws.com",
                            collect_metrics=True),
    ),
    workloads=(
        Workload("5Nodes", {"initNodes": 5, "initPods": 5, "measurePods": 10}),
        Workload("5000Nodes_2000Pods",
                 {"initNodes": 5000, "initPods": 1000, "measurePods": 2000},
                 threshold=100, labels=("performance",)),
    ),
))

_case(TestCase(
    name="GangScheduling",
    source="podgroup/gangscheduling/performance-config.yaml:7 (no thresholds yet — new suite)",
    feature_gates=(("GenericWorkload", True), ("GangScheduling", True)),
    ops=(
        CreateNodesOp("initNodes"),
        CreateNamespacesOp("gang", 1),
        CreatePodGroupsOp("initPodGroups", "podsPerGroup"),
        CreateGangPodsOp("initPodGroups", "podsPerGroup",
                         collect_metrics=True),
    ),
    workloads=(
        Workload("10Nodes_3Gangs",
                 {"initNodes": 10, "initPodGroups": 3, "podsPerGroup": 3}),
        Workload("100Nodes_10Gangs",
                 {"initNodes": 100, "initPodGroups": 10, "podsPerGroup": 3}),
        Workload("5000Nodes_1000Gangs_3000Pods",
                 {"initNodes": 5000, "initPodGroups": 1000, "podsPerGroup": 3},
                 labels=("performance",)),
        Workload("5000Nodes_3Gangs_3000Pods_1000PerGroup",
                 {"initNodes": 5000, "initPodGroups": 3, "podsPerGroup": 1000},
                 labels=("performance",)),
    ),
))

_case(TestCase(
    name="Unschedulable",
    source="misc/performance-config.yaml:252",
    ops=(
        CreateNodesOp("initNodes"),
        CreatePodsOp("initPods"),
        ChurnOp(mode="create", template=pod_high_priority_large_cpu,
                interval_ms=200),
        CreatePodsOp("measurePods", template=pod_default,
                     collect_metrics=True),
    ),
    workloads=(
        Workload("500Nodes/10Init/1kPods",
                 {"initNodes": 500, "initPods": 10, "measurePods": 1000}),
        Workload("5kNodes/100Init/10kPods",
                 {"initNodes": 5000, "initPods": 100, "measurePods": 10000},
                 threshold=590, labels=("performance",)),
    ),
))

_case(TestCase(
    name="PreemptionAsync",
    source="misc/performance-config.yaml:186 (threshold 570)",
    ops=(
        CreateNodesOp("initNodes"),
        CreatePodsOp("initPods", template=pod_low_priority),
        ChurnOp(mode="create", template=pod_high_priority_3cpu,
                interval_ms=200),
        CreatePodsOp("measurePods", template=pod_default,
                     collect_metrics=True),
    ),
    workloads=(
        Workload("5Nodes", {"initNodes": 5, "initPods": 20, "measurePods": 5}),
        Workload("500Nodes",
                 {"initNodes": 500, "initPods": 2000, "measurePods": 500}),
        Workload("5000Nodes",
                 {"initNodes": 5000, "initPods": 20000, "measurePods": 5000},
                 threshold=570, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingDaemonset",
    source="misc/performance-config.yaml:91 (threshold 1100)",
    default_pod_template=daemonset_pod,
    ops=(
        # one named node receives every pod; the default nodes exist only
        # to be filtered out (the reference's PreFilterResult scenario)
        CreateNodesOp(count=1, template=node_with_name),
        CreateNodesOp("initNodes"),
        CreatePodsOp("measurePods", collect_metrics=True),
    ),
    workloads=(
        Workload("5Nodes", {"initNodes": 5, "measurePods": 10}),
        Workload("15000Nodes", {"initNodes": 15000, "measurePods": 30000},
                 threshold=1100, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingWhileGated",
    source="misc/performance-config.yaml:365 (threshold 910)",
    default_pod_template=light_pod,
    ops=(
        CreateNodesOp(count=1, template=node_with_name),
        # pods that stay gated to the end of the test
        CreatePodsOp("gatedPods", template=gated_pod, namespace="gated",
                     skip_wait=True),
        # pods that get scheduled then gradually deleted, generating
        # AssignedPodDelete events the queue must absorb
        CreatePodsOp("deletingPods", namespace="deleting"),
        DeletePodsOp(namespace="deleting", per_second=50),
        CreatePodsOp("measurePods", collect_metrics=True),
    ),
    workloads=(
        Workload("1Node_10GatedPods",
                 {"gatedPods": 10, "deletingPods": 10, "measurePods": 10}),
        Workload("1Node_10000GatedPods",
                 {"gatedPods": 10000, "deletingPods": 20000,
                  "measurePods": 20000},
                 threshold=910, labels=("performance",)),
    ),
))

_case(TestCase(
    name="DefaultTopologySpreading",
    source="topology_spreading/performance-config.yaml:104 (threshold 160 at 50k; "
           "a service's selector drives the DEFAULT spread constraints)",
    default_pod_template=pod_with_label,
    ops=(
        CreateNodesOp("initNodes", zones=("moon-1", "moon-2", "moon-3")),
        CreateServiceOp(namespace="service-ns"),
        CreatePodsOp("initPods", template=pod_default),
        CreatePodsOp("measurePods", collect_metrics=True,
                     namespace="service-ns"),
    ),
    workloads=(
        Workload("500Nodes", {"initNodes": 500, "initPods": 1000, "measurePods": 1000}),
        Workload("5000Nodes_50000Pods",
                 {"initNodes": 5000, "initPods": 5000, "measurePods": 50000},
                 threshold=160, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingPreferredAntiAffinityWithNSSelector",
    source="affinity/performance-config.yaml:391",
    default_pod_template=pod_preferred_anti_affinity_ns_selector,
    ops=(
        CreateNodesOp("initNodes"),
        CreateNamespacesOp("init-ns", count_param="initNamespaces",
                           labels=(("team", "devops"),)),
        CreateNamespacesOp("measure-ns", count=1,
                           labels=(("team", "devops"),)),
        CreatePodSetsOp("initNamespaces", "initPodsPerNamespace",
                        prefix="init-ns"),
        CreatePodsOp("measurePods", collect_metrics=True,
                     namespace="measure-ns-0"),
    ),
    workloads=(
        Workload("10Nodes",
                 {"initNodes": 10, "initPodsPerNamespace": 2,
                  "initNamespaces": 2, "measurePods": 10}),
        Workload("500Nodes",
                 {"initNodes": 500, "initPodsPerNamespace": 4,
                  "initNamespaces": 10, "measurePods": 100},
                 labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingWithExtendedResource",
    source="misc/performance-config.yaml:452 (threshold 180)",
    ops=(
        CreateNodesOp("nodesWithoutExtendedResource"),
        CreateNodesOp("nodesWithExtendedResource",
                      template=node_with_extended_resource),
        CreateExtendedResourcePodsOp("measurePods", collect_metrics=True),
    ),
    workloads=(
        Workload("fast", {"nodesWithExtendedResource": 10,
                          "nodesWithoutExtendedResource": 1,
                          "measurePods": 10}),
        Workload("5000pods_5000nodes",
                 {"nodesWithExtendedResource": 5000,
                  "nodesWithoutExtendedResource": 0, "measurePods": 5000},
                 threshold=180, labels=("performance",)),
    ),
))

_case(TestCase(
    name="SchedulingWithResourceClaimTemplate",
    source="dra/performance-config.yaml:58 (threshold 56, 'typically above 70')",
    feature_gates=(("DynamicResourceAllocation", True),),
    ops=(
        CreateNodesOp("nodesWithoutDRA"),
        CreateNodesOp("nodesWithDRA", template=node_with_dra),
        CreateResourceDriverOp(),
        CreateClaimPodsOp("initPods", namespace="init"),
        CreateClaimPodsOp("measurePods", collect_metrics=True,
                          namespace="test"),
    ),
    workloads=(
        Workload("fast", {"nodesWithDRA": 1, "nodesWithoutDRA": 1,
                          "initPods": 0, "measurePods": 10,
                          "maxClaimsPerNode": 10}),
        Workload("5000pods_500nodes",
                 {"nodesWithDRA": 500, "nodesWithoutDRA": 0,
                  "initPods": 2500, "measurePods": 2500,
                  "maxClaimsPerNode": 10},
                 threshold=56, labels=("performance",)),
    ),
))

# ---------------------------------------------------------------------------
# Trace-shaped workloads (ROADMAP item 5 / the PR-14 scale frontier)
#
# Uniform createPods op-lists never exercise what Tesserae (2508.04953) and
# "Priority Matters" (2511.08373) judge schedulers on: time-varying,
# multi-tenant load. A *trace* is a seeded, DETERMINISTIC event stream —
# (trace-clock offset, op) tuples the runner replays against the real
# scheduler loop, measuring an admission-latency SLO (p99 enqueue→bind vs a
# declared budget) instead of only steady-state throughput. Four generators:
#
# - diurnal_burst_trace: a sinusoidal diurnal arrival curve with flash-crowd
#   bursts layered on top (queue-wait spikes are the point);
# - node_wave_trace: autoscaler-style node ADD waves that later DRAIN, under
#   a steady pod trickle (exercises the append-incremental encode + scoped
#   cache extension + incremental reshard at scale);
# - rolling_update_trace: delete+create trains over a standing fleet (the
#   informer→invalidate→re-encode path under realistic update storms);
# - multitenant_trace: priority tiers + gangs + spread constraints arriving
#   INTERLEAVED (the mixed-tenant shape single-template cases never hit).
#
# Determinism contract: same (generator, seed, params) → identical event
# tuple, asserted in tier-1 — replay TIMING is wall-clock, the op sequence
# is not.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TraceEvent:
    """One trace op. ``at_s`` is the trace-clock offset; the runner fires
    every event whose offset has elapsed before each scheduling cycle."""

    at_s: float
    kind: str                   # create_pod|delete_pod|add_node|drain_node|create_group
    name: str = ""
    namespace: str = "trace"
    template: str = "default"   # build_trace_pod dispatch key
    priority: int = 0
    group: str = ""             # scheduling group (gang members)
    min_count: int = 0          # gang quorum (create_group)


_TRACE_REQ = dict(cpu_milli=100, memory=500 * 1024**2)


def build_trace_pod(ev: TraceEvent) -> t.Pod:
    """Materialize a trace create_pod event. Templates are deliberately few
    (controller-stamped workloads share specs — the encode cache's bet):
    ``default`` (pod-default shape), ``tiny`` (no requests), ``spread``
    (zone maxSkew-5 DoNotSchedule over color=blue), ``prio`` (default shape
    carrying the event's priority), ``gang`` (member of ``ev.group``)."""
    if ev.template == "tiny":
        return make_pod(ev.name, namespace=ev.namespace,
                        priority=ev.priority)
    if ev.template == "spread":
        return make_pod(
            ev.name, namespace=ev.namespace, labels={"color": "blue"},
            priority=ev.priority,
            spread=(spread_constraint(
                5, ZONE_KEY,
                when=t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE,
                match_labels={"color": "blue"},
            ),),
            **_TRACE_REQ,
        )
    if ev.template == "gang":
        return make_pod(
            ev.name, namespace=ev.namespace, priority=ev.priority,
            scheduling_group=ev.group, **_TRACE_REQ,
        )
    # "default" / "prio"
    return make_pod(ev.name, namespace=ev.namespace, priority=ev.priority,
                    **_TRACE_REQ)


def _sorted_events(events: list) -> tuple:
    """Stable total order: trace time, then name (ties must not depend on
    generator emit order — determinism is the contract)."""
    return tuple(sorted(events, key=lambda e: (e.at_s, e.kind, e.name)))


def diurnal_burst_trace(
    seed: int = 0,
    duration_s: float = 30.0,
    base_rate: float = 20.0,
    peak_rate: float = 120.0,
    bursts: int = 2,
    burst_pods: int = 150,
    burst_width_s: float = 1.0,
    namespace: str = "trace",
) -> tuple:
    """One diurnal cycle: Poisson arrivals at rate λ(t) = base + (peak −
    base)·½(1 − cos 2πt/T), plus ``bursts`` flash crowds of ``burst_pods``
    each landing inside ``burst_width_s`` at seeded times in the middle
    80% of the trace."""
    rng = np.random.default_rng(seed)
    events: list[TraceEvent] = []
    seq = 0
    for sec in range(int(duration_s)):
        lam = base_rate + (peak_rate - base_rate) * 0.5 * (
            1.0 - math.cos(2.0 * math.pi * sec / duration_s)
        )
        n = int(rng.poisson(lam))
        for k in range(n):
            events.append(TraceEvent(
                at_s=sec + (k + 0.5) / (n + 1), kind="create_pod",
                name=f"d-{seq}", namespace=namespace,
            ))
            seq += 1
    starts = np.sort(rng.uniform(
        0.1 * duration_s, 0.9 * duration_s, size=bursts
    ))
    for b, t0 in enumerate(starts):
        for k in range(burst_pods):
            events.append(TraceEvent(
                at_s=float(t0) + burst_width_s * k / max(burst_pods, 1),
                kind="create_pod", name=f"burst-{b}-{k}",
                namespace=namespace,
            ))
    return _sorted_events(events)


def node_wave_trace(
    seed: int = 0,
    duration_s: float = 30.0,
    pod_rate: float = 40.0,
    waves: int = 2,
    wave_nodes: int = 64,
    ramp_s: float = 2.0,
    drain: bool = True,
    namespace: str = "trace",
) -> tuple:
    """Steady pod trickle at ``pod_rate`` (uniform spacing — the wave is the
    variable, not the arrivals) + ``waves`` autoscaler waves: each adds
    ``wave_nodes`` nodes spread over ``ramp_s``, and — when ``drain`` —
    deletes them again in the trace's final quarter. Wave k's nodes are
    named ``wave-{k}-{i}`` so shape tests (and the drain) can address
    them."""
    rng = np.random.default_rng(seed)
    events: list[TraceEvent] = []
    total_pods = int(duration_s * pod_rate)
    for j in range(total_pods):
        events.append(TraceEvent(
            at_s=j / pod_rate, kind="create_pod", name=f"w-{j}",
            namespace=namespace,
        ))
    # wave starts inside the first half so their capacity matters to the
    # trailing arrivals; jittered but seeded
    starts = np.sort(rng.uniform(
        0.1 * duration_s, 0.5 * duration_s, size=waves
    ))
    for w, t0 in enumerate(starts):
        for i in range(wave_nodes):
            events.append(TraceEvent(
                at_s=float(t0) + ramp_s * i / max(wave_nodes, 1),
                kind="add_node", name=f"wave-{w}-{i}",
            ))
        if drain:
            t_drain = 0.75 * duration_s + w
            for i in range(wave_nodes):
                events.append(TraceEvent(
                    at_s=t_drain + ramp_s * i / max(wave_nodes, 1),
                    kind="drain_node", name=f"wave-{w}-{i}",
                ))
    return _sorted_events(events)


def rolling_update_trace(
    seed: int = 0,
    duration_s: float = 30.0,
    fleet: int = 200,
    trains: int = 4,
    train_size: int = 50,
    namespace: str = "trace",
) -> tuple:
    """A standing fleet of ``fleet`` pods (created over the first second),
    then ``trains`` rolling-update trains: train k deletes ``train_size``
    pods (round-robin over the fleet) and recreates them at the next
    version — the delete+create storm a Deployment rollout feeds the
    scheduler."""
    rng = np.random.default_rng(seed)
    events: list[TraceEvent] = []
    version = [0] * fleet
    for i in range(fleet):
        events.append(TraceEvent(
            at_s=i / max(fleet, 1), kind="create_pod",
            name=f"roll-{i}-v0", namespace=namespace,
        ))
    # trains fire between 20% and 90% of the trace, jittered but seeded
    starts = np.sort(rng.uniform(
        0.2 * duration_s, 0.9 * duration_s, size=trains
    ))
    for k, t0 in enumerate(starts):
        for j in range(train_size):
            i = (k * train_size + j) % fleet
            v = version[i]
            at = float(t0) + j * 0.01
            events.append(TraceEvent(
                at_s=at, kind="delete_pod", name=f"roll-{i}-v{v}",
                namespace=namespace,
            ))
            events.append(TraceEvent(
                at_s=at + 0.005, kind="create_pod",
                name=f"roll-{i}-v{v + 1}", namespace=namespace,
            ))
            version[i] = v + 1
    return _sorted_events(events)


def multitenant_trace(
    seed: int = 0,
    duration_s: float = 30.0,
    rate: float = 40.0,
    gangs: int = 6,
    gang_size: int = 4,
    namespace: str = "trace",
) -> tuple:
    """The mixed-tenant profile: arrivals at ``rate`` are drawn (seeded)
    from three tenant classes — latency-sensitive high-priority pods
    (priority 10), batch pods (priority 0), and spread-constrained service
    pods — while ``gangs`` gang groups (quorum ``gang_size``) arrive at
    seeded times with their members trickling in. Priority tiers, gangs
    and spread constraints are live SIMULTANEOUSLY, which is the point."""
    rng = np.random.default_rng(seed)
    events: list[TraceEvent] = []
    total = int(duration_s * rate)
    classes = rng.choice(3, size=total, p=(0.3, 0.5, 0.2))
    for j in range(total):
        at = j / rate
        cls = int(classes[j])
        if cls == 0:
            events.append(TraceEvent(
                at_s=at, kind="create_pod", name=f"hi-{j}",
                namespace=namespace, template="prio", priority=10,
            ))
        elif cls == 1:
            events.append(TraceEvent(
                at_s=at, kind="create_pod", name=f"batch-{j}",
                namespace=namespace,
            ))
        else:
            events.append(TraceEvent(
                at_s=at, kind="create_pod", name=f"svc-{j}",
                namespace=namespace, template="spread",
            ))
    starts = np.sort(rng.uniform(
        0.1 * duration_s, 0.8 * duration_s, size=gangs
    ))
    for g, t0 in enumerate(starts):
        events.append(TraceEvent(
            at_s=float(t0), kind="create_group", name=f"gang-{g}",
            namespace=namespace, min_count=gang_size,
        ))
        for m in range(gang_size):
            events.append(TraceEvent(
                at_s=float(t0) + 0.05 * (m + 1), kind="create_pod",
                name=f"gang-{g}-m{m}", namespace=namespace,
                template="gang", priority=5, group=f"gang-{g}",
            ))
    return _sorted_events(events)


def train_serve_churn_trace(
    seed: int = 0,
    duration_s: float = 30.0,
    serve_rate: float = 30.0,
    gangs: int = 8,
    gang_size: int = 4,
    gang_lifetime_s: float = 8.0,
    churn: float = 0.3,
    namespace: str = "trace",
) -> tuple:
    """Mixed train+serve churn: latency-sensitive SERVE pods arrive at
    ``serve_rate`` (a seeded fraction ``churn`` of them is deleted a few
    seconds later — rolling serve churn), while TRAIN gangs (quorum
    ``gang_size``) arrive at seeded times and DEPART ``gang_lifetime_s``
    later, members deleted. On a sliced fleet the scheduling question is
    whether departed train gangs leave their slices FULLY free at steady
    state, or scattered serve pods keep every slice partially occupied —
    the fragmentation-over-time evidence."""
    rng = np.random.default_rng(seed)
    events: list[TraceEvent] = []
    total = int(duration_s * serve_rate)
    kill = rng.random(total) < churn
    lifetimes = rng.uniform(2.0, 6.0, size=total)
    for j in range(total):
        at = j / serve_rate
        events.append(TraceEvent(
            at_s=at, kind="create_pod", name=f"serve-{j}",
            namespace=namespace, template="prio", priority=8,
        ))
        if kill[j]:
            events.append(TraceEvent(
                at_s=min(at + float(lifetimes[j]), 0.95 * duration_s),
                kind="delete_pod", name=f"serve-{j}", namespace=namespace,
            ))
    starts = np.sort(rng.uniform(
        0.1 * duration_s, 0.6 * duration_s, size=gangs
    ))
    for g, t0 in enumerate(starts):
        events.append(TraceEvent(
            at_s=float(t0), kind="create_group", name=f"train-{g}",
            namespace=namespace, min_count=gang_size,
        ))
        for m in range(gang_size):
            events.append(TraceEvent(
                at_s=float(t0) + 0.05 * (m + 1), kind="create_pod",
                name=f"train-{g}-m{m}", namespace=namespace,
                template="gang", priority=5, group=f"train-{g}",
            ))
            end = float(t0) + gang_lifetime_s + 0.05 * m
            if end < 0.9 * duration_s:
                events.append(TraceEvent(
                    at_s=end, kind="delete_pod", name=f"train-{g}-m{m}",
                    namespace=namespace,
                ))
    return _sorted_events(events)


@dataclass(frozen=True)
class TraceProfile:
    """A named trace shape: generator + params + initial cluster size +
    the admission SLO budget its record is judged against. ``events()`` is
    the deterministic op sequence; ``scaled()`` derives larger rungs (the
    50k/100k ladder) without re-declaring the shape. ``slices > 0`` stamps
    every node (initial fleet AND wave nodes — one grammar,
    trace_topology_labels) with rack/TPU-slice labels so the scenario can
    run with the node-topology axis engaged."""

    name: str
    gen: Callable[..., tuple]
    params: Mapping
    nodes: int
    slo_budget_ms: float
    seed: int = 0
    zones: tuple[str, ...] = ("zone-a", "zone-b", "zone-c")
    slices: int = 0
    description: str = ""

    def events(self) -> tuple:
        return self.gen(seed=self.seed, **dict(self.params))

    def scaled(self, suffix: str, nodes: int | None = None,
               slo_budget_ms: float | None = None, **param_overrides
               ) -> "TraceProfile":
        params = dict(self.params)
        params.update(param_overrides)
        return replace(
            self,
            name=f"{self.name}-{suffix}",
            params=params,
            nodes=nodes if nodes is not None else self.nodes,
            slo_budget_ms=(
                slo_budget_ms if slo_budget_ms is not None
                else self.slo_budget_ms
            ),
        )


TRACE_PROFILES: dict[str, TraceProfile] = {}


def _trace(p: TraceProfile) -> TraceProfile:
    TRACE_PROFILES[p.name] = p
    return p


_trace(TraceProfile(
    name="diurnal-burst",
    gen=diurnal_burst_trace,
    params=dict(duration_s=30.0, base_rate=20.0, peak_rate=120.0,
                bursts=2, burst_pods=150),
    nodes=5000,
    slo_budget_ms=4000.0,
    description="sinusoidal diurnal arrivals + flash-crowd bursts "
                "(flash-crowd admission p99 vs budget)",
))

_trace(TraceProfile(
    name="node-wave",
    gen=node_wave_trace,
    params=dict(duration_s=30.0, pod_rate=40.0, waves=2, wave_nodes=64,
                ramp_s=2.0),
    nodes=5000,
    slo_budget_ms=3000.0,
    description="autoscaler add/drain node waves under a steady pod "
                "trickle (incremental reshard + scoped cache extension)",
))

_trace(TraceProfile(
    name="rolling-update",
    gen=rolling_update_trace,
    params=dict(duration_s=30.0, fleet=200, trains=4, train_size=50),
    nodes=2000,
    slo_budget_ms=3000.0,
    description="delete+create trains over a standing fleet "
                "(rollout storms through the informer path)",
))

_trace(TraceProfile(
    name="multitenant",
    gen=multitenant_trace,
    params=dict(duration_s=30.0, rate=40.0, gangs=6, gang_size=4),
    nodes=2000,
    slo_budget_ms=5000.0,
    slices=32,
    description="priority tiers + gangs + spread constraints interleaved "
                "(the mixed-tenant admission shape) on a sliced fleet",
))

_trace(TraceProfile(
    name="train-serve-churn",
    gen=train_serve_churn_trace,
    params=dict(duration_s=30.0, serve_rate=30.0, gangs=8, gang_size=4,
                gang_lifetime_s=8.0, churn=0.3),
    nodes=512,
    slo_budget_ms=5000.0,
    slices=16,
    description="mixed train gangs + serve churn on a sliced fleet "
                "(topology on vs off: do train departures leave slices "
                "fully free?)",
))

_trace(TraceProfile(
    name="slice-fragmentation",
    gen=train_serve_churn_trace,
    params=dict(duration_s=30.0, serve_rate=20.0, gangs=10, gang_size=4,
                gang_lifetime_s=6.0, churn=0.5),
    nodes=256,
    slo_budget_ms=5000.0,
    slices=16,
    description="fragmentation-over-time: heavy gang arrival/departure "
                "churn — slices_free_at_steady_state is the gated metric",
))

_trace(TraceProfile(
    name="gang-contention",
    gen=multitenant_trace,
    params=dict(duration_s=20.0, rate=60.0, gangs=12, gang_size=6),
    nodes=128,
    slo_budget_ms=8000.0,
    slices=8,
    description="gang admission latency under contention: many gangs "
                "racing a small sliced fleet against a dense pod stream "
                "(gang_admission_p99_ms is the gated metric)",
))


_case(TestCase(
    name="BinPacking",
    source="PR 19: utilization-vs-throughput frontier workload (no "
           "reference config — skewed sizes + priority tiers built to "
           "compare the three engines)",
    default_pod_template=pod_binpack,
    ops=(
        CreateNodesOp("initNodes"),
        CreatePodsOp("initPods"),
        CreatePodsOp("measurePods", collect_metrics=True),
    ),
    workloads=(
        # no pods/s threshold: the workload's verdict is
        # nodes_used_at_steady_state and priority_slo_hit_rate against the
        # greedy baseline, not a reference throughput floor
        Workload("200Nodes",
                 {"initNodes": 200, "initPods": 50, "measurePods": 300}),
        Workload("1000Nodes_3000Pods",
                 {"initNodes": 1000, "initPods": 200, "measurePods": 3000},
                 labels=("performance", "packing")),
    ),
))

_case(TestCase(
    name="SchedulingWithMixedChurn",
    source="misc/performance-config.yaml:327",
    ops=(
        CreateNodesOp("initNodes"),
        ChurnOp(mode="recreate", template=pod_high_priority_large_cpu,
                interval_ms=1000, number=1),
        CreatePodsOp("measurePods", template=pod_default,
                     collect_metrics=True),
    ),
    workloads=(
        Workload("1000Nodes", {"initNodes": 1000, "measurePods": 1000},
                 threshold=710, threshold_note=(
                     "5k floor kept verbatim: like SchedulingBasic, the "
                     "per-pod cost of the linear churn workload is ~flat "
                     "in node count, so the 1000-node throughput is >= "
                     "the 5k floor")),
        Workload("5000Nodes_10000Pods",
                 {"initNodes": 5000, "measurePods": 10000},
                 threshold=710, labels=("performance",)),
    ),
))
