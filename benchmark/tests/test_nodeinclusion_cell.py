"""The cell ``nodeinclusion-5k.saturate``: its reader on fake /metrics pages,
its entries, its two templates beside upstream's yaml, and one CPU rehearsal
of the cell itself (control flow and counts only)."""

import pytest

from benchmark.harness import templates
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.tests.test_preferredaffinity_cell import DEVICE, LATER
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_spread_cell import LOOP, SHARED, FakeRun, reader

CELL = "nodeinclusion-5k.saturate"
COUNTER = "scheduler_spread_policy_pods_total"
NEW = "spread_policy_pod_share"
TEMPLATES = "benchmark.harness.templates_nodeinclusion:"


def page(policy, attempts) -> str:
    """``policy`` None: a program without the counter."""
    lines = ["scheduler_spread_constrained_pods_total 100"]
    if policy is not None:
        lines += [f'{COUNTER}{{policy="{k}"}} {v}' for k, v in policy.items()]
    lines += [f'scheduler_schedule_attempts_total{{result="{r}",'
              f'profile="default-scheduler"}} {v}'
              for r, v in attempts.items()]
    return "\n".join(lines) + "\n"


def test_the_policy_share_counts_the_taints_pods_over_every_attempt():
    before = page({"taints": 100, "affinity": 40},
                  {"scheduled": 100, "unschedulable": 0})
    after = page({"taints": 580, "affinity": 700},
                 {"scheduled": 600, "unschedulable": 100})
    # the affinity policy's pods are not in it
    assert reader(NEW)(FakeRun(before, after)) == \
        pytest.approx(100 * 480 / 600)
    every = page({"taints": 1124, "affinity": 40},
                 {"scheduled": 1124, "unschedulable": 0})
    assert reader(NEW)(FakeRun(before, every)) == 100.0


def test_a_program_without_the_counter_reads_as_nothing():
    """The parent commit counts spread-constrained pods and attempts, but
    has no policy counter: no value, no exception."""
    before = page(None, {"scheduled": 100})
    after = page(None, {"scheduled": 600})
    assert reader(NEW)(FakeRun(before, after)) is None
    assert reader(NEW)(FakeRun("up 1\n", "up 1\n")) is None


def test_no_attempt_in_the_window_gives_no_policy_share():
    same = page({"taints": 100, "affinity": 0}, {"scheduled": 100})
    assert reader(NEW)(FakeRun(same, same)) is None


def test_the_cell_s_entries():
    """Membership and content, never a position or an exhaustive list."""
    m = load_manifest()
    cell = Cell(m, CELL)
    assert CELL in [w["name"] for w in m["workloads"]]
    assert cell.chips == 1 and cell.traffic["mode"] == "saturate"
    assert {"pods_bound_per_s", "setup_s"} <= {
        e["name"] for e in cell.end_to_end}
    # the six thread-clock metrics of PR 38 list four cells, pinned by
    # test_thread_clocks.py as the spread readers are by test_spread_cell.py
    # (PERF.md 7 (l)): neither group takes this cell yet
    assert set(SHARED + LOOP + LATER + [NEW]) <= {
        e["name"] for e in cell.per_layer}
    entry = next(e for e in m["per_layer"] if e["name"] == NEW)
    assert CELL in entry["workloads"]
    assert entry["layer"] == "host encode" and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "pods_bound_per_s"
    assert entry["better"] == "higher"
    cfg = cell.config
    assert cfg["reduced"] == [] and cfg["nodes"] == 5000
    assert cfg["source"].startswith(
        "kubernetes test/integration/scheduler_perf/topology_spreading/"
        "performance-config.yaml:176 SchedulingWithNodeInclusionPolicy "
        "5000Nodes")
    assert cfg["node_template"] == TEMPLATES + "node_with_taint_one_in_five"
    assert cfg["zones"] == [] and cfg["namespaces"] == []
    pod = TEMPLATES + "pod_with_node_inclusion_policy"
    assert cfg["init_pods"] == {"count": 0, "template": pod,
                                "namespace": "namespace-0"}
    assert cfg["measured_pods"] == {"template": pod,
                                    "namespace": "namespace-1"}
    assert cfg["scheduler_flags"] == ["--engine", "greedy", "--mesh", "off"]
    assert cfg["assign_program"] == "greedy_assign_device"
    assert cfg["parity"]["rule"] == "pod_for_pod"
    assert cfg["parity"]["sample"] in (32, 64)
    assert cfg["parity"]["oracle"] == {"w_fit": 1, "w_balanced": 1,
                                       "w_spread": 2, "check_spread": True}
    basic = Cell(m, "basic-5k.saturate").config
    assert cfg["guarantees"][:4] == basic["guarantees"]
    assert len(cfg["guarantees"]) == 5
    assert "tainted node" in cfg["guarantees"][4]
    assert "maxSkew 1" in cfg["guarantees"][4]
    assumed = " ".join(cfg["assumed"])
    for said in ("two-stage", "greedy scan", "this repo's rendering",
                 "foo=bar:NoSchedule", "160000", "200000", "init_pods count 0",
                 "in-memory store"):
        assert said in assumed, said
    assert "pipeline off" not in assumed


def test_the_templates_are_upstream_s():
    """node-with-taint.yaml and pod-with-node-inclusion-policy.yaml as the
    module docstring writes them out, field for field; the node template is
    node-default's but for one tainted node in five."""
    from kubetpu.api import types as t

    from benchmark.harness import templates_nodeinclusion as mod

    cfg = Cell(load_manifest(), CELL).config
    node_of = templates.resolve(templates.NODE_TEMPLATES,
                                cfg["node_template"])
    assert node_of is mod.node_with_taint_one_in_five
    nodes = [node_of(i) for i in range(5000)]
    tainted = [n for n in nodes if n.taints]
    assert len(tainted) == 1000
    assert [int(n.name.rsplit("-", 1)[1]) % 5 for n in tainted] == [4] * 1000
    assert {n.taints for n in tainted} == {(t.Taint(
        "foo", "bar", t.TaintEffect.NO_SCHEDULE),)}
    for i in (3, 4):
        plain = templates.node_default(i)
        assert node_of(i).name == f"scheduler-perf-{i}"
        assert (node_of(i).labels, node_of(i).allocatable) == (
            plain.labels, plain.allocatable)
    assert not node_of(3).taints and node_of(3) == templates.node_default(3)

    make = templates.resolve(templates.POD_TEMPLATES,
                             cfg["measured_pods"]["template"])
    assert make is mod.pod_with_node_inclusion_policy
    assert make is templates.resolve(templates.POD_TEMPLATES,
                                     cfg["init_pods"]["template"])
    pod = make("p0", "namespace-1")
    assert (pod.name, pod.namespace) == ("p0", "namespace-1")
    assert dict(pod.labels) == {"foo": "bar"}
    assert dict(pod.requests) == {"cpu": 100, "memory": 500 * 1024 ** 2}
    [c] = pod.topology_spread_constraints
    assert (c.max_skew, c.topology_key) == (1, templates.HOSTNAME_KEY)
    assert c.when_unsatisfiable == \
        t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE
    assert dict(c.selector.match_labels) == {"foo": "bar"}
    assert not c.selector.match_expressions
    assert (c.node_affinity_policy, c.node_taints_policy) == ("Honor",
                                                               "Honor")
    assert c.min_domains is None and not c.match_label_keys
    assert not pod.tolerations and not pod.node_selector
    assert pod.affinity is None and not pod.node_name and pod.priority == 0
    for line in ("key: foo", "value: bar", "effect: NoSchedule",
                 "foo: bar", "maxSkew: 1", "topologyKey: kubernetes.io/hostname",
                 "whenUnsatisfiable: DoNotSchedule", "nodeAffinityPolicy: Honor",
                 "nodeTaintsPolicy: Honor", "cpu: 100m", "memory: 500Mi"):
        assert line in mod.__doc__, line
    # the harness counts the tainted nodes too (PERF.md 7): 40 pods a node
    # by CPU on 5000 nodes, where the untainted hold 160,000
    assert templates.capacity(cfg) == 200_000


def test_traced_rehearsal_reports_the_policy_share():
    line = rehearse(CELL, 1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["checks"]["oracle_disagreements"] == [0, 0]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # no TPU: the device-trace metrics find nothing to read; a later PR may
    # give the cell more metrics, so this is a subset and not the whole
    assert set(SHARED + LOOP + LATER + [NEW]) - DEVICE <= set(got)
    assert got[NEW] == pytest.approx(100.0)
    assert line["metrics"][NEW]["unit"] == "%"
