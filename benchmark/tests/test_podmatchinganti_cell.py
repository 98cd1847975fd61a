"""The cell ``podmatchinganti-5k.saturate``: its two readers on fake
/metrics pages, its entries, its two templates beside upstream's yaml as
``kubetpu/perf/workloads.py`` renders it, and one CPU rehearsal of the cell
itself (control flow and counts only)."""

import pytest

from benchmark.harness import templates
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.tests.test_preferredaffinity_cell import DEVICE, LATER
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_spread_cell import LOOP, SHARED, FakeRun, reader

CELL = "podmatchinganti-5k.saturate"
FILTER = "scheduler_podaffinity_filter_pods_total"
NODES = "scheduler_podaffinity_existing_anti_nodes_total"
SHARE = "podaffinity_existing_anti_pod_share"
PER_POD = "podaffinity_existing_anti_nodes_per_pod"
NEW = [SHARE, PER_POD]
#: appended to this cell's lists besides SHARED, LOOP and LATER
ALSO = ["watch_bind_delta_share", "podaffinity_encode_share"]
TEMPLATES = "benchmark.harness.templates_podmatchinganti:"


def page(terms, nodes, attempts) -> str:
    """``terms`` None: a program without the filter counter; ``nodes``
    None: one without the node counter."""
    lines = ['scheduler_podaffinity_pods_total{work="filter"} 100']
    if terms is not None:
        lines += [f'{FILTER}{{term="{k}"}} {v}' for k, v in terms.items()]
    if nodes is not None:
        lines.append(f"{NODES} {nodes}")
    lines += [f'scheduler_schedule_attempts_total{{result="{r}",'
              f'profile="default-scheduler"}} {v}'
              for r, v in attempts.items()]
    return "\n".join(lines) + "\n"


def terms(existing, anti=0, affinity=0):
    return {"affinity": affinity, "anti_affinity": anti,
            "existing_anti_affinity": existing}


def test_the_pod_share_counts_the_existing_anti_pods_over_every_attempt():
    before = page(terms(100, anti=40), 100_000,
                  {"scheduled": 100, "unschedulable": 0})
    after = page(terms(580, anti=700), 580_000,
                 {"scheduled": 600, "unschedulable": 100})
    # the incoming anti-affinity pods are not in it
    assert reader(SHARE)(FakeRun(before, after)) == \
        pytest.approx(100 * 480 / 600)
    every = page(terms(1124), 1_124_000, {"scheduled": 1124})
    assert reader(SHARE)(FakeRun(before, every)) == 100.0


def test_the_nodes_per_pod_are_the_refused_nodes_over_those_pods():
    before = page(terms(100), 100_000, {"scheduled": 100})
    after = page(terms(1124, affinity=5000), 1_124_000 - 1024,
                 {"scheduled": 1124})
    assert reader(PER_POD)(FakeRun(before, after)) == \
        pytest.approx((1_024_000 - 1024) / 1024)
    whole = page(terms(1124), 1_124_000, {"scheduled": 1124})
    assert reader(PER_POD)(FakeRun(before, whole)) == 1000.0


def test_a_program_without_the_counters_reads_as_nothing():
    """The parent commit counts affinity pods by kernel and attempts, but
    has neither counter: no value, no exception."""
    before = page(None, None, {"scheduled": 100})
    after = page(None, None, {"scheduled": 600})
    for name in NEW:
        assert reader(name)(FakeRun(before, after)) is None
        assert reader(name)(FakeRun("up 1\n", "up 1\n")) is None


def test_an_empty_window_gives_neither_reading():
    same = page(terms(100), 100_000, {"scheduled": 100})
    for name in NEW:
        assert reader(name)(FakeRun(same, same)) is None
    # attempts, but none that an existing term met: a share of 0, no
    # nodes per pod
    after = page(terms(100), 100_000, {"scheduled": 600})
    assert reader(SHARE)(FakeRun(same, after)) == 0.0
    assert reader(PER_POD)(FakeRun(same, after)) is None


def test_the_cell_s_entries():
    """Membership and content, never a position or an exhaustive list."""
    m = load_manifest()
    cell = Cell(m, CELL)
    assert CELL in [w["name"] for w in m["workloads"]]
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert entry["config"] == "podmatchinganti-5k"
    assert len(entry["why"]) <= 200
    assert cell.chips == 1 and cell.traffic["mode"] == "saturate"
    assert {"pods_bound_per_s", "setup_s"} <= {
        e["name"] for e in cell.end_to_end}
    have = {e["name"] for e in cell.per_layer}
    assert set(SHARED + LOOP + LATER + ALSO + NEW) <= have
    # a constant 0 here, the spread readers' cells, and the thread-clock
    # metrics pinned to four cells (PERF.md 7 (l)): none lists this cell
    assert not {"podaffinity_scored_pod_share", "spread_encode_share",
                "spread_constrained_pod_share", "spread_soft_pod_share",
                "spread_policy_pod_share", "loop_thread_cpu_share"} & have
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        e = by_name[name]
        assert CELL in e["workloads"]
        assert e["layer"] == "host encode"
        assert e["source"] == "program_counter"
        assert e["moves"] == "pods_bound_per_s"
        assert e["better"] == "higher"
    assert by_name[SHARE]["unit"] == "%"
    assert by_name[PER_POD]["unit"] == "nodes/pod"
    config = next(c for c in m["configs"] if c["name"] == "podmatchinganti-5k")
    assert config["file"] == "benchmark/configs/podmatchinganti-5k.json"
    assert config["reduced"] == []

    cfg = cell.config
    assert cfg["reduced"] == [] and cfg["nodes"] == 5000
    assert cfg["source"].startswith(
        "kubernetes test/integration/scheduler_perf/affinity/"
        "performance-config.yaml:94 SchedulingPodMatchingAntiAffinity "
        "5000Nodes_5000Pods")
    assert "floor 540" in cfg["source"]
    assert cfg["node_template"] == "node-default"
    assert cfg["zones"] == [] and cfg["namespaces"] == ["sched-0", "sched-1"]
    assert cfg["init_pods"] == {
        "count": 1000, "template": TEMPLATES + "pod_with_pod_anti_affinity",
        "namespace": "sched-0"}
    assert cfg["measured_pods"] == {
        "template": TEMPLATES + "pod_with_pod_anti_affinity_label",
        "namespace": "sched-1"}
    assert cfg["scheduler_flags"] == ["--engine", "batched", "--mesh", "off"]
    assert cfg["assign_program"] == "batched_assign_device"
    assert cfg["parity"]["rule"] == "pod_for_pod"
    assert cfg["parity"]["sample"] in (32, 64)
    assert cfg["parity"]["oracle"] == {"w_fit": 1, "w_balanced": 1,
                                       "w_interpod": 2,
                                       "check_interpod": True}
    basic = Cell(m, "basic-5k.saturate").config
    assert cfg["guarantees"][:4] == basic["guarantees"]
    assert len(cfg["guarantees"]) == 5
    for said in ("no measured pod", "more than one init pod",
                 "parity sample", "tools/affinity_nodes_run.py",
                 "reference/validity.py"):
        assert said in cfg["guarantees"][4], said
    assumed = " ".join(cfg["assumed"])
    for said in ("this repo's rendering", "sched-1 and sched-0",
                 "scheduler-perf-<i>", "160000", "200000", "batched engine",
                 "do not constrain each other", "two-stage",
                 "in-memory store"):
        assert said in assumed, said


def test_the_templates_are_upstream_s():
    """pod-with-pod-anti-affinity.yaml and
    pod-with-pod-anti-affinity-label.yaml as the module docstring writes
    them out, field for field, and as ``kubetpu/perf/workloads.py`` renders
    upstream's row: the same pods, built by the benchmark's own code."""
    from kubetpu.perf import workloads

    from benchmark.harness import templates_podmatchinganti as mod

    cfg = Cell(load_manifest(), CELL).config
    init = templates.resolve(templates.POD_TEMPLATES,
                             cfg["init_pods"]["template"])
    measured = templates.resolve(templates.POD_TEMPLATES,
                                 cfg["measured_pods"]["template"])
    assert init is mod.pod_with_pod_anti_affinity
    assert measured is mod.pod_with_pod_anti_affinity_label

    pod = init("i0", "sched-0")
    assert (pod.name, pod.namespace) == ("i0", "sched-0")
    assert dict(pod.labels) == {"color": "green"}
    assert dict(pod.requests) == {"cpu": 100, "memory": 500 * 1024 ** 2}
    assert pod.affinity.pod_affinity is None
    anti = pod.affinity.pod_anti_affinity
    assert not anti.preferred
    [term] = anti.required
    assert term.topology_key == templates.HOSTNAME_KEY
    assert dict(term.selector.match_labels) == {"color": "green"}
    assert not term.selector.match_expressions
    assert term.namespaces == ("sched-1", "sched-0")
    assert term.namespace_selector is None
    assert not pod.node_name and pod.priority == 0 and not pod.tolerations

    pod = measured("m0", "sched-1")
    assert dict(pod.labels) == {"color": "green"}
    assert dict(pod.requests) == {"cpu": 100, "memory": 500 * 1024 ** 2}
    assert pod.affinity is None and not pod.topology_spread_constraints
    assert not pod.node_name and pod.priority == 0

    # upstream's row as the program renders it: the same pods
    case = workloads.TEST_CASES["SchedulingPodMatchingAntiAffinity"]
    assert case.default_pod_template("x", "sched-0") == init("x", "sched-0")
    measure_op = next(op for op in case.ops
                      if getattr(op, "collect_metrics", False))
    assert measure_op.namespace == "sched-1"
    assert measure_op.template("y", "sched-1") == measured("y", "sched-1")
    sizes = next(w for w in case.workloads
                 if w.name == "5000Nodes_5000Pods").params
    assert (sizes["initNodes"], sizes["initPods"]) == (5000, 1000)
    assert cfg["nodes"] == 5000 and cfg["init_pods"]["count"] == 1000

    for line in ("color: green", "podAntiAffinity:",
                 "requiredDuringSchedulingIgnoredDuringExecution:",
                 "topologyKey: kubernetes.io/hostname",
                 'namespaces: ["sched-1", "sched-0"]', "cpu: 100m",
                 "memory: 500Mi", "pod-with-pod-anti-affinity-label.yaml"):
        assert line in mod.__doc__, line
    # the harness counts the init nodes too (PERF.md 7 (2)): 40 pods a node
    # by CPU on 5000 nodes, where the 4000 without an init pod hold 160,000
    assert templates.capacity(cfg) == 200_000


def test_traced_rehearsal_reports_the_existing_anti_affinity():
    line = rehearse(CELL, 1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["checks"]["oracle_disagreements"] == [0, 0]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # no TPU: the device-trace metrics find nothing to read; a later PR may
    # give the cell more metrics, so this is a subset and not the whole
    assert set(SHARED + LOOP + LATER + ALSO + NEW) - DEVICE <= set(got)
    assert got[SHARE] == pytest.approx(100.0)
    # the tiny cut's 24 init pods, one a node
    assert got[PER_POD] == pytest.approx(24.0)
    assert line["metrics"][PER_POD]["unit"] == "nodes/pod"
