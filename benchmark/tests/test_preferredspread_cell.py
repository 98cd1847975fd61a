"""The cell ``preferredspread-5k.saturate``: its two readers on fake /metrics
pages and a fake reduced trace, its entries, its template beside upstream's
yaml, and a CPU rehearsal of the cell itself (control flow and counts
only)."""

import dataclasses

import pytest

from benchmark.harness import templates
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.tests import test_spread_cell as spread_cell
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_spread_cell import LOOP, SHARED, reader

CELL = "preferredspread-5k.saturate"
SOFT = "scheduler_spread_soft_constrained_pods_total"
NEW = ["spread_soft_pod_share", "explain_device_ms_per_cycle"]
#: cells whose explain programs the second reader finds, as this cell brought
#: them; a later PR may append more (topologyspread-5k.saturate is left out
#: only because a test of its own pins that cell's list)
EXPLAIN_CELLS = ["basic-5k.saturate", "podaffinity-5k.saturate", CELL]
#: device-trace metrics, which find nothing to read without a TPU
DEVICE = {"assign_device_ms_per_cycle", "assign_hbm_share",
          "device_idle_share", "explain_device_ms_per_cycle"}


class FakeRun(spread_cell.FakeRun):
    def __init__(self, before: str = "up 1\n", after: str = "up 1\n",
                 device_trace: dict | None = None) -> None:
        super().__init__(before, after)
        self.device_trace = device_trace


def page(soft, attempts) -> str:
    lines = ["scheduler_spread_constrained_pods_total 7"]
    if soft is not None:
        lines.append(f"{SOFT} {soft}")
    lines += [f'scheduler_schedule_attempts_total{{result="{r}",'
              f'profile="default-scheduler"}} {v}'
              for r, v in attempts.items()]
    return "\n".join(lines) + "\n"


def trace(module_s: dict, assign_runs: float = 5.0) -> dict:
    return {"module_s": module_s, "assign_runs": assign_runs,
            "assign_s": 1.2}


def test_the_soft_share_counts_every_result_of_an_attempt():
    before = page(100, {"scheduled": 100, "unschedulable": 0})
    after = page(580, {"scheduled": 600, "unschedulable": 100})
    assert reader("spread_soft_pod_share")(FakeRun(before, after)) == \
        pytest.approx(100 * 480 / 600)
    every = page(1124, {"scheduled": 1124, "unschedulable": 0})
    assert reader("spread_soft_pod_share")(FakeRun(before, every)) == 100.0


def test_a_program_without_the_soft_counter_reads_as_nothing():
    """The parent commit counts constrained pods and attempts, but not the
    soft ones: no value, no exception."""
    before = page(None, {"scheduled": 100})
    after = page(None, {"scheduled": 600})
    assert reader("spread_soft_pod_share")(FakeRun(before, after)) is None
    assert reader("spread_soft_pod_share")(FakeRun()) is None


def test_no_attempt_in_the_window_gives_no_soft_share():
    same = page(100, {"scheduled": 100})
    assert reader("spread_soft_pod_share")(FakeRun(same, same)) is None


def test_the_explain_time_is_its_programs_seconds_over_the_assign_runs():
    read = reader("explain_device_ms_per_cycle")
    tr = trace({"jit_greedy_assign_device(123)": 1.2,
                "jit_explain_kernel(456)": 0.75,
                "jit_explain_masks_kernel(789)": 0.05,
                "jit__scatter_rows(1)": 0.01})
    # the masks program, which scores nothing, is not in it
    assert read(FakeRun(device_trace=tr)) == pytest.approx(1e3 * 0.75 / 5.0)


@pytest.mark.parametrize("tr", [
    None,                                                   # untraced
    trace({"jit_explain_kernel(4)": 0.5}, assign_runs=0),   # no assign run
    # the parent commit: both explain programs are called jit_kernel
    trace({"jit_greedy_assign_device(1)": 1.2, "jit_kernel(2)": 0.7}),
    trace({}),
])
def test_a_trace_without_the_named_programs_reads_as_nothing(tr):
    assert reader("explain_device_ms_per_cycle")(
        FakeRun(device_trace=tr)) is None


def test_the_cell_s_entries():
    """What this cell needs of the manifest: membership and content, never a
    position or an exhaustive list, so that a later PR can append a cell, a
    metric, or a cell to a metric's ``workloads`` without touching this."""
    m = load_manifest()
    cell = Cell(m, CELL)
    assert CELL in [w["name"] for w in m["workloads"]]
    assert cell.chips == 1 and cell.traffic["mode"] == "saturate"
    assert {"pods_bound_per_s", "setup_s"} <= {
        e["name"] for e in cell.end_to_end}
    assert set(SHARED + LOOP + ["pipeline_replay_share"] + NEW) <= {
        e["name"] for e in cell.per_layer}
    by_name = {e["name"]: e for e in m["per_layer"]}
    soft, explain = (by_name[n] for n in NEW)
    assert CELL in soft["workloads"] and soft["layer"] == "host encode"
    assert soft["source"] == "program_counter" and soft["better"] == "higher"
    assert set(EXPLAIN_CELLS) <= set(explain["workloads"])
    assert explain["layer"] == "flight recorder"
    assert explain["source"] == "device_trace"
    assert explain["better"] == "lower" and explain["unit"] == "ms/cycle"
    for e in (soft, explain):
        assert e["moves"] == "pods_bound_per_s"
    cfg = cell.config
    assert cfg["reduced"] == [] and cfg["nodes"] == 5000
    assert cfg["source"].startswith(
        "kubernetes test/integration/scheduler_perf/topology_spreading/"
        "performance-config.yaml:96 PreferredTopologySpreading "
        "5000Nodes_5000Pods")
    assert cfg["zones"] == ["moon-1", "moon-2", "moon-3"]
    assert cfg["init_pods"] == {"count": 5000, "template": "pod-default",
                                "namespace": "namespace-0"}
    assert cfg["measured_pods"]["namespace"] == "namespace-1"
    assert cfg["scheduler_flags"] == ["--engine", "greedy", "--mesh", "off"]
    assert cfg["assign_program"] == "greedy_assign_device"
    # the default profile's weights, and no hard constraint to check
    assert cfg["parity"] == {"rule": "pod_for_pod", "sample": 64, "oracle": {
        "w_fit": 1, "w_balanced": 1, "w_spread": 2}}
    basic = Cell(m, "basic-5k.saturate").config
    assert cfg["guarantees"][:4] == basic["guarantees"]
    assert len(cfg["guarantees"]) == 5
    assert "ScheduleAnyway" in cfg["guarantees"][4]
    # the served scheduler always runs the two-stage cycle
    assert not any("pipeline off" in line for line in cfg["assumed"])
    assert any("two-stage" in line for line in cfg["assumed"])


def test_the_template_is_upstream_s():
    """templates/pod-with-preferred-topology-spreading.yaml, field for
    field: the hard row's template with ScheduleAnyway."""
    from kubetpu.api import types as t

    cfg = Cell(load_manifest(), CELL).config
    make = templates.resolve(templates.POD_TEMPLATES,
                             cfg["measured_pods"]["template"])
    assert make.__module__ == "benchmark.harness.templates_preferredspread"
    pod = make("p0", "namespace-1")
    assert (pod.name, pod.namespace) == ("p0", "namespace-1")
    assert dict(pod.labels) == {"color": "blue"}
    assert dict(pod.requests) == {"cpu": 100, "memory": 500 * 1024 ** 2}
    [c] = pod.topology_spread_constraints
    assert (c.max_skew, c.topology_key) == (5, templates.ZONE_KEY)
    assert c.when_unsatisfiable == \
        t.UnsatisfiableConstraintAction.SCHEDULE_ANYWAY
    assert dict(c.selector.match_labels) == {"color": "blue"}
    assert not c.selector.match_expressions
    assert c.min_domains is None
    assert pod.affinity is None and not pod.tolerations
    assert not pod.node_name and pod.priority == 0
    # nothing but the action differs from the hard row's template
    hard = templates.resolve(
        templates.POD_TEMPLATES,
        Cell(load_manifest(), "topologyspread-5k.saturate")
        .config["measured_pods"]["template"])("p0", "namespace-1")
    [h] = hard.topology_spread_constraints
    assert dataclasses.replace(
        h, when_unsatisfiable=c.when_unsatisfiable) == c
    assert dataclasses.replace(
        hard, topology_spread_constraints=(c,)) == pod
    # capacity by CPU: 40 pods a node, no filter engages before 200,000
    assert templates.capacity(cfg) == 200_000


def test_end_to_end_rehearsal():
    line = rehearse(CELL, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"pods_bound_per_s", "setup_s"} <= set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["checks"]["oracle_disagreements"] == [0, 0]


def test_traced_rehearsal_reports_the_soft_share():
    line = rehearse(CELL, 1)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # no TPU: the device-trace metrics find nothing to read; a later PR may
    # give the cell more metrics, so this is a subset and not the whole
    assert set(SHARED + LOOP + ["pipeline_replay_share"] + NEW) - DEVICE \
        <= set(got)
    assert "explain_device_ms_per_cycle" not in got
    assert got["spread_soft_pod_share"] == pytest.approx(100.0)
    assert line["metrics"]["spread_soft_pod_share"]["unit"] == "%"
