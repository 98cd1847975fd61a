"""The cell ``topologyspread-5k.saturate``: its two readers on fake /metrics
pages, its template beside upstream's yaml, and a CPU rehearsal of the cell
itself (control flow and counts only)."""

import importlib

import pytest

from benchmark.harness import promtext, templates
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.tests.test_rehearsal import rehearse

CELL = "topologyspread-5k.saturate"
PLUGIN = "scheduler_plugin_execution_duration_seconds"
SPREAD = ('{plugin="PodTopologySpread",extension_point="PreFilter",'
          'status="Success"}')
OTHER = '{plugin="Gang",extension_point="Permit",status="Success"}'
SHARED = ["loop_idle_share", "api_rpcs_per_pod", "api_wire_bytes_per_pod",
          "encode_share", "encode_cache_hit_rate", "transfer_bytes_per_cycle",
          "assign_wait_share", "assign_device_ms_per_cycle",
          "assign_hbm_share", "device_idle_share", "apiserver_cpu_share",
          "scheduler_cpu_share", "generator_cpu_share"]
LOOP = ["loop_pump_rpc_share", "loop_pump_apply_share", "loop_cycle_share",
        "loop_explain_share", "loop_bind_dispatch_share", "loop_drain_share",
        "loop_events_share", "loop_sleep_share", "loop_unaccounted_share",
        "loop_iterations_per_s"]
NEW = ["spread_encode_share", "spread_constrained_pod_share"]


class FakeRun:
    window_s = 50.0

    def __init__(self, before: str, after: str) -> None:
        self.scheduler = promtext.Delta(promtext.Scrape(before),
                                        promtext.Scrape(after))


def page(spread_s, spread_n, constrained, attempts) -> str:
    lines = [f"{PLUGIN}_sum{OTHER} 3.0", f"{PLUGIN}_count{OTHER} 7"]
    if spread_n is not None:
        lines += [f"{PLUGIN}_sum{SPREAD} {spread_s}",
                  f"{PLUGIN}_count{SPREAD} {spread_n}"]
    if constrained is not None:
        lines.append(f"scheduler_spread_constrained_pods_total {constrained}")
    lines += [f'scheduler_schedule_attempts_total{{result="{r}",'
              f'profile="default-scheduler"}} {v}'
              for r, v in attempts.items()]
    return "\n".join(lines) + "\n"


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def test_the_encode_share_is_the_histogram_s_seconds_over_the_window():
    before = page(1.0, 10, 100, {"scheduled": 100})
    after = page(3.5, 60, 600, {"scheduled": 600})
    assert reader("spread_encode_share")(FakeRun(before, after)) == \
        pytest.approx(100 * 2.5 / 50.0)


def test_the_pod_share_counts_every_result_of_an_attempt():
    before = page(1.0, 10, 100, {"scheduled": 100, "unschedulable": 0})
    after = page(3.5, 60, 580, {"scheduled": 600, "unschedulable": 100})
    assert reader("spread_constrained_pod_share")(FakeRun(before, after)) == \
        pytest.approx(100 * 480 / 600)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_series_reads_as_nothing(name):
    """The parent commit times other plugins and counts attempts, but has
    neither the spread series nor the counter: no value, no exception."""
    before = page(0, None, None, {"scheduled": 100})
    after = page(0, None, None, {"scheduled": 600})
    assert reader(name)(FakeRun(before, after)) is None
    assert reader(name)(FakeRun("up 1\n", "up 1\n")) is None


def test_no_attempt_in_the_window_gives_no_share():
    same = page(1.0, 10, 100, {"scheduled": 100})
    assert reader("spread_constrained_pod_share")(FakeRun(same, same)) is None
    assert reader("spread_encode_share")(FakeRun(same, same)) == 0.0


def test_the_cell_s_entries():
    m = load_manifest()
    cell = Cell(m, CELL)
    assert cell.chips == 1 and cell.traffic["mode"] == "saturate"
    assert [e["name"] for e in cell.end_to_end] == ["pods_bound_per_s",
                                                    "setup_s"]
    assert [e["name"] for e in cell.per_layer] == SHARED + LOOP + NEW
    for e in m["per_layer"]:
        if e["name"] in NEW:
            assert e["workloads"] == [CELL]
            assert e["moves"] == "pods_bound_per_s"
            assert e["layer"] == "host encode"
    cfg = cell.config
    assert cfg["reduced"] == [] and cfg["nodes"] == 5000
    assert cfg["zones"] == ["moon-1", "moon-2", "moon-3"]
    assert cfg["init_pods"] == {"count": 5000, "template": "pod-default",
                                "namespace": "namespace-0"}
    assert cfg["scheduler_flags"] == ["--engine", "greedy", "--mesh", "off"]
    assert cfg["parity"]["oracle"]["check_spread"] is True
    assert len(cfg["guarantees"]) == 5 and "maxSkew 5" in cfg["guarantees"][4]


def test_the_template_is_upstream_s():
    from kubetpu.api import types as t

    make = templates.resolve(
        templates.POD_TEMPLATES,
        Cell(load_manifest(), CELL).config["measured_pods"]["template"])
    pod = make("p0", "namespace-1")
    assert dict(pod.labels) == {"color": "blue"}
    assert dict(pod.requests) == {"cpu": 100, "memory": 500 * 1024 ** 2}
    [c] = pod.topology_spread_constraints
    assert (c.max_skew, c.topology_key) == (5, templates.ZONE_KEY)
    assert c.when_unsatisfiable == \
        t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE
    assert dict(c.selector.match_labels) == {"color": "blue"}
    assert c.min_domains is None
    # the nodes it spreads over: round-robin zones
    zones = ("moon-1", "moon-2", "moon-3")
    assert [dict(templates.node_default(i, zones).labels)[templates.ZONE_KEY]
            for i in range(4)] == ["moon-1", "moon-2", "moon-3", "moon-1"]


def test_end_to_end_rehearsal():
    line = rehearse(CELL, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"pods_bound_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_rehearsal_reports_both_new_metrics():
    line = rehearse(CELL, 1)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # no TPU: the three device-trace metrics find nothing to read
    assert set(got) == set(SHARED + LOOP + NEW) - {
        "assign_device_ms_per_cycle", "assign_hbm_share", "device_idle_share"}
    assert got["spread_constrained_pod_share"] == pytest.approx(100.0)
    assert 0 < got["spread_encode_share"] < got["encode_share"]
    assert line["metrics"]["spread_encode_share"]["unit"] == "%"
