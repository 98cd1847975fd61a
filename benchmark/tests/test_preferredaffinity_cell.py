"""The cell ``preferredaffinity-5k.saturate``: its two readers on fake
/metrics pages, its entries, its template beside upstream's yaml, and a CPU
rehearsal of the cell itself (control flow and counts only)."""

import dataclasses

import pytest

from benchmark.harness import templates
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_spread_cell import LOOP, SHARED, FakeRun, reader

CELL = "preferredaffinity-5k.saturate"
REQUIRED_CELL = "podaffinity-5k.saturate"
PLUGIN = "scheduler_plugin_execution_duration_seconds"
AFFINITY = ('{plugin="InterPodAffinity",extension_point="PreFilter",'
            'status="Success"}')
OTHER = ('{plugin="PodTopologySpread",extension_point="PreFilter",'
         'status="Success"}')
COUNTER = "scheduler_podaffinity_pods_total"
NEW = ["podaffinity_encode_share", "podaffinity_scored_pod_share"]
#: what earlier PRs brought to every cell but the one a test of its own pins
LATER = ["pipeline_replay_share", "explain_device_ms_per_cycle",
         "pump_decode_share"]
#: device-trace metrics, which find nothing to read without a TPU
DEVICE = {"assign_device_ms_per_cycle", "assign_hbm_share",
          "device_idle_share", "explain_device_ms_per_cycle"}


def page(encode_s, encode_n, work, attempts) -> str:
    """``encode_n`` None: a program that does not time the affinity encode;
    ``work`` None: one without the counter."""
    lines = [f"{PLUGIN}_sum{OTHER} 3.0", f"{PLUGIN}_count{OTHER} 7"]
    if encode_n is not None:
        lines += [f"{PLUGIN}_sum{AFFINITY} {encode_s}",
                  f"{PLUGIN}_count{AFFINITY} {encode_n}"]
    if work is not None:
        lines += [f'{COUNTER}{{work="{k}"}} {v}' for k, v in work.items()]
    lines += [f'scheduler_schedule_attempts_total{{result="{r}",'
              f'profile="default-scheduler"}} {v}'
              for r, v in attempts.items()]
    return "\n".join(lines) + "\n"


def test_the_encode_share_is_the_histogram_s_seconds_over_the_window():
    before = page(1.0, 10, None, {"scheduled": 100})
    after = page(3.5, 60, None, {"scheduled": 600})
    # the spread encode's 3.0 s under the same family are not in it
    assert reader("podaffinity_encode_share")(FakeRun(before, after)) == \
        pytest.approx(100 * 2.5 / 50.0)


def test_the_scored_share_counts_the_score_s_pods_over_every_attempt():
    before = page(0, None, {"filter": 100, "score": 100},
                  {"scheduled": 100, "unschedulable": 0})
    after = page(0, None, {"filter": 700, "score": 580},
                 {"scheduled": 600, "unschedulable": 100})
    assert reader("podaffinity_scored_pod_share")(FakeRun(before, after)) == \
        pytest.approx(100 * 480 / 600)
    every = page(0, None, {"filter": 100, "score": 1124},
                 {"scheduled": 1124, "unschedulable": 0})
    assert reader("podaffinity_scored_pod_share")(
        FakeRun(before, every)) == 100.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_series_reads_as_nothing(name):
    """The parent commit times the spread encode and counts attempts, but has
    neither the affinity series nor the counter: no value, no exception."""
    before = page(0, None, None, {"scheduled": 100})
    after = page(0, None, None, {"scheduled": 600})
    assert reader(name)(FakeRun(before, after)) is None
    assert reader(name)(FakeRun("up 1\n", "up 1\n")) is None


def test_no_attempt_in_the_window_gives_no_scored_share():
    same = page(1.0, 10, {"filter": 0, "score": 100}, {"scheduled": 100})
    assert reader("podaffinity_scored_pod_share")(FakeRun(same, same)) is None
    assert reader("podaffinity_encode_share")(FakeRun(same, same)) == 0.0


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
    assert set(SHARED + LOOP + LATER + NEW) <= {
        e["name"] for e in cell.per_layer}
    by_name = {e["name"]: e for e in m["per_layer"]}
    encode, scored = (by_name[n] for n in NEW)
    for e in (encode, scored):
        assert {CELL, REQUIRED_CELL} <= set(e["workloads"])
        assert e["layer"] == "host encode" and e["unit"] == "%"
        assert e["source"] == "program_counter"
        assert e["moves"] == "pods_bound_per_s"
    assert encode["better"] == "lower" and scored["better"] == "higher"
    cfg = cell.config
    assert cfg["reduced"] == [] and cfg["nodes"] == 5000
    assert cfg["source"].startswith(
        "kubernetes test/integration/scheduler_perf/affinity/"
        "performance-config.yaml:175 SchedulingPreferredPodAffinity "
        "5000Nodes_5000Pods")
    assert cfg["zones"] == [] and cfg["namespaces"] == ["sched-0", "sched-1"]
    template = ("benchmark.harness.templates_preferredaffinity:"
                "pod_with_preferred_pod_affinity")
    assert cfg["init_pods"] == {"count": 5000, "template": template,
                                "namespace": "sched-0"}
    assert cfg["measured_pods"] == {"template": template,
                                    "namespace": "sched-1"}
    assert cfg["scheduler_flags"] == ["--engine", "greedy", "--mesh", "off"]
    assert cfg["assign_program"] == "greedy_assign_device"
    # the default profile's weights; a preferred term filters nothing
    assert cfg["parity"]["rule"] == "pod_for_pod"
    assert cfg["parity"]["sample"] in (32, 64)
    assert cfg["parity"]["oracle"] == {"w_fit": 1, "w_balanced": 1,
                                       "w_interpod": 2}
    basic = Cell(m, "basic-5k.saturate").config
    assert cfg["guarantees"][:4] == basic["guarantees"]
    assert len(cfg["guarantees"]) == 5
    assert "a preferred term filters nothing" in cfg["guarantees"][4]
    assert "over-committed" in cfg["guarantees"][4]
    assumed = " ".join(cfg["assumed"])
    for said in ("two-stage", "greedy scan", "do not switch the engine",
                 "InterPodAffinity 2", "this repo's rendering",
                 "multiply first", "an open question"):
        assert said in assumed, said
    assert "pipeline off" not in assumed


def test_the_template_is_upstream_s():
    """templates/pod-with-preferred-pod-affinity.yaml as the template's
    module docstring writes it out, field for field; and beside the required
    row's template, differing as upstream's two yaml files do."""
    from benchmark.harness import templates_preferredaffinity as mod

    cfg = Cell(load_manifest(), CELL).config
    make = templates.resolve(templates.POD_TEMPLATES,
                             cfg["measured_pods"]["template"])
    assert make is mod.pod_with_preferred_pod_affinity
    assert make is templates.resolve(templates.POD_TEMPLATES,
                                     cfg["init_pods"]["template"])
    pod = make("p0", "sched-1")
    assert (pod.name, pod.namespace) == ("p0", "sched-1")
    assert dict(pod.labels) == {"color": "red"}
    assert dict(pod.requests) == {"cpu": 100, "memory": 500 * 1024 ** 2}
    affinity = pod.affinity.pod_affinity
    assert pod.affinity.pod_anti_affinity is None
    assert not affinity.required
    [weighted] = affinity.preferred
    term = weighted.term
    assert weighted.weight == 1
    assert term.topology_key == templates.HOSTNAME_KEY
    assert dict(term.selector.match_labels) == {"color": "red"}
    assert not term.selector.match_expressions
    assert tuple(term.namespaces) == ("sched-1", "sched-0")
    assert term.namespace_selector is None
    assert not pod.topology_spread_constraints and not pod.tolerations
    assert not pod.node_name and pod.priority == 0
    # every field of the yaml in the docstring is the pod's
    for line in ("color: red", "topologyKey: kubernetes.io/hostname",
                 'namespaces: ["sched-1", "sched-0"]', "weight: 1",
                 "preferredDuringSchedulingIgnoredDuringExecution",
                 "cpu: 100m", "memory: 500Mi"):
        assert line in mod.__doc__, line
    # beside templates/pod-with-pod-affinity.yaml: another colour, hostname
    # and not zone, preferred at weight 1 and not required; nothing else
    required = templates.pod_with_pod_affinity("p0", "sched-1")
    [hard] = required.affinity.pod_affinity.required
    assert not required.affinity.pod_affinity.preferred
    assert dict(required.labels) == {"color": "blue"}
    assert hard.topology_key == templates.ZONE_KEY
    assert dataclasses.replace(
        hard, topology_key=term.topology_key, selector=term.selector) == term
    assert dataclasses.replace(
        required, labels=pod.labels, affinity=pod.affinity) == pod
    # capacity by CPU: 40 pods a node, which the packing reaches
    assert templates.capacity(cfg) == 200_000


def test_end_to_end_rehearsal():
    line = rehearse(CELL, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"pods_bound_per_s", "setup_s"} <= set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["checks"]["oracle_disagreements"] == [0, 0]


def test_traced_rehearsal_reports_the_affinity_shares():
    line = rehearse(CELL, 1)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # no TPU: the device-trace metrics find nothing to read; a later PR may
    # give the cell more metrics, so this is a subset and not the whole
    assert set(SHARED + LOOP + LATER + NEW) - DEVICE <= set(got)
    assert got["podaffinity_scored_pod_share"] == pytest.approx(100.0)
    assert got["podaffinity_encode_share"] > 0
    assert line["metrics"]["podaffinity_encode_share"]["unit"] == "%"
