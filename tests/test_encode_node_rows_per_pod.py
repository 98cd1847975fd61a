"""The reader of ``encode_node_rows_per_pod`` on fake /metrics pages, and on
the page a scheduler renders: the node rows the host encode wrote per pod
placed in the window; nothing where the program has no such counter (the
parent commit's) or the window placed no pod."""

import pytest

from benchmark.harness import promtext
from benchmark.harness.manifest import Cell, layer_reader, load_manifest
from benchmark.layer_metrics import encode_node_rows_per_pod

ROWS = "scheduler_encode_node_rows_total"
CELLS = ["basic-5k.saturate", "podaffinity-5k.saturate",
         "preferredspread-5k.saturate", "preferredaffinity-5k.saturate",
         "nodeinclusion-5k.saturate", "podmatchinganti-5k.saturate"]


class FakeRun:
    window_s = 50.0

    def __init__(self, before: str, after: str) -> None:
        self.scheduler = promtext.Delta(promtext.Scrape(before),
                                        promtext.Scrape(after))


def page(rows, scheduled, unschedulable=0) -> str:
    """``rows`` None: a program without the counter."""
    lines = [] if rows is None else [f"{ROWS} {rows}"]
    lines += [f'scheduler_schedule_attempts_total{{result="{r}",'
              f'profile="default-scheduler"}} {v}'
              for r, v in (("scheduled", scheduled),
                           ("unschedulable", unschedulable))]
    return "\n".join(lines) + "\n"


@pytest.mark.parametrize("rows, unschedulable, per_pod", [
    pytest.param(10_004, 0, 1.0, id="each-assume-one-row"),
    pytest.param(15_004, 300, 2.0, id="each-confirmation-one-more"),
    pytest.param(5_254, 0, 0.05, id="a-batch-packs"),
])
def test_the_metric_is_the_rows_over_the_pods_placed(
    rows, unschedulable, per_pod,
):
    before = page(5_004, 1_000)
    after = page(rows, 6_000, unschedulable)
    assert encode_node_rows_per_pod.read(FakeRun(before, after)) == \
        pytest.approx(per_pod)


def test_a_program_without_the_counter_reads_as_nothing():
    assert encode_node_rows_per_pod.read(
        FakeRun(page(None, 100), page(None, 600))) is None
    assert encode_node_rows_per_pod.read(FakeRun("up 1\n", "up 1\n")) is None


def test_no_pod_placed_in_the_window_reads_as_nothing():
    before = page(5_000, 100, unschedulable=5)
    after = page(5_100, 100, unschedulable=50)
    assert encode_node_rows_per_pod.read(FakeRun(before, after)) is None


def test_it_reads_the_page_a_scheduler_renders():
    """Cycles of 4 pods onto 4 fresh nodes each; the informer confirms a
    cycle's pods after the next cycle's encode, as in the served loop. An
    encode refreshes the rows of the cycle before's assumes, and the
    confirmations, equal to the assumed pods, add none: one row a pod."""
    pytest.importorskip("jax")
    from kubetpu.api.wrappers import make_node, make_pod

    from .test_scheduler import FakeClient, make_sched

    client = FakeClient()
    s, _ = make_sched(client, max_batch=4)
    try:
        assert f"{ROWS} 0" in s.metrics_text()
        for i in range(16):
            s.on_node_add(make_node(f"n{i}", cpu_milli=4000))
        pages, held = [], [[], []]
        for c in range(4):
            pods = [make_pod(f"p{c}-{j}", cpu_milli=100) for j in range(4)]
            for p in pods:
                s.on_pod_add(p)
            for p in held[-2]:      # confirmed one encode after its assume
                s.on_pod_update(p, p.with_node(
                    client.bound[f"default/{p.name}"]))
            pages.append(s.metrics_text())
            assert s.schedule_batch()["scheduled"] == 4
            held.append(pods)
        placed = {client.bound[f"default/p{c}-{j}"]
                  for c in range(4) for j in range(4)}
        assert len(placed) == 16    # fresh nodes every cycle
        assert f"{ROWS} 16" in pages[1]     # the first encode built all 16
        assert s.cache.bind_confirmations == {"kept": 8, "reapplied": 0}
        # cycles 2-4, 12 pods placed: 4 rows each, the assumes of the
        # cycle before; a reapplied confirmation would add 4 more
        run = FakeRun(pages[1], s.metrics_text())
        assert encode_node_rows_per_pod.read(run) == pytest.approx(1.0)
    finally:
        s.close()


def test_the_entry_is_the_host_encode_s_and_lists_six_cells():
    manifest = load_manifest()
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "encode_node_rows_per_pod"]
    assert {k: entry[k] for k in encode_node_rows_per_pod.META} == \
        encode_node_rows_per_pod.META
    assert entry["better"] == "lower"
    assert entry["workloads"] == CELLS
    assert layer_reader("encode_node_rows_per_pod") is \
        encode_node_rows_per_pod
    for name in CELLS:
        assert entry in Cell(manifest, name).per_layer
    # TopologySpreading's list is pinned (PERF.md 7 (l))
    assert entry not in Cell(manifest, "topologyspread-5k.saturate").per_layer
