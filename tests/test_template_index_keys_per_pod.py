"""The reader of ``template_index_keys_per_pod`` on fake /metrics pages, and
on the page a real encode cache renders: the bound pods the template-count
index keyed per pod placed in the window; nothing where the program has no
such counter (the parent commit's) or the window placed no pod."""

import pytest

from benchmark.harness import promtext
from benchmark.harness.manifest import Cell, layer_reader, load_manifest
from benchmark.layer_metrics import template_index_keys_per_pod

INDEX = "scheduler_encode_template_index_pods_total"
CELLS = ["podaffinity-5k.saturate", "preferredspread-5k.saturate",
         "preferredaffinity-5k.saturate", "nodeinclusion-5k.saturate",
         "podmatchinganti-5k.saturate"]


class FakeRun:
    window_s = 50.0

    def __init__(self, before: str, after: str) -> None:
        self.scheduler = promtext.Delta(promtext.Scrape(before),
                                        promtext.Scrape(after))


def page(keyed, kept, scheduled, unschedulable=0) -> str:
    """``keyed`` None: a program without the counter."""
    lines = []
    if keyed is not None:
        lines += [f'{INDEX}{{result="keyed"}} {keyed}',
                  f'{INDEX}{{result="kept"}} {kept}']
    lines += [f'scheduler_schedule_attempts_total{{result="{r}",'
              f'profile="default-scheduler"}} {v}'
              for r, v in (("scheduled", scheduled),
                           ("unschedulable", unschedulable))]
    return "\n".join(lines) + "\n"


@pytest.mark.parametrize("keyed, kept, unschedulable, per_pod", [
    pytest.param(2100, 90_000, 0, 1.0, id="each-bound-pod-keyed-once"),
    pytest.param(3100, 10, 400, 2.0, id="keyed-twice-a-pod"),
    pytest.param(18_100, 0, 0, 17.0, id="every-pod-of-every-touched-node"),
])
def test_the_metric_is_the_keyed_pods_over_the_pods_placed(
    keyed, kept, unschedulable, per_pod,
):
    before = page(1100, 500, 100)
    after = page(keyed, kept, 1100, unschedulable)
    assert template_index_keys_per_pod.read(FakeRun(before, after)) == \
        pytest.approx(per_pod)


def test_a_program_without_the_counter_reads_as_nothing():
    assert template_index_keys_per_pod.read(
        FakeRun(page(None, 0, 100), page(None, 0, 600))) is None
    assert template_index_keys_per_pod.read(
        FakeRun("up 1\n", "up 1\n")) is None


def test_no_pod_placed_in_the_window_reads_as_nothing():
    same = page(100, 0, 100, unschedulable=5)
    after = page(100, 0, 100, unschedulable=50)
    assert template_index_keys_per_pod.read(FakeRun(same, after)) is None


def test_it_reads_the_page_an_encode_cache_renders():
    from kubetpu.metrics.tpu import TPUBackendMetrics
    from kubetpu.state.encode_cache import EncodeCache

    metrics = TPUBackendMetrics()
    cache = EncodeCache(metrics=metrics)
    before = metrics.registry.expose() + page(None, 0, 0)
    cache.index_pods["keyed"] += 30
    cache.index_pods["kept"] += 900
    cache.flush_metrics()
    after = metrics.registry.expose() + page(None, 0, 20)
    assert template_index_keys_per_pod.read(FakeRun(before, after)) == \
        pytest.approx(1.5)


def test_the_entry_is_the_host_encode_s_and_lists_the_index_cells():
    manifest = load_manifest()
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "template_index_keys_per_pod"]
    assert {k: entry[k] for k in template_index_keys_per_pod.META} == \
        template_index_keys_per_pod.META
    assert entry["better"] == "lower"
    assert entry["workloads"] == CELLS
    assert layer_reader("template_index_keys_per_pod") is \
        template_index_keys_per_pod
    for name in CELLS:
        assert entry in Cell(manifest, name).per_layer
    # Basic never runs the index; TopologySpreading's list is pinned
    # (PERF.md 7 (l))
    for name in ("basic-5k.saturate", "topologyspread-5k.saturate"):
        assert entry not in Cell(manifest, name).per_layer
