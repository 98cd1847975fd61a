"""The reader of ``cache_confirm_kept_share`` on fake /metrics pages, and on
the page a scheduler renders: the confirmations of assumed pods the cache
kept as a share of all it took; nothing where the program has no such
counter (the parent commit's) or the window confirmed no pod."""

import pytest

from benchmark.harness import promtext
from benchmark.harness.manifest import Cell, layer_reader, load_manifest
from benchmark.layer_metrics import cache_confirm_kept_share

CONFIRMED = "scheduler_cache_bind_confirmations_total"
CELLS = ["basic-5k.saturate", "podaffinity-5k.saturate",
         "preferredspread-5k.saturate", "preferredaffinity-5k.saturate",
         "nodeinclusion-5k.saturate", "podmatchinganti-5k.saturate"]


class FakeRun:
    window_s = 50.0

    def __init__(self, before: str, after: str) -> None:
        self.scheduler = promtext.Delta(promtext.Scrape(before),
                                        promtext.Scrape(after))


def page(kept, reapplied) -> str:
    """``kept`` None: a program without the counter."""
    if kept is None:
        return "scheduler_schedule_attempts_total{result=\"scheduled\"} 9\n"
    return (f'{CONFIRMED}{{result="kept"}} {kept}\n'
            f'{CONFIRMED}{{result="reapplied"}} {reapplied}\n')


@pytest.mark.parametrize("kept, reapplied, share", [
    pytest.param(10_100, 0, 100.0, id="every-confirmation-kept"),
    pytest.param(5_100, 5_000, 50.0, id="half-reapplied"),
    pytest.param(100, 10_000, 0.0, id="none-kept"),
])
def test_the_metric_is_the_kept_share_of_the_confirmations(
    kept, reapplied, share,
):
    run = FakeRun(page(100, 0), page(kept, reapplied))
    assert cache_confirm_kept_share.read(run) == pytest.approx(share)


def test_a_program_without_the_counter_reads_as_nothing():
    assert cache_confirm_kept_share.read(
        FakeRun(page(None, 0), page(None, 0))) is None
    assert cache_confirm_kept_share.read(FakeRun("up 1\n", "up 1\n")) is None


def test_no_confirmation_in_the_window_reads_as_nothing():
    assert cache_confirm_kept_share.read(
        FakeRun(page(40, 2), page(40, 2))) is None


def test_it_reads_the_page_a_scheduler_renders():
    """Nine pods placed; the informer confirms six as the loop assumed
    them and two with another label: 75."""
    import dataclasses

    pytest.importorskip("jax")
    from kubetpu.api import types as t
    from kubetpu.api.wrappers import make_node, make_pod

    from .test_scheduler import FakeClient, make_sched

    client = FakeClient()
    s, _ = make_sched(client)
    try:
        before = s.metrics_text()
        assert f'{CONFIRMED}{{result="kept"}} 0' in before
        for i in range(3):
            s.on_node_add(make_node(f"n{i}", cpu_milli=4000))
        pods = [make_pod(f"p{j}", cpu_milli=100) for j in range(9)]
        for p in pods:
            s.on_pod_add(p)
        s.run_until_idle()
        assert len(client.bound) == 9
        for p in pods[:8]:
            new = p.with_node(client.bound[f"default/{p.name}"])
            if p.name in ("p6", "p7"):
                new = dataclasses.replace(
                    new, labels=t.freeze_map({"app": "x"}))
            s.on_pod_update(p, new)
        run = FakeRun(before, s.metrics_text())
        assert cache_confirm_kept_share.read(run) == pytest.approx(75.0)
    finally:
        s.close()


def test_the_entry_is_the_host_encode_s_and_lists_six_cells():
    manifest = load_manifest()
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "cache_confirm_kept_share"]
    assert {k: entry[k] for k in cache_confirm_kept_share.META} == \
        cache_confirm_kept_share.META
    assert entry["better"] == "higher"
    assert entry["workloads"] == CELLS
    assert layer_reader("cache_confirm_kept_share") is \
        cache_confirm_kept_share
    for name in CELLS:
        assert entry in Cell(manifest, name).per_layer
    # TopologySpreading's list is pinned (PERF.md 7 (l))
    assert entry not in Cell(manifest, "topologyspread-5k.saturate").per_layer
