"""The reader of ``watch_bind_delta_share`` on fake /metrics pages, and on the
page a real informer bundle renders: 100 where every pod placed came back as
a bind delta, nothing where the program has no such counter (the parent
commit's) or the window placed no pod."""

import pytest

from benchmark.harness import promtext
from benchmark.harness.manifest import Cell, layer_reader, load_manifest
from benchmark.layer_metrics import watch_bind_delta_share

DELTAS = "scheduler_watch_bind_deltas_total"
CELLS = ["basic-5k.saturate", "podaffinity-5k.saturate",
         "preferredspread-5k.saturate", "preferredaffinity-5k.saturate",
         "nodeinclusion-5k.saturate"]


class FakeRun:
    window_s = 50.0

    def __init__(self, before: str, after: str) -> None:
        self.scheduler = promtext.Delta(promtext.Scrape(before),
                                        promtext.Scrape(after))


def page(applied, relisted, scheduled, unschedulable=0) -> str:
    """``applied`` None: a program without the counter."""
    lines = []
    if applied is not None:
        lines += [f'{DELTAS}{{result="applied"}} {applied}',
                  f'{DELTAS}{{result="relisted"}} {relisted}']
    lines += [f'scheduler_schedule_attempts_total{{result="{r}",'
              f'profile="default-scheduler"}} {v}'
              for r, v in (("scheduled", scheduled),
                           ("unschedulable", unschedulable))]
    return "\n".join(lines) + "\n"


@pytest.mark.parametrize("applied, relisted, unschedulable, share", [
    pytest.param(1124, 0, 0, 100.0, id="every-bind-a-delta"),
    pytest.param(612, 1, 400, 50.0, id="half-and-one-relist"),
])
def test_the_share_is_the_applied_deltas_over_the_pods_placed(
    applied, relisted, unschedulable, share,
):
    before = page(100, 0, 100)
    after = page(applied, relisted, 1124, unschedulable)
    assert watch_bind_delta_share.read(FakeRun(before, after)) == \
        pytest.approx(share)


def test_a_program_without_the_counter_reads_as_nothing():
    assert watch_bind_delta_share.read(
        FakeRun(page(None, 0, 100), page(None, 0, 600))) is None
    assert watch_bind_delta_share.read(FakeRun("up 1\n", "up 1\n")) is None


def test_no_pod_placed_in_the_window_reads_as_nothing():
    same = page(100, 0, 100, unschedulable=5)
    after = page(100, 0, 100, unschedulable=50)
    assert watch_bind_delta_share.read(FakeRun(same, after)) is None


def test_it_reads_the_page_the_informers_render():
    pytest.importorskip("jax")
    from kubetpu.client.informers import PODS, SchedulerInformers
    from kubetpu.store.memstore import MemStore

    class Handlers:
        loop_clock = None

        def __getattr__(self, name):
            if not name.startswith("on_"):
                raise AttributeError(name)
            return lambda *args: None

    informers = SchedulerInformers(MemStore(), Handlers())
    (r,) = [r for r in informers._reflectors if r.informer.kind == PODS]
    before = informers.bind_delta_metrics_text() + page(None, 0, 0)
    with r.informer._lock:
        r.informer.bind_deltas_applied += 30
    after = informers.bind_delta_metrics_text() + page(None, 0, 40)
    assert watch_bind_delta_share.read(FakeRun(before, after)) == \
        pytest.approx(75.0)


def test_the_entry_is_the_api_plane_s_and_lists_five_cells():
    manifest = load_manifest()
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "watch_bind_delta_share"]
    assert {k: entry[k] for k in watch_bind_delta_share.META} == \
        watch_bind_delta_share.META
    assert entry["better"] == "higher"
    assert set(CELLS) <= set(entry["workloads"])
    assert layer_reader("watch_bind_delta_share") is watch_bind_delta_share
    for name in CELLS:
        assert entry in Cell(manifest, name).per_layer
    spread = Cell(manifest, "topologyspread-5k.saturate")
    assert entry not in spread.per_layer    # PERF.md 7 (l): its list is pinned
