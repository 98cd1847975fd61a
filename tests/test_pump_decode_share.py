"""The reader of ``pump_decode_share`` (ISSUE 35) on fake /metrics pages, and
on the page a real client renders: a number where the program has the
counter, nothing where it has none (the parent commit's program), 0.0 in a
window in which no reply was decoded."""

import pytest

from benchmark.harness import promtext
from benchmark.harness.manifest import Cell, layer_reader, load_manifest
from benchmark.layer_metrics import pump_decode_share

WATCH = "scheduler_watch_decode_seconds_total"
CELLS = ["basic-5k.saturate", "podaffinity-5k.saturate",
         "preferredspread-5k.saturate"]


class FakeRun:
    window_s = 50.0

    def __init__(self, before: str, after: str) -> None:
        self.scheduler = promtext.Delta(promtext.Scrape(before),
                                        promtext.Scrape(after))


def page(watch) -> str:
    lines = ['scheduler_loop_phase_seconds_total{phase="pump_rpc"} 9.5']
    if watch is not None:
        lines.append(f"{WATCH} {watch}")
    return "\n".join(lines) + "\n"


def test_the_share_is_the_counter_s_seconds_over_the_window():
    assert pump_decode_share.read(FakeRun(page(1.25), page(6.25))) == \
        pytest.approx(10.0)


def test_a_program_without_the_counter_reads_as_nothing():
    assert pump_decode_share.read(FakeRun(page(None), page(None))) is None


def test_a_window_with_no_reply_reads_zero():
    assert pump_decode_share.read(FakeRun(page(3.5), page(3.5))) == 0.0


def test_it_reads_the_page_the_client_renders():
    pytest.importorskip("jax")
    from kubetpu.apiserver import RemoteStore

    remote = RemoteStore("http://127.0.0.1:1")      # never dialled
    before = remote.decode_metrics_text()
    remote._decode_cell()[2] += 2.0
    run = FakeRun(before, remote.decode_metrics_text())
    assert pump_decode_share.read(run) == pytest.approx(4.0)


def test_the_entry_is_the_api_plane_s_and_lists_three_cells():
    manifest = load_manifest()
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "pump_decode_share"]
    assert {k: entry[k] for k in pump_decode_share.META} == \
        pump_decode_share.META
    # membership, not the whole list: a later PR appends its cell (PR 36 did)
    assert entry["better"] == "lower"
    assert set(CELLS) <= set(entry["workloads"])
    assert layer_reader("pump_decode_share") is pump_decode_share
    for name in CELLS:
        assert entry in Cell(manifest, name).per_layer
    spread = Cell(manifest, "topologyspread-5k.saturate")
    assert entry not in spread.per_layer    # PERF.md 7 (l): its list is pinned
