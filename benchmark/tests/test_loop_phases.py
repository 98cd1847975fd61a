"""The ten per-layer metrics that read the served loop's phase clock
(``scheduler_loop_phase_seconds_total`` and its two companions on the
scheduler's /metrics): their entries, their readers, and one traced CPU
rehearsal that returns them all."""

import importlib

import pytest

from benchmark.harness import promtext
from benchmark.harness.manifest import load_manifest
from benchmark.tests.test_rehearsal import rehearse

SHARES = {
    "loop_pump_rpc_share": "pump_rpc",
    "loop_pump_apply_share": "pump_apply",
    "loop_cycle_share": "cycle",
    "loop_explain_share": "explain",
    "loop_bind_dispatch_share": "bind_dispatch",
    "loop_drain_share": "drain",
    "loop_events_share": "events",
    "loop_sleep_share": "sleep",
}
TEN = [*SHARES, "loop_unaccounted_share", "loop_iterations_per_s"]
SECONDS = "scheduler_loop_phase_seconds_total"


def test_the_ten_entries_stand_in_the_manifest_for_every_cell():
    """Found by name: later PRs put their entries at the end of the list,
    and every cell drives the one served loop."""
    manifest = load_manifest()
    cells = [w["name"] for w in manifest["workloads"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert set(TEN) <= set(by_name)
    for m in (by_name[name] for name in TEN):
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] == "program_counter"
        assert m["moves"] == "pods_bound_per_s" and m["workloads"] == cells
        assert m["better"] == ("higher" if m["name"] == "loop_iterations_per_s"
                               else "lower")


class FakeRun:
    window_s = 50.0

    def __init__(self, before: str, after: str) -> None:
        self.scheduler = promtext.Delta(promtext.Scrape(before),
                                        promtext.Scrape(after))


def page(seconds: dict, iterations: int) -> str:
    lines = [f'{SECONDS}{{phase="{p}"}} {v}' for p, v in seconds.items()]
    lines.append(f"scheduler_loop_iterations_total {iterations}")
    return "\n".join(lines) + "\n"


@pytest.mark.parametrize("name", TEN)
def test_a_reader_reads_its_phase_and_nothing_from_a_program_without(name):
    read = importlib.import_module(f"benchmark.layer_metrics.{name}").read
    phases = [*SHARES.values(), "other"]
    before = page({p: 1.0 for p in phases}, 10)
    after = page({p: 1.0 + 0.5 * (k + 1) for k, p in enumerate(phases)}, 35)
    value = read(FakeRun(before, after))
    if name in SHARES:
        k = phases.index(SHARES[name])
        assert value == pytest.approx(100 * 0.5 * (k + 1) / 50.0)
    elif name == "loop_unaccounted_share":
        assert value == pytest.approx(100 * (1 - 0.5 * 45 / 50.0))
    else:
        assert value == pytest.approx(25 / 50.0)
    # the parent commit has no such counter: nothing, and no exception
    assert read(FakeRun("up 1\n", "up 1\n")) is None


def test_a_traced_rehearsal_returns_all_ten_and_they_add_up():
    line = rehearse("basic-5k.saturate", 1)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(TEN) <= set(got)
    assert abs(got["loop_unaccounted_share"]) <= 1.0
    in_cycle = (got["loop_cycle_share"] + got["loop_explain_share"]
                + got["loop_bind_dispatch_share"])
    assert abs(in_cycle - (100.0 - got["loop_idle_share"])) <= 2.0
    assert got["loop_events_share"] > 0 and got["loop_sleep_share"] > 0
    assert got["loop_iterations_per_s"] > 0
    assert line["metrics"]["loop_iterations_per_s"]["unit"] == "iterations/s"
