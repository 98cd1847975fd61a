"""The six per-layer metrics that read the CPU clocks beside the wall clocks
on the scheduler's threads (``scheduler_loop_phase_cpu_seconds_total``, the
dispatcher's workers' two families, the diagnostics listener's and
``process_cpu_seconds_total`` on the scheduler's /metrics): their entries,
their readers, and one traced CPU rehearsal that returns them all."""

import importlib

import pytest

from benchmark.harness.manifest import load_manifest
from benchmark.tests.test_loop_phases import FakeRun
from benchmark.tests.test_rehearsal import rehearse

SIX = ["loop_thread_cpu_share", "loop_stall_share", "loop_blocked_share",
       "dispatcher_cpu_share", "dispatcher_busy_share",
       "scheduler_unclocked_cpu_share"]
#: every cell but the one whose list a test of its own pins (PERF.md 7 (l))
CELLS = ["basic-5k.saturate", "podaffinity-5k.saturate",
         "preferredspread-5k.saturate", "preferredaffinity-5k.saturate"]
PHASES = ("pump_rpc", "pump_apply", "cycle", "explain", "bind_dispatch",
          "drain", "events", "sleep", "other")
WALL = "scheduler_loop_phase_seconds_total"
CPU = "scheduler_loop_phase_cpu_seconds_total"
WORKER_WALL = "scheduler_api_dispatcher_worker_seconds_total"
WORKER_CPU = "scheduler_api_dispatcher_worker_cpu_seconds_total"
LISTENER_CPU = "scheduler_diagnostics_request_cpu_seconds_total"
PROCESS_CPU = "process_cpu_seconds_total"


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def test_the_six_entries_stand_in_the_manifest_for_the_four_cells():
    by_name = {m["name"]: m for m in load_manifest()["per_layer"]}
    assert set(SIX) <= set(by_name)
    for name in SIX:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "%", "lower", "program_counter", "pods_bound_per_s")
        assert m["workloads"] == CELLS
        assert m["layer"] == reader(name).META["layer"] == (
            "dispatch + bind" if name.startswith("dispatcher")
            else "entry point (cli.py loop)")


def test_stall_and_blocked_cover_every_phase_but_sleep_and_other_once():
    from kubetpu.tracing import LOOP_PHASES

    assert PHASES == LOOP_PHASES
    covered = (reader("loop_stall_share").PHASES
               + reader("loop_blocked_share").PHASES)
    assert sorted(covered) == sorted(set(PHASES) - {"sleep", "other"})


def page(wall: dict, cpu: dict, worker: dict, listener: dict,
         process: float) -> str:
    """A scheduler's /metrics as far as the six read it. ``worker`` is
    call_type -> (wall, CPU), ``listener`` endpoint -> CPU."""
    lines = [f'{WALL}{{phase="{p}"}} {v}' for p, v in wall.items()]
    lines += [f'{CPU}{{phase="{p}"}} {v}' for p, v in cpu.items()]
    for call_type, (busy, ran) in worker.items():
        lines.append(f'{WORKER_WALL}{{call_type="{call_type}"}} {busy}')
        lines.append(f'{WORKER_CPU}{{call_type="{call_type}"}} {ran}')
    lines += [f'{LISTENER_CPU}{{endpoint="{e}"}} {v}'
              for e, v in listener.items()]
    lines.append(f"{PROCESS_CPU} {process}")
    return "\n".join(lines) + "\n"


#: a window of FakeRun's 50 s. Per phase: wall 1, 2, ... 9 s (45 in all),
#: CPU a tenth of a second less a phase; the workers ran 6 of their 10 s,
#: the listener 0.5 s, the process 44.9 s
BEFORE = page({p: 10.0 for p in PHASES}, {p: 5.0 for p in PHASES},
              {"bind": (3.0, 2.0)}, {"metrics": 0.25, "trace": 1.0}, 100.0)
AFTER = page({p: 10.0 + (k + 1) for k, p in enumerate(PHASES)},
             {p: 5.0 + (k + 1) - 0.1 * (k + 1) for k, p in enumerate(PHASES)},
             {"bind": (11.0, 7.0), "status_patch": (2.0, 1.0)},
             {"metrics": 0.5, "trace": 1.25}, 144.9)
#: the same program one scrape later, nothing having moved but the sleep
IDLE = AFTER.replace(f'{WALL}{{phase="sleep"}} 18.0',
                     f'{WALL}{{phase="sleep"}} 68.0')
LOOP_CPU = 0.9 * 45
EXPECTED = {
    # page: AFTER against BEFORE, then IDLE against AFTER
    "loop_thread_cpu_share": (100 * LOOP_CPU / 50, 0.0),
    # pump_apply, bind_dispatch, drain are the 2nd, 5th and 6th phases
    "loop_stall_share": (100 * 0.1 * (2 + 5 + 6) / 50, 0.0),
    # pump_rpc, cycle, explain, events the 1st, 3rd, 4th and 7th
    "loop_blocked_share": (100 * 0.1 * (1 + 3 + 4 + 7) / 50, 0.0),
    "dispatcher_cpu_share": (100 * (5.0 + 1.0) / 50, 0.0),
    "dispatcher_busy_share": (100 * (8.0 + 2.0) / 50, 0.0),
    "scheduler_unclocked_cpu_share":
        (100 * (44.9 - LOOP_CPU - 6.0 - 0.5) / 50, 0.0),
}


@pytest.mark.parametrize("name", SIX)
def test_a_reader_reads_its_families_on_two_pages(name):
    read = reader(name).read
    busy, idle = EXPECTED[name]
    assert read(FakeRun(BEFORE, AFTER)) == pytest.approx(busy)
    assert read(FakeRun(AFTER, IDLE)) == pytest.approx(idle, abs=1e-9)


@pytest.mark.parametrize("name", SIX)
def test_a_reader_reads_nothing_from_a_program_without_its_family(name):
    """The parent commit: a phase clock with no CPU beside it, no clock on
    the workers, no process counter. Nothing, and no exception."""
    parent = "\n".join(line for line in AFTER.splitlines()
                       if line.startswith(WALL)) + "\n"
    assert reader(name).read(FakeRun(parent, parent)) is None
    assert reader(name).read(FakeRun("up 1\n", "up 1\n")) is None


def test_unclocked_reads_a_program_whose_workers_ran_nothing():
    """``workers=0``, or a window with no bind: the workers' families have
    no series, and there is nothing of theirs to take away."""
    def without_workers(text):
        return "\n".join(line for line in text.splitlines()
                         if "dispatcher_worker" not in line) + "\n"

    run = FakeRun(without_workers(BEFORE), without_workers(AFTER))
    assert reader("scheduler_unclocked_cpu_share").read(run) == \
        pytest.approx(100 * (44.9 - LOOP_CPU - 0.5) / 50)
    assert reader("dispatcher_cpu_share").read(run) is None
    assert reader("dispatcher_busy_share").read(run) is None


def test_a_traced_rehearsal_returns_all_six_and_they_add_up():
    line = rehearse("basic-5k.saturate", 1)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SIX) <= set(got)
    # the loop thread's wall outside ``other``: it ran, stalled, was
    # blocked or slept (the CPU of ``other`` and ``sleep`` is in the first)
    parts = (got["loop_thread_cpu_share"] + got["loop_stall_share"]
             + got["loop_blocked_share"] + got["loop_sleep_share"])
    eight = sum(got[name] for name in (
        "loop_pump_rpc_share", "loop_pump_apply_share", "loop_cycle_share",
        "loop_explain_share", "loop_bind_dispatch_share", "loop_drain_share",
        "loop_events_share", "loop_sleep_share"))
    assert abs(eight - parts) <= 2.0
    assert got["loop_stall_share"] >= -1.0 and got["loop_blocked_share"] > 0
    # one GIL, and the workers' CPU is part of their busy time
    assert (got["loop_thread_cpu_share"] + got["dispatcher_cpu_share"]
            <= 101.0)
    assert 0 < got["dispatcher_cpu_share"] <= got["dispatcher_busy_share"] + 1
    # thread by thread the process is accounted for, and from /proc too
    assert got["scheduler_unclocked_cpu_share"] >= -1.0
    clocked = (got["loop_thread_cpu_share"] + got["dispatcher_cpu_share"]
               + got["scheduler_unclocked_cpu_share"])
    assert clocked <= got["scheduler_cpu_share"] + 3.0
