"""The window's close (``phases._close_window``) on fakes: what is read, in
which order, and what a run says when its cluster is full or nearly so."""

import json
import threading

import pytest

from benchmark.harness import generator, phases, promtext
from benchmark.harness.manifest import Cell, load_manifest

PAGE = "scheduler_scheduling_algorithm_duration_seconds_count 7\n"


class FakeMeter:
    programs = 3

    def snapshot(self) -> dict:
        return {"programs": self.programs}


class FakeChild:
    """The generator's process, answering from a table: its own stdin too."""

    def __init__(self, log: list, answers: dict) -> None:
        self.log, self.answers = log, answers
        self.proc = self.stdin = self
        self.pending: list[str] = []

    def write(self, text: str) -> None:
        cmd = json.loads(text)["cmd"]
        self.log.append(cmd)
        self.pending.append(json.dumps(self.answers[cmd]))

    def flush(self) -> None:
        pass

    def next_line(self, timeout_s: float) -> str:
        return self.pending.pop(0)


def fake_generator(log: list, created: int, created_at_t1: int,
                   unbound: int = 0, capacity: int = 200_000,
                   **more) -> phases.GeneratorLink:
    """The real link to a generator that made ``created`` pods."""
    return phases.GeneratorLink(FakeChild(log, {
        "pause": {"event": "paused", "created_at_t1": created_at_t1,
                  "capacity": capacity, "past_t1_s": 0.25},
        "stop": {"event": "drained", "ok": unbound == 0, "created": created,
                 "unbound": unbound, "s": 1.5, **more}}))


class FakeSpans:
    def __init__(self, log: list) -> None:
        self.log = log

    def poll(self) -> int:
        self.log.append("spans.poll")
        return 0


@pytest.fixture
def closing(monkeypatch):
    """(log, meter, close): ``close(gen, traced)`` closes a window of a
    run whose every reading lands in ``log``, under a profiler whose write
    "compiles" 44 programs, as a full cluster's unschedulable pods did."""
    log: list[str] = []
    meter = FakeMeter()

    def scrape(url: str) -> promtext.Scrape:
        log.append(f"scrape {url}")
        return promtext.Scrape(PAGE)

    def stop_profiler(session) -> bytes:
        log.append("stop_profiler")
        meter.programs += 44
        return b"xspace"

    monkeypatch.setattr(phases.promtext, "scrape", scrape)
    monkeypatch.setattr(phases, "cpu_seconds",
                        lambda pid: log.append("cpu") or 1.0)
    monkeypatch.setattr(phases, "stop_profiler", stop_profiler)

    def close(gen, traced: bool) -> phases.Run:
        run = phases.Run(
            cell=Cell(load_manifest(), "basic-5k.saturate"), seed=1,
            seconds=51.0, trace=traced, t_start=0.0, api_url="api",
            diag_url="diag", pids={"scheduler": 1})
        opened = phases.Opened(
            phases.clock(), promtext.Scrape(PAGE), promtext.Scrape(PAGE),
            {"scheduler": 0.5}, meter.snapshot()["programs"])
        snapshot = meter.snapshot
        meter.snapshot = lambda: log.append("programs") or snapshot()
        _t1, xspace = phases._close_window(
            run, gen, meter, FakeSpans(log) if traced else None,
            object() if traced else None, opened)
        assert xspace == (b"xspace" if traced else b"")
        return run

    return log, meter, close


def test_a_traced_window_is_closed_before_the_profiler_stops(closing, capsys):
    log, meter, close = closing
    run = close(fake_generator(log, created=40_960, created_at_t1=39_936),
                traced=True)
    assert log == ["cpu", "scrape diag", "scrape api", "programs", "pause",
                   "spans.poll", "stop_profiler", "stop"]
    # the 44 programs of the profiler's minutes are not the window's
    assert meter.programs == 47 and run.compiles_in_window == 0
    assert run.phases["stop_trace_s"] >= 0 and run.phases["drain_s"] == 1.5
    window, drain = capsys.readouterr().out.splitlines()[:2]
    assert '"created_at_t1": 39936, "capacity": 200000' in window
    assert "near_full" not in window and '"created": 40960' in drain


def test_an_untraced_window_reads_the_same_in_the_same_order(closing):
    log, _meter, close = closing
    run = close(fake_generator(log, created=40_960, created_at_t1=40_960),
                traced=False)
    assert log == ["cpu", "scrape diag", "scrape api", "programs", "pause",
                   "stop"]
    assert "stop_trace_s" not in run.phases and run.compiles_in_window == 0


def full_generator(created: int, bound: int, capacity: int):
    """A Generator past its ``pause``, without its apiserver: ``created``
    pods in its ledger, ``bound`` of them seen bound."""
    gen = generator.Generator.__new__(generator.Generator)
    gen.ledger = generator.Ledger()
    gen.ledger.register([f"ns/p{i}" for i in range(created)], True,
                        [0.0] * created, 0.0)
    gen.ledger.deliver(1.0, [(f"ns/p{i}", "node-0") for i in range(bound)])
    gen.capacity, gen.traffic_thread = capacity, None
    gen.stop_traffic = threading.Event()
    gen.ended_at, gen.past_t1_s = generator.clock(), 120.3
    return gen


def test_a_full_cluster_is_called_by_its_name(closing, capsys):
    """The generator finds the words, the link carries them, and the window
    said beforehand that it was near."""
    drained = full_generator(4024, 4000, 4000).stop(0.0)
    assert drained["ok"] is False and drained["why"].startswith(
        "the cluster is full: created 4024 of 4000; the load ran 120.3 s "
        "past the window (24 pods unbound after ")
    assert "why" not in full_generator(3000, 2988, 4000).stop(0.0)
    assert "why" not in full_generator(4000, 4000, 4000).stop(0.0)
    log, _meter, close = closing
    gen = fake_generator(log, created=203_752, created_at_t1=170_000,
                         unbound=3_752, why="the cluster is full: created "
                         "203752 of 200000; the load ran 0.25 s past the "
                         "window (3752 pods unbound after 1.5 s)")
    with pytest.raises(phases.RunFailed, match=(
            "^generator: the cluster is full: created 203752 of 200000; the "
            "load ran 0.25 s past the window")):
        close(gen, traced=False)
    assert '"near_full": "the window alone made 170000 of the 200000' in \
        capsys.readouterr().out


def test_a_backlog_that_does_not_bind_in_a_cluster_with_room(closing):
    log, _meter, close = closing
    gen = fake_generator(log, created=50_000, created_at_t1=50_000,
                         unbound=12)
    with pytest.raises(phases.RunFailed, match="generator: .*'unbound': 12"):
        close(gen, traced=False)
