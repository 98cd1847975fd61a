"""The served loop's phase clock, its counters on /metrics, the loop's spans
on /trace, and the two rings of the tracer (ISSUE 24); the CPU clock beside
the wall clock on the loop thread, on its spans and on the diagnostics
listener's request threads (ISSUE 38)."""

import threading
import time
import urllib.error
import urllib.request

import pytest

pytest.importorskip("jax")

from kubetpu import cli
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.client import SchedulerInformers, StoreClient
from kubetpu.client.events import EVENTS, MAX_SEEN, EventRecorder
from kubetpu.client.informers import NODES, PODS
from kubetpu.framework import config as C
from kubetpu.metrics.textparse import parse_prometheus_text
from kubetpu.sched import DiagnosticsServer, Scheduler
from kubetpu.store import MemStore
from kubetpu.tracing import LOOP_PHASES, PhaseClock, Tracer

SECONDS = "scheduler_loop_phase_seconds_total"
CPU_SECONDS = "scheduler_loop_phase_cpu_seconds_total"
PROCESS_CPU = "process_cpu_seconds_total"
DIAG_REQUESTS = "scheduler_diagnostics_requests_total"
DIAG_CPU = "scheduler_diagnostics_request_cpu_seconds_total"
ENTRIES = "scheduler_loop_phase_entries_total"
ITERATIONS = "scheduler_loop_iterations_total"


# ------------------------------------------------------------ the clock

def fake_clock():
    t = [100.0]
    return t, PhaseClock(clock=lambda: t[0])


def test_phases_sum_to_the_elapsed_time_exactly():
    t, clock = fake_clock()
    for k, phase in enumerate(LOOP_PHASES * 3):
        clock.switch(phase)
        t[0] += 0.125 * (k + 1)
        with clock.phase("events"):
            t[0] += 0.5
    clock.switch("other")
    assert sum(clock.seconds.values()) == t[0] - 100.0
    assert clock.seconds["events"] == 0.5 * 27 + 0.125 * (7 + 16 + 25)
    assert clock.entries["events"] == 27 + 3
    # resuming an interrupted phase is not another entry
    assert clock.entries["drain"] == 3


@pytest.mark.parametrize("raises", [False, True])
def test_phase_restores_the_phase_it_interrupted(raises):
    t, clock = fake_clock()
    clock.switch("drain")
    t[0] += 1.0
    try:
        with clock.phase("events"):
            assert clock.current == "events"
            t[0] += 2.0
            if raises:
                raise ConnectionError("apiserver away")
    except ConnectionError:
        pass
    assert clock.current == "drain"
    t[0] += 4.0
    clock.switch("other")
    assert clock.seconds["drain"] == 5.0 and clock.seconds["events"] == 2.0
    assert clock.entries["drain"] == 1 and clock.entries["events"] == 1


def test_a_scrape_in_the_middle_of_a_phase_includes_its_elapsed_part():
    t, clock = fake_clock()
    clock.switch("drain")
    t[0] += 2.0
    seconds, entries, iterations = clock.snapshot()
    assert seconds["drain"] == 2.0 and clock.seconds["drain"] == 0.0
    assert sum(seconds.values()) == 2.0
    assert entries["drain"] == 1 and iterations == 0
    t[0] += 1.0
    assert clock.snapshot()[0]["drain"] == 3.0


# -------------------------------------------------------- the CPU clock

def spin(seconds):
    """Hold a core: what a pure-Python phase does."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_cpu_is_at_most_wall_in_every_phase_and_sums_to_the_thread_s():
    clock = PhaseClock()
    cpu0 = time.thread_time()
    for k, phase in enumerate(LOOP_PHASES * 2):
        clock.switch(phase)
        spin(0.002) if k % 2 else time.sleep(0.002)
    clock.switch("other")
    thread_cpu = time.thread_time() - cpu0
    for phase in LOOP_PHASES:
        # to the clocks' grain: the two are read a microsecond apart
        assert clock.cpu_seconds[phase] <= clock.seconds[phase] + 1e-3, phase
    assert sum(clock.cpu_seconds.values()) == pytest.approx(
        thread_cpu, abs=2e-3)
    assert clock.cpu_snapshot().keys() == set(LOOP_PHASES)


def test_a_sleeping_phase_has_wall_and_no_cpu_a_spinning_one_has_both():
    for _ in range(3):      # a spin can lose its core on a shared machine
        clock = PhaseClock()
        with clock.phase("sleep"):
            time.sleep(0.05)
        with clock.phase("drain"):
            spin(0.05)
        wall, cpu = clock.seconds["drain"], clock.cpu_seconds["drain"]
        if cpu >= 0.8 * wall:
            break
    assert clock.seconds["sleep"] >= 0.05
    assert clock.cpu_seconds["sleep"] < 0.005
    assert wall >= 0.05 and 0.8 * wall <= cpu <= wall + 1e-3


def test_a_scrape_from_a_spinning_thread_leaks_no_cpu_into_any_phase():
    """``cpu_snapshot`` reads the LOOP thread's clock, not its caller's."""
    clock = PhaseClock()
    clock.switch("pump_rpc")        # the loop thread, blocked on a socket
    got = []

    def scraper():
        spin(0.1)
        got.append(clock.cpu_snapshot())

    th = threading.Thread(target=scraper)
    th.start()
    th.join()                       # blocked: no CPU of this thread's
    assert got[0]["pump_rpc"] < 0.02
    assert sum(got[0].values()) < 0.02


def test_the_thread_that_switches_is_the_thread_that_is_clocked():
    """A loop started on another thread than its scheduler's builder (the
    tests' way): ``switch`` finds the new thread and reads ITS clock."""
    clock = PhaseClock()            # built here ...

    def loop():                     # ... switched there
        clock.switch("cycle")
        spin(0.05)
        clock.switch("sleep")

    spin(0.05)                      # the builder's CPU is nobody's phase
    th = threading.Thread(target=loop)
    th.start()
    th.join()
    assert 0.02 <= clock.cpu_seconds["cycle"] <= clock.seconds["cycle"] + 1e-3
    assert clock.seconds["other"] >= 0.05
    # the phase that changed hands kept its wall and gained no CPU
    assert clock.cpu_seconds["other"] == 0.0
    # the thread has ended: its clock is gone, the totals stay
    assert clock.cpu_snapshot()["cycle"] == clock.cpu_seconds["cycle"]


def test_a_span_carries_cpu_s_and_a_recorded_span_does_not():
    tr = Tracer()
    with tr.span("encode") as busy:
        spin(0.02)
    with tr.span("pump") as blocked:
        time.sleep(0.02)
    tr.record("bind", start=1.0, end=2.0, per_item=True)
    tr.instant("invalidate")
    assert 0.5 * 0.02 <= busy.attrs["cpu_s"] <= busy.duration_s + 1e-3
    assert blocked.attrs["cpu_s"] < 0.005 <= blocked.duration_s
    by_name = {e["name"]: e for e in tr.chrome_trace()["traceEvents"]}
    assert by_name["encode"]["args"]["cpu_s"] == busy.attrs["cpu_s"]
    assert "cpu_s" not in by_name["bind"]["args"]
    assert "cpu_s" not in by_name["invalidate"]["args"]


# ------------------------------------------------- a scheduler on a store

def served(nodes=2, pods=0, **kw):
    st = MemStore()
    for i in range(nodes):
        st.create(NODES, f"n{i}", make_node(f"n{i}", cpu_milli=4000))
    s = Scheduler(
        StoreClient(st), profile=C.minimal_profile(), dispatcher_workers=0,
        recorder=EventRecorder(st, "kubetpu-scheduler"), **kw,
    )
    informers = SchedulerInformers(st, s)
    informers.start()
    for j in range(pods):
        st.create(PODS, f"default/p{j}", make_pod(f"p{j}", cpu_milli=100))
    return st, s, cli._scheduler_iteration(s, informers)


def bound(st):
    return [p for _, p in st.list(PODS)[0] if p.node_name]


def test_all_nine_phases_are_on_metrics_from_the_first_scrape():
    _, s, _ = served()
    pm = parse_prometheus_text(s.metrics_text())
    for phase in LOOP_PHASES:
        assert pm.value(SECONDS, phase=phase) is not None, phase
        assert pm.value(ENTRIES, phase=phase) == 0
    assert len(LOOP_PHASES) == 9
    assert pm.value(ITERATIONS) == 0
    s.close()


def test_the_cpu_families_are_on_metrics_from_the_first_scrape():
    _, s, _ = served()
    pm = parse_prometheus_text(s.metrics_text())
    for phase in LOOP_PHASES:
        assert pm.value(CPU_SECONDS, phase=phase) is not None, phase
    assert 0 < pm.value(PROCESS_CPU) <= time.process_time()
    s.close()


def test_the_loop_s_spans_carry_cpu_s_and_the_per_pod_spans_do_not():
    st, s, once = served(pods=5)
    once()
    assert len(bound(st)) == 5
    by_name = {}
    for sp in s.tracer.recent(1000):
        by_name.setdefault(sp.name, []).append(sp)
    for name in ("loop-iteration", "pump", "encode", "explain",
                 "bind-dispatch", "drain"):
        (sp,) = by_name[name]
        assert 0 <= sp.attrs["cpu_s"] <= sp.duration_s + 1e-3, name
    assert len(by_name["bind"]) == 5
    assert all("cpu_s" not in sp.attrs for sp in by_name["bind"])
    s.close()


def test_a_busy_iteration_leaves_its_spans_under_one_parent():
    st, s, once = served(pods=5)
    once()          # delivers 5 pods, binds them (inline dispatcher), drains
    assert len(bound(st)) == 5
    spans = s.tracer.recent(1000)
    (it,) = [sp for sp in spans if sp.name == "loop-iteration"]
    assert it.parent_id is None
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    for name in ("pump", "snapshot", "encode", "scheduling-cycle", "explain",
                 "bind-dispatch", "drain"):
        (sp,) = by_name[name]
        assert sp.parent_id == it.span_id, name
        assert it.start <= sp.start and sp.end <= it.end, name
    (pump,), (drain,) = by_name["pump"], by_name["drain"]
    assert pump.attrs["deliveries"] == 5 and pump.attrs["rpc_s"] >= 0
    assert drain.attrs["completions"] == 5 and drain.attrs["events"] == 5
    assert 0 <= drain.attrs["events_s"] <= drain.duration_s
    assert by_name["bind-dispatch"][0].attrs["pods"] == 5
    # no span per Event write, and the per-pod spans point at no iteration
    assert "events" not in by_name
    assert len(by_name["bind"]) == 5
    s.close()


def test_the_cycle_span_covers_explain_and_bind_dispatch():
    _, s, once = served(pods=3)
    once()
    spans = {sp.name: sp for sp in s.tracer.recent(1000)}
    cycle = spans["scheduling-cycle"]
    assert not cycle.off_stack      # serial: on the loop's lane
    for name in ("snapshot", "encode", "explain", "bind-dispatch"):
        assert cycle.start <= spans[name].start, name
        assert spans[name].end <= cycle.end, name
    # and it is what the algorithm-duration histogram observed, to within
    # the pop that the span leaves out
    observed = s.metrics.prom.scheduling_algorithm_duration.merged().sum
    assert observed == pytest.approx(cycle.duration_s, abs=0.05)
    s.close()


def test_an_idle_iteration_leaves_no_span_and_is_still_counted():
    _, s, once = served()
    stop = threading.Event()
    ticks = []

    def run_once():
        once()
        ticks.append(1)
        if len(ticks) == 3:
            stop.set()

    assert cli._make_loop(run_once, period_s=0.01, stop=stop,
                          clock=s.loop_clock)() == 0
    assert s.tracer.recent(1000) == []
    seconds, entries, iterations = s.loop_clock.snapshot()
    assert iterations == 3
    assert entries["sleep"] == 3 and seconds["sleep"] >= 0.03
    assert entries["pump_apply"] == 3 and entries["cycle"] == 3
    assert entries["events"] == 0
    assert s.loop_clock.current == "other"
    s.close()


def test_event_entries_equal_the_pods_bound():
    st, s, once = served(pods=7)
    once()
    once()
    n = len(bound(st))
    assert n == 7
    pm = parse_prometheus_text(s.metrics_text())
    assert pm.value(ENTRIES, phase="events") == n
    assert len(st.list(EVENTS)[0]) == n
    assert pm.value(SECONDS, phase="events") > 0
    s.close()


def test_make_loop_without_a_clock_behaves_as_before():
    stop = threading.Event()
    calls = []

    def run_once():
        calls.append(time.perf_counter())
        if len(calls) == 2:
            raise ConnectionError("once")
        if len(calls) == 4:
            stop.set()

    slept = []
    real_sleep = time.sleep
    time.sleep = lambda s: slept.append(s)
    try:
        assert cli._make_loop(run_once, period_s=0.2, stop=stop)() == 0
    finally:
        time.sleep = real_sleep
    # the period after each good call, the 2 s back-off after the bad one
    assert slept == [0.2, 2.0, 0.2, 0.2]


def scrape(url):
    t0 = time.perf_counter()
    with urllib.request.urlopen(url + "/metrics", timeout=10) as resp:
        text = resp.read().decode()
    return t0, time.perf_counter(), parse_prometheus_text(text)


def test_the_real_loop_for_two_seconds_sums_to_the_wall():
    st, s, once = served(nodes=4)
    stop = threading.Event()
    loop = threading.Thread(
        target=cli._make_loop(once, stop=stop, clock=s.loop_clock))
    diag = DiagnosticsServer(s).start()
    loop.start()
    try:
        a0, a1, before = scrape(diag.url)
        for j in range(40):     # work arrives while the loop runs
            st.create(PODS, f"default/w{j}", make_pod(f"w{j}", cpu_milli=10))
            time.sleep(0.05)
        deadline = time.perf_counter() + 60
        while (s.loop_clock.entries["events"] < 40
               and time.perf_counter() < deadline):
            time.sleep(0.05)    # the last binds drain, their Events go out
        b0, b1, after = scrape(diag.url)
    finally:
        stop.set()
        loop.join(timeout=30)
        diag.close()
        s.close()
    total = sum(after.value(SECONDS, phase=p) - before.value(SECONDS, phase=p)
                for p in LOOP_PHASES)
    assert b0 - a1 >= 2.0
    assert 0.99 * (b0 - a1) <= total <= 1.01 * (b1 - a0)
    assert after.value(ITERATIONS) > before.value(ITERATIONS)
    assert after.value(ENTRIES, phase="events") == 40 == len(bound(st))
    assert after.value(SECONDS, phase="sleep") > 0
    # the CPU clock of the thread that ran the loop, read by the scrapes'
    # threads: at most the wall in every phase, and next to none asleep
    window = b1 - a0
    for p in LOOP_PHASES:
        wall = after.value(SECONDS, phase=p) - before.value(SECONDS, phase=p)
        cpu = (after.value(CPU_SECONDS, phase=p)
               - before.value(CPU_SECONDS, phase=p))
        assert 0 <= cpu <= wall + 0.01 * window, p
        if p == "sleep":
            assert cpu <= 0.05 * wall
    assert after.value(PROCESS_CPU) > before.value(PROCESS_CPU)


# ------------------------------------------- the listener times itself

def test_a_diagnostics_request_adds_to_its_endpoint_and_to_no_other():
    _, s, _ = served()
    diag = DiagnosticsServer(s).start()

    def get(path):
        try:
            with urllib.request.urlopen(diag.url + path, timeout=10) as resp:
                return resp.read().decode()
        except urllib.error.HTTPError:
            return ""

    endpoints = ("metrics", "trace", "health", "debug", "other")

    def counts():
        pm = parse_prometheus_text(diag.metrics_text())
        return ({e: pm.value(DIAG_REQUESTS, endpoint=e) for e in endpoints},
                {e: pm.value(DIAG_CPU, endpoint=e) for e in endpoints})
    try:
        # every endpoint is there before the first request, at zero
        assert counts() == (dict.fromkeys(endpoints, 0),
                            dict.fromkeys(endpoints, 0.0))
        for path, n in (("/trace", 3), ("/metrics", 2), ("/readyz", 1),
                        ("/healthz/ping", 1), ("/debug/queue?limit=1", 1),
                        ("/nowhere", 1)):
            for _ in range(n):
                get(path)
        deadline = time.perf_counter() + 10
        while (sum(counts()[0].values()) < 9
               and time.perf_counter() < deadline):
            time.sleep(0.01)    # a request counts once it has been answered
        requests, cpu = counts()
    finally:
        diag.close()
        s.close()
    assert requests == {"metrics": 2, "trace": 3, "health": 2, "debug": 1,
                        "other": 1}
    assert all(v > 0 for v in cpu.values())
    # the scrape itself is answered over HTTP too, and sees the ones before
    assert "scheduler_diagnostics_requests_total" in diag.metrics_text()


# ------------------------------------------------------- the two rings

def test_five_thousand_bind_spans_leave_the_cycle_in_the_ring():
    tr = Tracer()
    tr.record("scheduling-cycle", start=1.0, end=2.0, off_stack=False,
              cycle=1)
    tr.record("assign", start=1.2, end=1.8, cycle=1)
    for k in range(5000):
        tr.record("bind", start=2.0 + k * 1e-3, end=2.5 + k * 1e-3,
                  per_item=True, cycle=1)
    names = [sp.name for sp in tr.recent(1 << 30)]
    assert names[:2] == ["scheduling-cycle", "assign"]
    assert names.count("bind") == 4096
    starts = [sp.start for sp in tr.recent(1 << 30)]
    assert starts == sorted(starts)
    events = tr.chrome_trace()["traceEvents"]
    assert {e["name"] for e in events} == {"scheduling-cycle", "assign",
                                           "bind"}
    # recent(n) is the LAST n by start, across both rings
    assert [sp.name for sp in tr.recent(3)] == ["bind"] * 3


def test_drain_pops_exactly_what_it_handed_out_from_each_ring():
    tr = Tracer()
    tr.record("a", 0.0, 1.0)
    tr.record("bind", 0.5, 1.5, per_item=True)
    orig = tr._snapshot_spans

    def racing_snapshot():
        out = orig()
        tr._snapshot_spans = orig
        tr.record("b", 2.0, 3.0)                    # after the snapshot,
        tr.record("bind", 2.5, 3.5, per_item=True)  # into either ring
        return out

    tr._snapshot_spans = racing_snapshot
    assert [s.name for s in tr.drain()] == ["a", "bind"]
    assert [s.name for s in tr.recent()] == ["b", "bind"]
    assert [s.name for s in tr.drain()] == ["b", "bind"]
    assert tr.recent() == []


def test_a_discarded_span_is_not_recorded_and_keeps_the_stack_whole():
    tr = Tracer()
    with tr.span("loop-iteration", log_long=False) as it:
        with tr.span("pump") as sp:
            sp.discard = True
        with tr.span("drain") as kept:
            pass
    assert tr.current_id is None
    assert [s.name for s in tr.recent()] == ["loop-iteration", "drain"]
    assert kept.parent_id == it.span_id
    assert "log_long" not in it.attrs


def test_a_long_envelope_logs_nothing():
    t = [0.0]
    logged = []
    tr = Tracer(clock=lambda: t[0], threshold_s=0.1, log=logged.append)
    with tr.span("loop-iteration", log_long=False):
        t[0] += 5.0
    assert logged == [] and len(tr.recent()) == 1


# ------------------------------------------------------ the event cache

def test_the_event_aggregation_cache_is_bounded():
    st = MemStore()
    rec = EventRecorder(st, "kubetpu-scheduler")
    for k in range(MAX_SEEN + 10):
        rec.event(f"Pod/default/p{k}", "Scheduled", "ok")
    assert len(rec._seen) == MAX_SEEN
    # a repeat of a remembered event still aggregates ...
    rec.event(f"Pod/default/p{MAX_SEEN + 9}", "Scheduled", "ok")
    assert len(st.list(EVENTS)[0]) == MAX_SEEN + 10
    # ... a repeat of a forgotten one finds its series in the store again
    # (the key is a digest of the same fields), and nothing is dropped
    rec.event("Pod/default/p0", "Scheduled", "ok")
    assert len(st.list(EVENTS)[0]) == MAX_SEEN + 10 and rec.dropped == 0
    assert len(rec._seen) == MAX_SEEN
