"""A drain's Events in one bulk request (ISSUE 27): ``EventRecorder.events``
leaves the store as its occurrences one at a time would, in one round trip
(two with repeats), best effort and counted; the scheduler writes what it
recorded before the drain or the cycle returns."""

import random

import pytest

pytest.importorskip("jax")

from kubetpu import cli
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.apiserver import APIServer, RemoteStore
from kubetpu.client import SchedulerInformers, StoreClient
from kubetpu.client import events as events_mod
from kubetpu.client.events import EVENTS, MAX_SEEN, EventRecorder
from kubetpu.client.informers import NODES, PODS
from kubetpu.framework import config as C
from kubetpu.metrics.textparse import parse_prometheus_text
from kubetpu.sched import Scheduler
from kubetpu.store import MemStore

ENTRIES = "scheduler_loop_phase_entries_total"
COUNTERS = {
    "written": "kubetpu_events_written_total",
    "dropped": "kubetpu_events_dropped_total",
    "requests": "kubetpu_event_write_requests_total",
}


def scheduled(k, ts=0.0):
    return (f"Pod/default/p{k}", "Scheduled", f"assigned p{k}", "Normal", ts)


def failed(k, ts=0.0):
    return (f"Pod/default/p{k}", "FailedScheduling", "0 nodes", "Warning", ts)


def one_by_one(rec, now, batch):
    """The batch through ``event()`` — batches of one, nothing to fold —
    the clock set to each timestamp."""
    for regarding, reason, note, type_, ts in batch:
        now[0] = ts
        rec.event(regarding, reason, note, type=type_)


def recorder(store=None):
    now = [0.0]
    store = MemStore() if store is None else store
    return store, now, EventRecorder(store, "tester", clock=lambda: now[0])


# ------------------------------------------------------- (a) equivalence

def sequence(seed):
    """Three batches over twelve pods: new signatures, repeats of earlier
    ones, one signature several times in a batch, two types of Event."""
    rng = random.Random(seed)
    ts = 100.0
    batches = []
    for _ in range(3):
        batch = []
        for _ in range(rng.randrange(2, 40)):
            ts += rng.random()
            make = failed if rng.random() < 0.6 else scheduled
            batch.append(make(rng.randrange(12), ts))
        # always: one signature twice inside a batch
        batch.append(batch[0][:4] + (ts + 1.0,))
        ts += 2.0
        batches.append(batch)
    return rng, batches


@pytest.mark.parametrize("max_seen", [MAX_SEEN, 5])
@pytest.mark.parametrize("seed", range(8))
def test_a_batch_leaves_what_its_events_one_by_one_leave(seed, max_seen,
                                                        monkeypatch):
    # a cache of 5 forgets signatures between, and inside, the batches
    monkeypatch.setattr(events_mod, "MAX_SEEN", max_seen)
    rng, batches = sequence(seed)
    st_a, now, rec_a = recorder()
    st_b, _, rec_b = recorder()
    for batch in batches:
        one_by_one(rec_a, now, batch)
        rec_b.events(batch)
        assert dict(st_a.list(EVENTS)[0]) == dict(st_b.list(EVENTS)[0])
        assert list(rec_a._seen.items()) == list(rec_b._seen.items())
        # an object vanishes (a TTL, an operator's delete): its signature
        # is still in the cache, and the repeat starts a new series
        key = rng.choice(sorted(k for k, _ in st_a.list(EVENTS)[0]))
        st_a.delete(EVENTS, key)
        st_b.delete(EVENTS, key)
    recorded = sum(len(b) for b in batches)
    assert rec_a.written == rec_b.written == recorded
    assert rec_a.dropped == rec_b.dropped == 0
    assert rec_b.requests <= 2 * len(batches) < rec_a.requests


def test_a_repeat_in_a_batch_is_folded_and_keeps_the_first_type():
    st, _, rec = recorder()
    rec.events([failed(1, 10.0), scheduled(2, 11.0), failed(1, 12.0),
                ("Pod/default/p1", "FailedScheduling", "0 nodes", "Normal",
                 13.0)])
    (ev,) = [e for _, e in st.list(EVENTS)[0] if e.reason != "Scheduled"]
    assert (ev.count, ev.first_timestamp, ev.last_timestamp, ev.type) == (
        3, 10.0, 13.0, "Warning")
    assert ev.name.startswith("p1.") and len(ev.name) == len("p1.") + 10
    assert ev.reporting_controller == "tester" and ev.namespace == "default"
    # the next batch continues the series the store holds
    rec.events([failed(1, 20.0), failed(1, 21.0)])
    ev = dict(st.list(EVENTS)[0])[ev.key]
    assert (ev.count, ev.first_timestamp, ev.last_timestamp) == (
        5, 10.0, 21.0)
    assert rec.written == 6 and rec.dropped == 0 and rec.requests == 3


# -------------------------------------------------- (b) the round trips

class Counting:
    """A MemStore behind a counter of the calls by verb."""

    def __init__(self, verbs=("get", "update", "bulk")):
        self.inner = MemStore()
        self.calls = []
        for verb in verbs:
            setattr(self, verb, self._counted(verb))

    def _counted(self, verb):
        def call(*args, **kwargs):
            self.calls.append(verb)
            return getattr(self.inner, verb)(*args, **kwargs)
        return call

    def events(self):
        return dict(self.inner.list(EVENTS)[0])


@pytest.mark.parametrize("n", [2, 3, 1024])
def test_new_events_travel_in_one_bulk_request(n):
    st, _, rec = recorder(Counting())
    rec.events([scheduled(k, float(k)) for k in range(n)])
    assert st.calls == ["bulk"]
    assert len(st.events()) == n
    assert (rec.written, rec.dropped, rec.requests) == (n, 0, 1)


def test_repeats_cost_one_bulk_of_gets_more_and_no_single_op():
    st, _, rec = recorder(Counting())
    rec.events([failed(k, 1.0) for k in range(6)])
    st.calls.clear()
    rec.events([failed(k, 2.0) for k in range(4)]
               + [scheduled(k, 2.0) for k in range(4)])
    assert st.calls == ["bulk", "bulk"]
    counts = sorted(e.count for e in st.events().values())
    assert counts == [1] * 6 + [2] * 4
    assert (rec.written, rec.dropped, rec.requests) == (14, 0, 3)


@pytest.mark.parametrize("repeat", [False, True])
def test_a_lone_event_is_a_batch_of_one(repeat):
    st, now, rec = recorder(Counting())
    if repeat:
        rec.events([failed(1, 1.0)])
        st.calls.clear()
    now[0] = 2.0
    rec.event(*failed(1)[:3], type="Warning")
    assert st.calls == (["bulk", "bulk"] if repeat else ["bulk"])
    (ev,) = st.events().values()
    assert ev.count == (2 if repeat else 1) and ev.last_timestamp == 2.0
    assert rec.requests == (3 if repeat else 1) and rec.dropped == 0


def test_an_empty_batch_asks_nothing():
    st, _, rec = recorder(Counting())
    rec.events([])
    assert st.calls == [] and rec.requests == 0


# ------------------------------------------------------ (c) best effort

class Failing(Counting):
    """``bulk`` raises while ``down``; ops on ``bad`` keys fail alone."""

    def __init__(self):
        super().__init__(verbs=("get", "update"))   # its own bulk, below
        self.down = set()       # {"get", "update"}: bulks of that op raise
        self.bad = set()

    def bulk(self, kind, ops):
        self.calls.append("bulk")
        if ops[0]["op"] in self.down:
            raise ConnectionError("apiserver away")
        good = [op for op in ops if op["key"] not in self.bad]
        results = iter(self.inner.bulk(kind, good))
        return [{"status": 500, "resourceVersion": 0, "error": "boom"}
                if op["key"] in self.bad else next(results) for op in ops]


def test_a_bulk_that_raises_drops_all_it_carried_and_raises_nothing():
    st, _, rec = recorder(Failing())
    st.down = {"update"}
    rec.events([scheduled(k) for k in range(50)])
    assert st.calls == ["bulk"]             # no retry, one by one or at all
    assert (rec.written, rec.dropped, rec.requests) == (0, 50, 1)
    assert st.events() == {}
    st.down = set()
    rec.events([scheduled(k) for k in range(50, 60)])
    assert (rec.written, rec.dropped) == (10, 50)


def test_a_store_without_bulk_drops_what_it_is_handed():
    st, _, rec = recorder(Counting(verbs=("get", "update")))
    rec.events([scheduled(k, float(k)) for k in range(5)])
    rec.event("Pod/default/p9", "Scheduled", "x")
    assert st.calls == [] and st.events() == {}
    assert (rec.written, rec.dropped, rec.requests) == (0, 6, 2)


class Garbled(Counting):
    """``bulk`` answers, but not with one result an op."""

    def __init__(self, answer):
        super().__init__(verbs=("get", "update"))
        self.answer = answer

    def bulk(self, kind, ops):
        self.calls.append("bulk")
        return self.answer(self.inner.bulk(kind, ops))


@pytest.mark.parametrize("answer", [
    lambda results: results[:-1],                   # one result short
    lambda results: results + results[:1],          # one too many
    lambda results: [None] + results[1:],           # an entry that is no dict
    lambda results: None,                           # no list at all
], ids=["short", "long", "not-a-dict", "nothing"])
def test_an_answer_that_is_not_one_result_an_op_drops_the_request(answer):
    st, _, rec = recorder(Garbled(answer))
    rec.events([scheduled(k) for k in range(6)])    # raises nothing
    assert st.calls == ["bulk"]
    assert (rec.written, rec.dropped, rec.requests) == (0, 6, 1)


def test_a_read_that_is_garbled_leaves_the_stored_count_alone():
    st, _, rec = recorder(Garbled(lambda results: results))
    rec.events([failed(k, 1.0) for k in range(3)] * 2)      # counts of 2
    st.answer = lambda results: (
        results[:-1] if "object" in results[0] else results)
    rec.events([failed(0, 2.0), failed(1, 2.0), scheduled(7, 2.0)])
    # the two repeats went with their read; the new Event still landed
    assert (rec.written, rec.dropped) == (6 + 1, 2)
    assert sorted(e.count for e in st.events().values()) == [1, 2, 2, 2]


def test_an_object_that_came_back_undecoded_drops_its_repeat_alone():
    st, _, rec = recorder(Garbled(lambda results: results))
    rec.events([failed(0, 1.0), failed(1, 1.0)])
    st.answer = lambda results: [
        {**res, "object": {"count": 1}} if "object" in res else res
        for res in results]
    rec.events([failed(0, 2.0), scheduled(7, 2.0)])          # raises nothing
    assert (rec.written, rec.dropped) == (2 + 1, 1)
    assert sorted(e.count for e in st.events().values()) == [1, 1, 1]


def test_one_failing_op_drops_one_and_the_rest_land():
    st, _, rec = recorder(Failing())
    st.bad = {"default/p3." + key_digest(rec, scheduled(3))}
    rec.events([scheduled(k) for k in range(8)])
    assert (rec.written, rec.dropped, rec.requests) == (7, 1, 1)
    assert len(st.events()) == 7
    assert not any(e.regarding.endswith("/p3") for e in st.events().values())


def key_digest(rec, occurrence):
    return rec._new_series(*occurrence).name.split(".")[1]


def test_a_failed_read_drops_the_repeats_and_the_new_ones_still_land():
    st, _, rec = recorder(Failing())
    rec.events([failed(k, 1.0) for k in range(3)])
    st.down = {"get"}
    rec.events([failed(0, 2.0), failed(0, 3.0), failed(1, 2.0),
                scheduled(7, 2.0), scheduled(8, 2.0)])
    assert (rec.written, rec.dropped) == (3 + 2, 3)
    assert rec.written + rec.dropped == 8
    by_count = sorted(e.count for e in st.events().values())
    assert by_count == [1] * 5          # no series was bumped, none reset


def test_the_three_counters_are_on_the_owners_metrics_page():
    st, _, rec = recorder(Failing())
    rec.events([scheduled(k) for k in range(4)])
    st.down = {"update"}
    rec.events([scheduled(k) for k in range(4, 6)])
    st.down = set()
    rec.event("Pod/default/p9", "Scheduled", "x")
    pm = parse_prometheus_text(rec.metrics_text())
    got = {k: pm.value(name, controller="tester")
           for k, name in COUNTERS.items()}
    assert got == {"written": 5, "dropped": 2, "requests": 3}


# ------------------------------------------------------- (d) the cache

def test_a_batch_of_five_thousand_leaves_the_cache_at_its_bound():
    st, _, rec = recorder()
    rec.events([scheduled(k, float(k)) for k in range(5000)])
    assert len(rec._seen) == MAX_SEEN
    assert next(iter(rec._seen))[0] == f"Pod/default/p{5000 - MAX_SEEN}"
    assert len(st.list(EVENTS)[0]) == 5000 == rec.written


# --------------------------------------------- (e) the scheduler's part

def served(st=None, seen_by=None, nodes=2, pods=0, recorder=True, **kw):
    """A scheduler on ``st`` (what it and its recorder talk to);
    ``seen_by`` is the store the test fills (the same one, or the
    apiserver's behind a RemoteStore)."""
    st = MemStore() if st is None else st
    seen_by = st if seen_by is None else seen_by
    for i in range(nodes):
        seen_by.create(NODES, f"n{i}", make_node(f"n{i}", cpu_milli=4000))
    s = Scheduler(
        StoreClient(st), profile=C.minimal_profile(), dispatcher_workers=0,
        recorder=EventRecorder(st, "kubetpu-scheduler") if recorder else None,
        **kw,
    )
    informers = SchedulerInformers(st, s)
    informers.start()
    for j in range(pods):
        seen_by.create(PODS, f"default/p{j}", make_pod(f"p{j}", cpu_milli=100))
    return st, s, informers


@pytest.mark.parametrize("pods", [1, 2, 30])
def test_when_the_drain_returns_every_scheduled_event_is_in_the_store(pods):
    st, s, informers = served(pods=pods)
    informers.pump()
    s.schedule_batch()          # inline dispatcher: the binds are done
    assert s._pending_events == [] and st.list(EVENTS)[0] == []
    assert s._drain_bind_completions() == pods
    assert s._pending_events == []
    events = [e for _, e in st.list(EVENTS)[0]]
    assert sorted(e.regarding for e in events) == sorted(
        f"Pod/default/p{j}" for j in range(pods))
    assert {e.reason for e in events} == {"Scheduled"}
    rec = s.recorder
    assert (rec.written, rec.dropped, rec.requests) == (pods, 0, 1)
    assert s.loop_clock.entries["events"] == pods
    (drain,) = [sp for sp in s.tracer.recent(1000) if sp.name == "drain"]
    assert drain.attrs["events"] == pods
    assert drain.attrs["event_requests"] == 1
    s.close()


@pytest.mark.parametrize("huge", [1, 3])
def test_when_the_cycle_returns_its_failed_scheduling_events_are_there(huge):
    st, s, informers = served(pods=2)
    for j in range(huge):
        st.create(PODS, f"default/huge{j}",
                  make_pod(f"huge{j}", cpu_milli=99999))
    informers.pump()
    s.schedule_batch()          # no drain yet
    assert s._pending_events == []
    events = [e for _, e in st.list(EVENTS)[0]]
    assert sorted(e.regarding for e in events) == [
        f"Pod/default/huge{j}" for j in range(huge)]
    assert {(e.reason, e.type) for e in events} == {
        ("FailedScheduling", "Warning")}
    assert s.recorder.requests == 1 and s.loop_clock.entries["events"] == huge
    s.close()
    assert len(st.list(EVENTS)[0]) == huge + 2 and s._pending_events == []


def test_the_events_carry_the_moment_they_were_recorded():
    st, s, informers = served(pods=3)
    now = [50.0]
    s.recorder.clock = lambda: now.__setitem__(0, now[0] + 1.0) or now[0]
    informers.pump()
    s.schedule_batch()
    s._drain_bind_completions()
    stamps = sorted(e.first_timestamp for _, e in st.list(EVENTS)[0])
    assert stamps == [51.0, 52.0, 53.0]
    s.close()


def test_a_store_that_fails_costs_the_drain_nothing_but_the_count():
    st, s, informers = served(pods=4)
    s.recorder.store = failing = Failing()
    failing.down = {"update"}
    informers.pump()
    s.schedule_batch()
    assert s._drain_bind_completions() == 4     # and raises nothing
    assert s._pending_events == []
    assert (s.recorder.written, s.recorder.dropped) == (0, 4)
    assert len([p for _, p in st.list(PODS)[0] if p.node_name]) == 4
    pm = parse_prometheus_text(s.metrics_text())
    assert pm.value(COUNTERS["dropped"], controller="kubetpu-scheduler") == 4
    assert pm.value(ENTRIES, phase="events") == 4
    s.close()


def test_without_a_recorder_nothing_is_gathered():
    st, s, informers = served(pods=3, recorder=False)
    informers.pump()
    s.schedule_batch()
    assert s._drain_bind_completions() == 3
    assert s._pending_events == [] and st.list(EVENTS)[0] == []
    assert s.loop_clock.entries["events"] == 0
    assert "kubetpu_events_written_total" not in s.metrics_text()
    s.close()


# ------------------------------------------------------------ (f) served

def events_requests(srv):
    """Requests the apiserver counted that name the ``events`` resource,
    whatever the verb."""
    pm = parse_prometheus_text(srv.metrics_text())
    return sum(s.value for s in pm.samples("apiserver_request_total")
               if "events" in (s.label("resource") or ""))


@pytest.mark.parametrize("wire", ["binary", "json"])
def test_forty_pods_over_a_real_apiserver_cost_two_event_requests_a_drain(
        wire):
    backing = MemStore()
    srv = APIServer(backing).start()
    try:
        remote = RemoteStore(srv.url, wire=wire)
        _, s, informers = served(st=remote, seen_by=backing, nodes=4, pods=40)
        once = cli._scheduler_iteration(s, informers)
        before = events_requests(srv)
        for _ in range(4):
            once()
        bound = [p for _, p in backing.list(PODS)[0] if p.node_name]
        assert len(bound) == 40
        drains = [sp for sp in s.tracer.recent(1000) if sp.name == "drain"
                  and sp.attrs["events"]]
        asked = events_requests(srv) - before
        assert 1 <= asked <= 2 * len(drains) < 40
        assert asked == s.recorder.requests == sum(
            sp.attrs["event_requests"] for sp in drains)
        listed = [e for _, e in remote.list(EVENTS)[0]]
        assert len(listed) == 40
        assert {e.regarding for e in listed} == {
            f"Pod/default/p{j}" for j in range(40)}
        pm = parse_prometheus_text(s.metrics_text())
        assert pm.value(ENTRIES, phase="events") == 40
        who = {"controller": "kubetpu-scheduler"}
        assert pm.value(COUNTERS["written"], **who) == 40
        assert pm.value(COUNTERS["dropped"], **who) == 0
        # a second wave repeats nothing: still no read, one bulk a drain
        for j in range(40, 50):
            backing.create(PODS, f"default/p{j}",
                           make_pod(f"p{j}", cpu_milli=100))
        for _ in range(3):
            once()
        assert len(remote.list(EVENTS)[0]) == 50
        assert s.recorder.written == 50 and s._pending_events == []
        s.close()
    finally:
        srv.close()
