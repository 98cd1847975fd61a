"""kubetpu.launch: the process supervisor + multi-process control plane.

Tier-1 contract (ISSUE 13): the readiness-banner format round-trips and
rejects garbage; the restart-policy grammar parses; a child that dies
before its banner fails LOUDLY with its captured log tail; the
``on-failure`` policy respawns a SIGKILLed child (and ``never`` gives up);
and — the integration spine — a real 2-replica hash cluster over a
persistent apiserver survives a replica SIGKILL mid-run (the respawned
process re-federates and every pod binds), the SIGTERM cascade leaves no
orphan processes, ``store fsck`` passes on the WAL dir afterwards, and
per-child resource stats are sampled while the children live. The watch
fan-out load starts too: ``WatchFanout`` in process, and ``kubetpu up
--watch-fanout N --fanout-procs M`` as ``watch-driver`` processes.
"""

from __future__ import annotations

import os
import sys
import time

import pytest

from kubetpu.launch import (
    ChildSpec,
    Cluster,
    RestartPolicy,
    Supervisor,
    SupervisorError,
    format_banner,
    parse_banner,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = {"JAX_PLATFORMS": "cpu"}

#: a fast non-jax child that banners and then parks (the supervisor's
#: lifecycle can be tested without paying a scheduler boot)
_FAKE_CHILD = (
    "from kubetpu.launch.banner import emit_banner\n"
    "import time\n"
    "emit_banner('fake', note='hello')\n"
    "time.sleep(600)\n"
)


def _fake_spec(name: str = "fake", restart: str = "never",
               script: str = _FAKE_CHILD, **kw) -> ChildSpec:
    return ChildSpec(
        name=name, argv=[sys.executable, "-c", script],
        restart=restart, ready_timeout_s=30.0, cwd=REPO, **kw,
    )


# ---------------------------------------------------------------------------
# banner + restart-policy grammar
# ---------------------------------------------------------------------------

def test_banner_roundtrip_and_machine_fields():
    line = format_banner(
        "apiserver", url="http://127.0.0.1:1234",
        readyz="http://127.0.0.1:1234/readyz",
    )
    assert line.count("\n") == 0, "banner must be ONE line"
    payload = parse_banner(line)
    assert payload == {
        "component": "apiserver",
        "url": "http://127.0.0.1:1234",
        "readyz": "http://127.0.0.1:1234/readyz",
        "pid": os.getpid(),
    }
    # tolerant of the trailing newline a pipe reader hands over
    assert parse_banner(line + "\n") == payload


@pytest.mark.parametrize("bad", [
    None, "", "serving on http://127.0.0.1:8080",
    "KUBETPU-READY", "KUBETPU-READY not-json",
    "KUBETPU-READY [1, 2]",                       # not an object
    'KUBETPU-READY {"no_component": true}',
])
def test_malformed_banner_reads_as_none(bad):
    assert parse_banner(bad) is None


def test_restart_policy_grammar():
    assert RestartPolicy.parse("never") == RestartPolicy("never")
    assert RestartPolicy.parse("") == RestartPolicy("never")
    assert RestartPolicy.parse("on-failure") == RestartPolicy(
        "on-failure", None
    )
    assert RestartPolicy.parse("on-failure:3") == RestartPolicy(
        "on-failure", 3
    )
    assert RestartPolicy.parse("on-failure:0").allows(0) is False
    assert RestartPolicy.parse("on-failure:2").allows(1) is True
    assert RestartPolicy.parse("on-failure:2").allows(2) is False
    assert RestartPolicy.parse("never").allows(0) is False
    for bad in ("on-failure:x", "on-failure:-1", "always", "onfailure"):
        with pytest.raises(ValueError):
            RestartPolicy.parse(bad)


# ---------------------------------------------------------------------------
# spec argv threading: `kubetpu up --engine/--topology` → scheduler children
# ---------------------------------------------------------------------------

def test_scheduler_spec_threads_engine_into_argv():
    """``kubetpu up --engine packing`` reaches the child argv through ONE
    seam (scheduler_spec) — the packing engine must survive the spec
    builder, not silently fall back to greedy in every child."""
    from kubetpu.launch.cluster import scheduler_spec

    spec = scheduler_spec(
        name="scheduler-r0", server="http://127.0.0.1:1",
        engine="packing",
    )
    i = spec.argv.index("--engine")
    assert spec.argv[i + 1] == "packing"
    default = scheduler_spec(
        name="scheduler-r0", server="http://127.0.0.1:1",
    )
    j = default.argv.index("--engine")
    assert default.argv[j + 1] == "greedy"


def test_scheduler_spec_topology_argv_off_is_byte_identical():
    """--topology on/auto appends the flag; the default "off" spec's argv
    is byte-for-byte what it was before the topology axis existed."""
    from kubetpu.launch.cluster import scheduler_spec

    base = scheduler_spec(name="s", server="http://127.0.0.1:1")
    off = scheduler_spec(name="s", server="http://127.0.0.1:1",
                         topology="off")
    assert off.argv == base.argv
    assert "--topology" not in base.argv
    for mode in ("on", "auto"):
        spec = scheduler_spec(name="s", server="http://127.0.0.1:1",
                              topology=mode)
        i = spec.argv.index("--topology")
        assert spec.argv[i + 1] == mode


def test_up_parser_threads_engine_and_topology():
    """The ``kubetpu up`` CLI accepts --engine packing and --topology and
    lands them on the parsed args the Cluster is built from."""
    from kubetpu.cli import build_parser

    p = build_parser()
    args = p.parse_args(["up", "--engine", "packing", "--topology", "on"])
    assert args.engine == "packing"
    assert args.topology == "on"
    args = p.parse_args(["up"])
    assert getattr(args, "topology", "off") == "off"
    with pytest.raises(SystemExit):
        p.parse_args(["up", "--topology", "sideways"])
    sched = p.parse_args(
        ["scheduler", "--server", "http://x", "--topology", "auto"]
    )
    assert sched.topology == "auto"


# ---------------------------------------------------------------------------
# supervisor failure paths (fast fake children — no scheduler boot)
# ---------------------------------------------------------------------------

def test_child_death_before_ready_is_loud_with_log_tail():
    sup = Supervisor()
    spec = ChildSpec(
        name="doomed",
        argv=[sys.executable, "-c",
              "import sys; print('boom-evidence-line'); sys.exit(3)"],
        ready_timeout_s=30.0,
    )
    with pytest.raises(SupervisorError) as ei:
        sup.spawn(spec)
    msg = str(ei.value)
    assert "rc=3" in msg
    assert "boom-evidence-line" in msg, "log tail must travel with the error"
    sup.shutdown()


def test_on_failure_policy_respawns_a_sigkilled_child():
    with Supervisor() as sup:
        child = sup.spawn(_fake_spec(restart="on-failure:2"))
        first_pid = child.pid
        sup.start_monitor(period_s=0.05)
        sup.kill("fake")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            # the "restarted" event lands only after the respawned child
            # re-bannered — THE ready-again signal, so wait for it
            if any(e[0] == "restarted" for e in sup.events):
                break
            time.sleep(0.05)
        assert child.restarts == 1 and child.alive(), sup.events
        assert child.pid != first_pid
        kinds = [e[0] for e in sup.events]
        assert kinds == ["died", "restarted"]
        # the respawned child re-bannered (fresh ephemeral-port contract)
        assert child.banner and child.banner["component"] == "fake"


def test_never_policy_gives_up_and_records_it():
    with Supervisor() as sup:
        child = sup.spawn(_fake_spec(restart="never"))
        sup.start_monitor(period_s=0.05)
        sup.kill("fake")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if child.failed:
                break
            time.sleep(0.05)
        assert child.failed and not child.alive()
        kinds = [e[0] for e in sup.events]
        assert kinds == ["died", "gave-up"]
        assert child.restarts == 0


def test_invalid_restart_policy_fails_the_spawn_not_the_monitor():
    """A bad --restart string must die AT SPAWN with a usage error — the
    lazy alternative (first parse inside _handle_death) would kill the
    monitor thread on the first crash and silently end all supervision."""
    with Supervisor() as sup:
        with pytest.raises(ValueError):
            sup.spawn(_fake_spec(restart="always"))


def test_duplicate_child_name_rejected():
    with Supervisor() as sup:
        sup.spawn(_fake_spec())
        with pytest.raises(ValueError):
            sup.spawn(_fake_spec())


# ---------------------------------------------------------------------------
# the integration spine: real cluster, kill/respawn, cascade, fsck
# ---------------------------------------------------------------------------

def test_mp_cluster_replica_kill_respawn_cascade_and_fsck(tmp_path):
    """One end-to-end run covering the ISSUE's supervisor failure paths on
    REAL components: a 2-replica hash-partitioned cluster over a
    persistent apiserver; replica r1 is SIGKILLed mid-run and the
    on-failure policy respawns it (the respawned process re-federates —
    its informer relist re-adopts the rank's backlog — so every pod
    binds); the SIGTERM cascade then leaves no orphan processes, and
    ``store fsck`` passes on the WAL dir (the apiserver's TERM handler
    rode the PR-11 graceful-close path — no torn tail)."""
    from kubetpu.api.wrappers import make_node, make_pod
    from kubetpu.apiserver import RemoteStore

    wal_dir = str(tmp_path / "wal")
    cluster = Cluster(
        replicas=2, partition="hash", restart="on-failure:2",
        persistence=wal_dir, env=CPU_ENV, cwd=REPO,
    )
    with cluster:
        # whoever launches a scheduler sees what it holds, and which store
        # core its apiserver serves from, on the readiness banners
        for sched in cluster.schedulers:
            assert sched.banner["platform"] == "cpu"
            assert sched.banner["device_kind"] and sched.banner["devices"] >= 1
        assert cluster.apiserver_children[0].banner["store_core"] in (
            "native", "python")
        admin = RemoteStore(cluster.api_url)
        for i in range(4):
            admin.create("nodes", f"n{i}",
                         make_node(f"n{i}", cpu_milli=64000, pods=110))
        admin.bulk("pods", [
            {"op": "create", "key": f"ns/p{i}",
             "object": make_pod(f"p{i}", namespace="ns")}
            for i in range(12)
        ])
        cluster.kill_replica(1)
        admin.bulk("pods", [
            {"op": "create", "key": f"ns/q{i}",
             "object": make_pod(f"q{i}", namespace="ns")}
            for i in range(12)
        ])
        deadline = time.monotonic() + 120
        bound = 0
        while time.monotonic() < deadline:
            items, _rv = admin.list("pods")
            bound = sum(1 for _k, o in items if o.node_name)
            if bound == 24:
                break
            time.sleep(0.2)
        assert bound == 24, (
            f"only {bound}/24 bound after replica kill; "
            f"events={cluster.supervisor.events}"
        )
        r1 = cluster.schedulers[1]
        assert r1.restarts == 1, cluster.supervisor.events
        assert ("restarted", "scheduler-r1", r1.pid) in (
            cluster.supervisor.events
        )
        pids = [c.pid for c in cluster.supervisor.children]
        # per-child resource sampling delivered evidence while alive
        stats = cluster.supervisor.child_stats()
        assert stats["apiserver"].get("peak_rss_bytes", 0) > 0
    # SIGTERM cascade: every child reaped, none orphaned
    for child in cluster.supervisor.children:
        assert not child.alive(), f"{child.name} survived the cascade"
    for pid in pids:
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)
    # and the graceful close left a recoverable WAL: fsck exit 0
    from kubetpu.cli import main as cli_main

    assert cli_main(["store", "fsck", "--dir", wal_dir]) == 0


# ---------------------------------------------------------------------------
# the watch fan-out load: in process, and as `kubetpu up` driver processes
# ---------------------------------------------------------------------------

def test_watch_fanout_watchers_share_each_event_and_stop():
    """Every watcher drains the same events: with more than one, the
    store's serialize-once body ring answers all but the first from its
    cache. ``stop`` ends every watcher thread."""
    from kubetpu.api.wrappers import make_pod
    from kubetpu.apiserver import APIServer, RemoteStore
    from kubetpu.launch import WatchFanout

    srv = APIServer().start()
    fanout = WatchFanout(srv.url, "binary", 3)
    try:
        remote = RemoteStore(srv.url)
        deadline = time.monotonic() + 30
        hits = 0
        j = 0
        while time.monotonic() < deadline:
            remote.create("pods", f"ns/p{j}", make_pod(f"p{j}",
                                                       namespace="ns"))
            j += 1
            time.sleep(0.1)
            hits = srv.store.body_cache_stats()["binary"][0]
            if hits >= 2:
                break
        assert hits >= 2, srv.store.body_cache_stats()
    finally:
        fanout.stop()
        srv.close()
    assert not any(t.is_alive() for t in fanout._threads)


def test_up_watch_fanout_starts_watch_driver_processes():
    """``kubetpu up --watch-fanout 3 --fanout-procs 2`` spreads three
    watchers over two ``kubetpu watch-driver`` children, each one
    bannered ready beside the apiserver and the scheduler, and a SIGTERM
    takes the whole topology down cleanly."""
    import signal
    import subprocess

    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetpu", "up", "--replicas", "1",
         "--watch-fanout", "3", "--fanout-procs", "2"],
        cwd=REPO, env={**os.environ, **CPU_ENV}, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    lines = []
    try:
        for line in proc.stdout:
            lines.append(line)
            if line.startswith("kubetpu up:"):
                break
        out = "".join(lines)
        assert "4 process(es) ready" in out, out
        drivers = [ln.split() for ln in lines
                   if ln.strip().startswith("watch-driver-")]
        assert [d[0] for d in drivers] == ["watch-driver-0",
                                           "watch-driver-1"], out
        pids = [int(d[2]) for d in drivers]
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
        for pid in pids:
            with pytest.raises(ProcessLookupError):
                os.kill(pid, 0)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
