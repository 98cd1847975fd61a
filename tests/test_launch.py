"""kubetpu.launch: the process supervisor + multi-process control plane.

Tier-1 contract (ISSUE 13): the readiness-banner format round-trips and
rejects garbage; the restart-policy grammar parses; a child that dies
before its banner fails LOUDLY with its captured log tail; the
``on-failure`` policy respawns a SIGKILLed child (and ``never`` gives up);
and — the integration spine — a real 2-replica hash cluster over a
persistent apiserver survives a replica SIGKILL mid-run (the respawned
process re-federates and every pod binds), the SIGTERM cascade leaves no
orphan processes, ``store fsck`` passes on the WAL dir afterwards, and
``run_workload_multiprocess`` joins on store-verified binding parity with
per-child resource stats in the record.
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
# the mp perf runner: parity-joined measurement on a tiny workload
# ---------------------------------------------------------------------------

def _tiny_case():
    from kubetpu.perf import workloads as W

    return W.TestCase(
        name="MpSmoke",
        ops=(
            W.CreateNodesOp(count=4),
            W.CreatePodsOp("initPods"),
            W.CreatePodsOp("measurePods", collect_metrics=True),
        ),
        workloads=(
            W.Workload("tiny", {"initPods": 8, "measurePods": 24}),
        ),
    )


def test_run_workload_multiprocess_joins_on_parity():
    from kubetpu.perf.runner import run_workload_multiprocess

    case = _tiny_case()
    r = run_workload_multiprocess(
        case, case.workloads[0], replicas=2, partition="race",
        max_batch=32, timeout_s=120.0, child_env=CPU_ENV,
    )
    assert r.scheduled == 24 and r.measure_pods == 24
    assert r.binding_parity == 24        # join-verified exactly-once
    assert r.replicas == 2 and r.partition == "race"
    assert r.n_processes == 3            # apiserver + 2 schedulers
    assert r.restarts == 0
    assert r.throughput > 0
    # CI/bench hygiene: per-child peak RSS + cpu_seconds in the record
    doc = r.to_json()
    assert doc["n_processes"] == 3
    # the device stamp is the scheduler CHILDREN's (the parent holds none)
    assert doc["platform"] == "cpu" and doc["devices"] >= 1
    stats = doc["child_stats"]
    assert set(stats) == {"apiserver", "scheduler-r0", "scheduler-r1"}
    for child in stats.values():
        assert child.get("peak_rss_bytes", 0) > 0
        assert child.get("cpu_seconds", 0) > 0
    # the API-plane evidence was scraped over HTTP, not read in-process
    assert r.rpcs_per_scheduled_pod is not None
    assert r.wire_codec == "binary"


def test_run_workload_multiprocess_rejects_unknown_ops():
    from kubetpu.perf import workloads as W
    from kubetpu.perf.runner import run_workload_multiprocess

    case = W.TestCase(
        name="MpUnsupported",
        ops=(W.ChurnOp(interval_ms=100, template=W.pod_default),),
        workloads=(W.Workload("w", {}),),
    )
    with pytest.raises(NotImplementedError):
        run_workload_multiprocess(case, case.workloads[0])


# ---------------------------------------------------------------------------
# trace replay against the mp federation (ROADMAP 5b): paced arrivals,
# forced lease handover, store-observed admission latency
# ---------------------------------------------------------------------------

def test_run_trace_multiprocess_lease_handover():
    from kubetpu.perf.runner import run_trace_multiprocess
    from kubetpu.perf.workloads import TRACE_PROFILES

    prof = TRACE_PROFILES["diurnal-burst"].scaled(
        "mp-smoke", nodes=6, duration_s=4.0, base_rate=3.0,
        peak_rate=6.0, bursts=1, burst_pods=4, slo_budget_ms=60000.0,
    )
    r = run_trace_multiprocess(
        prof, replicas=2, partition="lease", max_batch=32,
        timeout_s=180.0, handover_at=0.5, child_env=CPU_ENV,
    )
    created = r.trace_stats["created"]
    assert created > 0
    # every live trace pod bound, parity read off the store
    assert r.trace_stats["unbound"] == 0
    assert r.binding_parity == created
    assert r.scheduled == created
    # the forced handover actually happened: kill recorded mid-trace,
    # the supervisor respawned the victim, recovery wall measured
    assert r.trace_stats["handover"] is True
    assert r.trace_stats["handover_at_s"] is not None
    assert r.restarts >= 1
    assert r.recovery_s is not None and r.recovery_s > 0
    # the SLO record shape: p99 spans the handover, judged vs budget
    assert r.admission_p99_ms is not None and r.admission_p99_ms > 0
    assert r.slo_budget_ms == 60000.0
    assert r.slo_ok is True and not r.truncated
    assert r.partition == "lease" and r.replicas == 2


def test_run_trace_multiprocess_rejects_gang_profiles():
    from kubetpu.perf.runner import run_trace_multiprocess
    from kubetpu.perf.workloads import TRACE_PROFILES

    # multitenant emits create_group events — no REST kind, mp replay
    # must refuse loudly before spawning anything
    with pytest.raises(NotImplementedError):
        run_trace_multiprocess(
            TRACE_PROFILES["multitenant"], replicas=2, handover_at=None,
        )
