"""Versioned config decoding (kubescheduler.config.k8s.io/v1), the tracing
subsystem, lease-based leader election, and the ``python -m kubetpu`` CLI.

Reference semantics: staging/src/k8s.io/kube-scheduler/config/v1/types.go:44
(KubeSchedulerConfiguration), pkg/scheduler/apis/config/v1/default_plugins.go:79
(mergePlugins: defaults + disabled + enabled), k8s.io/utils/trace
(LogIfLong), client-go tools/leaderelection (tryAcquireOrRenew).
"""

import json

import pytest

pytest.importorskip("jax")

from kubetpu import names as N
from kubetpu.framework import config as C
from kubetpu.framework.configload import (
    ConfigError,
    decode_config,
    load_config,
)

HEADER = {
    "apiVersion": "kubescheduler.config.k8s.io/v1",
    "kind": "KubeSchedulerConfiguration",
}


# ------------------------------------------------------------- config decode

def test_empty_config_yields_defaults():
    cfg = decode_config(dict(HEADER))
    assert len(cfg.profiles) == 1
    assert cfg.profiles[0].name == "default-scheduler"
    assert cfg.profiles[0].filters == C.DEFAULT_FILTERS
    assert cfg.parallelism == 16


def test_wrong_api_version_and_kind_fail_loudly():
    with pytest.raises(ConfigError, match="apiVersion"):
        decode_config({"apiVersion": "v1", "kind": "KubeSchedulerConfiguration"})
    with pytest.raises(ConfigError, match="kind"):
        decode_config({"apiVersion": HEADER["apiVersion"], "kind": "Pod"})


def test_merge_semantics_disable_star_then_enable():
    """mergePlugins: disabled '*' clears the default set; enabled appends."""
    cfg = decode_config({
        **HEADER,
        "profiles": [{
            "schedulerName": "lean",
            "plugins": {
                "filter": {
                    "disabled": [{"name": "*"}],
                    "enabled": [{"name": N.NODE_RESOURCES_FIT}],
                },
                "score": {
                    "disabled": [{"name": N.IMAGE_LOCALITY}],
                    "enabled": [{"name": N.NODE_RESOURCES_FIT, "weight": 5}],
                },
            },
        }],
    })
    prof = cfg.profile("lean")
    assert prof.filters.names() == [N.NODE_RESOURCES_FIT]
    assert N.IMAGE_LOCALITY not in prof.scores.names()
    # re-enabling replaces the default entry, new weight wins
    assert prof.scores.weight(N.NODE_RESOURCES_FIT) == 5


def test_plugin_args_decode():
    cfg = decode_config({
        **HEADER,
        "profiles": [{
            "schedulerName": "tuned",
            "pluginConfig": [
                {"name": N.NODE_RESOURCES_FIT, "args": {
                    "scoringStrategy": {
                        "type": "RequestedToCapacityRatio",
                        "resources": [{"name": "cpu", "weight": 3}],
                        "requestedToCapacityRatio": {
                            "shape": [
                                {"utilization": 0, "score": 0},
                                {"utilization": 100, "score": 10},
                            ],
                        },
                    },
                }},
                {"name": N.INTER_POD_AFFINITY,
                 "args": {"hardPodAffinityWeight": 7}},
                {"name": N.POD_TOPOLOGY_SPREAD, "args": {
                    "defaultingType": "List",
                    "defaultConstraints": [{
                        "maxSkew": 2,
                        "topologyKey": "topology.kubernetes.io/zone",
                        "whenUnsatisfiable": "ScheduleAnyway",
                    }],
                }},
            ],
        }],
    })
    prof = cfg.profile("tuned")
    assert prof.scoring_strategy.type == C.REQUESTED_TO_CAPACITY_RATIO
    assert prof.scoring_strategy.resources == (("cpu", 3),)
    assert prof.scoring_strategy.shape == ((0, 0), (100, 10))
    assert prof.hard_pod_affinity_weight == 7
    assert prof.default_spread_constraints[0].max_skew == 2


def test_multipoint_expands_across_interfaces():
    cfg = decode_config({
        **HEADER,
        "profiles": [{
            "schedulerName": "mp",
            "plugins": {
                "multiPoint": {
                    "disabled": [{"name": "*"}],
                    "enabled": [
                        {"name": N.NODE_RESOURCES_FIT, "weight": 2},
                        {"name": N.VOLUME_BINDING},
                    ],
                },
            },
        }],
    })
    prof = cfg.profile("mp")
    assert prof.filters.names() == [N.NODE_RESOURCES_FIT, N.VOLUME_BINDING]
    assert prof.scores.names() == [N.NODE_RESOURCES_FIT]
    assert prof.lifecycle.names() == [N.VOLUME_BINDING]


def test_invalid_resulting_profile_fails_at_decode():
    with pytest.raises(ConfigError, match="unknown plugin"):
        decode_config({
            **HEADER,
            "profiles": [{
                "schedulerName": "bad",
                "plugins": {"filter": {"enabled": [{"name": "NoSuchPlugin"}]}},
            }],
        })


def test_unknown_extension_point_fails():
    with pytest.raises(ConfigError, match="unknown extension point"):
        decode_config({
            **HEADER,
            "profiles": [{"plugins": {"frobnicate": {}}}],
        })


def test_duplicate_profile_names_fail():
    with pytest.raises(ConfigError, match="duplicate"):
        decode_config({
            **HEADER,
            "profiles": [{"schedulerName": "x"}, {"schedulerName": "x"}],
        })


def test_extenders_and_durations_decode():
    cfg = decode_config({
        **HEADER,
        "podInitialBackoffSeconds": "500ms",
        "podMaxBackoffSeconds": 8,
        "extenders": [{
            "urlPrefix": "http://127.0.0.1:9999/ext",
            "filterVerb": "filter",
            "prioritizeVerb": "prioritize",
            "bindVerb": "bind",
            "weight": 2,
            "httpTimeout": "2s",
            "nodeCacheCapable": True,
            "ignorable": True,
            "managedResources": [{"name": "foo.com/bar"}],
        }],
    })
    assert cfg.pod_initial_backoff_seconds == 0.5
    assert cfg.pod_max_backoff_seconds == 8.0
    e = cfg.extenders[0]
    assert e.filter_verb == "filter" and e.bind_verb == "bind"
    assert e.http_timeout_s == 2.0 and e.weight == 2
    assert e.managed_resources == ("foo.com/bar",)


def test_load_config_yaml_file(tmp_path):
    p = tmp_path / "cfg.yaml"
    p.write_text(
        "apiVersion: kubescheduler.config.k8s.io/v1\n"
        "kind: KubeSchedulerConfiguration\n"
        "profiles:\n"
        "- schedulerName: from-yaml\n"
    )
    cfg = load_config(str(p))
    assert cfg.profiles[0].name == "from-yaml"


# ------------------------------------------------------------------ tracing

def test_tracer_spans_nest_and_record():
    from kubetpu.tracing import Tracer

    t = [0.0]
    tr = Tracer(clock=lambda: t[0], threshold_s=10.0)
    with tr.span("cycle", pods=4) as root:
        t[0] += 0.01
        with tr.span("encode"):
            t[0] += 0.02
        with tr.span("assign"):
            t[0] += 0.03
    spans = tr.recent()
    by_name = {s.name: s for s in spans}
    assert by_name["cycle"].parent_id is None
    assert by_name["encode"].parent_id == by_name["cycle"].span_id
    assert abs(by_name["assign"].duration_s - 0.03) < 1e-9
    # beside what the caller gave, the thread's CPU time inside the span
    assert by_name["cycle"].attrs == {
        "pods": 4, "cpu_s": by_name["cycle"].attrs["cpu_s"]}
    assert root is not None and root.duration_s >= 0.06


def test_tracer_logs_long_top_level_spans_only():
    from kubetpu.tracing import Tracer

    t = [0.0]
    logged = []
    tr = Tracer(clock=lambda: t[0], threshold_s=0.1, log=logged.append)
    with tr.span("fast"):
        t[0] += 0.05
    assert logged == []
    with tr.span("slow", profile="default"):
        with tr.span("step-a"):
            t[0] += 0.15
    assert len(logged) == 1
    assert "slow" in logged[0] and "step-a" in logged[0]


def test_tracer_disabled_is_free():
    from kubetpu.tracing import Tracer

    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        assert sp is None
    assert tr.recent() == []


def test_scheduler_cycle_emits_spans():
    from kubetpu.api.wrappers import make_node, make_pod

    from .test_scheduler import FakeClient, make_sched

    s, _ = make_sched(FakeClient())
    s.on_node_add(make_node("n0", cpu_milli=4000))
    s.on_pod_add(make_pod("p0", cpu_milli=100))
    s.schedule_batch()
    names = [sp.name for sp in s.tracer.recent()]
    for expected in ("snapshot", "encode", "assign", "scheduling-cycle"):
        assert expected in names


# ---------------------------------------------------------- leader election

def _elector(client, ident, clock, **kw):
    from kubetpu.sched.leaderelection import LeaderElector

    return LeaderElector(
        client=client, identity=ident, lease_duration_s=15.0,
        renew_deadline_s=10.0, clock=lambda: clock[0], **kw,
    )


def test_leader_acquire_renew_and_follower_waits():
    from kubetpu.sched.leaderelection import InMemoryLeaseClient

    clock = [100.0]
    client = InMemoryLeaseClient()
    events = []
    a = _elector(client, "a", clock,
                 on_started_leading=lambda: events.append("a-start"))
    b = _elector(client, "b", clock,
                 on_new_leader=lambda who: events.append(f"b-sees-{who}"))
    assert a.tick() is True
    assert b.tick() is False          # lease held and fresh
    assert events == ["a-start", "b-sees-a"]
    clock[0] += 5
    assert a.tick() is True           # renew
    clock[0] += 14                    # a renewed at 105; b observed at 105
    assert b.tick() is False          # 119 - 105 < 15: not yet expired


def test_failover_after_lease_expiry():
    from kubetpu.sched.leaderelection import InMemoryLeaseClient

    clock = [0.0]
    client = InMemoryLeaseClient()
    stopped = []
    a = _elector(client, "a", clock,
                 on_stopped_leading=lambda: stopped.append("a"))
    b = _elector(client, "b", clock)
    assert a.tick()
    assert not b.tick()               # b first observes a's record at t=0
    clock[0] += 16                    # past lease duration with no renewal
    assert b.tick() is True           # b usurps
    rec, _ = client.get_lease("kube-system", "kube-scheduler")
    assert rec.holder_identity == "b"
    assert rec.leader_transitions == 1
    # a's next tick notices it lost (renew deadline blown + CAS sees b)
    assert a.tick() is False
    assert stopped == ["a"]


def test_release_hands_off_immediately():
    from kubetpu.sched.leaderelection import InMemoryLeaseClient

    clock = [0.0]
    client = InMemoryLeaseClient()
    a = _elector(client, "a", clock)
    b = _elector(client, "b", clock)
    assert a.tick()
    a.release()
    assert a.is_leader is False
    assert b.tick() is True           # no lease-duration wait after release


# ----------------------------------------------------------------- CLI

def test_cli_check_config(tmp_path, capsys):
    from kubetpu.cli import main

    good = tmp_path / "good.yaml"
    good.write_text(
        "apiVersion: kubescheduler.config.k8s.io/v1\n"
        "kind: KubeSchedulerConfiguration\n"
    )
    assert main(["check-config", str(good)]) == 0
    assert "ok: 1 profile(s)" in capsys.readouterr().out
    bad = tmp_path / "bad.yaml"
    bad.write_text("apiVersion: nope\nkind: KubeSchedulerConfiguration\n")
    assert main(["check-config", str(bad)]) == 1
    assert "invalid" in capsys.readouterr().err


def test_cli_version(capsys):
    from kubetpu.cli import main

    assert main(["version"]) == 0
    assert "kubetpu" in capsys.readouterr().out


def test_serve_endpoints_healthz_configz(tmp_path):
    """The serve path's backend surface: /healthz, /configz, and an
    extender /filter round-trip (in-process, ExtenderServer)."""
    import urllib.request

    from kubetpu.bridge.server import ExtenderBackend, ExtenderServer
    from kubetpu.cli import _config_to_dict

    cfg = decode_config(dict(HEADER))
    backend = ExtenderBackend(profile=cfg.profile())
    backend.configz_source = lambda: _config_to_dict(cfg)
    srv = ExtenderServer(backend).start()
    try:
        with urllib.request.urlopen(f"{srv.url}/healthz", timeout=5) as r:
            assert json.load(r)["ok"] is True
        with urllib.request.urlopen(f"{srv.url}/configz", timeout=5) as r:
            body = json.load(r)
        assert body["parallelism"] == 16
        assert body["profiles"][0]["name"] == "default-scheduler"
    finally:
        srv.close()


# ---------------------------------------------- review-fix regression tests

def test_specific_point_config_wins_over_multipoint_any_order():
    """default_plugins.go: a specific extension point's config beats the
    multiPoint expansion regardless of key order in the file."""
    for order in (("score", "multiPoint"), ("multiPoint", "score")):
        plugins = {}
        for key in order:
            if key == "score":
                plugins["score"] = {
                    "enabled": [{"name": N.NODE_RESOURCES_FIT, "weight": 5}]
                }
            else:
                plugins["multiPoint"] = {
                    "enabled": [{"name": N.NODE_RESOURCES_FIT}]
                }
        cfg = decode_config({
            **HEADER,
            "profiles": [{"schedulerName": "p", "plugins": plugins}],
        })
        assert cfg.profile("p").scores.weight(N.NODE_RESOURCES_FIT) == 5, order


def test_malformed_yaml_raises_config_error(tmp_path):
    p = tmp_path / "broken.yaml"
    p.write_text("a: [unclosed\n")
    with pytest.raises(ConfigError):
        load_config(str(p))
    from kubetpu.cli import main

    assert main(["check-config", str(p)]) == 1


def test_null_plugin_config_entry_raises_config_error():
    with pytest.raises(ConfigError, match="pluginConfig"):
        decode_config({
            **HEADER,
            "profiles": [{"schedulerName": "p", "pluginConfig": [None]}],
        })


def test_leader_tick_throttles_renew_api_traffic():
    from kubetpu.sched.leaderelection import InMemoryLeaseClient

    clock = [0.0]
    client = InMemoryLeaseClient()
    calls = []
    real_update = client.update_lease
    client.update_lease = lambda *a: (calls.append(1), real_update(*a))[1]
    a = _elector(client, "a", clock)
    assert a.tick()
    n0 = len(calls)
    for _ in range(100):          # hot loop, no time passing
        assert a.tick()
    assert len(calls) == n0       # no extra CAS writes within retry period
    clock[0] += 3                 # past retry_period_s (2s)
    assert a.tick()
    assert len(calls) == n0 + 1   # exactly one renewal


def test_go_compound_durations_and_malformed_structure():
    """time.Duration.String() compound forms load; structural garbage
    surfaces as ConfigError, never a raw traceback."""
    cfg = decode_config({
        **HEADER,
        "podInitialBackoffSeconds": "1m0s",
        "podMaxBackoffSeconds": "1m30s",
    })
    assert cfg.pod_initial_backoff_seconds == 60.0
    assert cfg.pod_max_backoff_seconds == 90.0
    with pytest.raises(ConfigError):
        decode_config({**HEADER, "profiles": ["not-a-mapping"]})
    with pytest.raises(ConfigError):
        decode_config({**HEADER, "extenders": [
            {"urlPrefix": "http://x", "weight": "abc"},
        ]})
    with pytest.raises(ConfigError):
        decode_config({**HEADER, "podMaxBackoffSeconds": "10 parsecs"})


def test_inmemory_lease_cas_is_atomic_under_threads():
    """Two electors racing from threads: exactly one may hold the lease."""
    import threading

    from kubetpu.sched.leaderelection import (
        InMemoryLeaseClient,
        LeaderElector,
    )

    for _ in range(20):
        client = InMemoryLeaseClient()
        barrier = threading.Barrier(2)
        winners = []

        def race(ident):
            e = LeaderElector(client=client, identity=ident)
            barrier.wait()
            if e.tick():
                winners.append(ident)

        ts = [threading.Thread(target=race, args=(i,)) for i in ("a", "b")]
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        assert len(winners) == 1, winners


def test_kubectl_printers_selectors_and_output_modes():
    """kubectl get: table printers per kind, -l/-field selectors applied
    SERVER-side, -o json/yaml (the kubectl printers registry shape)."""
    import dataclasses
    import os
    import subprocess
    import sys

    import jax  # noqa: F401

    from kubetpu.api.wrappers import make_node, make_pod
    from kubetpu.apiserver import APIServer

    srv = APIServer().start()
    try:
        st = srv.store
        st.create("nodes", "n0", make_node("n0"))
        st.create("pods", "default/a", dataclasses.replace(
            make_pod("a", node_name="n0", labels={"app": "web"}),
            phase="Running"))
        st.create("pods", "default/b", make_pod("b", labels={"app": "db"}))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

        def run(*cmd):
            out = subprocess.run(
                [sys.executable, "-m", "kubetpu", *cmd],
                env=env, capture_output=True, text=True, timeout=60,
                cwd=repo,
            )
            assert out.returncode == 0, out.stderr
            return out.stdout

        table = run("get", "pods", "--server", srv.url)
        assert "NAME" in table and "STATUS" in table and "NODE" in table
        assert "Running" in table and "<pending>" in table
        filtered = run("get", "pods", "--server", srv.url, "-l", "app=web")
        assert "default/a" in filtered and "default/b" not in filtered
        by_field = run("get", "pods", "--server", srv.url,
                       "--field-selector", "spec.nodeName=n0")
        assert "default/a" in by_field and "default/b" not in by_field
        as_json = json.loads(run("get", "pods", "--server", srv.url,
                                 "-o", "json", "-l", "app=db"))
        assert [o["name"] for o in as_json] == ["b"]
        nodes_table = run("get", "nodes", "--server", srv.url)
        assert "Ready" in nodes_table and "CPU(m)" in nodes_table
    finally:
        srv.close()
