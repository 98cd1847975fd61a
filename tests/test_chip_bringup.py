"""Tier-1 (CPU) checks of what the chip bring-up rests on: where the compile
cache goes, that nothing falls back to the CPU under a device metric's name,
that records and banners say which device they ran on, and that the one
installation's jax raises no deprecation on the mesh path. The chip itself
is ``chip_smoke.py``'s job; here only its refusal is checked."""

import json
import os
import subprocess
import sys
import warnings

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code: str, **env) -> subprocess.CompletedProcess:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env)
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=full,
        capture_output=True, text=True, timeout=120,
    )


# ---------------------------------------------------------------------------
# compile cache placed from outside
# ---------------------------------------------------------------------------

def test_compile_cache_env_is_honoured_and_nothing_else_is_set(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program caches there — a
    compiled program really lands in it — and never names another dir."""
    placed = str(tmp_path / "placed")
    p = _python(
        "import kubetpu, jax, jax.numpy as jnp\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n",
        JAX_COMPILATION_CACHE_DIR=placed,
        JAX_ENABLE_COMPILATION_CACHE="true",
    )
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == placed
    assert os.listdir(placed), "nothing was cached where the env said"


def test_compile_cache_defaults_to_the_checkout_and_cli_stays_off_jax():
    """Unset, the cache goes to <checkout>/.jax_cache (a fixed path, through
    jax.config — assigning the env var after ``import jax`` does nothing),
    small programs are stored too, and importing the CLI — what the parent
    of ``kubetpu up`` and the apiserver child do — initialises no backend."""
    p = _python(
        "import kubetpu.cli, jax, json\n"
        "from jax._src import xla_bridge\n"
        "print(json.dumps([jax.config.jax_compilation_cache_dir,\n"
        "    jax.config.jax_persistent_cache_min_compile_time_secs,\n"
        "    len(xla_bridge._backends)]))\n"
    )
    assert p.returncode == 0, p.stderr
    cache_dir, min_secs, backends = json.loads(p.stdout)
    assert cache_dir == os.path.join(REPO, ".jax_cache")
    assert min_secs == 0.0
    assert backends == 0


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout == "", "no result may be printed without a TPU"
    reason = [ln for ln in p.stderr.splitlines() if "needs a TPU" in ln]
    assert reason and "cpu" in reason[0], p.stderr


# ---------------------------------------------------------------------------
# device stamps
# ---------------------------------------------------------------------------

def test_workload_result_json_carries_the_device_stamp():
    import jax

    from kubetpu.perf.runner import WorkloadResult

    base = dict(
        case_name="c", workload_name="w", threshold=None, measure_pods=1,
        scheduled=1, duration_s=1.0, throughput=1.0, vs_threshold=None,
        attempts=1, cycles=1,
    )
    doc = WorkloadResult(**base).to_json()
    assert doc["platform"] == "cpu"
    assert doc["device_kind"] == jax.devices()[0].device_kind
    assert doc["devices"] == len(jax.devices())
    # the stamp is this process's, as kubetpu.device_stamp() reports it
    from kubetpu import device_stamp

    assert {k: doc[k] for k in ("platform", "device_kind", "devices")} \
        == device_stamp()


# ---------------------------------------------------------------------------
# failures that used to be swallowed
# ---------------------------------------------------------------------------

def _client():
    class Client:
        def bind(self, pod, node_name):
            pass

        def patch_status(self, pod, reason, message=""):
            pass

    return Client()


def test_failed_collective_probe_raises(monkeypatch):
    from kubetpu.parallel import mesh as M
    from kubetpu.sched import Scheduler

    def refuse(_mesh):
        raise RuntimeError("collective refused")

    monkeypatch.setattr(M, "measure_collective_wall", refuse)
    with pytest.raises(RuntimeError, match="collective refused"):
        Scheduler(_client(), mesh="auto", dispatcher_workers=0)


def test_explain_kernel_failure_is_logged_once_and_counted(monkeypatch):
    """The cycle stays safe — every pod still binds — but the refusal is
    logged with its exception (once) and counted on /metrics."""
    from kubetpu import klog
    from kubetpu.api.wrappers import make_node, make_pod
    from kubetpu.sched import Scheduler, flightrecorder

    def refuse(*_a, **_k):
        raise RuntimeError("backend refused the explain kernel")

    monkeypatch.setattr(flightrecorder, "_explain_kernel", refuse)
    lines: list[str] = []
    klog.set_sink(lines.append)
    try:
        s = Scheduler(_client(), dispatcher_workers=0, max_batch=4)
        s.on_node_add(make_node("n0", cpu_milli=64000, pods=110))
        for i in range(16):
            s.on_pod_add(make_pod(f"p{i}", cpu_milli=10))
        assert s.run_until_idle() == 16
        s.close()
    finally:
        klog.set_sink(None)
    logged = [ln for ln in lines if "explain kernel failed" in ln]
    assert len(logged) == 1 and "backend refused" in logged[0]
    fr = s.flight_recorder
    assert fr.breakdown_failures == 3 and not fr._breakdown_ok
    assert "scheduler_explain_kernel_failures_total 3" in s.metrics_text()
    assert s.metrics.prom.snapshot()["explain_kernel_failures"] == 3


# ---------------------------------------------------------------------------
# code for the one installation there is
# ---------------------------------------------------------------------------

def test_mesh_scheduler_constructs_without_deprecation_warnings():
    """``Scheduler(mesh=…)`` builds its routed shard_map scatter at
    construction; on the virtual 8-device mesh that must not touch any API
    the installed jax has deprecated."""
    from kubetpu.sched import Scheduler

    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        s = Scheduler(_client(), mesh="auto", dispatcher_workers=0)
    assert s.mesh_shape == (8,)
    assert isinstance(s._collective_wall_s, float)
    s.close()


# ---------------------------------------------------------------------------
# built from what git would commit
# ---------------------------------------------------------------------------

def test_native_artifact_is_keyed_by_source_hash():
    import hashlib

    from kubetpu import native

    src = os.path.join(os.path.dirname(native.__file__), "memstore_core.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = native._so_path("_kubetpu_store", src)
    assert os.path.basename(so).split(".")[-2] == digest
    # touching the file (a checkout, a copy) must not change the key
    os.utime(src)
    assert native._so_path("_kubetpu_store", src) == so
    if native.store_core() is not None:
        assert native.build_status() in ("cached", "built")
        assert os.path.exists(so)
    else:
        assert native.build_status().startswith(("failed", "disabled"))


def test_prewarm_against_an_empty_cluster_compiles_nothing():
    """Every program is shaped by the node axis, so before the first node
    arrives there is nothing to warm (on the chip the throwaway ladder cost
    minutes and ``kubetpu up --prewarm`` missed its readiness timeout)."""
    from kubetpu.api.wrappers import make_node
    from kubetpu.sched import Scheduler

    s = Scheduler(_client(), dispatcher_workers=0, max_batch=8)
    s.prewarm()
    assert s._prev_nt is None, "warmed (encoded) against no nodes"
    s.on_node_add(make_node("n0", cpu_milli=1234, pods=7))
    s.prewarm()
    assert s._prev_nt is not None
    s.close()
