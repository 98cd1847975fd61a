"""chip_smoke.py — the quickest proof that the served scheduling path starts,
compiles and answers RIGHT on the TPU.

    python3 chip_smoke.py

No arguments. It needs a TPU: with none it says in one line what JAX found
and exits non-zero, printing no result. Every stdout line is one JSON object
carrying ``platform``, ``device_kind`` and ``devices``; every stage's line
carries set-up seconds (process start, chip acquisition, trace + lower +
compile) apart from run seconds. Any pods/s here is a SMOKE number
(``smoke_pods_per_s``) — one run, compile-dominated, not a benchmark. The
last line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

One process owns a chip at a time, so this file itself never initialises a
JAX backend: each stage is a child process (``chip_smoke.py <stage>``), run
in turn, all sharing the persistent compile cache that ``import kubetpu``
places (JAX_COMPILATION_CACHE_DIR when set, else ``<checkout>/.jax_cache``).

Stages, each at the size upstream publishes (5000 nodes):

  probe    what JAX holds; which store core serves; compile-cache entries
  up       ``python -m kubetpu up --replicas 1 --prewarm`` as OS processes:
           5000 nodes + 3000 pods over REST, every pod read back bound
           exactly once, SIGTERM cascade, clean exit; only the scheduler
           child may hold the chip
  parity   device greedy engine vs ``tests/oracle.py``, pod for pod, on a
           seeded 5000-node cluster: fit+balanced, spread, inter-pod
           affinity, and a nominated-fit batch with GiB-scale memory — where
           the TPU's emulated 64-bit arithmetic could change an answer
  compile  every jitted device program lowered, compiled and executed once
           at 5000-node width; then the SAME stage again in a new process,
           showing the warm set-up time against the cold one
  mesh     >1 device only: SchedulingBasic 15000Nodes through the
           scheduler loop in one process (``perf.run_workload``) with mesh
           on — bound map equal to the single-device run's, resident block
           spanning every chip ("not run (1 device)" otherwise)

The served path at 5000 nodes (``kubetpu scheduler`` against its
apiserver, every pod bound once, the store agreeing pod for pod, parity
with the oracle) is what every benchmark cell's ``correct`` holds, so no
stage here repeats it.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
#: the contract's limit is 1200 s for the whole script, compilation included
TOTAL_BUDGET_S = 1150.0
NODES = 5000
UP_PODS = 3000
SEED = 20260926

#: set by each child before its first line (the parent hands the probe's
#: answer to the one child that must not ask JAX itself)
DEVICE: dict = {}


def emit(stage: str, **fields) -> None:
    print(json.dumps({"stage": stage, **fields, **DEVICE}), flush=True)


# ---------------------------------------------------------------------------
# parent: runs the stages as children, never touches a backend
# ---------------------------------------------------------------------------

def _run_child(stage: str, timeout_s: float, *args: str) -> tuple[int, list]:
    """Run ``chip_smoke.py <stage>`` to its end, passing its stdout on line by
    line; returns (exit code, the JSON objects it printed). Its process
    group is killed at the time limit and, whatever happens, before this
    returns — nothing a stage starts outlives it."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), stage, *args],
        cwd=ROOT, stdout=subprocess.PIPE, text=True, start_new_session=True,
    )

    def kill() -> None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(timeout_s, kill)
    timer.start()
    records = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            try:
                records.append(json.loads(line))
            except ValueError:
                pass
        return proc.wait(), records
    finally:
        timer.cancel()
        kill()


def _cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def _judge(label: str, rc: int, recs: list, failed: list) -> None:
    """A stage passed when it exited 0 and every line it printed says ok."""
    stage = label.split(":")[0]
    verdicts = [r.get("ok") for r in recs if r.get("stage") == stage]
    if rc != 0 or not verdicts or not all(verdicts):
        failed.append(label)
        emit(stage, ok=False, exit_code=rc,
             error="stage failed (its own lines and stderr say where)")


def main() -> int:
    t_start = time.monotonic()
    rc, recs = _run_child("probe", 180.0)
    if rc != 0 or not recs:
        if rc != 3:     # 3: the probe itself said what JAX found instead
            print(f"chip_smoke: not run (probe exit {rc}): it needs a TPU "
                  "and this checkout's kubetpu package", file=sys.stderr)
        return 2
    probe = recs[-1]
    DEVICE.update({k: probe[k] for k in ("platform", "device_kind", "devices")})
    failed = [] if probe["ok"] else ["probe"]
    cold_warm: list = []
    # (stage, its arguments, the share of what is left of the budget it may
    # take at most)
    plan = [("up", (json.dumps(DEVICE),), 0.35), ("parity", (), 0.6),
            ("compile", (), 0.8), ("compile", ("warm",), 1.0)]
    for stage, args, share in plan:
        left = TOTAL_BUDGET_S - (time.monotonic() - t_start)
        rc, recs = _run_child(stage, max(left * share, 30.0), *args)
        _judge(stage + (":warm" if "warm" in args else ""), rc, recs, failed)
        if stage == "compile":
            cold_warm += [r["setup_s"] for r in recs if r.get("summary")]
    if DEVICE["devices"] > 1:
        # four chips are the builder's check, outside the one-chip contract
        # and its time limit
        _judge("mesh", *_run_child("mesh", 900.0), failed)
    else:
        emit("mesh", summary=True, ok=True, ran=False,
             note="not run (1 device)", setup_s=0.0, run_s=0.0)
    emit(
        "cache", ok=True, cache_dir=probe["cache_dir"],
        entries_before=probe["cache_entries"],
        entries_after=_cache_entries(probe["cache_dir"]),
        compile_stage_setup_s_cold=cold_warm[0] if cold_warm else None,
        compile_stage_setup_s_warm=cold_warm[1] if len(cold_warm) > 1 else None,
        warm_below_cold=len(cold_warm) > 1 and cold_warm[1] < cold_warm[0],
        total_s=round(time.monotonic() - t_start, 1),
    )
    if failed:
        emit("result", ok=False, failed=failed)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": DEVICE["platform"], "kind": DEVICE["device_kind"],
        "count": DEVICE["devices"],
    }}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

class CompileMeter:
    """Seconds spent tracing, lowering and compiling, and how the persistent
    cache answered — from jax.monitoring, so the program under test is not
    touched. jax reports each event on the thread that compiled, so the
    counts are kept per thread: ``mark``/``since`` bracket what the CALLING
    thread compiled, ``total`` is the whole process."""

    def __init__(self) -> None:
        import jax

        # thread id -> [seconds, programs, cache hits, cache misses]
        self._by_thread: dict[int, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _mine(self) -> list:
        return self._by_thread.setdefault(threading.get_ident(), [0.0, 0, 0, 0])

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self._mine()[0] += secs
            if event.endswith("backend_compile_duration"):
                self._mine()[1] += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._mine()[2] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._mine()[3] += 1

    def mark(self) -> tuple:
        return tuple(self._mine())

    @staticmethod
    def _fields(now, mark) -> dict:
        return dict(
            compile_s=round(now[0] - mark[0], 2),
            programs_compiled=now[1] - mark[1],
            cache_hits=now[2] - mark[2],
            cache_misses=now[3] - mark[3],
        )

    def since(self, mark: tuple) -> dict:
        return self._fields(self._mine(), mark)

    def total(self) -> dict:
        sums = [sum(c[i] for c in list(self._by_thread.values()))
                for i in range(4)]
        return self._fields(sums, (0.0, 0, 0, 0))


def _own_the_chip() -> None:
    """A device-owning child's first act: initialise the backend, stamp
    DEVICE, refuse anything but a TPU."""
    import kubetpu

    DEVICE.update(kubetpu.device_stamp())
    if DEVICE["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {DEVICE}",
              file=sys.stderr)
        raise SystemExit(3)


def stage_probe() -> bool:
    t0 = time.perf_counter()
    _own_the_chip()
    import jax

    from kubetpu.native import build_status, store_core

    core = store_core()
    status = build_status()
    cache_dir = jax.config.jax_compilation_cache_dir
    ok = not status.startswith("failed")
    emit("probe", ok=ok, store_core="native" if core else "python",
         native_build=status, cache_dir=cache_dir,
         cache_entries=_cache_entries(cache_dir), jax=jax.__version__,
         setup_s=round(time.perf_counter() - t0, 1), run_s=0.0)
    return ok


# ------------------------------------------------------------------ stage up

def _scrape_metrics(url: str):
    """One component's /metrics, parsed (None if it cannot be read)."""
    import urllib.request

    from kubetpu.metrics.textparse import parse_prometheus_text

    try:
        with urllib.request.urlopen(url.rstrip("/") + "/metrics",
                                    timeout=10) as resp:
            return parse_prometheus_text(resp.read().decode())
    except Exception:
        return None


def _sum_samples(parsed, name: str, **labels) -> float:
    """Sum of every sample of family ``name`` whose label set contains
    ``labels`` (a sum() over a PromQL instant selector)."""
    if parsed is None:
        return 0.0
    want = {(k, str(v)) for k, v in labels.items()}
    return sum(
        s.value for s in parsed.samples(name)
        if s.name == name and want <= set(s.labels)
    )


def _maps_libtpu(pid: int) -> bool:
    """Whether process ``pid`` has libtpu mapped — it is loaded when, and
    only when, a process initialises the TPU backend."""
    with open(f"/proc/{pid}/maps", encoding="utf-8", errors="replace") as f:
        return "libtpu" in f.read()


def stage_up(device_json: str) -> bool:
    """The control plane as OS processes. THIS process drives REST and must
    stay off the chip: the scheduler child of ``kubetpu up`` owns it."""
    import queue

    import jax
    from jax._src import xla_bridge

    import kubetpu  # noqa: F401  (x64 + cache placement, no backend)
    from kubetpu.apiserver import RemoteStore
    from kubetpu.client.informers import NODES as NODES_KIND, PODS
    from kubetpu.launch.banner import parse_banner
    from kubetpu.perf import workloads as W
    from kubetpu.perf.runner import _bulk_create

    DEVICE.update(json.loads(device_json))
    problems: list[str] = []
    t0 = time.perf_counter()
    up = subprocess.Popen(
        [sys.executable, "-m", "kubetpu", "up", "--replicas", "1",
         "--prewarm"],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    lines: "queue.Queue[str | None]" = queue.Queue()

    def pump() -> None:
        for line in up.stdout:
            lines.put(line)
        lines.put(None)

    threading.Thread(target=pump, daemon=True).start()
    seen: list[str] = []
    children: dict[str, dict] = {}
    api_url = ""
    try:
        while True:
            line = lines.get(timeout=300)
            if line is None:
                raise RuntimeError(
                    "kubetpu up exited before it was ready:\n" + "".join(seen)
                )
            seen.append(line)
            banner = parse_banner(line)
            if banner is not None and banner.get("component") == "cluster":
                api_url = banner["apiserver"]
            parts = line.split()
            if len(parts) >= 3 and parts[1] == "pid" and line.startswith("  "):
                held = line[line.index("{"):] if "{" in line else "{}"
                children[parts[0]] = {"pid": int(parts[2]),
                                      **json.loads(held)}
                if parts[0].startswith("scheduler"):
                    children[parts[0]]["url"] = parts[3]
            if line.startswith("kubetpu up:") and "ready" in line:
                break
        setup_s = time.perf_counter() - t0
        sched = children["scheduler-r0"]
        held = {k: sched.get(k) for k in ("platform", "device_kind", "devices")}
        if held != DEVICE:
            problems.append(f"scheduler banner says it holds {held}")
        # only the scheduler child may have initialised the TPU backend
        for name, pid in (("up", up.pid),
                          ("apiserver", children["apiserver"]["pid"])):
            if _maps_libtpu(pid):
                problems.append(f"{name} (pid {pid}) initialised the TPU")
        if not _maps_libtpu(sched["pid"]):
            problems.append("the scheduler child does not hold the TPU")

        remote = RemoteStore(api_url)
        t1 = time.perf_counter()
        nodes = [W.node_default(i) for i in range(NODES)]
        _bulk_create(remote, NODES_KIND, [(n.name, n) for n in nodes])
        pods = [W.pod_default(f"smoke-{j}", "smoke") for j in range(UP_PODS)]
        # one request: the backlog arrives whole, so the cycles run at the
        # full batch width and compile one rung of the ladder, not several
        _bulk_create(remote, PODS, [(f"smoke/{p.name}", p) for p in pods],
                     chunk=UP_PODS)
        deadline = time.perf_counter() + 420
        while True:
            items, _rv = remote.list(PODS)
            bound = {k: p.node_name for k, p in items if p.node_name}
            if len(bound) == UP_PODS or time.perf_counter() > deadline:
                break
            time.sleep(0.5)
        run_s = time.perf_counter() - t1
        # every pod read back from the apiserver, bound exactly once: the
        # store holds one node per pod, every node is real and within its
        # capacity, and the scheduler counted one success per pod
        if len(items) != UP_PODS or len(bound) != UP_PODS:
            problems.append(f"{len(bound)} of {UP_PODS} pods bound "
                            f"({len(items)} in the store)")
        per_node: dict[str, int] = {}
        for node in bound.values():
            per_node[node] = per_node.get(node, 0) + 1
        names = {n.name for n in nodes}
        if not set(per_node) <= names:
            problems.append("a pod is bound to a node that does not exist")
        if per_node and max(per_node.values()) * 100 > 4000:
            problems.append("a node is over its cpu capacity")
        metrics = _scrape_metrics(sched["url"])
        scheduled = _sum_samples(
            metrics, "scheduler_schedule_attempts_total", result="scheduled")
        if scheduled != UP_PODS:
            problems.append(f"scheduler counted {scheduled} successes")
        explain_failures = _sum_samples(
            metrics, "scheduler_explain_kernel_failures_total")
        if explain_failures:
            problems.append(f"{explain_failures} explain-kernel failures")
        compile_cycles = _sum_samples(metrics, "tpu_jit_cache_misses_total")
        # SIGTERM cascades; the exit is clean; nobody is left behind
        up.send_signal(signal.SIGTERM)
        rc = up.wait(timeout=90)
        if rc != 0:
            problems.append(f"kubetpu up exited {rc} on SIGTERM")
        for name, child in children.items():
            if os.path.exists(f"/proc/{child['pid']}"):
                problems.append(f"{name} (pid {child['pid']}) outlived up")
    finally:
        if up.poll() is None:
            up.kill()
            up.wait()
    if xla_bridge._backends:
        problems.append("the REST driver itself initialised a backend")
    emit("up", summary=True, ok=not problems, problems=problems,
         nodes=NODES, pods=UP_PODS, bound=len(bound),
         store_core=children["apiserver"].get("store_core"),
         scheduler_compile_cycles=int(compile_cycles),
         setup_s=round(setup_s, 1), run_s=round(run_s, 1),
         note="set-up is kubetpu up to ready (process start, chip; "
              "--prewarm finds no node yet and warms nothing); run is REST "
              "post to last bind and includes the 5000-node compiles",
         jax=jax.__version__)
    return not problems


# -------------------------------------------------------------- stage parity

def _parity_cases(rng):
    """(name, cache, pending, profile, oracle kwargs, nominator) per profile
    — each on its own seeded 5000-node cluster from tests/cluster_gen."""
    from kubetpu.api import types as t
    from kubetpu.api.wrappers import make_pod
    from kubetpu.framework import config as C
    from kubetpu.queue.nominator import Nominator
    from tests.cluster_gen import random_cluster
    from tests.test_podaffinity import add_affinity, affinity_profile
    from tests.test_spread import add_spread_pods, spread_profile

    gi = 1024 ** 3
    resources = [(t.CPU, 1), (t.MEMORY, 1)]
    fit_balanced = C.Profile(
        filters=C.PluginSet(enabled=(
            (C.NODE_UNSCHEDULABLE, 1), (C.NODE_NAME, 1),
            (C.TAINT_TOLERATION, 1), (C.NODE_AFFINITY, 1),
            (C.NODE_PORTS, 1), (C.NODE_RESOURCES_FIT, 1),
        )),
        scores=C.PluginSet(enabled=(
            (C.TAINT_TOLERATION, 3), (C.NODE_AFFINITY, 2),
            (C.NODE_RESOURCES_FIT, 1), (C.NODE_RESOURCES_BALANCED, 1),
        )),
        default_spread_constraints=(),
    )
    cache, pending = random_cluster(
        rng, num_nodes=NODES, num_existing=4000, num_pending=128,
        with_taints=True,
    )
    yield ("fit+balanced", cache, pending, fit_balanced, dict(
        resources=resources, w_fit=1, w_balanced=1, w_node_affinity=2,
        w_taint=3,
    ), None)

    cache, pending = random_cluster(
        rng, num_nodes=NODES, num_existing=4000, num_pending=64)
    yield ("spread", cache, add_spread_pods(rng, pending, hard_ratio=0.5),
           spread_profile(), dict(
        w_fit=1, w_spread=2, check_ports=False, check_static=False,
        check_spread=True,
    ), None)

    cache, pending = random_cluster(
        rng, num_nodes=NODES, num_existing=4000, num_pending=64)
    yield ("inter-pod-affinity", cache, add_affinity(rng, pending),
           affinity_profile(), dict(
        w_fit=1, w_interpod=2, check_ports=False, check_static=False,
        check_interpod=True,
    ), None)

    # the nominated-fit kernel: high-priority nominees hold GiB-scale (and
    # deliberately odd, > 2^32) memory on the best-scoring nodes, so the
    # f64 reservation einsum decides the answer for the pods below them;
    # one nominee rides the batch itself (its charge is dropped at assume)
    cache, pending = random_cluster(
        rng, num_nodes=NODES, num_existing=4000, num_pending=0)
    infos = cache.update_snapshot().node_infos()
    free = sorted(
        infos, reverse=True,
        key=lambda i: i.node.allocatable_dict().get(t.MEMORY, 0)
        - i.requested.get(t.MEMORY, 0),
    )[:24]
    nom = Nominator()
    nominees = []
    for k, info in enumerate(free):
        room = (info.node.allocatable_dict()[t.MEMORY]
                - info.requested.get(t.MEMORY, 0))
        nominee = make_pod(
            f"nominee-{k}", cpu_milli=10, memory=room - 3 * gi + 2 * k + 1,
            priority=100, creation_index=k,
        )
        nom.add(nominee, info.node.name)
        nominees.append((nominee, info.node.name))
    pending = [
        make_pod(f"pending-{j}", cpu_milli=10,
                 memory=(2 + j % 3) * gi + 7 * j + 1, priority=j % 2 * 100,
                 creation_index=100 + j)
        for j in range(63)
    ] + [nominees[0][0]]
    yield ("nominated-fit", cache, pending, C.minimal_profile(), dict(
        resources=resources, w_fit=1, check_ports=False, check_static=False,
    ), (nom, nominees))


def stage_parity() -> bool:
    _own_the_chip()
    meter = CompileMeter()
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from kubetpu.assign import greedy_assign
    from kubetpu.framework import encode_batch
    from tests import oracle

    def one(case) -> bool:
        name, cache, pending, profile, kwargs, nominated = case
        snap = cache.update_snapshot()
        entries, by_node = [], None
        if nominated is not None:
            nom, nominees = nominated
            entries = nom.entries()
            by_node = {}
            for pod, node in nominees:
                by_node.setdefault(node, []).append(pod)
        mark = meter.mark()
        t0 = time.perf_counter()
        batch = encode_batch(snap, pending, profile, nominated=entries)
        got = greedy_assign(batch, profile)
        device_s = time.perf_counter() - t0
        compiled = meter.since(mark)
        t1 = time.perf_counter()
        infos = [info.clone() for info in snap.node_infos()]
        want = oracle.greedy(infos, pending, nominated=by_node, **kwargs)
        oracle_s = time.perf_counter() - t1
        differ = [
            {"pod": p.name, "device": g, "oracle": w}
            for p, g, w in zip(pending, got, want) if g != w
        ]
        ok = not differ and len(got) == len(pending)
        if nominated is not None:
            # the case must really take the nominated kernel, and the
            # reservations must really decide an answer
            plain = oracle.greedy(
                [i.clone() for i in snap.node_infos()], pending, **kwargs)
            ok = ok and batch.device.nominated_req is not None
            ok = ok and plain != want
        emit("parity", summary=True, ok=ok, profile=name, nodes=NODES,
             pods=len(pending), placed=sum(1 for g in got if g),
             differ=differ[:5], oracle_s=round(oracle_s, 1),
             setup_s=compiled["compile_s"],
             run_s=round(max(device_s - compiled["compile_s"], 0.0), 2),
             **compiled)
        return ok

    # the four programs compile side by side (XLA compiles outside the
    # interpreter lock) while the oracle, plain Python, takes its turns
    cases = list(_parity_cases(np.random.default_rng(SEED)))
    with ThreadPoolExecutor(max_workers=len(cases)) as pool:
        return all(list(pool.map(one, cases)))


# ------------------------------------------------------------- stage compile

def _full_problem(rng):
    """One 5000-node cluster that lights every kernel family at once:
    zones/hostnames for spread, labelled pods for inter-pod affinity, host
    ports, priorities for preemption, rack/slice labels for topology."""
    from kubetpu.api.wrappers import make_node, make_pod
    from kubetpu.perf import workloads as W
    from kubetpu.state import Cache
    from tests.test_podaffinity import add_affinity
    from tests.test_spread import add_spread_pods

    cache = Cache()
    zones = ("zone-a", "zone-b", "zone-c")
    for i in range(NODES):
        name = f"node-{i}"
        labels = {W.HOSTNAME_KEY: name, W.ZONE_KEY: zones[i % 3]}
        labels.update(W.trace_topology_labels(name, 8))
        cache.add_node(make_node(
            name, cpu_milli=4000, memory=32 * 1024 ** 3, pods=110,
            labels=labels,
        ))
    apps = ("web", "db", "cache")
    for j in range(4000):
        cache.add_pod(make_pod(
            f"existing-{j}", cpu_milli=int(rng.integers(100, 1500)),
            memory=int(rng.integers(1, 8)) * 1024 ** 3,
            labels={"app": apps[j % 3]}, priority=int(rng.integers(0, 3)),
            node_name=f"node-{int(rng.integers(0, NODES))}",
            creation_index=j,
        ))
    pending = [
        make_pod(
            f"pending-{j}", cpu_milli=int(rng.integers(100, 2000)),
            memory=int(rng.integers(1, 6)) * 1024 ** 3,
            labels={"app": apps[j % 3]}, priority=10,
            host_ports=[8000 + j % 4] if j % 5 == 0 else [],
            creation_index=10_000 + j,
        )
        for j in range(63)
    ]
    pending = add_affinity(rng, add_spread_pods(rng, pending))
    # one pod nothing can hold: the unschedulable pod the preemption dry
    # run and the explain-masks kernel exist for
    pending.append(make_pod(
        "pending-huge", cpu_milli=3900, memory=30 * 1024 ** 3,
        priority=1000, creation_index=20_000))
    return cache, pending


def stage_compile(mode: str = "cold") -> bool:
    """Lower, compile and execute once, at 5000-node width, each jitted
    device program the served path can reach — called the way the program
    calls it, no reference path, no swallowed exception. The programs are
    independent, and XLA compiles outside the interpreter lock, so they
    compile side by side: the stage lasts about as long as its slowest
    program (cold: packing) instead of their sum."""
    _own_the_chip()
    meter = CompileMeter()
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    import numpy as np

    import __graft_entry__ as ge
    from kubetpu.api.wrappers import make_pod
    from kubetpu.assign.batched import batched_assign_device
    from kubetpu.assign.greedy import greedy_assign_device
    from kubetpu.assign.packing import PackingWeights, packing_assign_device
    from kubetpu.assign.placement import placement_assign_device
    from kubetpu.framework import encode_batch, score_params
    from kubetpu.framework import runtime as rt
    from kubetpu.framework.preemption import PreemptionEvaluator
    from kubetpu.ops import preemption as P
    from kubetpu.ops.topology import free_slices
    from kubetpu.parallel.mesh import make_mesh
    from kubetpu.sched import flightrecorder as fr

    t_stage = time.perf_counter()
    cache, pending = _full_problem(np.random.default_rng(SEED))
    profile = ge._full_profile()
    snap = cache.update_snapshot()
    resident = rt.ResidentNodeState()
    batch = encode_batch(snap, pending, profile, resident=resident,
                         topology="on")
    params = score_params(profile, batch.resource_names)
    b = batch.device
    n_pad, p_pad = int(b.alloc.shape[0]), int(b.requests.shape[0])
    if b.spread is None or b.podaffinity is None or b.topology is None:
        raise RuntimeError("the problem must light every kernel family")

    def first_call(spec) -> tuple:
        """Compile + run, timed, with what THIS thread compiled."""
        _name, jitted, call, _check = spec
        before = jitted()._cache_size()
        mark = meter.mark()
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        return before, time.perf_counter() - t0, meter.since(mark)

    ran_s: list[float] = []

    def second_call(spec, first) -> bool:
        """Run alone, timed; proof that the program compiled HERE (its jit
        cache grew) and that its answer passes the spec's check."""
        name, jitted, call, check = spec
        before, first_s, compiled = first
        t0 = time.perf_counter()
        out = jax.block_until_ready(call())
        ran_s.append(time.perf_counter() - t0)
        problem = ("" if jitted()._cache_size() > before
                   else "did not compile here")
        problem = problem or check(jax.device_get(out)) or ""
        emit("compile", ok=not problem, program=name, problem=problem,
             nodes_padded=n_pad, pods_padded=p_pad, cache=mode,
             setup_s=round(max(first_s - ran_s[-1], 0.0), 2),
             run_s=round(ran_s[-1], 4), **compiled)
        return not problem

    def assignments_ok(out) -> str:
        a = np.asarray(out[0] if isinstance(out, tuple) else out)
        real = a[..., : len(pending)]
        if not ((real >= -1) & (real < NODES)).all():
            return "assignment out of range"
        return "" if (real >= 0).any() else "nothing was placed"

    weights = PackingWeights().tensor()
    lam = {"v": jnp.zeros(n_pad, dtype=jnp.float32)}

    def packing():
        out = packing_assign_device(b, params, lam["v"], weights)
        lam["v"] = out[2]            # the dual vector is donated: rebind
        return out

    masks = np.zeros((4, n_pad), dtype=bool)
    for d in range(4):
        masks[d, d * 1000: d * 1000 + 1500] = True
    assigned = jnp.asarray(np.arange(p_pad, dtype=np.int32) % NODES)
    # the per-pod preemption dry run goes through its host-side evaluator,
    # for the one pod nothing can hold
    evaluator = PreemptionEvaluator(batch, params)
    plan = {}

    def dry_run():
        plan["r"] = evaluator.preempt(len(pending) - 1)
        return jnp.zeros(())

    cand = np.zeros((3, n_pad), dtype=bool)
    for c in range(3):
        cand[c, c * 64: c * 64 + 64] = True
    freed_req = np.asarray(b.requested)[None] * cand[:, :, None]
    freed_cnt = np.asarray(b.pod_count)[None] * cand

    side_by_side = [
        ("assign.packing.packing_assign_device",
         lambda: packing_assign_device, packing,
         lambda out: assignments_ok(out)
         or ("" if np.isfinite(out[3]) else "objective not finite")),
        ("assign.batched.batched_assign_device",
         lambda: batched_assign_device,
         lambda: batched_assign_device(b, params), assignments_ok),
        ("framework.runtime.filter_score_batch",
         lambda: rt.filter_score_batch,
         lambda: rt.filter_score_batch(b, params),
         lambda out: "" if out[0][: len(pending), :NODES].any()
         else "no feasible (pod, node)"),
        ("assign.greedy.greedy_assign_device",
         lambda: greedy_assign_device,
         lambda: greedy_assign_device(b, params), assignments_ok),
        ("sched.flightrecorder._explain_kernel",
         lambda: fr._EXPLAIN_JIT or _Uncompiled,
         lambda: fr._explain_kernel(b, params, assigned),
         lambda out: "" if (out[0][: len(pending) - 1] > 0).any()
         else "no feasible counts"),
        ("assign.placement.placement_assign_device",
         lambda: placement_assign_device,
         lambda: placement_assign_device(b, params, jnp.asarray(masks)),
         assignments_ok),
        ("ops.preemption.dry_run_gang_preemption",
         lambda: P.dry_run_gang_preemption,
         lambda: P.dry_run_gang_preemption(
             b, params, jnp.asarray(cand), jnp.asarray(freed_req),
             jnp.asarray(freed_cnt)),
         lambda out: "" if (np.asarray(out[0]) >= 0).all()
         else "bad counts"),
        ("ops.preemption.dry_run_preemption",
         lambda: P.dry_run_preemption, dry_run,
         lambda _out: "" if plan["r"].status == "success"
         and plan["r"].victim_pods else f"dry run said {plan['r'].status}"),
        ("sched.flightrecorder._explain_masks_kernel",
         lambda: fr._EXPLAIN_MASKS_JIT or _Uncompiled,
         lambda: fr._explain_masks_kernel(b, params),
         lambda out: "" if any(c is not None for c in out) else "no masks"),
        ("ops.topology.free_slices",
         lambda: free_slices,
         lambda: free_slices(b.requested, b.node_valid, b.topology.slice_id,
                             b.topology.num_slices),
         lambda out: "" if 0 <= int(out) <= b.topology.num_slices
         else "slice count out of range"),
    ]
    with ThreadPoolExecutor(max_workers=len(side_by_side)) as pool:
        firsts = list(pool.map(first_call, side_by_side))
    setup_s = time.perf_counter() - t_stage
    verdicts = [second_call(spec, first)
                for spec, first in zip(side_by_side, firsts)]

    # the resident block's donated dirty-row scatters, one after the other
    # (they move the shared cluster): bind pods onto a few nodes, re-encode
    # against the previous tensors, and the delta rides the scatter —
    # single-device, then routed through shard_map over every visible device
    def scatter_via(res, tag: str):
        first = encode_batch(snap, pending, profile, resident=res,
                             topology="on")
        state = {"snap": snap, "nt": first.node_tensors, "round": 0}

        def call():
            state["round"] += 1
            for k in range(8):
                cache.add_pod(make_pod(
                    f"bound-{tag}-{state['round']}-{k}", cpu_milli=50,
                    memory=64 * 1024 ** 2,
                    node_name=f"node-{(97 * k + state['round']) % NODES}",
                ))
            state["snap"] = cache.update_snapshot(state["snap"])
            nxt = encode_batch(state["snap"], pending, profile,
                               prev_nt=state["nt"], resident=res,
                               topology="on")
            state["nt"] = nxt.node_tensors
            state["bytes"] = res.last_upload_bytes
            return nxt.device.nodes

        def check(nodes) -> str:
            if not 0 < state["bytes"] < res.nbytes:
                return f"no delta upload ({state['bytes']} bytes)"
            return "" if np.array_equal(
                np.asarray(nodes.requested), state["nt"].requested
            ) else "resident block != host rows"

        return call, check

    routed = rt.ResidentNodeState(mesh=make_mesh())
    for spec in (
        ("framework.runtime._scatter_node_rows",
         lambda: rt._scatter_node_rows, *scatter_via(resident, "single")),
        ("framework.runtime._make_routed_scatter",
         lambda: routed._routed_scatter, *scatter_via(routed, "routed")),
    ):
        t0 = time.perf_counter()
        first = first_call(spec)
        setup_s += time.perf_counter() - t0
        verdicts.append(second_call(spec, first))

    total = meter.total()
    ok = all(verdicts) and len(verdicts) == 12
    if mode == "warm":
        # the pass that follows a cold one must find every program cached
        ok = ok and total["cache_misses"] == 0
    emit("compile", summary=True, ok=ok, programs=len(verdicts), cache=mode,
         setup_s=round(setup_s, 1), run_s=round(sum(ran_s), 1),
         note="set-up: cluster build, encode, and every program's first "
              "call, ten of them side by side (compile_s sums the compile "
              "seconds over the threads); run: the second calls, alone",
         **total)
    return ok


class _Uncompiled:
    """Stands in for a lazily-built jit that does not exist yet."""

    @staticmethod
    def _cache_size() -> int:
        return 0


# ---------------------------------------------------------------- stage mesh

def stage_mesh() -> bool:
    _own_the_chip()
    meter = CompileMeter()
    from kubetpu.perf import run_workload

    runs = {}
    for mesh in (None, "on"):
        mark = meter.mark()
        t0 = time.perf_counter()
        r = run_workload("SchedulingBasic", "15000Nodes", engine="greedy",
                         mesh=mesh, timeout_s=600.0, warmup=False)
        runs[mesh] = r
        emit("mesh", ok=r.scheduled == r.measure_pods,
             mesh=mesh or "off", scheduled=r.scheduled,
             measure_pods=r.measure_pods,
             bindings_digest=r.bindings_digest,
             mesh_shape=list(r.mesh_shape),
             mesh_placement=r.mesh_placement,
             collective_wall_s=r.collective_wall_s,
             setup_s=round(time.perf_counter() - t0 - r.duration_s, 1),
             run_s=round(r.duration_s, 2),
             smoke_pods_per_s=round(r.throughput, 1),
             note="smoke number, not a benchmark", **meter.since(mark))
    single, meshed = runs[None], runs["on"]
    placement = meshed.mesh_placement or {}
    n = meshed.n_devices
    problems = []
    if meshed.bindings_digest != single.bindings_digest:
        problems.append("bound map differs from the single-device run's")
    if n != DEVICE["devices"]:
        problems.append(f"mesh resolved to {n} of {DEVICE['devices']} devices")
    if not placement.get("block_sharded"):
        problems.append("the resident block fell back to one device")
    if placement.get("resident_devices") != n:
        problems.append("a node-major leaf does not span every chip")
    if not placement.get("shards_with_transfer", 0) > 1:
        problems.append("routed delta bytes reached at most one shard")
    if not isinstance(meshed.collective_wall_s, float):
        problems.append("collective_wall_s is not a number")
    emit("mesh", summary=True, ok=not problems, ran=True, problems=problems,
         n_devices=n, setup_s=meter.total()["compile_s"],
         run_s=round(single.duration_s + meshed.duration_s, 2))
    return not problems


STAGES = {
    "probe": stage_probe, "up": stage_up, "parity": stage_parity,
    "compile": stage_compile, "mesh": stage_mesh,
}

if __name__ == "__main__":
    if len(sys.argv) > 1:
        sys.exit(0 if STAGES[sys.argv[1]](*sys.argv[2:]) else 1)
    sys.exit(main())
