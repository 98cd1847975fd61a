"""One run of one cell: three OS processes as ``kubetpu up`` deploys them,
driven through set-up, ramp, window, drain and check.

- the apiserver is a child (``python -m kubetpu apiserver``, the program's
  defaults);
- the load generator is a child (``benchmark/harness/generator.py``), REST
  only, never on the chip;
- the scheduler runs in THIS process, which therefore holds the chip:
  ``kubetpu.cli.main(["scheduler", ...])`` on the main thread — the entry
  point users run, with its own loop, its own sleep and its own defaults —
  while a controller thread directs the run from outside, through the
  scheduler's diagnostics listener, and ends it with SIGTERM to this pid.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import NamedTuple

from benchmark.harness import promtext
from benchmark.harness.manifest import BENCH, ROOT, Cell

clock = time.perf_counter
#: a window needs this many cycles in a row that compiled nothing before it
QUIET_CYCLES = 3
#: the traced part of a traced run: the LAST seconds of the window, so that
#: stopping the profiler falls after the window's closing scrape. Longer
#: than the 4.5 s in which today's loop repeats itself (PERF.md, PR 22), and
#: short enough for the trace to stay readable: 100,000 device events and
#: 7 MB a second
TRACE_SECONDS = 6.0
ANCHOR = "benchmark-anchor"
CYCLES = "scheduler_scheduling_algorithm_duration_seconds_count"


class RunFailed(Exception):
    """The run is not a measurement; the message says why."""


def say(phase: str, **fields) -> None:
    """An earlier line of the output: everything but the contract's last."""
    print(json.dumps({"phase": phase, **fields}), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """A child process whose stdout lines arrive on a queue. It stays in
    this process's group, so whoever ends the group ends it too."""

    def __init__(self, name: str, argv: list[str], env: dict | None = None,
                 stdin: bool = False) -> None:
        self.name = name
        self.lines: "queue.Queue[str | None]" = queue.Queue()
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, text=True, stdout=subprocess.PIPE,
            stdin=subprocess.PIPE if stdin else subprocess.DEVNULL)
        threading.Thread(target=self._pump, daemon=True,
                         name=f"{name}-stdout").start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line)
        self.lines.put(None)

    def next_line(self, timeout_s: float) -> str:
        try:
            line = self.lines.get(timeout=timeout_s)
        except queue.Empty:
            raise RunFailed(f"{self.name}: silent for {timeout_s:.0f} s")
        if line is None:
            raise RunFailed(f"{self.name} exited ({self.proc.poll()})")
        return line

    def end(self, timeout_s: float = 20.0) -> int | None:
        """SIGTERM, wait, SIGKILL if it must be: nothing outlives a run."""
        if self.proc.poll() is None:
            if self.proc.stdin is not None:
                try:
                    self.proc.stdin.close()
                except OSError:
                    pass
            else:
                self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode


class GeneratorLink:
    """Commands to the generator child, and its answers."""

    def __init__(self, child: Child) -> None:
        self.child = child

    def ask(self, cmd: dict, answer: str, timeout_s: float) -> dict:
        self.child.proc.stdin.write(json.dumps(cmd) + "\n")
        self.child.proc.stdin.flush()
        return self.expect(answer, timeout_s)

    def expect(self, answer: str, timeout_s: float) -> dict:
        """The next ``answer``. One that says ``"ok": false`` fails the run
        here, in the generator's own words where it gives them (``why``)."""
        deadline = clock() + timeout_s
        while True:
            doc = json.loads(self.child.next_line(max(deadline - clock(), 0.1)))
            if doc.get("event") == "error":
                raise RunFailed(f"generator: {doc}")
            if doc.get("event") == answer:
                if doc.get("ok") is False:
                    raise RunFailed(f"generator: {doc.get('why', doc)}")
                return doc

    def quit(self) -> dict:
        """The generator's last word: whether it ever touched a backend."""
        try:
            return self.ask({"cmd": "quit"}, "bye", 30.0)
        except (RunFailed, OSError, ValueError) as e:
            return {"error": str(e)}


@dataclass
class Run:
    """What one run gathered; the per-layer readers take their numbers from
    here (``benchmark/layer_metrics/<name>.py: read(run)``)."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    device: dict = field(default_factory=dict)
    api_url: str = ""
    diag_url: str = ""
    phases: dict = field(default_factory=dict)       # name -> seconds
    window_s: float = 0.0
    setup_s: float = 0.0
    #: promtext.Delta over the window, per component
    scheduler: promtext.Delta | None = None
    apiserver: promtext.Delta | None = None
    compiles_in_window: int = 0
    report: dict = field(default_factory=dict)        # the generator's
    #: harness.xplane.reduce_trace(...) of a traced run, else None
    device_trace: dict | None = None
    idle_by_span: dict = field(default_factory=dict)
    pods_bound: int = 0                               # in the window
    cycles: float = 0.0                               # in the window
    errors: list = field(default_factory=list)
    scratch: str = ""          # this run's directory under TMPDIR
    pids: dict = field(default_factory=dict)          # process -> pid
    #: CPU seconds each of the three processes used inside the window
    cpu_s: dict = field(default_factory=dict)


def cpu_seconds(pid: int) -> float:
    """CPU time a process has used so far, all its threads, user + system
    (``/proc/<pid>/stat``)."""
    with open(f"/proc/{pid}/stat", encoding="utf-8") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


def _wait_http_ok(url: str, timeout_s: float, what: str) -> None:
    deadline = clock() + timeout_s
    while True:
        try:
            promtext.fetch(url, timeout_s=2.0)
            return
        except OSError:
            if clock() > deadline:
                raise RunFailed(f"{what} not ready after {timeout_s:.0f} s")
            time.sleep(0.1)


def _await_banner(child: Child, url: str) -> dict:
    """The apiserver's readiness banner (``launch/banner.py``): it serves
    where it was told to, and says which store core it runs."""
    from kubetpu.launch.banner import parse_banner

    deadline = clock() + 300    # a new checkout builds the native core
    while True:
        banner = parse_banner(child.next_line(max(deadline - clock(), 0.1)))
        if banner is not None and banner.get("component") == "apiserver":
            if banner["url"] != url:
                raise RunFailed(f"apiserver serves {banner['url']}, not {url}")
            return banner


def _control(run: Run, gen: GeneratorLink, meter) -> None:
    """The controller thread: everything from scheduler-ready to SIGTERM."""
    cell = run.cell
    traffic = cell.traffic
    try:
        _wait_http_ok(run.diag_url + "/readyz", 300, "scheduler")
        t = clock()
        run.phases["processes_ready_s"] = t - run.t_start
        done = gen.ask({"cmd": "await_init"}, "init_bound", 960)
        run.phases["init_bound_s"] = clock() - t
        say("set-up", **done, since_start_s=round(clock() - run.t_start, 2),
            compiled=meter.snapshot())

        # ---- ramp: real pods of the cell's own template through the real
        # path, until the cycles compile nothing
        t = clock()
        if traffic["ramp"] == "ladder":
            for n in traffic["ladder"]:
                say("ramp", **gen.ask({"cmd": "burst", "n": n},
                                      "burst_done", 900))
        gen.ask({"cmd": "start"}, "started", 30)
        quiet_since, programs_seen = None, -1
        deadline = clock() + 900
        while True:
            cycles = promtext.scrape(run.diag_url).total(CYCLES)
            programs = meter.snapshot()["programs"]
            if programs != programs_seen:
                quiet_since, programs_seen = cycles, programs
            if cycles - quiet_since >= QUIET_CYCLES:
                break
            if clock() > deadline:
                raise RunFailed("ramp: the cycles never stopped compiling")
            time.sleep(0.2)
        run.phases["ramp_s"] = clock() - t
        say("ramp", done=True, s=round(clock() - t, 2),
            compiled=meter.snapshot())

        # ---- window
        spans = session = None
        programs0 = meter.snapshot()["programs"]
        sched0 = promtext.scrape(run.diag_url)
        api0 = promtext.scrape(run.api_url)
        cpu0 = {name: cpu_seconds(pid) for name, pid in run.pids.items()}
        t0 = clock()
        run.setup_s = t0 - run.t_start
        t1 = t0 + run.seconds
        anchors: list[float] = []
        if run.trace:
            import jax

            from benchmark.harness.spans import SpanLog

            spans = SpanLog(run.diag_url)
            time.sleep(max(t1 - min(TRACE_SECONDS, run.seconds) - clock(), 0))
            session = start_profiler()
            # the anchor puts this process's clock on the trace's clock
            with jax.profiler.TraceAnnotation(ANCHOR):
                anchors.append(clock())
            while clock() < t1:
                spans.poll()
                time.sleep(max(min(1.0, t1 - clock()), 0))
            anchors.append(clock())
        else:
            time.sleep(max(t1 - clock(), 0))
        t1, xspace = _close_window(run, gen, meter, spans, session,
                                   Opened(t0, sched0, api0, cpu0, programs0))
        out = os.path.join(run.scratch, "generator.json")
        gen.ask({"cmd": "report", "t0": t0, "t1": t1, "out": out},
                "report_done", 120)
        with open(out, encoding="utf-8") as f:
            run.report = json.load(f)
        run.pods_bound = run.report["bound_in_window"]
        if run.trace:
            _reduce_trace(run, xspace, anchors, spans)
    except Exception as e:  # noqa: BLE001 — reported by the main thread
        run.errors.append(f"{type(e).__name__}: {e}")
    finally:
        # ends the scheduler's loop through its own stop event
        os.kill(os.getpid(), signal.SIGTERM)


class Opened(NamedTuple):
    """What was read as the window opened."""

    t0: float
    scheduler: promtext.Scrape
    apiserver: promtext.Scrape
    cpu: dict
    programs: int


#: a window that leaves less room than this in the cluster says so
NEAR_FULL = 0.8


def start_profiler():
    """A profiler session of this process: what ``jax.profiler.start_trace``
    starts, kept in hand, because ``jax.profiler.stop_trace`` also writes
    the trace out as files, among them a gzipped JSON of every operation,
    and on the chip that writing is most of the minutes a stop takes
    (PERF.md section 6, PR 30). The session hands the same XSpace over as
    bytes. The scheduler has initialised the backend long before."""
    import jax
    from jax._src.lib import _profiler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return _profiler.ProfilerSession(options)


def stop_profiler(session) -> bytes:
    """The trace, a serialized XSpace. Seconds to minutes on the chip: it
    grows with the operations traced, 125 of them a pod bound."""
    return session.stop()


def _close_window(run: Run, gen: GeneratorLink, meter, spans, session,
                  opened: Opened) -> tuple[float, bytes]:
    """The window's close, and the drain. Everything a run measures is
    closed at ``t1`` and the load ends at ``t1``, before anything slow
    happens: stopping the profiler takes seconds to minutes, and a closed
    loop left running meanwhile fills the cluster, the sooner the faster the
    program is. ``spans`` and ``session`` are a traced run's SpanLog and
    profiler session, else None. Returns ``t1`` and the trace."""
    t1 = clock()
    run.cpu_s = {name: cpu_seconds(pid) - opened.cpu[name]
                 for name, pid in run.pids.items()}
    sched1 = promtext.scrape(run.diag_url)
    api1 = promtext.scrape(run.api_url)
    programs1 = meter.snapshot()["programs"]
    paused = gen.ask({"cmd": "pause", "t1": t1}, "paused", 90)
    xspace = b""
    if session is not None:
        spans.poll()
        t = clock()
        xspace = stop_profiler(session)
        run.phases["stop_trace_s"] = clock() - t
    run.window_s = t1 - opened.t0
    run.scheduler = promtext.Delta(opened.scheduler, sched1)
    run.apiserver = promtext.Delta(opened.apiserver, api1)
    run.cycles = run.scheduler.total(CYCLES)
    run.compiles_in_window = programs1 - opened.programs
    run.phases["window_s"] = run.window_s
    created, capacity = paused["created_at_t1"], paused["capacity"]
    near_full = {}
    if created > NEAR_FULL * capacity:
        near_full["near_full"] = (
            f"the window alone made {created} of the {capacity} pods the "
            "cluster holds: recycle pods before this rate rises (PERF.md "
            "section 7 (2))")
    say("window", s=round(run.window_s, 3), cycles=run.cycles,
        compiles_in_window=run.compiles_in_window,
        cpu_s={k: round(v, 2) for k, v in run.cpu_s.items()},
        created_at_t1=created, capacity=capacity,
        load_past_t1_s=paused["past_t1_s"], **near_full)
    # ---- drain: the backlog the traffic left binds, bounded; ``created``
    # less the window's ``created_at_t1`` was made outside the window
    timeout_s = run.cell.traffic["drain_timeout_s"]
    drained = gen.ask({"cmd": "stop", "timeout_s": timeout_s}, "drained",
                      timeout_s + 90)
    run.phases["drain_s"] = drained["s"]
    say("drain", **drained)
    return t1, xspace


def _reduce_trace(run: Run, xspace: bytes, anchors: list[float],
                  spans) -> None:
    import jax

    from benchmark.harness import intervals as iv
    from benchmark.harness import xplane
    from benchmark.harness.spans import attribute_gaps

    t = clock()
    size = len(xspace)
    data = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    if run.device["platform"] != "tpu":
        # reachable from benchmark/tests only: a CPU trace has no device
        # plane, so the rehearsal stops at having read the file
        say("trace", xplane_bytes=size, planes=len(list(data.planes)),
            note="no TPU: nothing reduced")
        return
    first = xplane.annotation(data, ANCHOR)
    if first is None:
        raise RunFailed("the trace does not hold the harness's anchor")
    # the anchor was written at anchors[0] on this process's clock and sits
    # at first[0] on the trace's: one offset moves the spans over
    offset = first[0] - anchors[0]
    window = (first[0], anchors[1] + offset)
    reduced = xplane.reduce_trace(
        data, window, run.cell.config.get("assign_program", ""))
    run.device_trace = reduced
    by_name = spans.by_name(offset)
    idle: dict[str, float] = {}
    for chip in reduced["chips"]:
        gaps = iv.gaps(chip["busy"], *window)
        for name, secs in attribute_gaps(gaps, by_name).items():
            idle[name] = idle.get(name, 0.0) + secs / len(reduced["chips"])
        say("trace", chip=chip["chip"], busy_s=chip["busy_s"],
            idle_share_pct=100 * chip["idle_share"],
            collective_s=chip["collective_s"],
            collective_exposed_s=chip["collective_exposed_s"],
            ops=chip["ops"], programs=chip["programs"])
    run.idle_by_span = idle
    say("trace", xplane_bytes=size, window_s=reduced["window_s"],
        spans_seen=len(spans.spans),
        programs=xplane.top(reduced["module_s"], 8),
        reduce_s=round(clock() - t, 2))


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float | None = None, platform: str = "tpu",
             cell: Cell | None = None) -> dict:
    """Run one cell and return the contract's last line as a dict.
    ``platform`` and ``cell`` are for the benchmark's own tests (a tiny
    cluster on the CPU); ``run.py`` passes neither, so a run from the
    command line needs the TPU and the cell BENCHMARK.json names.
    ``t_start`` is when the process started, on ``time.perf_counter``."""
    t_start = clock() if t_start is None else t_start
    from benchmark.harness.manifest import load_manifest

    if cell is None:
        cell = Cell(load_manifest(), name)
    children: list[Child] = []
    old = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    scratch = tempfile.mkdtemp(prefix="kubetpu-bench-")
    try:
        # both children start at once, while this process takes the chip;
        # neither touches a backend
        api_url = f"http://127.0.0.1:{free_port()}"
        api = Child("apiserver", [
            sys.executable, "-m", "kubetpu", "apiserver",
            "--port", api_url.rsplit(":", 1)[1]])
        children.append(api)
        cfg_path = os.path.join(scratch, "cell.json")
        with open(cfg_path, "w", encoding="utf-8") as f:
            json.dump({"config": cell.config, "traffic": cell.traffic}, f)
        gen_child = Child(
            "generator",
            [sys.executable, os.path.join(BENCH, "harness", "generator.py"),
             "--server", api_url, "--cell", cfg_path, "--seed", str(seed)],
            # the generator never touches a backend; should it ever, it
            # must not take the chip from under the scheduler
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdin=True)
        children.append(gen_child)
        import kubetpu
        import kubetpu.cli
        from benchmark.harness.compilemeter import CompileMeter

        stamp = kubetpu.device_stamp()      # takes the chip
        if stamp["platform"] != platform or stamp["devices"] < cell.chips:
            raise RunFailed(f"{name} needs {cell.chips} {platform} chip(s); "
                            f"kubetpu.device_stamp() found {stamp}")
        meter = CompileMeter()
        run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  t_start=t_start, device=stamp, api_url=api_url,
                  scratch=scratch,
                  pids={"scheduler": os.getpid(), "apiserver": api.proc.pid,
                        "generator": gen_child.proc.pid})
        say("start", device=stamp, chip_taken_s=round(clock() - t_start, 2))
        banner = _await_banner(api, api_url)
        gen = GeneratorLink(gen_child)
        gen.expect("hello", 300)
        say("start", store_core=banner.get("store_core"),
            children_ready_s=round(clock() - t_start, 2))
        # the cluster is whole before the scheduler lists it (see post())
        say("set-up", **gen.ask({"cmd": "post"}, "posted", 300),
            since_start_s=round(clock() - t_start, 2))
        port = free_port()
        run.diag_url = f"http://127.0.0.1:{port}"
        controller = threading.Thread(
            target=_control, args=(run, gen, meter), name="controller",
            daemon=True)
        controller.start()
        rc = kubetpu.cli.main([
            "scheduler", "--server", run.api_url,
            *cell.config["scheduler_flags"],
            "--diagnostics-port", str(port)])
        # the controller ends the scheduler as its last act, so a scheduler
        # that returns while it still works has failed to start
        controller.join(timeout=10)
        if rc != 0 or controller.is_alive():
            raise RunFailed(f"kubetpu scheduler returned {rc} "
                            f"(controller: {run.errors or 'still at work'})")
        for sig, handler in old.items():
            signal.signal(sig, handler)
        if run.errors:
            raise RunFailed("; ".join(run.errors))
        if run.compiles_in_window:
            raise RunFailed(
                f"{run.compiles_in_window} program(s) compiled inside the "
                "window: this run is not a measurement")
        result = _judge(run, meter)
        bye = gen.quit()
        if bye.get("backend_initialised") is not False:
            raise RunFailed(f"the generator ended with {bye}")
        return result
    finally:
        for child in reversed(children):
            child.end()
        shutil.rmtree(scratch, ignore_errors=True)


def _judge(run: Run, meter) -> dict:
    """Check, then the metrics of this kind of run, then the last line."""
    import jax

    from benchmark.harness import check, xplane
    from benchmark.harness.manifest import layer_reader

    t = clock()
    cell, rep = run.cell, run.report
    nodes, stored = check.readback(run.api_url)
    failed, problems = check.store_agreement(rep, stored)
    invalid = check.validity_problems(nodes, stored)
    parity = check.oracle_parity(cell.config, nodes, stored, run.seed)
    #: every number `correct` compares, beside its limit: all exact
    checks = {"pods_failed": [failed, 0],
              "ack_store_problems": [len(problems), 0],
              "generator_errors": [len(rep["errors"]), 0],
              "invalid_bindings": [len(invalid), 0],
              "oracle_disagreements": [len(parity["problems"]), 0]}
    problems += [f"generator: {e}" for e in rep["errors"]] + invalid
    problems += parity["problems"]
    run.phases["check_s"] = clock() - t
    say("check", attempted=rep["attempted"], failed=failed,
        stored_pods=len(stored), parity=parity, problems=problems[:10],
        s=round(clock() - t, 2), compiled=meter.snapshot())

    metrics: dict = {}
    if run.trace:
        for m in cell.per_layer:
            value = layer_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = END_TO_END[m["name"]](run)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    say("api", requests_in_window=run.apiserver.by_labels(
        "apiserver_request_total", "verb", "resource"),
        wire_bytes_in_window=run.apiserver.by_labels(
            "apiserver_wire_bytes_total", "codec", "direction"))
    say("report", **{k: v for k, v in rep.items()
                     if k not in ("acks", "init_keys", "measured_keys")})
    say("phases", **{k: round(v, 3) for k, v in run.phases.items()})
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    device = {"platform": run.device["platform"],
              "kind": run.device["device_kind"],
              "count": run.device["devices"], "memory_peak_bytes": peak}
    line = {"correct": not problems and failed == 0,
            "attempted": rep["attempted"], "failed": failed,
            "metrics": metrics, "device": device}
    if run.trace and run.device_trace is not None:
        tr = run.device_trace
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        ops = [["program " + n, s] for n, s in xplane.top(tr["module_s"], 3)]
        ops += xplane.top(tr["op_self_s"], 10 - len(ops))
        line["breakdown"] = {
            "device_ops": ops,
            "idle_gaps": xplane.top(run.idle_by_span, 10)}
    line["checks"] = checks
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr,
              flush=True)
    return line


def _rate(run: Run) -> float:
    rate = run.report["rate"]
    if "error" in rate:
        raise RunFailed(f"pods_bound_per_s: {rate['error']}")
    return rate["pods_per_s"]


def _latency(key: str):
    def read(run: Run) -> float:
        lat = run.report["latency"]
        if lat[key] is None:
            raise RunFailed("no pod due inside the window was seen bound")
        if key == "p99_ms" and not lat["p99_resolved"]:
            raise RunFailed(f"p99 of {lat['n']} samples: fewer than ten "
                            "beyond it")
        return lat[key]
    return read


#: the end-to-end metrics, all taken on the client's side by the benchmark
#: itself (host clock); a later PR adds one by adding an entry HERE in a PR
#: of the benchmark kind, since an end-to-end metric changes every cell
END_TO_END = {
    "pods_bound_per_s": _rate,
    "bind_latency_p50_ms": _latency("p50_ms"),
    "bind_latency_p99_ms": _latency("p99_ms"),
    "setup_s": lambda run: run.setup_s,
}
