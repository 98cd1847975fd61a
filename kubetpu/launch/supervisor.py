"""Process supervisor — real OS processes for the control plane.

THE spawn seam (graftcheck PS001): every ``subprocess.Popen`` in
``kubetpu/`` lives here, so child lifecycle — ephemeral-port readiness
banners, health polling, log capture, restart policy, SIGTERM-cascade
shutdown — is owned by one auditable module instead of re-grown ad hoc in
every test or runner that needs a process. Generalizes the
spawn/banner-wait/timeout-kill pattern the PR-12 telemetry smoke proved.

Lifecycle of one child:

1. **spawn** — ``Popen`` with stdout/stderr merged into a pipe; a reader
   thread captures every line into a bounded ring (the tail-on-failure
   evidence) and parses the first ``KUBETPU-READY`` banner (launch.banner).
2. **ready** — the banner arrives (carrying the REAL ephemeral-port URLs);
   if it advertises a ``readyz`` URL the supervisor additionally polls it
   until 200. A child that dies first fails LOUDLY with its captured log
   tail — never a silent hang.
3. **monitored** — the monitor thread samples per-child peak RSS and CPU
   seconds (/proc) and applies the declarative restart policy
   (``never | on-failure[:max]``) when a child dies unexpectedly: the
   respawned child re-runs the same argv, re-banners on a fresh ephemeral
   port, and (for a scheduler replica) re-federates through its informer
   relist + partition machinery.
4. **shutdown** — SIGTERM cascade in two phases: phase-0 children
   (schedulers, watch drivers) first, then phase-1 (collector, apiserver) —
   so the apiserver outlives its clients and its graceful close rides the
   PR-11 WAL path (flush + close after the listener stops: no torn tail).
   ``join(verify=…)`` runs a verification callback BETWEEN the phases,
   while the apiserver is still serving — the store-verified exactly-once
   binding-parity check the multi-process runs report success through.

The supervisor never daemonizes: children are direct children of the
calling process, so a dead supervisor's children die with the test run
(pipes break, CI reaps) instead of orphaning.
"""

from __future__ import annotations

import collections
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from dataclasses import dataclass, field

from .banner import parse_banner

#: lines of child output kept for tail-on-failure evidence
LOG_RING = 800


class SupervisorError(RuntimeError):
    """A child failed the lifecycle contract (died before ready, exhausted
    its restart budget, failed verification). The message embeds the
    captured log tail — the evidence travels with the error."""


@dataclass(frozen=True)
class RestartPolicy:
    """``never`` or ``on-failure[:max]`` (max = respawn budget per child;
    omitted = unbounded)."""

    mode: str = "never"
    max_restarts: int | None = None

    @classmethod
    def parse(cls, spec: str) -> "RestartPolicy":
        spec = (spec or "never").strip()
        if spec == "never":
            return cls("never")
        if spec == "on-failure":
            return cls("on-failure", None)
        if spec.startswith("on-failure:"):
            raw = spec[len("on-failure:"):]
            try:
                n = int(raw)
            except ValueError:
                raise ValueError(
                    f"invalid restart policy {spec!r}: max must be an int"
                ) from None
            if n < 0:
                raise ValueError(f"invalid restart policy {spec!r}: max < 0")
            return cls("on-failure", n)
        raise ValueError(
            f"invalid restart policy {spec!r} (never | on-failure[:max])"
        )

    def allows(self, restarts_so_far: int) -> bool:
        if self.mode != "on-failure":
            return False
        return self.max_restarts is None or restarts_so_far < self.max_restarts


@dataclass
class ChildSpec:
    """One child's declaration: full argv (so tests can supervise tiny
    non-kubetpu scripts), restart policy, readiness contract, and which
    shutdown phase it belongs to (0 = stopped first — clients; 1 = stopped
    after the join verification — servers)."""

    name: str
    argv: list[str]
    restart: str = "never"
    ready_timeout_s: float = 120.0
    expect_banner: bool = True
    env: dict | None = None
    cwd: str | None = None
    shutdown_phase: int = 0
    term_timeout_s: float = 15.0

    def policy(self) -> RestartPolicy:
        return RestartPolicy.parse(self.restart)


class Child:
    """One supervised process: the live Popen, its banner, its log ring,
    and its resource high-water marks (sampled from /proc while alive)."""

    def __init__(self, spec: ChildSpec) -> None:
        self.spec = spec
        self.proc: subprocess.Popen | None = None
        self.banner: dict | None = None
        self.banner_event = threading.Event()
        self.log: "collections.deque[str]" = collections.deque(maxlen=LOG_RING)
        self.stopping = False
        self.failed = False
        self.restarts = 0
        self.peak_rss_bytes: int | None = None
        self.cpu_seconds: float | None = None
        # CPU accumulated by PREVIOUS incarnations (folded in on respawn
        # so a restarted child's cpu_seconds stays cumulative — /proc of
        # the new pid starts at zero)
        self._cpu_base: float = 0.0
        self._reader: threading.Thread | None = None

    # ------------------------------------------------------------- accessors
    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def pid(self) -> int | None:
        return self.proc.pid if self.proc is not None else None

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def url(self, key: str = "url") -> str:
        """A URL field off the readiness banner ('' when absent)."""
        return str((self.banner or {}).get(key) or "")

    def tail(self, n: int = 60) -> str:
        return "".join(list(self.log)[-n:])

    # ----------------------------------------------------------------- stats
    def sample_stats(self) -> None:
        """Best-effort /proc sample of peak RSS (VmHWM) and CPU seconds
        (utime+stime). Linux-only by nature; silently a no-op elsewhere —
        the fields stay None and the record says so."""
        pid = self.pid
        if pid is None:
            return
        try:
            with open(f"/proc/{pid}/status", encoding="ascii") as f:
                for line in f:
                    if line.startswith("VmHWM:"):
                        kb = int(line.split()[1])
                        rss = kb * 1024
                        if self.peak_rss_bytes is None or rss > self.peak_rss_bytes:
                            self.peak_rss_bytes = rss
                        break
            with open(f"/proc/{pid}/stat", encoding="ascii") as f:
                fields = f.read().rsplit(") ", 1)[-1].split()
                # fields after comm: state is [0]; utime/stime are [11]/[12]
                ticks = int(fields[11]) + int(fields[12])
            hz = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100
            cpu = self._cpu_base + ticks / float(hz or 100)
            if self.cpu_seconds is None or cpu > self.cpu_seconds:
                self.cpu_seconds = cpu
        except (OSError, ValueError, IndexError):
            pass

    def stats(self) -> dict:
        out: dict = {
            "pid": self.pid,
            "restarts": self.restarts,
        }
        if self.peak_rss_bytes is not None:
            out["peak_rss_bytes"] = self.peak_rss_bytes
        if self.cpu_seconds is not None:
            out["cpu_seconds"] = round(self.cpu_seconds, 2)
        return out


class Supervisor:
    """See module docstring. ``env`` entries overlay ``os.environ`` for
    every child (specs can overlay further); ``cwd`` is the default child
    working directory."""

    def __init__(self, env: dict | None = None, cwd: str | None = None) -> None:
        self.env = dict(env or {})
        self.cwd = cwd
        self.children: list[Child] = []
        self._by_name: dict[str, Child] = {}
        #: lifecycle evidence: ("died", name, rc, tail) /
        #: ("restarted", name, pid) / ("gave-up", name, rc)
        self.events: list[tuple] = []
        self._lock = threading.Lock()
        self._monitor: threading.Thread | None = None
        self._monitor_stop = threading.Event()
        self._closed = False

    # ----------------------------------------------------------------- spawn
    def child(self, name: str) -> Child:
        return self._by_name[name]

    def spawn(self, spec: ChildSpec, wait_ready: bool = True) -> Child:
        """Launch one child; by default block until its readiness contract
        holds (banner [+ readyz 200]). A child that dies first raises
        ``SupervisorError`` carrying its log tail."""
        if spec.name in self._by_name:
            raise ValueError(f"duplicate child name {spec.name!r}")
        spec.policy()   # validate the restart grammar NOW: an invalid
        #                 --restart must fail the spawn, not kill the
        #                 monitor thread on the first death
        child = Child(spec)
        self.children.append(child)
        self._by_name[spec.name] = child
        self._launch(child)
        if wait_ready:
            self.wait_ready(child)
        return child

    def _launch(self, child: Child) -> None:
        spec = child.spec
        if child.proc is not None:
            # respawn: fold the dead incarnation's CPU into the running
            # total (its last pre-death sample) — peak RSS is already a
            # high-water mark, where max-across-incarnations is correct
            child._cpu_base = child.cpu_seconds or 0.0
        env = dict(os.environ)
        env.setdefault("PYTHONUNBUFFERED", "1")
        env.update(self.env)
        env.update(spec.env or {})
        child.banner = None
        child.banner_event.clear()
        # THE spawn seam (PS001): the one Popen in kubetpu/
        child.proc = subprocess.Popen(
            spec.argv,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=spec.cwd or self.cwd,
        )
        child._reader = threading.Thread(
            target=self._read_output, args=(child, child.proc),
            name=f"supervisor-log-{spec.name}", daemon=True,
        )
        child._reader.start()

    def _read_output(self, child: Child, proc: subprocess.Popen) -> None:
        """Per-child log pump: capture every line, parse the first banner.
        Bound to the Popen it was started for — a respawn gets a fresh
        reader, and this one drains the dead pipe to EOF."""
        stream = proc.stdout
        if stream is None:
            return
        for line in stream:
            child.log.append(line)
            if child.banner is None:
                payload = parse_banner(line)
                if payload is not None:
                    child.banner = payload
                    child.banner_event.set()
        try:
            stream.close()
        except OSError:
            pass

    # ------------------------------------------------------------- readiness
    def wait_ready(self, child: Child) -> dict:
        """Block until ``child`` satisfies its readiness contract; returns
        the banner payload ({} when the spec expects none)."""
        spec = child.spec
        deadline = time.monotonic() + spec.ready_timeout_s
        if spec.expect_banner:
            while not child.banner_event.wait(timeout=0.05):
                child.sample_stats()
                self._check_alive(child, "before its readiness banner")
                if time.monotonic() > deadline:
                    raise SupervisorError(
                        f"child {child.name!r} published no readiness "
                        f"banner within {spec.ready_timeout_s:.0f}s; "
                        f"log tail:\n{child.tail()}"
                    )
            readyz = child.url("readyz")
            if readyz:
                self._poll_readyz(child, readyz, deadline)
        return dict(child.banner or {})

    def _check_alive(self, child: Child, when: str) -> None:
        proc = child.proc
        if proc is not None and proc.poll() is not None:
            # let the reader drain the last buffered lines into the ring
            if child._reader is not None:
                child._reader.join(timeout=2)
            raise SupervisorError(
                f"child {child.name!r} died (rc={proc.returncode}) {when}; "
                f"log tail:\n{child.tail()}"
            )

    def _poll_readyz(self, child: Child, url: str, deadline: float) -> None:
        while True:
            self._check_alive(child, f"while health-polling {url}")
            try:
                with urllib.request.urlopen(url, timeout=2) as resp:
                    if resp.status == 200:
                        return
            except OSError:
                pass
            if time.monotonic() > deadline:
                raise SupervisorError(
                    f"child {child.name!r} never reported ready at {url} "
                    f"within {child.spec.ready_timeout_s:.0f}s; "
                    f"log tail:\n{child.tail()}"
                )
            time.sleep(0.05)

    # --------------------------------------------------------------- monitor
    def start_monitor(self, period_s: float = 0.2) -> None:
        """Start the death-watch/restart/stats thread (idempotent)."""
        if self._monitor is not None and self._monitor.is_alive():
            return
        self._monitor_stop.clear()
        self._monitor = threading.Thread(
            target=self._monitor_loop, args=(period_s,),
            name="supervisor-monitor", daemon=True,
        )
        self._monitor.start()

    def stop_monitor(self) -> None:
        self._monitor_stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5)
            self._monitor = None

    def _monitor_loop(self, period_s: float) -> None:
        while not self._monitor_stop.wait(timeout=period_s):
            for child in list(self.children):
                if child.stopping or child.failed:
                    continue
                if child.alive():
                    child.sample_stats()
                    continue
                self._handle_death(child)

    def _handle_death(self, child: Child) -> None:
        rc = child.proc.returncode if child.proc is not None else None
        with self._lock:
            if child.stopping or child.failed:
                return
            self.events.append(("died", child.name, rc, child.tail(20)))
            policy = child.spec.policy()
            if not policy.allows(child.restarts):
                child.failed = True
                self.events.append(("gave-up", child.name, rc))
                return
            child.restarts += 1
        # respawn OUTSIDE the lock: readiness can take seconds and other
        # children's deaths must still be observable through events.
        # Known tradeoff: the respawn's wait_ready runs ON the monitor
        # thread, so a second near-simultaneous death is detected (and
        # stats sampled) only after this child is ready again — fine for
        # the handful-of-children topologies this supervises; a fleet
        # supervisor would respawn asynchronously
        self._launch(child)
        try:
            self.wait_ready(child)
        except SupervisorError:
            child.failed = True
            self.events.append(("gave-up", child.name, rc))
            return
        self.events.append(("restarted", child.name, child.pid))

    def restarts_total(self) -> int:
        return sum(c.restarts for c in self.children)

    # ---------------------------------------------------------------- deaths
    def kill(self, name: str, sig: int = signal.SIGKILL) -> None:
        """Simulate a crash: hard-signal a child WITHOUT marking it
        stopping — the monitor sees an unexpected death and the restart
        policy decides what happens next. (Graceful stops go through
        ``stop_child``/``shutdown``.)"""
        child = self._by_name[name]
        if child.proc is not None and child.alive():
            child.sample_stats()
            child.proc.send_signal(sig)

    def stop_child(self, name_or_child) -> None:
        """Graceful, restart-free stop of one child: SIGTERM (the CLI's
        handler closes exporters/listeners and — for the apiserver — rides
        the WAL graceful-close path), bounded wait, SIGKILL stragglers."""
        child = (
            name_or_child if isinstance(name_or_child, Child)
            else self._by_name[name_or_child]
        )
        child.stopping = True
        proc = child.proc
        if proc is None:
            return
        child.sample_stats()
        if proc.poll() is None:
            try:
                proc.terminate()
            except OSError:
                pass
        try:
            proc.wait(timeout=child.spec.term_timeout_s)
        except subprocess.TimeoutExpired:
            self.events.append(("term-timeout", child.name))
            proc.kill()
            proc.wait(timeout=10)
        if child._reader is not None:
            child._reader.join(timeout=5)

    # -------------------------------------------------------------- teardown
    def join(self, verify=None) -> None:
        """The verified shutdown: stop the monitor, SIGTERM-cascade
        phase-0 children (clients: schedulers, drivers), run ``verify()``
        while phase-1 children (apiserver, collector) still serve — the
        store-verified binding-parity hook — then cascade phase 1. A
        verify failure still tears everything down, then re-raises."""
        self.stop_monitor()
        for child in reversed(self.children):
            if child.spec.shutdown_phase == 0:
                self.stop_child(child)
        try:
            if verify is not None:
                verify()
        finally:
            for child in reversed(self.children):
                if child.spec.shutdown_phase != 0:
                    self.stop_child(child)
            self._closed = True

    def shutdown(self) -> None:
        """Unconditional SIGTERM cascade (``join`` without verification).
        Safe to call twice; always leaves zero live children behind."""
        if self._closed and not any(c.alive() for c in self.children):
            return
        self.join(verify=None)

    # -------------------------------------------------------------- evidence
    def child_stats(self) -> dict:
        """{name: {pid, restarts, peak_rss_bytes?, cpu_seconds?}} — the
        per-child resource evidence a multi-process result embeds."""
        return {c.name: c.stats() for c in self.children}

    def __enter__(self) -> "Supervisor":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
