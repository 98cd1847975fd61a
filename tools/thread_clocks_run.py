"""``benchmark/run.py``'s run of one cell, plus one line with who held the
scheduler process's core inside the window: wall and CPU seconds of the loop
thread by phase, of the dispatcher's workers by call type, the diagnostics
listener's requests and CPU by endpoint, the process's CPU (its own counter
and ``/proc``), the six per-layer shares that read them (computed in an
untraced run too, and in the cell whose list of metrics is pinned), and the
loop's spans by name with their ``cpu_s``, from one ``/trace`` read after the
window has closed. Beside them, the apiserver's bind ops by result
(``apiserver_pod_binds_total``, ISSUE 39: ``null`` on a program without
the op), its pods ``:bulk`` ops by path, and the pods the scheduler placed
in the same window (``scheduler_schedule_attempts_total{result=
"scheduled"}``): ``bound`` ÷ ``scheduled`` near 100 says every bind took
the op.

    python3 tools/thread_clocks_run.py --workload basic-5k.saturate \\
        --seed <n> --seconds 51 --trace <0|1>
    python3 tools/thread_clocks_run.py --rehearse basic-5k.saturate 1

Run it from the root of the checkout to be measured (the working directory,
not this file's place, is the tree that runs), so that one copy serves the
parent's checkout too: there the CPU families are absent and read ``null``.
``--rehearse`` hands the rest to ``benchmark/tests/rehearse.py`` (the CPU,
a toy cluster: control flow and counts only). The harness's own numbers are
untouched: everything here is read from what it had gathered anyway, but for
the one ``/trace``.
"""

from __future__ import annotations

import json
import os
import sys

WALL = "scheduler_loop_phase_seconds_total"
CPU = "scheduler_loop_phase_cpu_seconds_total"
WORKER_WALL = "scheduler_api_dispatcher_worker_seconds_total"
WORKER_CPU = "scheduler_api_dispatcher_worker_cpu_seconds_total"
REQUESTS = "scheduler_diagnostics_requests_total"
REQUEST_CPU = "scheduler_diagnostics_request_cpu_seconds_total"
POD_BINDS = "apiserver_pod_binds_total"
BULK_OPS = "apiserver_bulk_ops_total"
ATTEMPTS = "scheduler_schedule_attempts_total"
SHARES = ("loop_thread_cpu_share", "loop_stall_share", "loop_blocked_share",
          "loop_sleep_share", "dispatcher_cpu_share", "dispatcher_busy_share",
          "scheduler_unclocked_cpu_share", "scheduler_cpu_share")


def paired(delta, first: str, second: str, label: str) -> dict:
    """label value -> [Δfirst, Δsecond]; None where a family is absent."""
    a, b = delta.by_labels(first, label), delta.by_labels(second, label)
    have = delta.after.samples
    return {k: [a.get(k, 0.0) if first in have else None,
                b.get(k, 0.0) if second in have else None]
            for k in sorted(set(a) | set(b))}


def share(name: str, run):
    """The per-layer reader's value; None where this checkout's benchmark
    has no such reader (the parent's)."""
    from benchmark.harness.manifest import layer_reader

    try:
        return layer_reader(name).read(run)
    except ModuleNotFoundError:
        return None


def spans_by_name(diag_url: str, t0: float, t1: float) -> dict:
    """name -> [spans, wall seconds, cpu_s] of the spans that began inside
    the window and carry a ``cpu_s`` (the loop's, and the bulk bind's)."""
    from benchmark.harness.promtext import fetch

    out: dict[str, list] = {}
    for ev in json.loads(fetch(diag_url + "/trace")).get("traceEvents", ()):
        cpu_s = ev.get("args", {}).get("cpu_s")
        if cpu_s is None or not t0 <= ev["ts"] / 1e6 <= t1:
            continue
        cell = out.setdefault(ev["name"], [0, 0.0, 0.0])
        cell[0] += 1
        cell[1] += ev["dur"] / 1e6
        cell[2] += cpu_s
    return out


def install() -> None:
    from benchmark.harness import phases

    close_window = phases._close_window

    def close_and_tell(run, gen, meter, spans, session, opened):
        t1, xspace = close_window(run, gen, meter, spans, session, opened)
        d = run.scheduler
        phases.say(
            "thread-clocks", window_s=run.window_s,
            loop=paired(d, WALL, CPU, "phase"),
            workers=paired(d, WORKER_WALL, WORKER_CPU, "call_type"),
            listener=paired(d, REQUESTS, REQUEST_CPU, "endpoint"),
            process_cpu_s=(d.total("process_cpu_seconds_total")
                           if "process_cpu_seconds_total" in d.after.samples
                           else None),
            proc_stat_cpu_s=run.cpu_s.get("scheduler"),
            shares={name: share(name, run) for name in SHARES},
            spans=spans_by_name(run.diag_url, opened.t0, t1),
            pod_binds=(run.apiserver.by_labels(POD_BINDS, "result")
                       if POD_BINDS in run.apiserver.after.samples
                       else None),
            bulk_ops=run.apiserver.by_labels(BULK_OPS, "resource", "path"),
            scheduled=d.total(ATTEMPTS, result="scheduled"))
        return t1, xspace

    phases._close_window = close_and_tell


def main(argv: list[str]) -> int:
    sys.path.insert(0, os.getcwd())
    install()
    if argv and argv[0] == "--rehearse":
        from benchmark.tests import rehearse

        return rehearse.main(argv[1:])
    from benchmark import run

    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
