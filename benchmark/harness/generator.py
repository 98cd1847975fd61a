"""The load generator: a child process of the benchmark, REST only.

    python benchmark/harness/generator.py --server URL --cell FILE --seed N

ONE general generator reads a configuration (the cluster) and a traffic mix
(how pods arrive), both data: ``--cell`` is a JSON object holding the cell's
``config`` and ``traffic`` as the harness read them from their files. It sends bulk creates, watches the pods
through the store's watch (``apiserver/remote.py``) as any client would, and
keeps every stamp on one clock in one process: when a pod was DUE on the
schedule, when its create was sent, and when the watch showed it bound. It
never initialises a JAX backend (asserted at exit): the chip belongs to the
scheduler.

It takes commands as JSON lines on stdin and answers with JSON lines on
stdout: post, await_init, burst, start, pause, stop, report, quit. End of
input is quit.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import stats  # noqa: E402
from benchmark.harness.promtext import fetch  # noqa: E402

#: one clock for every stamp: CLOCK_MONOTONIC, which the harness's own
#: process reads too, so window edges and stamps compare across processes
clock = time.perf_counter


class Ledger:
    """Every pod this generator created, and what the watch said of it."""

    def __init__(self) -> None:
        self.lock = threading.Condition()
        self.index: dict[str, int] = {}
        self.keys: list[str] = []
        self.measured: list[bool] = []
        self.due: list[float] = []
        self.sent: list[float] = []
        self.bound_at: list[float | None] = []
        self.node: list[str] = []
        self.n_bound = 0
        #: (arrival stamp, measured pods first seen bound) per watch delivery
        self.deliveries: list[tuple[float, int]] = []
        self.violations: list[str] = []

    def register(self, keys: list[str], measured: bool, due: list[float],
                 sent: float) -> None:
        with self.lock:
            for key, d in zip(keys, due):
                self.index[key] = len(self.keys)
                self.keys.append(key)
                self.measured.append(measured)
                self.due.append(d)
                self.sent.append(sent)
                self.bound_at.append(None)
                self.node.append("")

    def deliver(self, stamp: float, bound: list[tuple[str, str]]) -> None:
        """One delivery of the watch: (pod key, node name) of every event
        that shows a pod with a node."""
        with self.lock:
            fresh = 0
            for key, node in bound:
                i = self.index.get(key)
                if i is None:
                    continue
                if self.bound_at[i] is None:
                    self.bound_at[i] = stamp
                    self.node[i] = node
                    self.n_bound += 1
                    fresh += self.measured[i]
                elif self.node[i] != node:
                    self.violations.append(
                        f"{key} bound to {self.node[i]} and then to {node}")
            if fresh:
                self.deliveries.append((stamp, fresh))
            self.lock.notify_all()

    def standing(self) -> int:
        return len(self.keys) - self.n_bound

    def wait_all_bound(self, timeout_s: float) -> bool:
        """Until every pod created so far is seen bound."""
        deadline = clock() + timeout_s
        with self.lock:
            while self.n_bound < len(self.keys):
                left = deadline - clock()
                if left <= 0:
                    return False
                self.lock.wait(min(left, 0.5))
            return True


class Generator:
    def __init__(self, server: str, config: dict, traffic: dict,
                 seed: int) -> None:
        from kubetpu.apiserver import RemoteStore

        from benchmark.harness import templates

        self.remote = RemoteStore(server)
        # started together with the apiserver: wait until it serves
        deadline = clock() + 300
        while True:
            try:
                fetch(server + "/readyz", timeout_s=2.0)
                break
            except OSError:
                if clock() > deadline:
                    raise
                time.sleep(0.05)
        self.config, self.traffic, self.seed = config, traffic, seed
        self.ledger = Ledger()
        self.stop_watch = threading.Event()
        self.stop_traffic = threading.Event()
        self.traffic_thread: threading.Thread | None = None
        self.errors: list[str] = []
        measured = config["measured_pods"]
        self.template = templates.resolve(
            templates.POD_TEMPLATES, measured["template"])
        self.namespace = measured["namespace"]
        #: pods the cluster holds: past it a created pod can never bind
        self.capacity = templates.capacity(config)
        #: when the traffic was told to end, on ``clock`` (None while it runs)
        self.ended_at: float | None = None
        #: how long after its window's close that was (``pause`` knows)
        self.past_t1_s: float | None = None
        self.serial = 0
        self.watch_thread = threading.Thread(
            target=self._watch, name="generator-watch", daemon=True)
        self.watch_thread.start()

    # ------------------------------------------------------------- the watch
    def _watch(self) -> None:
        from kubetpu.client.informers import PODS

        watcher = self.remote.watch(PODS, 0)
        watcher.poll_timeout_s = 1.0     # long poll: no busy loop
        while not self.stop_watch.is_set():
            try:
                events = watcher.poll()
            except ConnectionError as e:
                if not self.stop_watch.is_set():
                    self.errors.append(f"watch: {e}")
                return
            stamp = clock()
            bound = [(e.key, e.obj.node_name) for e in events
                     if e.type != "DELETED" and e.obj.node_name]
            if bound:
                self.ledger.deliver(stamp, bound)

    # ------------------------------------------------------------- creates
    def _create(self, kind: str, items: list, chunk: int) -> None:
        from kubetpu.store.memstore import bulk_result_error

        for i in range(0, len(items), chunk):
            ops = [{"op": "create", "key": k, "object": o}
                   for k, o in items[i:i + chunk]]
            for res in self.remote.bulk(kind, ops):
                err = bulk_result_error(res)
                if err is not None:
                    raise err

    def _create_pods(self, n: int, due: list[float] | None = None,
                     template=None, namespace: str | None = None,
                     measured: bool = True, prefix: str = "m") -> None:
        """One bulk create of ``n`` pods, registered before it is sent so
        that no watch event can overtake the ledger."""
        from kubetpu.client.informers import PODS

        template = template or self.template
        namespace = namespace or self.namespace
        items = []
        for _ in range(n):
            # the seed is in the name: names order hash-keyed structures
            name = f"{prefix}{self.seed:x}-{self.serial}"
            self.serial += 1
            items.append((f"{namespace}/{name}", template(name, namespace)))
        now = clock()
        self.ledger.register([k for k, _ in items], measured,
                             due if due is not None else [now] * n, now)
        self._create(PODS, items, chunk=max(n, 1))

    # ------------------------------------------------------------- commands
    def post(self) -> dict:
        """Nodes, namespaces and init pods, posted BEFORE the scheduler
        starts: its informers then list the whole cluster at once, so the
        init pods bind in full batches whatever the timing, and the
        programs they compile are the same in every run."""
        from kubetpu.api import types as t
        from kubetpu.client.informers import NAMESPACES, NODES

        from benchmark.harness import templates

        cfg = self.config
        t0 = clock()
        node_of = templates.resolve(templates.NODE_TEMPLATES,
                                    cfg["node_template"])
        zones = tuple(cfg.get("zones", ()))
        nodes = [node_of(i, zones) for i in range(cfg["nodes"])]
        self._create(NODES, [(n.name, n) for n in nodes], chunk=1000)
        self._create(NAMESPACES, [
            (name, t.Namespace(name=name))
            for name in cfg.get("namespaces", ())], chunk=64)
        init = cfg["init_pods"]
        template = templates.resolve(templates.POD_TEMPLATES,
                                     init["template"])
        left = init["count"]
        while left > 0:
            n = min(left, 1000)
            self._create_pods(n, template=template,
                              namespace=init["namespace"], measured=False,
                              prefix="i")
            left -= n
        return {"event": "posted", "nodes": len(nodes),
                "init_pods": init["count"], "s": round(clock() - t0, 3)}

    def await_init(self, timeout_s: float) -> dict:
        t0 = clock()
        ok = self.ledger.wait_all_bound(timeout_s)
        return {"event": "init_bound", "ok": ok,
                "waited_s": round(clock() - t0, 3)}

    def burst(self, n: int, timeout_s: float) -> dict:
        t0 = clock()
        self._create_pods(n)
        ok = self.ledger.wait_all_bound(timeout_s)
        return {"event": "burst_done", "ok": ok, "n": n,
                "s": round(clock() - t0, 3)}

    def start(self) -> dict:
        mode = self.traffic["mode"]
        loop = {"saturate": self._saturate, "paced": self._paced}[mode]
        self.traffic_thread = threading.Thread(
            target=self._guard, args=(loop,), name="generator-traffic",
            daemon=True)
        self.traffic_thread.start()
        return {"event": "started", "mode": mode}

    def _guard(self, loop) -> None:
        try:
            loop()
        except Exception as e:  # noqa: BLE001 — reported, and fails the run
            self.errors.append(f"traffic: {type(e).__name__}: {e}")

    def _saturate(self) -> None:
        """Closed loop: keep ``standing_pods`` unbound pods standing, topped
        up in bulk creates of ``bulk_create``, so every batch is full."""
        standing = self.traffic["standing_pods"]
        bulk = self.traffic["bulk_create"]
        ledger = self.ledger
        while not self.stop_traffic.is_set():
            with ledger.lock:
                if ledger.standing() > standing - bulk:
                    ledger.lock.wait(0.05)
                    continue
            self._create_pods(bulk)

    def _paced(self) -> None:
        """Open loop: Poisson arrivals from the seed at a fixed rate, sent
        in one bulk create per tick, each pod stamped with its DUE time."""
        import numpy as np

        rate = float(self.traffic["rate_pods_per_s"])
        tick = self.traffic["tick_ms"] / 1e3
        rng = np.random.default_rng(self.seed)
        start = clock()
        gaps = rng.exponential(1.0 / rate, size=8192)
        pos, next_due, k = 0, start + float(gaps[0]), 0
        while not self.stop_traffic.is_set():
            now = clock()
            due = []
            while next_due <= now:
                due.append(next_due)
                pos += 1
                if pos == len(gaps):
                    gaps = rng.exponential(1.0 / rate, size=8192)
                    pos = 0
                next_due += float(gaps[pos])
            if due:
                self._create_pods(len(due), due=due)
            k += 1
            wait = start + k * tick - clock()
            if wait > 0:
                time.sleep(wait)

    def _end_traffic(self) -> None:
        """No create starts after this; the one in flight lands."""
        if self.ended_at is None:
            self.stop_traffic.set()
            self.ended_at = clock()

    def pause(self, t1: float) -> dict:
        """The traffic ends, and nothing else: no waiting, not even for the
        bulk create in flight. ``t1`` is the window's close on ``clock``;
        ``created_at_t1`` counts the pods whose create was sent by then, so
        ``created`` of the drain less it is what was made outside the
        window."""
        self._end_traffic()
        self.past_t1_s = round(self.ended_at - t1, 3)
        with self.ledger.lock:     # stamps of one clock, in their order
            at_t1 = bisect.bisect_right(self.ledger.sent, t1)
        return {"event": "paused", "created_at_t1": at_t1,
                "capacity": self.capacity, "past_t1_s": self.past_t1_s}

    def stop(self, timeout_s: float) -> dict:
        """The traffic ends, if ``pause`` has not ended it, and the backlog
        binds. ``s`` runs from the end of the traffic to the last bind the
        watch showed (to now, where pods are left unbound), however late
        the harness asks. A backlog that cannot bind because the cluster
        holds no more pods says so under ``why``."""
        self._end_traffic()
        if self.traffic_thread is not None:
            self.traffic_thread.join(timeout=60)
        ok = self.ledger.wait_all_bound(timeout_s)
        with self.ledger.lock:
            last = max((b for b in self.ledger.bound_at if b is not None),
                       default=self.ended_at)
        end = last if ok else clock()
        doc = {"event": "drained", "ok": ok,
               "created": len(self.ledger.keys),
               "unbound": self.ledger.standing(),
               "s": round(max(end - self.ended_at, 0.0), 3)}
        if not ok and doc["created"] >= self.capacity:
            past = "" if self.past_t1_s is None else (
                f"; the load ran {self.past_t1_s} s past the window")
            doc["why"] = (
                f"the cluster is full: created {doc['created']} of "
                f"{self.capacity}{past} ({doc['unbound']} pods unbound "
                f"after {doc['s']} s)")
        return doc

    def report(self, t0: float, t1: float, out: str) -> dict:
        """Everything the client saw, reduced over the window [t0, t1], with
        the acks the check reads back against the store."""
        lg = self.ledger
        with lg.lock:
            idx = [i for i, m in enumerate(lg.measured) if m]
            due = [lg.due[i] for i in idx]
            sent = [lg.sent[i] for i in idx]
            bound_at = [lg.bound_at[i] for i in idx]
            deliveries = list(lg.deliveries)
            acks = {k: n for k, n in zip(lg.keys, lg.node) if n}
            init_keys = [k for k, m in zip(lg.keys, lg.measured) if not m]
            violations = list(lg.violations)
        doc: dict = {
            "mode": self.traffic["mode"],
            "attempted": len(idx),
            "never_bound": sum(1 for b in bound_at if b is None),
            "violations": violations, "errors": list(self.errors),
            "acks": acks, "init_keys": init_keys,
            "measured_keys": [lg.keys[i] for i in idx],
            "bound_in_window": sum(n for t, n in deliveries if t0 <= t <= t1),
            "backlog_t0": stats.backlog_at(sent, bound_at, t0),
            "backlog_t1": stats.backlog_at(sent, bound_at, t1),
        }
        # the window in half seconds: how lumpy the binding was
        bins = [0] * (int((t1 - t0) / 0.5) + 1)
        for t, n in deliveries:
            if t0 <= t <= t1:
                bins[int((t - t0) / 0.5)] += n
        doc["bound_per_half_second"] = bins
        doc["deliveries_in_window"] = sum(
            1 for t, _n in deliveries if t0 <= t <= t1)
        mid = (t0 + t1) / 2
        for name, a, b in (("rate", t0, t1), ("rate_first_half", t0, mid),
                           ("rate_second_half", mid, t1)):
            try:
                rate, pods, events = stats.slope_rate(deliveries, a, b)
                doc[name] = {"pods_per_s": rate, "pods": pods,
                             "deliveries": events}
            except ValueError as e:
                doc[name] = {"error": str(e)}
        try:
            doc["rate_between_outermost_events"] = \
                stats.interval_rate(deliveries, t0, t1)[0]
        except ValueError:
            pass
        if self.traffic["mode"] == "paced":
            lat, missing = stats.due_latencies_ms(due, bound_at, t0, t1)
            late = [(s - d) * 1e3 for s, d in zip(sent, due) if t0 <= d <= t1]
            doc["latency"] = {
                "n": len(lat), "missing": missing,
                "p50_ms": stats.quantile(lat, 0.5) if lat else None,
                "p99_ms": stats.quantile(lat, 0.99) if lat else None,
                "p99_resolved": stats.tail_is_resolved(len(lat), 0.99),
                "generator_late_p50_ms":
                    stats.quantile(late, 0.5) if late else None,
                "generator_late_p99_ms":
                    stats.quantile(late, 0.99) if late else None,
            }
        with open(out, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        return {"event": "report_done", "out": out}

    def close(self) -> None:
        self.stop_traffic.set()
        self.stop_watch.set()
        self.watch_thread.join(timeout=10)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--server", required=True)
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    with open(args.cell, encoding="utf-8") as f:
        cell = json.load(f)
    gen = Generator(args.server, cell["config"], cell["traffic"], args.seed)

    def say(doc: dict) -> None:
        print(json.dumps(doc), flush=True)

    say({"event": "hello", "pid": os.getpid()})
    try:
        for line in sys.stdin:
            cmd = json.loads(line)
            what = cmd["cmd"]
            try:
                if what == "post":
                    say(gen.post())
                elif what == "await_init":
                    say(gen.await_init(cmd.get("timeout_s", 900.0)))
                elif what == "burst":
                    say(gen.burst(cmd["n"], cmd.get("timeout_s", 600.0)))
                elif what == "start":
                    say(gen.start())
                elif what == "pause":
                    say(gen.pause(cmd["t1"]))
                elif what == "stop":
                    say(gen.stop(cmd.get("timeout_s", 60.0)))
                elif what == "report":
                    say(gen.report(cmd["t0"], cmd["t1"], cmd["out"]))
                elif what == "quit":
                    break
                else:
                    say({"event": "error", "error": f"unknown {what!r}"})
            except Exception as e:  # noqa: BLE001 — the harness decides
                say({"event": "error", "cmd": what,
                     "error": f"{type(e).__name__}: {e}"})
    finally:
        gen.close()
    from jax._src import xla_bridge

    touched = bool(xla_bridge._backends)
    say({"event": "bye", "backend_initialised": touched})
    return 1 if touched else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
