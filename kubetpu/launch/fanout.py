"""The watch fan-out load a ``kubetpu watch-driver`` process carries.

``Cluster(fanout_watchers=N, fanout_procs=M)`` (``kubetpu up --watch-fanout
N --fanout-procs M``) spreads N pod watchers over M driver processes; each
driver holds one ``WatchFanout``.
"""

from __future__ import annotations

import threading
import time


class WatchFanout:
    """N extra concurrent pod watchers against the apiserver — the heavy
    fan-out load of a big cluster (hundreds of kubelets/controllers each
    holding a watch). Each watcher is its own RemoteStore connection on
    its own thread, long-polling the pods bucket; a compaction relists
    and resumes. The load is the POINT (every store write wakes every
    watcher, each draining the same events — the serialize-once body ring
    pays one encode for all of them), so the threads run until ``stop``."""

    def __init__(self, url: str, wire: str, n: int) -> None:
        from ..apiserver import RemoteStore
        from ..client.informers import PODS

        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

        def loop() -> None:
            try:
                rs = RemoteStore(url, wire=wire)
                w = rs.watch(PODS, 0)
                # 2s long-poll: a write still wakes the watcher instantly
                # through the store's condition variable — the timeout
                # only bounds IDLE churn (hundreds of watchers at 0.5s
                # would burn the host on empty polls, starving the very
                # scheduler the fan-out is supposed to load)
                w.poll_timeout_s = 2.0
                while not self._stop.is_set():
                    try:
                        w.poll()
                    except Exception:
                        if self._stop.is_set():
                            return
                        # compacted cursor or transient transport error:
                        # re-anchor at the current head and keep watching
                        try:
                            _items, rv = rs.list(PODS)
                            w = rs.watch(PODS, rv)
                            w.poll_timeout_s = 2.0
                        except Exception:
                            time.sleep(0.05)
            except Exception:
                pass    # a dead extra watcher must not kill the driver

        for _ in range(n):
            t = threading.Thread(target=loop, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)
