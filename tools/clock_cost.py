"""What one ``PhaseClock.switch`` and one ``tracer.span`` cost on this
host, in nanoseconds: a loop of 100,000 each, the median of five.

    python3 tools/clock_cost.py

Run it from the root of the checkout to be measured (the working directory,
not this file's place, is the tree that runs), so that one copy serves the
parent's checkout too. The served loop makes about 25 switches and 15 spans
an iteration of half a second and more.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

N = 100_000


def main() -> int:
    sys.path.insert(0, os.getcwd())
    from kubetpu.tracing import PhaseClock, Tracer

    def switches() -> None:
        clock = PhaseClock()
        switch = clock.switch
        for _ in range(N // 2):
            switch("cycle")
            switch("drain")

    def spans() -> None:
        span = Tracer().span
        for _ in range(N):
            with span("encode", cycle=1):
                pass

    def ns_each(fn) -> float:
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            fn()
            times.append((time.perf_counter() - t0) / N * 1e9)
        return statistics.median(times)

    print(json.dumps({"tree": os.getcwd(), "n": N,
                      "switch_ns": ns_each(switches),
                      "span_ns": ns_each(spans)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
