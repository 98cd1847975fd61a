"""How the knee of a paced cell was found, once, on the chip: the cell run at
one arrival rate of the builder's choosing, in a process of its own.

    python3 benchmark/tests/knee.py <paced cell> <pods per second> <seconds>

The knee is the highest rate at which the backlog does not grow through the
window; the traffic file then holds four fifths of it. The sweep itself is
a loop in the shell over rates (PERF.md records it). It needs the TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    from benchmark.harness.manifest import Cell, load_manifest
    from benchmark.harness.phases import run_cell

    name, rate, seconds = argv[0], float(argv[1]), float(argv[2])
    cell = Cell(load_manifest(), name)
    cell.traffic = {**cell.traffic, "rate_pods_per_s": rate}
    line = run_cell(name, seed=int(rate), seconds=seconds, trace=False,
                    t_start=T_START, cell=cell)
    print(json.dumps({"rate_pods_per_s": rate, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
