"""The benchmark's one command: one cell, one run, one new process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

It needs the TPU (and as many chips as the cell asks for): without, it says
in one line what ``kubetpu.device_stamp()`` found, prints no result and
exits non-zero. Earlier lines of the output are the phases, each timed; the
last line is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(end-to-end with ``--trace 0``, per-layer with ``--trace 1``), ``device``,
and ``breakdown`` when traced. How a run is put together:
``benchmark/harness/phases.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if not os.path.isfile(os.path.join(ROOT, "kubetpu", "cli.py")):
        print("benchmark: no result: this directory holds the benchmark but "
              "not the program (kubetpu/)", file=sys.stderr)
        return 2
    from benchmark.harness.phases import RunFailed, run_cell

    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START)
    except RunFailed as e:
        print(f"benchmark: no result: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
