"""``benchmark/run.py``'s run of one cell, plus one line with the zones'
counts of the bound ``color=blue`` pods as the store holds them when the
harness reads it back for its check: after the drain, outside the window.

    python3 tools/spread_zones_run.py --workload topologyspread-5k.saturate \\
        --seed <n> --seconds 51 --trace <0|1>

Run it from the root of the checkout to be measured (the working directory,
not this file's place, is the tree that runs), so that one copy serves the
parent's checkout too. It is the evidence of the configuration's fifth
guarantee (``max_minus_min`` ≤ maxSkew 5) until ``reference/validity.py``
holds a rule over all bindings; the comparison that decides ``correct`` is
the harness's own, untouched.
"""

from __future__ import annotations

import collections
import json
import os
import sys

ZONE = "topology.kubernetes.io/zone"


def bound_by_zone(nodes, pods, label=("color", "blue")) -> dict[str, int]:
    """Bound pods carrying ``label``, counted by their node's zone.
    ``pods`` as ``RemoteStore.list`` gives them: (key, pod) pairs."""
    zone_of = {n.name: dict(n.labels).get(ZONE, "") for n in nodes}
    counts: collections.Counter = collections.Counter()
    for _key, p in pods:
        if p.node_name and dict(p.labels).get(label[0]) == label[1]:
            counts[zone_of.get(p.node_name, "?")] += 1
    return dict(counts)


def main(argv: list[str]) -> int:
    sys.path.insert(0, os.getcwd())
    from benchmark import run
    from benchmark.harness import check

    harness_readback = check.readback

    def readback(url):
        nodes, pods = harness_readback(url)
        counts = bound_by_zone(nodes, pods)
        vals = list(counts.values()) or [0]
        print(json.dumps({"phase": "zones", "blue_bound_by_zone": counts,
                          "max_minus_min": max(vals) - min(vals)}),
              flush=True)
        return nodes, pods

    check.readback = readback
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
