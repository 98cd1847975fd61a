"""``benchmark/run.py``'s run of one cell, plus one line with the zones'
counts of the bound ``color=blue`` pods as the store holds them when the
harness reads it back for its check: after the drain, outside the window.
With ``--by-node``, the line counts the bound ``foo=bar`` pods of the cell's
measured namespace by node instead: how many sit on a tainted node, and the
least and most any untainted node holds.

    python3 tools/spread_zones_run.py --workload topologyspread-5k.saturate \\
        --seed <n> --seconds 51 --trace <0|1>
    python3 tools/spread_zones_run.py --by-node \\
        --workload nodeinclusion-5k.saturate --seed <n> --seconds 51 \\
        --trace <0|1>

Run it from the root of the checkout to be measured (the working directory,
not this file's place, is the tree that runs), so that one copy serves the
parent's checkout too. It is the evidence of the configuration's fifth
guarantee (``max_minus_min`` ≤ maxSkew 5; by node: ``on_tainted`` 0 and
``max_minus_min`` ≤ maxSkew 1) until ``reference/validity.py`` holds a rule
over all bindings; the comparison that decides ``correct`` is the harness's
own, untouched.
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


def bound_by_node(nodes, pods, namespace: str,
                  label=("foo", "bar")) -> dict[str, int]:
    """Bound pods of ``namespace`` carrying ``label``: ``on_tainted`` on a
    node with a taint, ``min`` and ``max`` over the untainted nodes (an
    empty one counts 0), ``max_minus_min``."""
    tainted = {n.name for n in nodes if n.taints}
    counts = {n.name: 0 for n in nodes if n.name not in tainted}
    on_tainted = 0
    for _key, p in pods:
        if (not p.node_name or p.namespace != namespace
                or dict(p.labels).get(label[0]) != label[1]):
            continue
        if p.node_name in tainted:
            on_tainted += 1
        else:
            counts[p.node_name] = counts.get(p.node_name, 0) + 1
    vals = list(counts.values()) or [0]
    return {"untainted_nodes": len(counts), "tainted_nodes": len(tainted),
            "bound": sum(vals) + on_tainted, "on_tainted": on_tainted,
            "min": min(vals), "max": max(vals),
            "max_minus_min": max(vals) - min(vals)}


def main(argv: list[str]) -> int:
    sys.path.insert(0, os.getcwd())
    from benchmark import run
    from benchmark.harness import check
    from benchmark.harness.manifest import Cell, load_manifest

    by_node = "--by-node" in argv
    argv = [a for a in argv if a != "--by-node"]
    harness_readback = check.readback

    def readback(url):
        nodes, pods = harness_readback(url)
        if by_node:
            workload = argv[argv.index("--workload") + 1]
            ns = Cell(load_manifest(), workload).config[
                "measured_pods"]["namespace"]
            doc = {"phase": "nodes", **bound_by_node(nodes, pods, ns)}
        else:
            counts = bound_by_zone(nodes, pods)
            vals = list(counts.values()) or [0]
            doc = {"phase": "zones", "blue_bound_by_zone": counts,
                   "max_minus_min": max(vals) - min(vals)}
        print(json.dumps(doc), flush=True)
        return nodes, pods

    check.readback = readback
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
