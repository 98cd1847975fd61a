"""``benchmark/run.py``'s run of one cell, plus one line with the shape of
the final cluster as the store holds it when the harness reads it back for
its check: how many nodes hold bound ``color=red`` pods, how many of them
hold 40 (full by CPU), and the counts on the others; and, in a traced run,
one line with the ``encode-podaffinity`` spans the harness polled from the
scheduler's ``/trace`` (it prints no span by name, and
``harness/spans.py::PRIORITY`` does not list this one). A second line is
the census of the cell's init pods over ALL bindings: the most pods of the
cell's init namespace any node holds, and how many pods of its measured
namespace sit on a node that holds an init pod.

    python3 tools/affinity_nodes_run.py \\
        --workload podmatchinganti-5k.saturate --seed <n> --seconds 51 \\
        --trace <0|1>

Run it from the root of the checkout to be measured (the working directory,
not this file's place, is the tree that runs), so that one copy serves the
parent's checkout too. It shows what the preference achieved (pods packed
node after node) and the evidence of
``podmatchinganti-5k``'s fifth guarantee over all bindings (init pods a
node at most 1, measured pods on init nodes 0) until
``reference/validity.py`` holds an anti-affinity rule; the comparison that
decides ``correct`` is the harness's own, untouched.
"""

from __future__ import annotations

import collections
import json
import os
import sys

FULL = 40


def red_packing(nodes, pods) -> dict:
    """Nodes holding bound ``color=red`` pods, how many at 40, how many
    over, and the counts on the others."""
    per_node = collections.Counter(
        p.node_name for _key, p in pods
        if p.node_name and dict(p.labels).get("color") == "red")
    return {
        "phase": "nodes", "nodes": len(nodes),
        "red_bound": sum(per_node.values()),
        "nodes_holding_red": len(per_node),
        "nodes_at_40": sum(1 for c in per_node.values() if c == FULL),
        "nodes_over_40": sum(1 for c in per_node.values() if c > FULL),
        "counts_on_the_others": sorted(
            c for c in per_node.values() if c < FULL)}


def anti_census(nodes, pods, init_ns: str, measured_ns: str) -> dict:
    """Over ALL bound pods: those of ``init_ns`` (the running pods with the
    required anti-affinity) and the most any node holds; those of
    ``measured_ns`` and how many sit on a node that holds an init pod."""
    init = collections.Counter(p.node_name for _key, p in pods
                               if p.node_name and p.namespace == init_ns)
    measured = [p.node_name for _key, p in pods
                if p.node_name and p.namespace == measured_ns]
    return {
        "phase": "anti", "nodes": len(nodes),
        "init_bound": sum(init.values()), "init_nodes": len(init),
        "init_pods_max_a_node": max(init.values(), default=0),
        "measured_bound": len(measured),
        "measured_on_init_nodes": sum(1 for n in measured if n in init)}


def main(argv: list[str]) -> int:
    sys.path.insert(0, os.getcwd())
    from benchmark import run
    from benchmark.harness import check
    from benchmark.harness.manifest import Cell, load_manifest

    config = Cell(load_manifest(), argv[argv.index("--workload") + 1]).config
    harness_readback = check.readback

    def readback(url):
        nodes, pods = harness_readback(url)
        print(json.dumps(red_packing(nodes, pods)), flush=True)
        print(json.dumps(anti_census(
            nodes, pods, config["init_pods"]["namespace"],
            config["measured_pods"]["namespace"])), flush=True)
        return nodes, pods

    check.readback = readback

    from benchmark.harness import spans

    harness_by_name = spans.SpanLog.by_name

    def by_name(self, offset_s=0.0):
        mine = [e - b for name, b, e in self.spans.values()
                if name == "encode-podaffinity"]
        print(json.dumps({"phase": "spans", "polled": len(self.spans),
                          "encode-podaffinity": len(mine),
                          "seconds": sum(mine)}), flush=True)
        return harness_by_name(self, offset_s)

    spans.SpanLog.by_name = by_name
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
