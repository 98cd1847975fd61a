"""``benchmark/run.py``'s run of one cell, plus one line with the shape of
the final cluster as the store holds it when the harness reads it back for
its check: how many nodes hold bound ``color=red`` pods, how many of them
hold 40 (full by CPU), and the counts on the others; and, in a traced run,
one line with the ``encode-podaffinity`` spans the harness polled from the
scheduler's ``/trace`` (it prints no span by name, and
``harness/spans.py::PRIORITY`` does not list this one).

    python3 tools/affinity_nodes_run.py \\
        --workload preferredaffinity-5k.saturate --seed <n> --seconds 51 \\
        --trace <0|1>

Run it from the root of the checkout to be measured (the working directory,
not this file's place, is the tree that runs), so that one copy serves the
parent's checkout too. It shows what the preference achieved (pods packed
node after node); the comparison that decides ``correct`` is the harness's
own, untouched.
"""

from __future__ import annotations

import collections
import json
import os
import sys

FULL = 40


def main(argv: list[str]) -> int:
    sys.path.insert(0, os.getcwd())
    from benchmark import run
    from benchmark.harness import check

    harness_readback = check.readback

    def readback(url):
        nodes, pods = harness_readback(url)
        per_node = collections.Counter(
            p.node_name for _key, p in pods
            if p.node_name and dict(p.labels).get("color") == "red")
        print(json.dumps({
            "phase": "nodes", "nodes": len(nodes),
            "red_bound": sum(per_node.values()),
            "nodes_holding_red": len(per_node),
            "nodes_at_40": sum(1 for c in per_node.values() if c == FULL),
            "nodes_over_40": sum(1 for c in per_node.values() if c > FULL),
            "counts_on_the_others": sorted(
                c for c in per_node.values() if c < FULL)}), flush=True)
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
