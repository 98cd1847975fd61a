"""The share of the greedy assign program's device time spent under the
inter-pod affinity path's named scopes (``interpod_filter``,
``interpod_score``, ``interpod_counts_update`` and, since PR 37,
``interpod_node_counts``: the per-row node table's build before the loop and
its compare-and-add in every step), at the size of
``preferredaffinity-5k.saturate``: its 5000 nodes, ``existing`` bound pods of
its template packed 40 a node (alternately of ``sched-0`` and ``sched-1``),
``real`` pending pods of the template padded to 1024.

    python3 tools/podaffinity_scope_share.py [nodes [existing [real]]]

``tools/spread_scope_share.py``'s method on another cell and other scopes:
ONE synthetic batch, not a run of the cell (``tools/scope_share.py`` says how
the scopes are found and why the persistent compile cache is off). Run from
the root of a checkout, on the chip (on the CPU it stops after the count of
instructions by scope).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import kubetpu  # noqa: E402

import scope_share  # noqa: E402
from benchmark.harness import templates  # noqa: E402
from benchmark.harness.manifest import Cell, load_manifest  # noqa: E402
from kubetpu.framework import config as C  # noqa: E402
from kubetpu.framework import runtime as rt  # noqa: E402
from kubetpu.state.snapshot import Cache  # noqa: E402

SCOPES = ("interpod_filter", "interpod_counts_update", "interpod_score",
          "interpod_node_counts")
PER_NODE = 40
config = Cell(load_manifest(), "preferredaffinity-5k.saturate").config
args = [int(a) for a in sys.argv[1:]]
nodes, existing, real = (args + [config["nodes"], 75000, 1024][len(args):])[:3]
template = templates.resolve(templates.POD_TEMPLATES,
                             config["measured_pods"]["template"])
namespaces = config["namespaces"]
cache = Cache()
for i in range(nodes):
    cache.add_node(templates.node_default(i))
for j in range(existing):
    cache.add_pod(template(f"e{j}", namespaces[j % 2]).with_node(
        f"scheduler-perf-{j // PER_NODE}"))
pending = [template(f"p{j}", namespaces[1]) for j in range(real)]
profile = C.Profile()
snap = cache.update_snapshot()
t0 = time.perf_counter()
batch = rt.encode_batch(snap, pending, profile, pad_pods=1024)
stamp = batch.podaffinity_encode
print(json.dumps({"encode_s": time.perf_counter() - t0,
                  "podaffinity_encode_s": stamp.end - stamp.start,
                  "rows": stamp.rows, "domains": stamp.domains,
                  "slots": stamp.slots,
                  "device": kubetpu.device_stamp()}), flush=True)
scope_share.report(batch, rt.score_params(profile, batch.resource_names),
                   SCOPES, real, existing)
