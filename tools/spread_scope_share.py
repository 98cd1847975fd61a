"""The share of the greedy assign program's device time spent under the
spread path's named scopes (``spread_filter``, ``spread_score``,
``spread_counts_update``), at the size of a spread cell's configuration
(``topologyspread-5k.saturate`` unless ``--workload`` names another, e.g.
``preferredspread-5k.saturate``, whose soft score is the ``spread_score``
scope, or ``nodeinclusion-5k.saturate``, D = 5000 hostname domains): its
nodes of its node template in its zones, ``existing`` bound pods of its
measured template on the nodes it tolerates, ``real`` pending pods of the
template padded to 1024.

    python3 tools/spread_scope_share.py [--workload CELL] \
        [nodes [existing [real]]]

ONE synthetic batch, not a run of the cell (``tools/scope_share.py`` says
how the scopes are found and why the persistent compile cache is off). Run
from the root of a checkout, on the chip (on the CPU it stops after the count
of instructions by scope).
"""
import argparse
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
from kubetpu.api.selectors import find_untolerated_taint  # noqa: E402
from kubetpu.framework import config as C  # noqa: E402
from kubetpu.framework import runtime as rt  # noqa: E402
from kubetpu.state.snapshot import Cache  # noqa: E402

SCOPES = ("spread_filter", "spread_counts_update", "spread_score")
ap = argparse.ArgumentParser()
ap.add_argument("--workload", default="topologyspread-5k.saturate")
ap.add_argument("nodes", type=int, nargs="?")
ap.add_argument("existing", type=int, nargs="?", default=15000)
ap.add_argument("real", type=int, nargs="?", default=128)
args = ap.parse_args()
config = Cell(load_manifest(), args.workload).config
nodes = args.nodes or config["nodes"]
existing, real = args.existing, args.real
zones = tuple(config["zones"])
measured = config["measured_pods"]
template = templates.resolve(templates.POD_TEMPLATES, measured["template"])
node_of = templates.resolve(templates.NODE_TEMPLATES, config["node_template"])
cache = Cache()
# the existing pods go round-robin over the nodes the template tolerates
tolerations = template("t", measured["namespace"]).tolerations
hosts = []
for i in range(nodes):
    node = node_of(i, zones)
    cache.add_node(node)
    if find_untolerated_taint(node.taints, tolerations) is None:
        hosts.append(node.name)
for j in range(existing):
    cache.add_pod(template(f"e{j}", measured["namespace"]).with_node(
        hosts[j % len(hosts)]))
pending = [template(f"p{j}", measured["namespace"]) for j in range(real)]
profile = C.Profile()
snap = cache.update_snapshot()
t0 = time.perf_counter()
batch = rt.encode_batch(snap, pending, profile, pad_pods=1024)
print(json.dumps({"encode_s": time.perf_counter() - t0,
                  "spread_encode_s": batch.spread_encode.end
                  - batch.spread_encode.start,
                  "workload": args.workload,
                  "device": kubetpu.device_stamp()}), flush=True)
scope_share.report(batch, rt.score_params(profile, batch.resource_names),
                   SCOPES, real, existing)
