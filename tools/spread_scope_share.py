"""The share of the greedy assign program's device time spent under the
spread path's named scopes (``spread_filter``, ``spread_score``,
``spread_counts_update``), at the size of a spread cell's configuration
(``topologyspread-5k.saturate`` unless ``--workload`` names another, e.g.
``preferredspread-5k.saturate``, whose soft score is the ``spread_score``
scope): its nodes in its zones, ``existing`` bound pods of its measured
template, ``real`` pending pods of the template padded to 1024.

    python3 tools/spread_scope_share.py [--workload CELL] \
        [nodes [existing [real]]]

ONE synthetic batch, not a run of the cell: the harness's
``xplane.reduce_trace`` keeps no scope, and the trace's op events carry none
either, so the compiled HLO's metadata maps each instruction to its op_name.
Run from the root of a checkout, on the chip (on the CPU it stops after the
count of instructions by scope). It turns the persistent compile cache off for
its own process: the cache's key leaves op metadata out, so a hit would hand
back a program compiled before the scopes existed, without their names.
"""
import argparse
import collections
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import numpy as np  # noqa: E402

import kubetpu  # noqa: E402,F401

jax.config.update("jax_enable_compilation_cache", False)
from benchmark.harness import templates, xplane  # noqa: E402
from benchmark.harness.manifest import Cell, load_manifest  # noqa: E402
from kubetpu.assign.greedy import greedy_assign_device  # noqa: E402
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
runs = 5
zones = tuple(config["zones"])
measured = config["measured_pods"]
template = templates.resolve(templates.POD_TEMPLATES, measured["template"])
cache = Cache()
for i in range(nodes):
    cache.add_node(templates.node_default(i, zones))
for j in range(existing):
    cache.add_pod(template(f"e{j}", measured["namespace"]).with_node(
        f"scheduler-perf-{j % nodes}"))
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
params = rt.score_params(profile, batch.resource_names)
compiled = greedy_assign_device.lower(batch.device, params).compile()
scope_of = {}
for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"",
                     compiled.as_text(), re.M):
    name, op_name = m.groups()
    scope_of[name] = next((s for s in SCOPES if f"/{s}/" in op_name
                           or op_name.endswith("/" + s)), "other")
print(json.dumps({"instructions_with_op_name": len(scope_of),
                  "by_scope": collections.Counter(scope_of.values())}),
      flush=True)
a, _ = greedy_assign_device(batch.device, params)
placed = np.asarray(a)
print(json.dumps({"placed": int((placed[:real] >= 0).sum())}), flush=True)
if jax.devices()[0].platform != "tpu":
    sys.exit(0)
trace_dir = tempfile.mkdtemp(prefix="scope-share-")
jax.profiler.start_trace(trace_dir)
for _ in range(runs):
    a, _ = greedy_assign_device(batch.device, params)
    jax.block_until_ready(a)
jax.profiler.stop_trace()
data = xplane.load(xplane.find_xplane(trace_dir))
red = xplane.reduce_trace(data, None, "greedy_assign_device")
by_scope = collections.Counter()
unmapped = 0.0
for name, secs in red["op_self_s"].items():
    scope = scope_of.get(name)
    if scope is None:
        unmapped += secs
        scope = "no op_name (control flow, copies)"
    by_scope[scope] += secs
total = sum(by_scope.values())
print(json.dumps({
    "assign_ms_per_run": 1e3 * red["assign_s"] / max(red["assign_runs"], 1),
    "runs": red["assign_runs"], "real_pods": real, "existing": existing,
    "op_self_s_total": total,
    "share_pct": {k: 100 * v / total for k, v in by_scope.items()},
    "top_ops": xplane.top(red["op_self_s"], 12)}), flush=True)
