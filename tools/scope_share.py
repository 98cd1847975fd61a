"""What ``tools/spread_scope_share.py`` and ``tools/podaffinity_scope_share.py``
share: the share of the greedy assign program's device time under a set of
``jax.named_scope`` names, on ONE synthetic batch. The harness's
``xplane.reduce_trace`` keeps no scope, and the trace's op events carry none
either, so the compiled HLO's metadata maps each instruction to its op_name.
On the CPU it stops after the count of instructions by scope.

Import it AFTER ``kubetpu``: it turns the persistent compile cache off for
the process, because the cache's key leaves op metadata out, so a hit would
hand back a program compiled before the scopes existed, without their names.
"""
import collections
import json
import re
import sys
import tempfile

import jax
import numpy as np

jax.config.update("jax_enable_compilation_cache", False)
from benchmark.harness import xplane  # noqa: E402
from kubetpu.assign.greedy import greedy_assign_device  # noqa: E402

RUNS = 5


def report(batch, params, scopes, real: int, existing: int) -> None:
    """Compile the scan for ``batch``, print its instructions by scope, run
    it once (the pods placed), then trace ``RUNS`` runs on the chip and
    print each scope's share of the ops' self time."""
    compiled = greedy_assign_device.lower(batch.device, params).compile()
    scope_of = {}
    for m in re.finditer(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"",
            compiled.as_text(), re.M):
        name, op_name = m.groups()
        scope_of[name] = next((s for s in scopes if f"/{s}/" in op_name
                               or op_name.endswith("/" + s)), "other")
    print(json.dumps({"instructions_with_op_name": len(scope_of),
                      "by_scope": collections.Counter(scope_of.values())}),
          flush=True)
    a, _ = greedy_assign_device(batch.device, params)
    placed = np.asarray(a)[:real]
    print(json.dumps({"placed": int((placed >= 0).sum()),
                      "nodes_chosen": len(set(placed[placed >= 0].tolist()))}),
          flush=True)
    if jax.devices()[0].platform != "tpu":
        sys.exit(0)
    trace_dir = tempfile.mkdtemp(prefix="scope-share-")
    jax.profiler.start_trace(trace_dir)
    for _ in range(RUNS):
        a, _ = greedy_assign_device(batch.device, params)
        jax.block_until_ready(a)
    jax.profiler.stop_trace()
    data = xplane.load(xplane.find_xplane(trace_dir))
    red = xplane.reduce_trace(data, None, "greedy_assign_device")
    by_scope = collections.Counter()
    for name, secs in red["op_self_s"].items():
        by_scope[scope_of.get(
            name, "no op_name (control flow, copies)")] += secs
    total = sum(by_scope.values())
    print(json.dumps({
        "assign_ms_per_run":
            1e3 * red["assign_s"] / max(red["assign_runs"], 1),
        "runs": red["assign_runs"], "real_pods": real, "existing": existing,
        "op_self_s_total": total,
        "share_pct": {k: 100 * v / total for k, v in by_scope.items()},
        "top_ops": xplane.top(red["op_self_s"], 12)}), flush=True)
