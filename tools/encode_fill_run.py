"""The host encode of a cell's batches while its cluster fills, without a
served run: ``rt.encode_batch`` with an encode cache and the previous cycle's
node tensors, as the scheduler's loop calls it, over ``cycles`` batches of
``batch`` pods of the cell's measured template. After each encode the batch
is assumed round-robin over the nodes the template tolerates (so a batch
touches ``batch`` nodes where there are that many), and the previous batch is
confirmed as the informer's bind delta confirms it (the pod it held, rebuilt
on its node with ``Pod.with_node``).

    python3 tools/encode_fill_run.py [--workload CELL] [--nodes N] \
        [--cycles C] [--batch B] [--prefill P] [--bands K]

The cell's init pods and ``prefill`` more pods of the measured template
(what a window's ramp binds) are bound before the first cycle. One JSON line:
per fill band (the cycles split into ``bands`` runs of equal length, the
first cycle, which builds the template index from nothing, apart), the bound
pods at the band's first and last cycle and the mean milliseconds a cycle of
the snapshot refresh (``Cache.update_snapshot``), of the whole encode, of
``encode_spread`` and of ``encode_pod_affinity`` (the ``finalize_batch``
stamps; 0 where the encoder did not run), of ``EncodeCache.pod_groups`` and
of the confirmations (the ``Cache.add_pod`` loop), and the node rows the
encode wrote a cycle (``NodeTensors.last_dirty_rows``); the seconds
``pod_groups`` took in all; the node rows written per pod assumed after the
first cycle; and what ``Cache.add_pod`` did with the confirmations, where
the cache counts it.
Run from the root of a checkout. It runs no device program: the times are
the host's (the batch's transfer to the device is in the whole encode, as it
is in the loop's ``encode`` span), so the chip's host gives the chip's.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import kubetpu  # noqa: E402

from benchmark.harness import templates  # noqa: E402
from benchmark.harness.manifest import Cell, load_manifest  # noqa: E402
from kubetpu.api.selectors import find_untolerated_taint  # noqa: E402
from kubetpu.framework import config as C  # noqa: E402
from kubetpu.framework import runtime as rt  # noqa: E402
from kubetpu.state.encode_cache import EncodeCache  # noqa: E402
from kubetpu.state.snapshot import Cache  # noqa: E402


def stamp_ms(stamp) -> float:
    return 0.0 if stamp is None else 1e3 * (stamp.end - stamp.start)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="topologyspread-5k.saturate")
    ap.add_argument("--nodes", type=int)
    ap.add_argument("--cycles", type=int, default=90)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--prefill", type=int, default=8000)
    ap.add_argument("--bands", type=int, default=2)
    args = ap.parse_args()
    config = Cell(load_manifest(), args.workload).config
    nodes = args.nodes or config["nodes"]
    zones = tuple(config["zones"])
    node_of = templates.resolve(templates.NODE_TEMPLATES,
                                config["node_template"])
    measured = config["measured_pods"]
    template = templates.resolve(templates.POD_TEMPLATES,
                                 measured["template"])
    ns = measured["namespace"]
    tolerations = template("t", ns).tolerations
    cache = Cache()
    hosts = []
    for i in range(nodes):
        node = node_of(i, zones)
        cache.add_node(node)
        if find_untolerated_taint(node.taints, tolerations) is None:
            hosts.append(node.name)
    init = config.get("init_pods") or {}
    if init.get("count"):
        init_of = templates.resolve(templates.POD_TEMPLATES, init["template"])
        for j in range(init["count"]):
            cache.add_pod(init_of(f"init-{j}", init["namespace"]).with_node(
                hosts[j % len(hosts)]))
    bound = init.get("count", 0) + args.prefill
    cursor = 0
    for j in range(args.prefill):
        cache.add_pod(template(f"fill-{j}", ns).with_node(
            hosts[cursor % len(hosts)]))
        cursor += 1

    ec = EncodeCache()
    index_s = [0.0]
    pod_groups = ec.pod_groups

    def timed_pod_groups(nt):
        t0 = time.perf_counter()
        try:
            return pod_groups(nt)
        finally:
            index_s[0] += time.perf_counter() - t0

    ec.pod_groups = timed_pod_groups
    profile = C.Profile()
    snap, prev_nt = None, None
    held, placed = [], []
    rows = []
    for c in range(args.cycles):
        pending = [template(f"m{c}-{j}", ns) for j in range(args.batch)]
        t0 = time.perf_counter()
        snap = cache.update_snapshot(snap)
        snap_ms = 1e3 * (time.perf_counter() - t0)
        before = index_s[0]
        t0 = time.perf_counter()
        batch = rt.encode_batch(snap, pending, profile, prev_nt=prev_nt,
                                cache=ec, pad_pods=args.batch)
        encode_ms = 1e3 * (time.perf_counter() - t0)
        prev_nt = batch.node_tensors
        written = len(prev_nt.last_dirty_rows)
        t0 = time.perf_counter()
        for p, node in zip(held, placed):      # the informer's confirmation
            cache.add_pod(p.with_node(node))
        rows.append((bound, encode_ms, stamp_ms(batch.spread_encode),
                     stamp_ms(batch.podaffinity_encode),
                     1e3 * (index_s[0] - before), snap_ms,
                     1e3 * (time.perf_counter() - t0), written))
        placed = [hosts[(cursor + j) % len(hosts)]
                  for j in range(len(pending))]
        cursor += len(pending)
        bound += len(pending)
        for p, node in zip(pending, placed):
            cache.assume_pod(p.with_node(node))
        held = pending

    def mean(band, k):
        return sum(r[k] for r in band) / len(band)

    steady = rows[1:]
    size = max(1, -(-len(steady) // args.bands))
    bands = []
    for b in range(0, len(steady), size):
        band = steady[b:b + size]
        bands.append({
            "bound_from": band[0][0], "bound_to": band[-1][0],
            "cycles": len(band),
            "snapshot_ms": mean(band, 5),
            "encode_ms": mean(band, 1), "spread_ms": mean(band, 2),
            "affinity_ms": mean(band, 3), "pod_groups_ms": mean(band, 4),
            "confirm_ms": mean(band, 6), "node_rows": mean(band, 7),
        })
    index_pods = getattr(ec, "index_pods", None)
    print(json.dumps({
        "workload": args.workload, "nodes": nodes, "hosts": len(hosts),
        "cycles": args.cycles, "batch": args.batch,
        "first": {"bound": rows[0][0], "encode_ms": rows[0][1],
                  "pod_groups_ms": rows[0][4]} if rows else None,
        "bands": bands,
        "pod_groups_s": index_s[0],
        # cycle c's encode holds the assumes of cycle c - 1
        "node_rows_per_pod": (sum(r[7] for r in steady)
                              / (len(steady) * args.batch)
                              if steady else None),
        "confirmations": getattr(cache, "bind_confirmations", None),
        "index_pods": dict(index_pods) if index_pods is not None else None,
        "device": kubetpu.device_stamp(),
    }), flush=True)


if __name__ == "__main__":
    main()
