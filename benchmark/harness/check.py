"""What decides ``correct``: (a) the store, read back over REST, agrees with
every ack the client saw; (b) the plain validity check over ALL bindings
(``benchmark/reference/validity.py``); (c) the cell's own engine under the
cell's own mesh held to the plain oracle (``benchmark/reference/oracle.py``)
on a seeded sample of pending pods against the run's final cluster."""

from __future__ import annotations

import time
from collections import Counter

import numpy as np

from benchmark.harness import templates
from benchmark.reference import oracle, validity


def readback(url: str):
    """(nodes, pods) as the store holds them, over REST."""
    from kubetpu.apiserver import RemoteStore
    from kubetpu.client.informers import NODES, PODS

    remote = RemoteStore(url)
    nodes = [n for _k, n in remote.list(NODES, limit=5000)[0]]
    pods = list(remote.list(PODS, limit=5000)[0])
    return nodes, pods


def store_agreement(report: dict, stored_pods: list) -> tuple[int, list[str]]:
    """(a): every measured pod bound exactly once, on the node the client
    was shown. Returns (failed pods, problems)."""
    problems = list(report["violations"])
    keys = Counter(k for k, _p in stored_pods)
    stored = {k: p.node_name for k, p in stored_pods}
    acks = report["acks"]
    measured = set(report["measured_keys"])
    failed = 0
    for key in report["measured_keys"] + report["init_keys"]:
        ack, have = acks.get(key), stored.get(key)
        if ack and have == ack and keys[key] == 1:
            continue
        failed += key in measured
        if len(problems) < 20:
            problems.append(f"{key}: client saw {ack!r}, store holds {have!r}"
                            f" ({keys[key]} objects)")
    return failed, problems


def _plain(nodes, stored_pods):
    """The store's typed objects as the plain numbers validity.py takes."""
    plain_nodes = {}
    for n in nodes:
        alloc = dict(n.allocatable)
        plain_nodes[n.name] = {
            "cpu": alloc.get("cpu", 0), "memory": alloc.get("memory", 0),
            "pods": alloc.get("pods", 0), "labels": dict(n.labels)}
    plain_pods = []
    for key, p in stored_pods:
        if not p.node_name:
            continue
        req = dict(p.requests)
        terms = []
        aff = p.affinity.pod_affinity if p.affinity is not None else None
        for term in (aff.required if aff is not None else ()):
            sel = term.selector
            if (sel is None or sel.match_expressions
                    or term.namespace_selector is not None):
                raise NotImplementedError(
                    f"{key}: validity.py reads match_labels terms only")
            terms.append({"topology_key": term.topology_key,
                          "match_labels": dict(sel.match_labels),
                          "namespaces": list(term.namespaces)})
        plain_pods.append({
            "key": key, "namespace": p.namespace, "labels": dict(p.labels),
            "cpu": req.get("cpu", 0), "memory": req.get("memory", 0),
            "node": p.node_name, "affinity": terms})
    return plain_nodes, plain_pods


def validity_problems(nodes, stored_pods) -> list[str]:
    """(b)."""
    return validity.check(*_plain(nodes, stored_pods))


def _flag(flags: list[str], name: str) -> str:
    return flags[flags.index(name) + 1]


def oracle_parity(config: dict, nodes, stored_pods, seed: int) -> dict:
    """(c): encode the sample against the final cluster, run the engine the
    configuration names under the mesh it names, and hold the answer to the
    oracle by the configuration's rule."""
    import jax

    from kubetpu.assign.batched import batched_assign_device
    from kubetpu.assign.greedy import greedy_assign_device
    from kubetpu.framework import config as C
    from kubetpu.framework import runtime as rt
    from kubetpu.parallel.mesh import resolve_mesh
    from kubetpu.state.snapshot import Cache

    t0 = time.perf_counter()
    rule = config["parity"]
    flags = config["scheduler_flags"]
    engine = {"greedy": greedy_assign_device,
              "batched": batched_assign_device}[_flag(flags, "--engine")]
    mesh = resolve_mesh(_flag(flags, "--mesh"))
    cache = Cache()
    for n in nodes:
        cache.add_node(n)
    for _k, p in stored_pods:
        if p.node_name:
            cache.add_pod(p)
    measured = config["measured_pods"]
    template = templates.resolve(templates.POD_TEMPLATES,
                                 measured["template"])
    rng = np.random.default_rng(seed)
    tag = int(rng.integers(0, 1 << 30))
    pending = [template(f"check-{tag:x}-{j}", measured["namespace"])
               for j in range(rule["sample"])]
    snap = cache.update_snapshot()
    profile = C.Profile()    # what `kubetpu scheduler` runs with no --config
    batch = rt.encode_batch(snap, pending, profile, mesh=mesh)
    params = rt.score_params(profile, batch.resource_names)
    assignments, _ = engine(batch.device, params)
    idx = np.asarray(jax.device_get(assignments))
    got = [batch.node_names[int(j)] if 0 <= int(j) < len(batch.node_names)
           else None for j in idx[: len(pending)]]
    device_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    infos = [info.clone() for info in snap.node_infos()]
    want = oracle.greedy(infos, pending, **rule["oracle"])
    problems: list[str] = []
    if rule["rule"] == "pod_for_pod":
        for pod, g, w in zip(pending, got, want):
            if g != w and len(problems) < 5:
                problems.append(f"{pod.name}: engine {g}, oracle {w}")
    elif rule["rule"] == "as_many_and_feasible":
        placed_got = sum(1 for g in got if g)
        placed_want = sum(1 for w in want if w)
        if placed_got != placed_want:
            problems.append(f"engine placed {placed_got}, oracle "
                            f"{placed_want}")
        if got != want:     # pod for pod equal: the oracle chose them too
            problems += _feasible_at_its_turn(
                [info.clone() for info in snap.node_infos()], pending, got,
                rule["oracle"])
    else:
        raise ValueError(f"unknown parity rule {rule['rule']!r}")
    return {"rule": rule["rule"], "sample": len(pending),
            "placed": sum(1 for g in got if g), "problems": problems,
            "pod_for_pod": got == want,
            "engine_s": round(device_s, 2),
            "oracle_s": round(time.perf_counter() - t1, 2)}


def _feasible_at_its_turn(infos, pending, got, kwargs) -> list[str]:
    """Each pod, in order, on the node the engine gave it: the oracle's own
    filters must find that node feasible in the state the earlier
    placements left."""
    by_name = {info.node.name: info for info in infos}
    problems = []
    for pod, node in zip(pending, got):
        if node is None:
            continue
        info = by_name[node]
        ok = (oracle.static_feasible(pod, info) and oracle.fits(pod, info)
              and oracle.ports_ok(pod, info))
        if ok and kwargs.get("check_interpod"):
            ok = oracle.interpod_filter(pod, infos, info)
        if ok and kwargs.get("check_spread"):
            ok = oracle.spread_filter(pod, infos, info)
        if not ok and len(problems) < 5:
            problems.append(f"{pod.name}: the oracle finds {node} "
                            "infeasible at its turn")
        info.add_pod(pod.with_node(node))
    return problems
