"""A plain validity check over ALL bindings of a run, written from the
scheduling rules and not from the program: it takes plain numbers and
strings, no object of kubetpu.

Rules held, on the FINAL state of the cluster:

1. no pod is bound to a node that does not exist;
2. per node, the sum of the bound pods' requests is within the node's
   allocatable for cpu and for memory, and the number of pods within its pod
   capacity;
3. every required pod-affinity term of every bound pod is met: some OTHER
   pod that matches the term's selector, in one of the term's namespaces,
   sits on a node with the same value of the term's topology key. (The one
   exception of the rules: a pod that matches its own term may stand alone
   when no other pod of the cluster matches it, as the first of its group.)

Vectorised with numpy over the pods; the distinct terms of a run are few,
so the per-term work is a loop over them.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np


def _matches(selector: Mapping[str, str], labels: Mapping[str, str]) -> bool:
    return all(labels.get(k) == v for k, v in selector.items())


def check(nodes: Mapping[str, Mapping], pods: Sequence[Mapping],
          limit: int = 20) -> list[str]:
    """``nodes``: name -> {"cpu", "memory", "pods", "labels"}.
    ``pods``: bound pods, each {"key", "namespace", "labels", "cpu",
    "memory", "node", "affinity": [{"topology_key", "match_labels",
    "namespaces"}]}. Returns the problems found, at most ``limit``."""
    problems: list[str] = []
    names = list(nodes)
    index = {name: i for i, name in enumerate(names)}
    where = np.array([index.get(p["node"], -1) for p in pods], dtype=np.int64)
    for p in np.flatnonzero(where < 0)[:limit]:
        problems.append(f"{pods[p]['key']} is bound to {pods[p]['node']!r}, "
                        "which is not a node")
    present = where >= 0
    n = len(names)
    for res in ("cpu", "memory"):
        used = np.bincount(
            where[present], minlength=n,
            weights=np.array([p[res] for p in pods], dtype=np.float64)[present])
        cap = np.array([nodes[name][res] for name in names], dtype=np.float64)
        for j in np.flatnonzero(used > cap)[:limit]:
            problems.append(f"node {names[j]}: {res} requests {int(used[j])} "
                            f"over allocatable {int(cap[j])}")
    count = np.bincount(where[present], minlength=n)
    cap = np.array([nodes[name]["pods"] for name in names])
    for j in np.flatnonzero(count > cap)[:limit]:
        problems.append(f"node {names[j]}: {int(count[j])} pods over its "
                        f"capacity of {int(cap[j])}")

    # required pod affinity, one distinct term at a time
    terms: dict[tuple, list[int]] = {}
    for i, p in enumerate(pods):
        if where[i] < 0:
            continue
        for term in p.get("affinity", ()):
            sig = (term["topology_key"],
                   tuple(sorted(term["match_labels"].items())),
                   tuple(sorted(term["namespaces"] or (p["namespace"],))))
            terms.setdefault(sig, []).append(i)
    for (key, selector, namespaces), owners in terms.items():
        sel = dict(selector)
        domain_of_node = [nodes[name]["labels"].get(key) for name in names]
        domains = {d: k for k, d in enumerate(
            sorted({d for d in domain_of_node if d is not None}))}
        node_domain = np.array(
            [domains.get(d, -1) for d in domain_of_node], dtype=np.int64)
        match = np.array([
            where[i] >= 0 and p["namespace"] in namespaces
            and _matches(sel, p["labels"]) for i, p in enumerate(pods)])
        pod_domain = np.where(where >= 0, node_domain[where], -1)
        in_domain = match & (pod_domain >= 0)
        per_domain = np.bincount(pod_domain[in_domain],
                                 minlength=max(len(domains), 1))
        total = int(match.sum())
        own = np.array(owners, dtype=np.int64)
        dom = pod_domain[own]
        others = np.where(dom >= 0, per_domain[np.maximum(dom, 0)], 0) \
            - match[own]
        alone_ok = match[own] & (total == 1)
        bad = own[(others < 1) & ~alone_ok]
        for i in bad[:limit]:
            problems.append(
                f"{pods[i]['key']} on {pods[i]['node']}: no other pod "
                f"matching {sel} in {list(namespaces)} shares its {key}")
    return problems[:limit]
