"""Reference-semantics oracle for parity tests.

A deliberately naive, per-(pod, node) scalar-Python implementation of the
reference scheduler's Filter/Score math (cited per function). The JAX kernels
are tested against this oracle on randomized clusters — the same role the
reference's golden table-driven unit tests play (SURVEY §4).
"""

from __future__ import annotations

import math

from kubetpu.api import selectors as sel
from kubetpu.api import types as t
from kubetpu.state.snapshot import NodeInfo

MAX = 100


# --- NodeResourcesFit Filter (fit.go:647) ---------------------------------

def fits(pod: t.Pod, info: NodeInfo) -> bool:
    alloc = info.node.allocatable_dict()
    if len(info.pods) + 1 > alloc.get(t.PODS, 0):
        return False
    req = pod.requests_dict()
    for k, v in req.items():
        if v <= 0:
            continue
        if v > alloc.get(k, 0) - info.requested.get(k, 0):
            return False
    return True


# --- LeastAllocated (least_allocated.go:31) -------------------------------

def least_requested_score(requested: int, capacity: int) -> int:
    if capacity == 0 or requested > capacity:
        return 0
    return ((capacity - requested) * MAX) // capacity


def least_allocated(pod: t.Pod, info: NodeInfo, resources: list[tuple[str, int]]) -> int:
    pod_nz = pod.nonzero_requests()
    score_sum = 0
    weight_sum = 0
    for name, weight in resources:
        pod_req = pod_nz.get(name, 0)
        is_scalar = name not in (t.CPU, t.MEMORY, t.EPHEMERAL_STORAGE)
        if is_scalar and pod_req == 0:
            continue
        cap = info.node.allocatable_dict().get(name, 0)
        if cap == 0:
            continue
        requested = info.nonzero_requested.get(name, 0) + pod_req
        score_sum += least_requested_score(requested, cap) * weight
        weight_sum += weight
    if weight_sum == 0:
        return 0
    return score_sum // weight_sum


def most_requested_score(requested: int, capacity: int) -> int:
    if capacity == 0:
        return 0
    requested = min(requested, capacity)
    return (requested * MAX) // capacity


def most_allocated(pod: t.Pod, info: NodeInfo, resources: list[tuple[str, int]]) -> int:
    pod_nz = pod.nonzero_requests()
    score_sum = 0
    weight_sum = 0
    for name, weight in resources:
        pod_req = pod_nz.get(name, 0)
        is_scalar = name not in (t.CPU, t.MEMORY, t.EPHEMERAL_STORAGE)
        if is_scalar and pod_req == 0:
            continue
        cap = info.node.allocatable_dict().get(name, 0)
        if cap == 0:
            continue
        requested = info.nonzero_requested.get(name, 0) + pod_req
        score_sum += most_requested_score(requested, cap) * weight
        weight_sum += weight
    if weight_sum == 0:
        return 0
    return score_sum // weight_sum


# --- RequestedToCapacityRatio (requested_to_capacity_ratio.go) ------------

def broken_linear(shape: list[tuple[int, int]], p: int) -> int:
    for i, (x, y) in enumerate(shape):
        if p <= x:
            if i == 0:
                return shape[0][1]
            x0, y0 = shape[i - 1]
            num = (y - y0) * (p - x0)
            den = x - x0
            q = abs(num) // den
            return y0 + (-q if num < 0 else q)  # Go truncating division
    return shape[-1][1]


def requested_to_capacity_ratio(
    pod: t.Pod, info: NodeInfo, resources: list[tuple[str, int]],
    shape: list[tuple[int, int]],
) -> int:
    pod_nz = pod.nonzero_requests()
    score_sum = 0
    weight_sum = 0
    for name, weight in resources:
        pod_req = pod_nz.get(name, 0)
        is_scalar = name not in (t.CPU, t.MEMORY, t.EPHEMERAL_STORAGE)
        if is_scalar and pod_req == 0:
            continue
        cap = info.node.allocatable_dict().get(name, 0)
        if cap == 0:
            continue
        requested = info.nonzero_requested.get(name, 0) + pod_req
        if requested > cap:
            rs = broken_linear(shape, MAX)
        else:
            rs = broken_linear(shape, requested * MAX // cap)
        if rs > 0:
            score_sum += rs * weight
            weight_sum += weight
    if weight_sum == 0:
        return 0
    # math.Round on non-negative
    return (2 * score_sum + weight_sum) // (2 * weight_sum)


# --- ImageLocality (image_locality.go:96) ---------------------------------

def image_locality(sum_scores: int, image_count: int) -> int:
    min_threshold = 23 * 1024 * 1024
    max_threshold = 1000 * 1024 * 1024 * image_count
    s = max(sum_scores, min_threshold)
    s = min(s, max(max_threshold, min_threshold))
    denom = max(max_threshold - min_threshold, 1)
    return MAX * (s - min_threshold) // denom


# --- BalancedAllocation (balanced_allocation.go:248) ----------------------

def _balanced_resource_score(fractions: list[float]) -> int:
    std = 0.0
    if len(fractions) == 2:
        std = abs((fractions[0] - fractions[1]) / 2)
    elif len(fractions) > 2:
        mean = sum(fractions) / len(fractions)
        std = math.sqrt(sum((f - mean) ** 2 for f in fractions) / len(fractions))
    return int((1 - std) * MAX)


def balanced_allocation(pod: t.Pod, info: NodeInfo, resources: list[tuple[str, int]]) -> int:
    pod_req = pod.requests_dict()
    # best-effort skip (PreScore Skip)
    if all(pod_req.get(name, 0) == 0 for name, _ in resources):
        return 0
    f_with, f_without = [], []
    for name, _w in resources:
        preq = pod_req.get(name, 0)
        is_scalar = name not in (t.CPU, t.MEMORY, t.EPHEMERAL_STORAGE)
        if is_scalar and preq == 0:
            continue
        cap = info.node.allocatable_dict().get(name, 0)
        if cap == 0:
            continue
        have = info.requested.get(name, 0)
        f_with.append(min((have + preq) / cap, 1.0))
        f_without.append(min(have / cap, 1.0))
    sw = _balanced_resource_score(f_with)
    swo = _balanced_resource_score(f_without)
    return MAX // 2 + (MAX // 2 + sw - swo) // 2


# --- TaintToleration / NodeAffinity / normalize ---------------------------

def taint_filter(pod: t.Pod, info: NodeInfo) -> bool:
    return sel.find_untolerated_taint(info.node.taints, pod.tolerations) is None


def taint_score_raw(pod: t.Pod, info: NodeInfo) -> int:
    return sel.count_intolerable_prefer_no_schedule(info.node.taints, pod.tolerations)


def node_affinity_filter(pod: t.Pod, info: NodeInfo) -> bool:
    labels = info.node.labels_dict()
    for k, v in pod.node_selector:
        if labels.get(k) != v:
            return False
    na = pod.affinity.node_affinity if pod.affinity else None
    if na and na.required is not None:
        if not sel.node_selector_matches(na.required, labels, info.node.name):
            return False
    return True


def node_affinity_score_raw(pod: t.Pod, info: NodeInfo) -> int:
    na = pod.affinity.node_affinity if pod.affinity else None
    if not na:
        return 0
    labels = info.node.labels_dict()
    count = 0
    for pref in na.preferred:
        if sel.node_selector_term_matches(pref.term, labels, info.node.name):
            count += pref.weight
    return count


def default_normalize(scores: list[int], reverse: bool = False) -> list[int]:
    mx = max(scores) if scores else 0
    if mx == 0:
        return [MAX] * len(scores) if reverse else list(scores)
    out = [MAX * s // mx for s in scores]
    if reverse:
        out = [MAX - s for s in out]
    return out


# --- static filters + greedy loop (schedule_one.go ScheduleOne) ------------

_UNSCHED_TAINT = t.Taint(
    key="node.kubernetes.io/unschedulable", effect=t.TaintEffect.NO_SCHEDULE
)


def _ports_of(info: NodeInfo) -> set:
    used = set()
    for pod in info.pods.values():
        for cp in pod.ports:
            if cp.host_port > 0:
                used.add((cp.host_port, cp.protocol or "TCP", cp.host_ip or "0.0.0.0"))
    return used


def ports_ok(pod: t.Pod, info: NodeInfo) -> bool:
    want = [
        (p.host_port, p.protocol or "TCP", p.host_ip or "0.0.0.0")
        for p in pod.ports
        if p.host_port > 0
    ]
    if not want:
        return True
    used = _ports_of(info)
    for port, proto, ip in want:
        for uport, uproto, uip in used:
            if port == uport and proto == uproto:
                if ip == "0.0.0.0" or uip == "0.0.0.0" or ip == uip:
                    return False
    return True


def static_feasible(pod: t.Pod, info: NodeInfo) -> bool:
    """NodeName + NodeUnschedulable + TaintToleration + NodeAffinity.
    NodePorts is dynamic (in-batch assignments occupy ports) — checked
    separately via ``ports_ok`` under ``greedy(check_ports=True)``."""
    if pod.node_name and pod.node_name != info.node.name:
        return False
    if info.node.unschedulable:
        if not any(sel.tolerates(tol, _UNSCHED_TAINT) for tol in pod.tolerations):
            return False
    if not taint_filter(pod, info):
        return False
    if not node_affinity_filter(pod, info):
        return False
    return True


def greedy(
    infos: list[NodeInfo],
    pods: list[t.Pod],
    resources: list[tuple[str, int]] | None = None,
    w_fit: int = 1,
    w_balanced: int = 0,
    w_node_affinity: int = 0,
    w_taint: int = 0,
    w_spread: int = 0,
    w_interpod: int = 0,
    strategy: str = "least",
    check_ports: bool = True,
    check_static: bool = True,
    check_spread: bool = False,
    check_interpod: bool = False,
    hard_weight: int = 1,
    tie_rng=None,
    nominated: dict[str, list[t.Pod]] | None = None,
) -> list[str | None]:
    """The per-pod greedy loop: Filter → Score → Normalize → weighted sum →
    first-max selectHost → assume (NodeInfo.add_pod). Mutates ``infos``.

    ``nominated`` ({node name: pods nominated there}, mutated): the fit
    filter sees each node WITH the pods nominated to it whose priority is
    >= the filtered pod's (RunFilterPluginsWithNominatedPods, fit
    dimension); scores see the node as it is. A nominee this loop assigns
    stops being charged (nominations are deleted at assume,
    schedule_one.go:307)."""
    resources = resources or [(t.CPU, 1), (t.MEMORY, 1)]
    out: list[str | None] = []
    for pod in pods:
        # the spread / inter-pod maps depend on the pod and the cluster,
        # never on the candidate node: built once per pod, not once per
        # (pod, node) — what keeps the oracle usable at 5000 nodes
        sp_state = _spread_filter_state(pod, infos) if check_spread else None
        ip_state = _interpod_filter_state(pod, infos) if check_interpod else None
        feas = [
            (not check_static or static_feasible(pod, info))
            and fits(pod, _with_nominated(pod, info, nominated))
            and (not check_ports or ports_ok(pod, info))
            and (not check_spread or spread_filter(pod, infos, info, sp_state))
            and (not check_interpod
                 or interpod_filter(pod, infos, info, ip_state))
            for info in infos
        ]
        if not any(feas):
            out.append(None)
            continue
        totals = [0] * len(infos)
        if w_fit:
            fn = least_allocated if strategy == "least" else most_allocated
            for j, info in enumerate(infos):
                totals[j] += w_fit * fn(pod, info, resources)
        if w_balanced:
            for j, info in enumerate(infos):
                totals[j] += w_balanced * balanced_allocation(pod, info, resources)
        if w_node_affinity:
            raw = [node_affinity_score_raw(pod, info) if feas[j] else 0
                   for j, info in enumerate(infos)]
            norm = default_normalize(raw)
            for j in range(len(infos)):
                totals[j] += w_node_affinity * norm[j]
        if w_taint:
            raw = [taint_score_raw(pod, info) if feas[j] else 0
                   for j, info in enumerate(infos)]
            norm = default_normalize(raw, reverse=True)
            for j in range(len(infos)):
                totals[j] += w_taint * norm[j]
        if w_spread:
            sp = spread_scores(pod, infos, feas)
            for j in range(len(infos)):
                totals[j] += w_spread * sp[j]
        if w_interpod:
            ip = interpod_scores(pod, infos, feas, hard_weight=hard_weight)
            for j in range(len(infos)):
                totals[j] += w_interpod * ip[j]
        best, best_score = -1, -1
        for j in range(len(infos)):
            if feas[j] and totals[j] > best_score:
                best, best_score = j, totals[j]
        if tie_rng is not None:
            # the reference's selectHost reservoir-samples uniformly among
            # max-score nodes (schedule_one.go:1037); the deterministic
            # first-max rule is the framework's documented deviation
            ties = [j for j in range(len(infos))
                    if feas[j] and totals[j] == best_score]
            best = ties[int(tie_rng.integers(0, len(ties)))]
        infos[best].add_pod(pod.with_node(infos[best].node.name))
        out.append(infos[best].node.name)
        for noms in (nominated or {}).values():
            noms[:] = [n for n in noms if n.uid != pod.uid]
    return out


def _with_nominated(pod: t.Pod, info: NodeInfo, nominated) -> NodeInfo:
    """The node as the fit filter sees it: plus the pods nominated to it
    with priority >= ``pod``'s (never ``pod`` itself)."""
    extra = [
        n for n in (nominated or {}).get(info.node.name, ())
        if n.priority >= pod.priority and n.uid != pod.uid
    ]
    if not extra:
        return info
    view = info.clone()
    for n in extra:
        view.add_pod(n.with_node(info.node.name))
    return view


# --- PodTopologySpread (plugins/podtopologyspread) -------------------------

def _sel_matches(selector, labels):
    """Selector.Matches: None = Nothing, empty = Everything."""
    if selector is None:
        return False
    return sel.label_selector_matches(selector, labels)


def _sel_counts(selector, labels):
    """countPodsMatchSelector (common.go:145): empty selector counts nothing."""
    if selector is None:
        return False
    if not selector.match_labels and not selector.match_expressions:
        return False
    return sel.label_selector_matches(selector, labels)


def _spread_node_eligible(pod: t.Pod, info: NodeInfo, key_set, c) -> bool:
    """calPreFilterState processNode guards + matchNodeInclusionPolicies."""
    labels = info.node.labels_dict()
    for k in key_set:
        if k not in labels:
            return False
    if c.node_affinity_policy == "Honor":
        if not node_affinity_filter(pod, info):
            return False
    if c.node_taints_policy == "Honor":
        if sel.find_untolerated_taint(info.node.taints, pod.tolerations) is not None:
            return False
    return True


def _spread_counts(pod: t.Pod, infos, c, key_set):
    """{topology value: matching pod count} over eligible nodes."""
    m: dict[str, int] = {}
    for info in infos:
        if not _spread_node_eligible(pod, info, key_set, c):
            continue
        v = info.node.labels_dict()[c.topology_key]
        n = 0
        for ex in info.pods.values():
            if ex.namespace != pod.namespace:
                continue
            if _sel_counts(c.selector, ex.labels_dict()):
                n += 1
        m[v] = m.get(v, 0) + n
    return m


def _spread_filter_state(pod: t.Pod, infos) -> list:
    """Per hard constraint: (constraint, {domain: count}, min_match,
    self_match) — everything of filtering.go:314 that does not depend on
    the candidate node."""
    hard = [
        c for c in pod.topology_spread_constraints
        if c.when_unsatisfiable == t.UnsatisfiableConstraintAction.DO_NOT_SCHEDULE
    ]
    key_set = frozenset(c.topology_key for c in hard)
    state = []
    for c in hard:
        m = _spread_counts(pod, infos, c, key_set)
        min_domains = c.min_domains if c.min_domains is not None else 1
        if len(m) < min_domains:
            min_match = 0
        else:
            min_match = min(m.values()) if m else 0
        self_match = 1 if _sel_matches(c.selector, pod.labels_dict()) else 0
        state.append((c, m, min_match, self_match))
    return state


def spread_filter(pod: t.Pod, infos, info_j: NodeInfo, state=None) -> bool:
    """filtering.go:314 Filter for one candidate node. ``state``: a
    ``_spread_filter_state(pod, infos)`` computed against the same
    ``infos`` (None = compute it here)."""
    if state is None:
        state = _spread_filter_state(pod, infos)
    labels_j = info_j.node.labels_dict()
    for c, m, min_match, self_match in state:
        if c.topology_key not in labels_j:
            return False
        match_num = m.get(labels_j[c.topology_key], 0)
        if match_num + self_match - min_match > c.max_skew:
            return False
    return True


def spread_scores(pod: t.Pod, infos, feasible: list[bool]) -> list[int]:
    """scoring.go Score + NormalizeScore over the feasible set. Returns a
    per-node normalized score (0 for infeasible/ignored nodes)."""
    soft = [
        c for c in pod.topology_spread_constraints
        if c.when_unsatisfiable == t.UnsatisfiableConstraintAction.SCHEDULE_ANYWAY
    ]
    n = len(infos)
    if not soft:
        return [0] * n
    key_set = frozenset(c.topology_key for c in soft)
    ignored = []
    for info in infos:
        labels = info.node.labels_dict()
        ignored.append(any(k not in labels for k in key_set))
    scored = [feasible[j] and not ignored[j] for j in range(n)]

    raw = [0.0] * n
    for c in soft:
        m = _spread_counts(pod, infos, c, key_set)
        hostname = c.topology_key == "kubernetes.io/hostname"
        # topoSize over scored nodes
        if hostname:
            size = sum(scored)
        else:
            vals = {
                infos[j].node.labels_dict().get(c.topology_key)
                for j in range(n) if scored[j]
            }
            size = len(vals)
        weight = math.log(size + 2)
        for j in range(n):
            labels = infos[j].node.labels_dict()
            if c.topology_key not in labels:
                continue
            if hostname:
                cnt = 0
                for ex in infos[j].pods.values():
                    if ex.namespace == pod.namespace and _sel_counts(
                        c.selector, ex.labels_dict()
                    ):
                        cnt += 1
                # hostname counting is still gated on node eligibility in our
                # batch model (counts state zeroed on ineligible nodes)
                if not _spread_node_eligible(pod, infos[j], key_set, c):
                    cnt = 0
            else:
                cnt = m.get(labels[c.topology_key], 0)
            raw[j] += cnt * weight + (c.max_skew - 1)
    score = [round(raw[j]) for j in range(n)]

    smin = min((score[j] for j in range(n) if scored[j]), default=0)
    smax = max((score[j] for j in range(n) if scored[j]), default=0)
    out = [0] * n
    for j in range(n):
        if not scored[j]:
            out[j] = 0
        elif smax == 0:
            out[j] = MAX
        else:
            out[j] = MAX * (smax + smin - score[j]) // smax
    return out


# --- InterPodAffinity (plugins/interpodaffinity) ---------------------------

def _term_matches(term: t.PodAffinityTerm, owner_ns: str, pod: t.Pod) -> bool:
    namespaces = term.namespaces or (owner_ns,)
    ns_ok = pod.namespace in namespaces
    if not ns_ok and term.namespace_selector is not None:
        ns_ok = sel.label_selector_matches(term.namespace_selector, {})
    if not ns_ok:
        return False
    if term.selector is None:
        return False
    return sel.label_selector_matches(term.selector, pod.labels_dict())


def _req_aff(pod):
    a = pod.affinity.pod_affinity if pod.affinity else None
    return a.required if a else ()


def _req_anti(pod):
    a = pod.affinity.pod_anti_affinity if pod.affinity else None
    return a.required if a else ()


def _pref_aff(pod):
    a = pod.affinity.pod_affinity if pod.affinity else None
    return a.preferred if a else ()


def _pref_anti(pod):
    a = pod.affinity.pod_anti_affinity if pod.affinity else None
    return a.preferred if a else ()


def _interpod_filter_state(pod: t.Pod, infos) -> tuple:
    """(existing_anti, anti_counts, aff_counts): the calPreFilterState maps
    of filtering.go:364-419, built from scratch — none depends on the
    candidate node."""
    aff_terms = _req_aff(pod)
    anti_terms = _req_anti(pod)
    existing_anti: dict[tuple, int] = {}
    anti_counts: dict[tuple, int] = {}
    aff_counts: dict[tuple, int] = {}
    for info in infos:
        labels_n = info.node.labels_dict()
        for ex in info.pods.values():
            for term in _req_anti(ex):
                if _term_matches(term, ex.namespace, pod):
                    v = labels_n.get(term.topology_key)
                    if v is not None:
                        existing_anti[(term.topology_key, v)] = (
                            existing_anti.get((term.topology_key, v), 0) + 1
                        )
            for term in anti_terms:
                if _term_matches(term, pod.namespace, ex):
                    v = labels_n.get(term.topology_key)
                    if v is not None:
                        anti_counts[(term.topology_key, v)] = (
                            anti_counts.get((term.topology_key, v), 0) + 1
                        )
            if aff_terms and all(
                _term_matches(tm, pod.namespace, ex) for tm in aff_terms
            ):
                for term in aff_terms:
                    v = labels_n.get(term.topology_key)
                    if v is not None:
                        aff_counts[(term.topology_key, v)] = (
                            aff_counts.get((term.topology_key, v), 0) + 1
                        )
    return existing_anti, anti_counts, aff_counts


def interpod_filter(pod: t.Pod, infos, info_j: NodeInfo, state=None) -> bool:
    """filtering.go:364-419 for one candidate node. ``state``: an
    ``_interpod_filter_state(pod, infos)`` computed against the same
    ``infos`` (None = compute it here)."""
    existing_anti, anti_counts, aff_counts = (
        state if state is not None else _interpod_filter_state(pod, infos)
    )
    aff_terms = _req_aff(pod)
    labels_j = info_j.node.labels_dict()
    # existingAntiAffinityCounts
    for k, v in labels_j.items():
        if existing_anti.get((k, v), 0) > 0:
            return False
    # incoming anti-affinity
    for term in _req_anti(pod):
        v = labels_j.get(term.topology_key)
        if v is not None and anti_counts.get((term.topology_key, v), 0) > 0:
            return False
    # incoming affinity
    if aff_terms:
        pods_exist = True
        for term in aff_terms:
            v = labels_j.get(term.topology_key)
            if v is None:
                return False
            if aff_counts.get((term.topology_key, v), 0) <= 0:
                pods_exist = False
        if not pods_exist:
            if len(aff_counts) == 0 and all(
                _term_matches(tm, pod.namespace, pod) for tm in aff_terms
            ):
                return True
            return False
    return True


def interpod_scores(
    pod: t.Pod, infos, feasible: list[bool], hard_weight: int = 1
) -> list[int]:
    """scoring.go processExistingPod + Score + NormalizeScore."""
    topo: dict[tuple, int] = {}

    def add(term, weight, target, owner_ns, node_labels, mult):
        if _term_matches(term, owner_ns, target):
            v = node_labels.get(term.topology_key)
            if v is not None:
                key = (term.topology_key, v)
                topo[key] = topo.get(key, 0) + weight * mult

    for info in infos:
        labels_n = info.node.labels_dict()
        if not labels_n:
            continue
        for ex in info.pods.values():
            for wt in _pref_aff(pod):
                add(wt.term, wt.weight, ex, pod.namespace, labels_n, 1)
            for wt in _pref_anti(pod):
                add(wt.term, wt.weight, ex, pod.namespace, labels_n, -1)
            if hard_weight > 0:
                for term in _req_aff(ex):
                    add(term, hard_weight, pod, ex.namespace, labels_n, 1)
            for wt in _pref_aff(ex):
                add(wt.term, wt.weight, pod, ex.namespace, labels_n, 1)
            for wt in _pref_anti(ex):
                add(wt.term, wt.weight, pod, ex.namespace, labels_n, -1)

    n = len(infos)
    raw = [0] * n
    for j, info in enumerate(infos):
        labels_j = info.node.labels_dict()
        s = 0
        for (k, v), w in topo.items():
            if labels_j.get(k) == v:
                s += w
        raw[j] = s
    if not topo:
        return [0] * n
    feas_scores = [raw[j] for j in range(n) if feasible[j]]
    if not feas_scores:
        return [0] * n
    mn, mx = min(feas_scores), max(feas_scores)
    out = [0] * n
    for j in range(n):
        if feasible[j] and mx > mn:
            out[j] = int(MAX * (raw[j] - mn) / (mx - mn))
    return out


# --- Preemption (framework/preemption/preemption.go +
#     defaultpreemption/default_preemption.go) ------------------------------

PRIO_SHIFT = 2**31  # preemption.go:339


def _more_important(a: t.Pod, b: t.Pod) -> bool:
    """util.MoreImportantPod: higher priority, then earlier start."""
    if a.priority != b.priority:
        return a.priority > b.priority
    return a.creation_index < b.creation_index


def _imp_sorted(pods: list[t.Pod]) -> list[t.Pod]:
    import functools

    return sorted(
        pods,
        key=functools.cmp_to_key(
            lambda a, b: -1 if _more_important(a, b) else 1
        ),
    )


def _pdb_matches(pdb: t.PodDisruptionBudget, pod: t.Pod) -> bool:
    if pdb.namespace != pod.namespace or not pod.labels:
        return False
    if pdb.selector is None or (
        not pdb.selector.match_labels and not pdb.selector.match_expressions
    ):
        return False
    if pod.name in pdb.disrupted_pods:
        return False
    return sel.label_selector_matches(pdb.selector, pod.labels_dict())


def _fits_state(pod: t.Pod, info: NodeInfo, present: list[t.Pod]) -> bool:
    """Preemptor fit against an explicit pod set (fit + count + ports)."""
    alloc = info.node.allocatable_dict()
    if len(present) + 1 > alloc.get(t.PODS, 0):
        return False
    used: dict[str, int] = {}
    for p in present:
        for k, v in p.requests:
            used[k] = used.get(k, 0) + v
    for k, v in pod.requests_dict().items():
        if v > 0 and v > alloc.get(k, 0) - used.get(k, 0):
            return False
    want = [
        (p.host_port, p.protocol or "TCP", p.host_ip or "0.0.0.0")
        for p in pod.ports if p.host_port > 0
    ]
    if want:
        in_use = set()
        for p in present:
            for cp in p.ports:
                if cp.host_port > 0:
                    in_use.add(
                        (cp.host_port, cp.protocol or "TCP", cp.host_ip or "0.0.0.0")
                    )
        for port, proto, ip in want:
            for uport, uproto, uip in in_use:
                if port == uport and proto == uproto and (
                    ip == "0.0.0.0" or uip == "0.0.0.0" or ip == uip
                ):
                    return False
    return True


def select_victims_on_node(
    pod: t.Pod, info: NodeInfo, pdbs: list[t.PodDisruptionBudget]
):
    """default_preemption.go:252 SelectVictimsOnNode →
    (victims list, num_pdb_violations) or None."""
    potential = [p for p in info.pods.values() if p.priority < pod.priority]
    if not potential:
        return None
    keep = [p for p in info.pods.values() if p.priority >= pod.priority]
    if not _fits_state(pod, info, keep):
        return None
    ordered = _imp_sorted(potential)
    # PDB violation marking (default_preemption.go:406)
    allowed = [p.disruptions_allowed for p in pdbs]
    violating_set = set()
    for p in ordered:
        hit = False
        for i, b in enumerate(pdbs):
            if _pdb_matches(b, p):
                allowed[i] -= 1
                if allowed[i] < 0:
                    hit = True
        if hit:
            violating_set.add(p.uid)
    violating = [p for p in ordered if p.uid in violating_set]
    nonviolating = [p for p in ordered if p.uid not in violating_set]
    victims: list[t.Pod] = []
    n_viol = 0
    present = list(keep)
    for group, count_violations in ((violating, True), (nonviolating, False)):
        for p in group:
            if _fits_state(pod, info, present + [p]):
                present.append(p)       # reprieved
            else:
                victims.append(p)
                if count_violations:
                    n_viol += 1
    if not victims:
        return None
    return victims, n_viol


def preempt(
    pod: t.Pod,
    infos: list[NodeInfo],
    pdbs: list[t.PodDisruptionBudget] | None = None,
    check_spread: bool = False,
    check_interpod: bool = False,
):
    """Exhaustive dry run + pickOneNodeForPreemption (preemption.go:311).
    Returns (node_name, victim uid list) or (None, [])."""
    pdbs = pdbs or []
    if pod.preemption_policy == "Never":
        return None, []
    candidates = {}
    for info in infos:
        # potential = victim-independent filters pass, fit/ports fail
        if not static_feasible(pod, info):
            continue
        if check_spread and not spread_filter(pod, infos, info):
            continue
        if check_interpod and not interpod_filter(pod, infos, info):
            continue
        if fits(pod, info) and ports_ok(pod, info):
            continue  # feasible — not a preemption target
        res = select_victims_on_node(pod, info, pdbs)
        if res is not None:
            candidates[info.node.name] = res
    if not candidates:
        return None, []
    names = [info.node.name for info in infos if info.node.name in candidates]

    def stats(name):
        victims, n_viol = candidates[name]
        max_prio = max(v.priority for v in victims)
        sum_prio = sum(v.priority + PRIO_SHIFT for v in victims)
        earliest = min(
            v.creation_index for v in victims if v.priority == max_prio
        )
        return n_viol, max_prio, sum_prio, len(victims), earliest

    remaining = list(names)
    for key_fn in (
        lambda n: -stats(n)[0],
        lambda n: -stats(n)[1],
        lambda n: -stats(n)[2],
        lambda n: -stats(n)[3],
        lambda n: stats(n)[4],
    ):
        best = max(key_fn(n) for n in remaining)
        remaining = [n for n in remaining if key_fn(n) == best]
        if len(remaining) == 1:
            break
    chosen = remaining[0]
    return chosen, [v.uid for v in candidates[chosen][0]]
