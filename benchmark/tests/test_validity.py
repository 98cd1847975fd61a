"""The plain validity checker on hand-made clusters."""

from benchmark.reference import validity

NODES = {
    "n0": {"cpu": 4000, "memory": 32 << 30, "pods": 110,
           "labels": {"zone": "z1"}},
    "n1": {"cpu": 4000, "memory": 32 << 30, "pods": 2,
           "labels": {"zone": "z2"}},
}


def pod(key, node, cpu=100, memory=500 << 20, labels=None, affinity=()):
    ns = key.split("/")[0]
    return {"key": key, "namespace": ns, "labels": labels or {}, "cpu": cpu,
            "memory": memory, "node": node, "affinity": list(affinity)}


def test_a_sound_cluster_has_no_problem():
    pods = [pod(f"a/p{j}", "n0") for j in range(40)]
    assert validity.check(NODES, pods) == []


def test_an_over_committed_node_is_found():
    pods = [pod(f"a/p{j}", "n0") for j in range(41)]     # 4100m on 4000m
    (problem,) = validity.check(NODES, pods)
    assert "n0" in problem and "cpu" in problem and "4100" in problem


def test_memory_and_pod_count_are_held_too():
    assert "memory" in validity.check(
        NODES, [pod("a/big", "n0", memory=33 << 30)])[0]
    over = validity.check(NODES, [pod(f"a/p{j}", "n1") for j in range(3)])
    assert "3 pods over its capacity of 2" in over[0]


def test_a_binding_to_an_absent_node_is_found():
    assert "not a node" in validity.check(NODES, [pod("a/p", "ghost")])[0]


BLUE = {"topology_key": "zone", "match_labels": {"color": "blue"},
        "namespaces": ["a", "b"]}


def test_required_affinity_needs_another_matching_pod_in_the_domain():
    blue = {"color": "blue"}
    ok = [pod("a/p0", "n0", labels=blue, affinity=[BLUE]),
          pod("b/p1", "n0", labels=blue, affinity=[BLUE])]
    assert validity.check(NODES, ok) == []
    # the second pod sits in another zone: neither has company
    split = [ok[0], pod("b/p1", "n1", labels=blue, affinity=[BLUE])]
    assert len(validity.check(NODES, split)) == 2
    # a match in a namespace the term does not name is no company (the
    # match in zone z2 keeps p0 from being the first of its group)
    other_ns = [ok[0], pod("c/p1", "n0", labels=blue),
                pod("a/p2", "n1", labels=blue)]
    (problem,) = validity.check(NODES, other_ns)
    assert "a/p0" in problem


def test_the_first_of_its_group_may_stand_alone():
    alone = [pod("a/p0", "n0", labels={"color": "blue"}, affinity=[BLUE])]
    assert validity.check(NODES, alone) == []
