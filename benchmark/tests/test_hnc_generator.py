"""The hierarchical-namespace configuration's generator, and the plain
reference on its recursive `parent->view` arrow."""

import json
import os
from collections import defaultdict

import pytest

from manifest import load_module
from reference import Reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "hnc-150k.json")) as f:
        return json.load(f)


def _schema() -> str:
    with open(os.path.join(BENCH, "configs", "hnc-150k.zed")) as f:
        return f.read()


hnc = load_module(os.path.join(BENCH, "generators", "hnc.py"))

SMALL = dict(n_tenants=4, n_users=300, n_groups=40, n_namespaces=40,
             n_pods=800, n_tuples=5000)


def _split(line: str) -> tuple:
    obj, rest = line.split("#", 1)
    rel, subj = rest.split("@", 1)
    return obj, rel, subj


def _trees(lines: list) -> tuple:
    """({child namespace: parent}, {root namespace: tenant})."""
    parent, bound = {}, {}
    for line in lines:
        if not line.startswith("namespace:"):
            continue
        obj, rel, subj = _split(line)
        if rel == "parent":
            assert obj not in parent, obj
            parent[obj] = subj
        elif rel == "tenant":
            bound[obj] = subj
    return parent, bound


def _depth(ns: str, parent: dict) -> int:
    d = 0
    while ns in parent:
        ns = parent[ns]
        d += 1
        assert d <= len(parent), "a parent cycle"
    return d


@pytest.fixture(scope="module")
def full():
    cfg = _config()
    return cfg["params"], hnc.build(cfg["params"], 2**31 + 11)


def test_every_tree_has_depth_eight_and_one_bound_root(full):
    p, data = full
    lines = data["relationships"]
    assert len(lines) == p["n_tuples"] == 1_000_000
    assert len(set(lines)) == len(lines)
    parent, bound = _trees(lines)
    assert len(bound) == p["n_tenants"]
    assert len(parent) == p["n_namespaces"] - p["n_tenants"]
    roots = defaultdict(list)
    for i in range(p["n_namespaces"]):
        ns = f"namespace:ns{i}"
        top = ns
        while top in parent:
            top = parent[top]
        roots[top].append(_depth(ns, parent))
    assert set(roots) == set(bound)
    for top, depths in roots.items():
        assert len(depths) == p["n_namespaces"] // p["n_tenants"]
        assert max(depths) == p["tree_depth"] == 8, top
        assert sorted(set(depths)) == list(range(9))
    # the direct pod grants are multitenant-150k's
    viewers = sum(1 for ln in lines if ln.startswith("pod:")
                  and "#viewer@user:" in ln)
    assert viewers == 795_214


def test_two_seeds_give_the_same_structure():
    p = dict(_config()["params"], **SMALL)
    a = hnc.build(p, 1)["relationships"]
    b = hnc.build(p, 2**31 + 12)["relationships"]
    assert a != b   # identities are relabeled
    pa, ba = _trees(a)
    pb, bb = _trees(b)
    assert pa == pb
    assert set(ba) == set(bb)
    assert len(a) == len(b) == p["n_tuples"]
    full = _config()["params"]
    assert hnc.parents(full) == hnc.parents(dict(full))


def test_the_reference_matches_an_ancestor_walk():
    """On a small instance: a user views a namespace exactly when some
    ancestor (or the namespace itself) has them as a viewer, or is the
    root bound to a tenant whose access they hold, group nesting followed
    to any depth; and a pod's view follows its namespace's."""
    p = dict(_config()["params"], **SMALL)
    data = hnc.build(p, 2**31 + 13)
    lines = data["relationships"]
    ref = Reference(_schema(), lines)
    parent, bound = _trees(lines)

    members = defaultdict(set)   # subject set -> its member subjects
    rels = defaultdict(set)      # (object, relation) -> subjects
    for line in lines:
        obj, rel, subj = _split(line)
        rels[(obj, rel)].add(subj)
        if obj.startswith("group:"):
            members[f"{obj}#member"].add(subj)

    def in_set(user: str, subj: str, seen=()) -> bool:
        if subj == f"user:{user}":
            return True
        if subj in seen or subj not in members:
            return False
        return any(in_set(user, s, seen + (subj,)) for s in members[subj])

    def access(user: str, tenant: str) -> bool:
        return any(in_set(user, s)
                   for rel in ("admin", "member")
                   for s in rels[(tenant, rel)])

    def ns_view(user: str, ns: str) -> bool:
        while True:
            if any(in_set(user, s) for s in rels[(ns, "viewer")]):
                return True
            if ns in bound and access(user, bound[ns]):
                return True
            if ns not in parent:
                return False
            ns = parent[ns]

    users = data["users"][:60]
    seen_true = 0
    for user in users:
        for i in range(p["n_namespaces"]):
            want = ns_view(user, f"namespace:ns{i}")
            seen_true += want
            assert ref.check("namespace", f"ns{i}", "view", user) == want
        for ns, name in hnc.kube_pods(p)[:200]:
            pod = f"pod:{ns}/{name}"
            direct = any(in_set(user, s) for r in ("creator", "viewer")
                         for s in rels[(pod, r)])
            want = (direct or ns_view(user, f"namespace:{ns}")) and \
                f"user:{user}" not in rels[(pod, "banned")]
            assert ref.check("pod", f"{ns}/{name}", "view", user) == want
        ref.forget(user)
    assert seen_true > 0
