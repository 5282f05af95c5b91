"""Hierarchical namespaces (HNC): the multi-tenant graph with each
tenant's namespaces in one tree, policy propagating from the root down.

The graph of `multitenant.py` at the same parameters, with one change:
of each tenant's namespaces only the root keeps its `tenant` binding, and
every other namespace instead holds one `parent` tuple, so the tuple count
and everything else are unchanged.  A tenant's namespaces are
ns{t}, ns{t + T}, ... (T tenants); the first is the root, the others fill
levels 1..`tree_depth` round-robin, and each takes a parent among its
tenant's namespaces on the level above, drawn from `structure_seed`.  So
every tree has depth exactly `tree_depth`, and the run's seed relabels
identities only, as in `multitenant.py`.
"""

from __future__ import annotations

import importlib.util
import os
import random


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_gen_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


multitenant = _sibling("multitenant")
kube_pods = multitenant.kube_pods


def parents(p: dict) -> dict:
    """{namespace number: parent namespace number} of every non-root
    namespace."""
    n_tenants, n_ns, depth = p["n_tenants"], p["n_namespaces"], \
        p["tree_depth"]
    per_tenant = n_ns // n_tenants
    if n_ns % n_tenants or per_tenant - 1 < depth:
        raise ValueError(f"{n_ns} namespaces over {n_tenants} tenants "
                         f"cannot fill trees of depth {depth}")
    rng = random.Random(f"hnc:{p['structure_seed']}")
    out = {}
    for t in range(n_tenants):
        members = [t + n_tenants * j for j in range(per_tenant)]
        levels = [[members[0]]] + [[] for _ in range(depth)]
        for j, ns in enumerate(members[1:]):
            level = j % depth + 1
            out[ns] = rng.choice(levels[level - 1])
            levels[level].append(ns)
    return out


def build(p: dict, seed: int) -> dict:
    data = multitenant.build(p, seed)
    parent = parents(p)
    lines = data["relationships"]
    for i, line in enumerate(lines):
        if line.startswith("namespace:") and "#tenant@" in line:
            ns = int(line[len("namespace:ns"):line.index("#")])
            if ns in parent:
                lines[i] = (f"namespace:ns{ns}#parent@"
                            f"namespace:ns{parent[ns]}")
    return data
