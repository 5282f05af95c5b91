"""Multi-tenant Kubernetes graph: users in groups, groups in tenants,
tenants owning namespaces, pods in namespaces with direct viewer grants.

The shape of BASELINE config 5 (`multitenant-1m`).  The structure (every
count, who is grouped with whom, each pod's number of viewers) comes from
the configuration's `structure_seed`, so that every run compiles the same
shapes and does the same work.  The run's seed picks identities only: it
relabels users, groups and tenants, and moves each pod's viewer grants to
another pod, by seeded permutations.
"""

from __future__ import annotations

import random


def kube_pods(p: dict) -> list:
    """[(namespace, name)] of every pod: pod p lives in namespace p % N."""
    n_ns = p["n_namespaces"]
    return [(f"ns{i % n_ns}", f"p{i}") for i in range(p["n_pods"])]


def _perm(rng, n: int) -> list:
    out = list(range(n))
    rng.shuffle(out)
    return out


def build(p: dict, seed: int) -> dict:
    n_users, n_groups = p["n_users"], p["n_groups"]
    n_tenants, n_ns, n_pods = p["n_tenants"], p["n_namespaces"], p["n_pods"]
    rng = random.Random(p["structure_seed"])
    group_of = [rng.randrange(n_groups) for _ in range(n_users)]
    tenant_of = [rng.randrange(n_tenants) for _ in range(n_groups)]
    admins = [rng.randrange(n_users) for _ in range(n_tenants)]
    n_viewed = round(p["namespace_viewer_share"] * n_ns)
    ns_viewer = {ns: rng.randrange(n_groups)
                 for ns in sorted(rng.sample(range(n_ns), n_viewed))}
    n_fixed = (n_users + n_groups + len(range(0, n_groups - 1,
                                              p["nest_every"]))
               + n_tenants + n_ns + n_viewed + n_pods)
    # direct pod viewers, distinct, up to exactly n_tuples
    grants = set()
    while len(grants) < p["n_tuples"] - n_fixed:
        grants.add(rng.randrange(n_pods) * n_users + rng.randrange(n_users))

    ids = random.Random(seed)
    user, group = _perm(ids, n_users), _perm(ids, n_groups)
    tenant, pod = _perm(ids, n_tenants), _perm(ids, n_pods)
    lines = [f"group:g{group[g]}#member@user:u{user[u]}"
             for u, g in enumerate(group_of)]
    lines += [f"tenant:t{tenant[t]}#member@group:g{group[g]}#member"
              for g, t in enumerate(tenant_of)]
    lines += [f"group:g{group[g + 1]}#member@group:g{group[g]}#member"
              for g in range(0, n_groups - 1, p["nest_every"])]
    lines += [f"tenant:t{tenant[t]}#admin@user:u{user[u]}"
              for t, u in enumerate(admins)]
    lines += [f"namespace:ns{ns}#tenant@tenant:t{tenant[ns % n_tenants]}"
              for ns in range(n_ns)]
    lines += [f"namespace:ns{ns}#viewer@group:g{group[g]}#member"
              for ns, g in ns_viewer.items()]
    lines += [f"pod:ns{i % n_ns}/p{i}#namespace@namespace:ns{i % n_ns}"
              for i in range(n_pods)]
    for key in sorted(grants):
        i, u = divmod(key, n_users)
        i = pod[i]
        lines.append(f"pod:ns{i % n_ns}/p{i}#viewer@user:u{user[u]}")
    return {
        "relationships": lines,
        "users": [f"u{u}" for u in range(n_users)],
    }
