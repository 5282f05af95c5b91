"""RBAC with deny: `view = assigned & approved - banned` over group grants.

The shape of BASELINE config 4 (`rbac_deny()` in the program's workload
models), with the namespace count as a parameter: each user joins one
group, a fixed share of users is blocked, each pod is assigned to one group
and approved for that group or the next, and a fixed share of pods bans the
blocked group.  The structure comes from the configuration's
`structure_seed`, so that every run compiles the same shapes and does the
same work; the run's seed picks identities only, by relabeling users and
groups and moving each pod's grants to another pod.
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
    n_pods, n_ns = p["n_pods"], p["n_namespaces"]
    rng = random.Random(p["structure_seed"])
    group_of = [rng.randrange(n_groups) for _ in range(n_users)]
    blocked = sorted(rng.sample(range(n_users),
                                round(p["blocked_share"] * n_users)))
    banned = set(rng.sample(range(n_pods), round(p["banned_share"] * n_pods)))
    grants = []
    for _ in range(n_pods):
        g = rng.randrange(n_groups)
        grants.append((g, (g + rng.randrange(2)) % n_groups))

    ids = random.Random(seed)
    user, group, pod = (_perm(ids, n_users), _perm(ids, n_groups),
                        _perm(ids, n_pods))
    lines = [f"group:g{group[g]}#member@user:u{user[u]}"
             for u, g in enumerate(group_of)]
    lines += [f"group:blocked#member@user:u{user[u]}" for u in blocked]
    for i, (g, h) in enumerate(grants):
        j = pod[i]
        name = f"pod:ns{j % n_ns}/p{j}"
        lines.append(f"{name}#assigned@group:g{group[g]}#member")
        lines.append(f"{name}#approved@group:g{group[h]}#member")
        if i in banned:
            lines.append(f"{name}#banned@group:blocked#member")
    return {
        "relationships": lines,
        "users": [f"u{u}" for u in range(n_users)],
    }
