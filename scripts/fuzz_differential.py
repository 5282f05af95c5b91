"""Randomized differential campaign: jax:// kernels vs the host oracle.

Broader than the committed randomized tier (tests/test_jax_backend.py):
random schema template x random graph x sustained churn that mixes
in-universe edits, BRAND-NEW object/subject ids (spare-pool path),
caveated tuples with random contexts, already-expired / far-future
expirations, and deletes — oracle agreement asserted after every burst.
Kernel choice (ell/segment) is randomized per seed; `--mesh` runs every
seed on the sharded endpoint (ell-only) over a virtual 8-device CPU
mesh instead.

Usage:
    python scripts/fuzz_differential.py [n_seeds] [--mesh]
Prints one line per seed; exits non-zero on the first divergence with a
reproduction recipe.
"""

import asyncio
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--mesh" in sys.argv and os.environ.get("_FUZZ_MESH_REEXEC") != "1":
    # the sharded path needs the virtual 8-device CPU mesh, and the env
    # must be in place before jax initializes its backend — re-exec with
    # it set
    env = dict(os.environ, _FUZZ_MESH_REEXEC="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"))
    os.execve(sys.executable, [sys.executable] + sys.argv, env)

from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    CheckRequest,
    ObjectRef,
    RelationshipUpdate,
    SubjectRef,
    UpdateOp,
    parse_relationship,
)

SCHEMAS = {
    "groups": """
definition user {}
definition group { relation member: user | group#member }
definition namespace {
  relation viewer: user | group#member
  relation creator: user
  permission view = viewer + creator
}
""",
    "rbac-deny": """
definition user {}
definition group { relation member: user | group#member }
definition project {
  relation assigned: user | group#member
  relation approved: user
  relation banned: user | group#member
  permission edit = assigned & approved - banned
}
""",
    "arrows": """
definition user {}
definition org {
  relation admin: user
  permission admin_perm = admin
}
definition namespace {
  relation org: org
  relation viewer: user
  permission view = viewer + org->admin_perm
}
definition pod {
  relation namespace: namespace
  relation creator: user
  permission view = creator + namespace->view
}
""",
    "caveats": """
caveat within_limit(current int, max int) { current < max }
definition user {}
definition doc {
  relation viewer: user | user with within_limit
  relation editor: user
  permission view = viewer + editor
}
""",
}

TARGET = {"groups": ("namespace", "view"), "rbac-deny": ("project", "edit"),
          "arrows": ("pod", "view"), "caveats": ("doc", "view")}


def rand_rel(rng, kind, n, new_id_rate=0.15):
    def oid(prefix, pool):
        if rng.random() < new_id_rate:
            return f"{prefix}{rng.randrange(10 * n)}x"  # mostly brand-new
        return f"{prefix}{rng.randrange(n)}"

    u = f"user:u{rng.randrange(n)}"
    if kind == "groups":
        c = rng.random()
        if c < 0.35:
            return f"group:{oid('g', n)}#member@{u}"
        if c < 0.5:
            a, b = oid("g", n), oid("g", n)
            return f"group:{a}#member@group:{b}#member"
        if c < 0.75:
            return f"namespace:{oid('ns', n)}#viewer@{u}"
        if c < 0.85:
            # deterministic expiration cases: already expired (the lazy
            # expiry-heap delete path) or far-future (plain tuple + heap
            # bookkeeping) — never near-now, which would race the oracle
            exp = (time.time() - 3600 if rng.random() < 0.5
                   else time.time() + 86400)
            return (f"namespace:{oid('ns', n)}#viewer@{u}"
                    f"[expiration:{exp}]")
        return f"namespace:{oid('ns', n)}#creator@{u}"
    if kind == "rbac-deny":
        c = rng.random()
        if c < 0.3:
            return f"group:{oid('g', 3)}#member@{u}"
        p = oid("p", n)
        if c < 0.55:
            return f"project:{p}#assigned@group:g{rng.randrange(3)}#member"
        if c < 0.75:
            return f"project:{p}#approved@{u}"
        return f"project:{p}#banned@{u}"
    if kind == "arrows":
        c = rng.random()
        if c < 0.2:
            return f"org:{oid('o', 3)}#admin@{u}"
        if c < 0.4:
            return f"namespace:{oid('ns', n)}#org@org:o{rng.randrange(3)}"
        if c < 0.6:
            return f"namespace:{oid('ns', n)}#viewer@{u}"
        if c < 0.8:
            return (f"pod:{oid('pd', n)}#namespace"
                    f"@namespace:ns{rng.randrange(n)}")
        return f"pod:{oid('pd', n)}#creator@{u}"
    # caveats
    c = rng.random()
    d = oid("d", n)
    if c < 0.4:
        cur, mx = rng.randrange(5), rng.randrange(5)
        return (f"doc:{d}#viewer@{u}"
                f"[caveat:within_limit:{{\"current\":{cur},\"max\":{mx}}}]")
    if c < 0.5:
        # undecidable: max missing -> context-dependent at check time
        cur = rng.randrange(5)
        return (f"doc:{d}#viewer@{u}"
                f"[caveat:within_limit:{{\"current\":{cur}}}]")
    if c < 0.8:
        return f"doc:{d}#viewer@{u}"
    return f"doc:{d}#editor@{u}"


def agree(jx, oracle, rt, perm, subjects, seed, step):
    async def run():
        for s in subjects:
            want = sorted(oracle.lookup_resources(rt, perm, s))
            got = sorted(await jx.lookup_resources(rt, perm, s))
            assert got == want, (
                f"LR mismatch seed={seed} step={step} subj={s}: "
                f"kernel-only={sorted(set(got)-set(want))} "
                f"oracle-only={sorted(set(want)-set(got))}")
            ids = jx.store.object_ids_of_type(rt)
            if ids:
                reqs = [CheckRequest(ObjectRef(rt, o), perm, s) for o in ids]
                res = await jx.check_bulk_permissions(reqs)
                for o, r in zip(ids, res):
                    w3 = oracle.check3(ObjectRef(rt, o), perm, s)
                    g3 = {"NO_PERMISSION": 0, "CONDITIONAL_PERMISSION": 1,
                          "HAS_PERMISSION": 2}[r.permissionship.name]
                    assert g3 == w3, (
                        f"check3 mismatch seed={seed} step={step} "
                        f"{rt}:{o}#{perm}@{s}: kernel={g3} oracle={w3}")
    asyncio.run(run())


def run_seed(seed, mesh=None):
    rng = random.Random(seed)
    kind = rng.choice(list(SCHEMAS))
    n = rng.randint(4, 16)
    schema = sch.parse_schema(SCHEMAS[kind])
    kwargs = {}
    if mesh is not None:
        kwargs["mesh"] = mesh
        kwargs["kernel"] = "ell"  # mesh sharding is ell-only
    else:
        kwargs["kernel"] = rng.choice(["ell", "ell", "segment"])
    jx = JaxEndpoint(schema, **kwargs)
    oracle = Evaluator(schema, jx.store)
    rt, perm = TARGET[kind]
    seeds = sorted({rand_rel(rng, kind, n, new_id_rate=0)
                    for _ in range(rng.randint(5, 40))})
    jx.store.write([RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(r))
                    for r in seeds])
    subjects = [SubjectRef("user", f"u{i}") for i in range(n)] + \
               [SubjectRef("user", "stranger")]
    agree(jx, oracle, rt, perm, subjects, seed, -1)
    for step in range(rng.randint(3, 7)):
        ops = []
        for _ in range(rng.randint(2, 12)):
            r = rand_rel(rng, kind, n)
            op = UpdateOp.DELETE if rng.random() < 0.35 else UpdateOp.TOUCH
            # deletes key on identity only: strip any caveat/expiry suffix
            rel = parse_relationship(r.split("[")[0]
                                     if op == UpdateOp.DELETE else r)
            ops.append(RelationshipUpdate(op, rel))
        jx.store.write(ops)
        agree(jx, oracle, rt, perm, subjects, seed, step)
    return kind, jx.stats


def main():
    args = [a for a in sys.argv[1:] if a != "--mesh"]
    n_seeds = int(args[0]) if args else 40
    mesh = None
    if "--mesh" in sys.argv:
        from spicedb_kubeapi_proxy_tpu.parallel.sharding import make_mesh
        mesh = make_mesh(data=2, graph=4)
    t0 = time.time()
    for seed in range(n_seeds):
        t1 = time.time()
        kind, stats = run_seed(seed, mesh=mesh)
        print(f"seed {seed:3d} [{kind:9s}] ok in {time.time()-t1:5.1f}s  "
              f"(rebuilds={stats['rebuilds']} spares="
              f"{stats['spare_assignments']} kernel={stats['kernel_calls']})")
    print(f"ALL {n_seeds} SEEDS AGREE in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
