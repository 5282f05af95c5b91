"""Passes per sweep of a recursive stage, derived from the data.

`compute_stages` gives each self-dependent type SCC as many passes per
sweep as its longest internal chain needs (`_stage_passes`), so a
hierarchical-namespace `parent->view` chain, or a chain of nested
groups, resolves within one sweep.  Seeded random namespace trees of
depth 1-12, with and without a parent cycle and with nested groups beside
them, are served through `jax://` and compared with the host oracle and
with the Jacobi step; the derived passes, the sweep count and the
`authz_stage_passes` gauge are checked on them.
"""

import asyncio
import importlib.util
import json
import os
import random

import pytest

from spicedb_kubeapi_proxy_tpu.ops.ell import MAX_STAGE_PASSES, compute_stages
from spicedb_kubeapi_proxy_tpu.ops.graph_compile import compile_graph
from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    CheckRequest,
    ObjectRef,
    SubjectRef,
    parse_relationship,
)
from spicedb_kubeapi_proxy_tpu.utils import workload
from spicedb_kubeapi_proxy_tpu.utils.features import GATES

SCHEMA = """
definition user {}
definition group {
  relation member: user | group#member
}
definition namespace {
  relation parent: namespace
  relation viewer: user | group#member
  permission view = viewer + parent->view
}
definition pod {
  relation namespace: namespace
  relation viewer: user
  permission view = viewer + namespace->view
}
"""

N_USERS = 24
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _forest(seed: int, cycle: bool, nest: int = 0) -> tuple:
    """Relationship lines of a random namespace tree of depth 1-12 (a
    parent chain of that length plus random branches), its depth, pods
    under random namespaces, viewers (users and groups) on random
    namespaces; with `cycle`, three more namespaces parented in a ring
    below the tree; with `nest`, a chain of that many nested groups."""
    rng = random.Random(seed)
    depth = rng.randint(1, 12)
    levels = [[f"n{lvl}_0"] for lvl in range(depth + 1)]
    rels = [f"namespace:{levels[lvl][0]}#parent@namespace:"
            f"{levels[lvl - 1][0]}" for lvl in range(1, depth + 1)]
    for k in range(2 * depth):
        lvl = rng.randint(1, depth)
        ns = f"n{lvl}_{k + 1}"
        rels.append(f"namespace:{ns}#parent@namespace:"
                    f"{rng.choice(levels[lvl - 1])}")
        levels[lvl].append(ns)
    names = [ns for lvl in levels for ns in lvl]
    if cycle:
        ring = ["c0", "c1", "c2"]
        rels += [f"namespace:{a}#parent@namespace:{b}"
                 for a, b in zip(ring, ring[1:] + ring[:1])]
        rels += [f"namespace:c0#parent@namespace:{levels[-1][0]}",
                 "namespace:c2#viewer@user:u1"]
        names += ring
    groups = [f"g{i}" for i in range(max(1, nest + 1))]
    rels += [f"group:{a}#member@group:{b}#member"
             for a, b in zip(groups[1:], groups)]
    for u in range(N_USERS):
        if rng.random() < 0.3:
            rels.append(f"group:{rng.choice(groups)}#member@user:u{u}")
    for _ in range(6):
        rels.append(f"namespace:{rng.choice(names)}#viewer@user:"
                    f"u{rng.randrange(N_USERS)}")
    rels.append(f"namespace:{levels[0][0]}#viewer@group:g0#member")
    rels.append(f"namespace:{rng.choice(names)}#viewer@group:"
                f"{groups[-1]}#member")
    for p in range(40):
        rels.append(f"pod:p{p}#namespace@namespace:{rng.choice(names)}")
        if rng.random() < 0.2:
            rels.append(f"pod:p{p}#viewer@user:u{rng.randrange(N_USERS)}")
    return sorted(set(rels)), depth


def _endpoint(rels: list) -> JaxEndpoint:
    # the Leopard closure index would answer some of these lookups
    # without the kernel: keep it off so every query runs the fixpoint
    schema = sch.parse_schema(SCHEMA)
    prev = GATES.enabled("LeopardIndex")
    GATES.set("LeopardIndex", False)
    try:
        jx = JaxEndpoint(schema)
    finally:
        GATES.set("LeopardIndex", prev)
    jx.store.bulk_load([parse_relationship(r) for r in rels])
    return jx


def _answers(jx: JaxEndpoint) -> dict:
    """Every user's namespace and pod lookups, and a check of every
    namespace, through the endpoint."""
    users = [SubjectRef("user", f"u{i}") for i in range(N_USERS)]
    ids = jx.store.object_ids_of_type("namespace")

    async def run():
        out = {}
        for s in users:
            for rtype in ("namespace", "pod"):
                out[(s.id, rtype)] = sorted(
                    await jx.lookup_resources(rtype, "view", s))
            res = await jx.check_bulk_permissions(
                [CheckRequest(ObjectRef("namespace", oid), "view", s)
                 for oid in ids])
            out[(s.id, "check")] = [r.allowed for r in res]
        return out
    return asyncio.run(run())


def _oracle(jx: JaxEndpoint) -> dict:
    ev = Evaluator(sch.parse_schema(SCHEMA), jx.store)
    ids = jx.store.object_ids_of_type("namespace")
    out = {}
    for i in range(N_USERS):
        s = SubjectRef("user", f"u{i}")
        for rtype in ("namespace", "pod"):
            out[(s.id, rtype)] = sorted(ev.lookup_resources(rtype, "view", s))
        out[(s.id, "check")] = [ev.check(ObjectRef("namespace", oid),
                                         "view", s) for oid in ids]
    return out


def _stage_of(kern, prog, rtype: str, slot: str) -> int:
    row = prog.slot_offsets[(rtype, slot)]
    return next(rep for ranges, rep, _ in kern.stages
                if any(lo <= row < hi for lo, hi in ranges))


@pytest.mark.parametrize("cycle", [False, True], ids=["tree", "cycle"])
@pytest.mark.parametrize("seed", [2, 3, 9, 5, 20])  # depths 1, 4, 8, 10, 12
def test_random_trees_match_oracle_and_jacobi(seed, cycle, monkeypatch):
    monkeypatch.setenv("SPICEDB_TPU_KERNEL", "ell")
    rels, depth = _forest(seed, cycle, nest=seed % 4)
    jx = _endpoint(rels)
    staged = _answers(jx)
    assert jx.stats["kernel_calls"] > 0
    assert staged == _oracle(jx)

    graph = jx._current_graph()
    passes = _stage_of(graph.kernel, graph.prog, "namespace", "view")
    if cycle:
        assert depth + 1 <= passes <= MAX_STAGE_PASSES
    else:
        assert passes == depth + 1
    assert _stage_of(graph.kernel, graph.prog, "group", "member") == \
        seed % 4 + 1

    monkeypatch.setenv("SPICEDB_TPU_STAGED", "0")
    jacobi = _endpoint(rels)
    assert jacobi._current_graph().kernel.stages is None
    assert _answers(jacobi) == staged


def _chain(depth: int) -> list:
    rels = [f"namespace:n{i}#parent@namespace:n{i - 1}"
            for i in range(1, depth + 1)]
    rels += ["namespace:n0#viewer@user:u0",
             f"pod:p0#namespace@namespace:n{depth}"]
    return rels


@pytest.mark.parametrize("depth", [1, 4, 8, 15, 20])
def test_passes_are_chain_length_plus_one(depth):
    prog = compile_graph(sch.parse_schema(SCHEMA),
                         [parse_relationship(r) for r in _chain(depth)])
    ns = prog.slot_offsets[("namespace", "view")]
    reps = [rep for ranges, rep, _ in compute_stages(prog)
            if any(lo <= ns < hi for lo, hi in ranges)]
    assert reps == [min(depth + 1, MAX_STAGE_PASSES)]


def _generator(name: str):
    spec = importlib.util.spec_from_file_location(
        f"gen_{name}", os.path.join(REPO, "benchmark", "generators",
                                    f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the stages of the benchmark's generators at a small size, (ranges,
# passes) in order: the type graph gives the ranges and their order, the
# data gives the passes
GENERATOR_STAGES = {
    "multitenant-150k": [(((0, 600),), 1), (((600, 720),), 2),
                         (((720, 736),), 1), (((736, 936),), 1),
                         (((936, 14936),), 1)],
    "rbac-deny-3k": [(((0, 200),), 1), (((200, 222),), 1),
                     (((222, 15222),), 1)],
    "hnc-150k": [(((0, 600),), 1), (((600, 720),), 2), (((720, 736),), 1),
                 (((736, 1016),), 9), (((1016, 15016),), 1)],
}
SMALL = {
    "multitenant-150k": dict(n_tenants=4, n_users=600, n_groups=60,
                             n_namespaces=40, n_pods=2000, n_tuples=12000),
    "rbac-deny-3k": dict(n_users=200, n_groups=10, n_pods=3000,
                         n_namespaces=5),
}
SMALL["hnc-150k"] = SMALL["multitenant-150k"]


@pytest.mark.parametrize("config,namespace_passes", [
    ("multitenant-150k", 1), ("hnc-150k", 9), ("rbac-deny-3k", None)])
def test_passes_on_the_benchmark_generators_shapes(config, namespace_passes):
    """The benchmark's generators at a small size: group nesting one hop
    deep gives the group stage 2 passes, as the fixed repeat did, the
    depth-8 HNC trees give the namespace stage 9, and every other stage,
    and the stages' ranges and order, are what the type graph gives."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    params = dict(cfg["params"], **SMALL[config])
    with open(os.path.join(REPO, "benchmark", "configs",
                           cfg["schema"])) as f:
        schema = sch.parse_schema(f.read())
    lines = _generator(cfg["generator"]).build(params, 7)["relationships"]
    prog = compile_graph(schema, [parse_relationship(r) for r in lines])
    stages = compute_stages(prog)
    assert [(ranges, rep) for ranges, rep, _ in stages] == \
        GENERATOR_STAGES[config]
    ns = prog.slot_offsets.get(("namespace", "__self__"))
    assert namespace_passes == (None if ns is None else next(
        rep for ranges, rep, _ in stages
        if any(lo <= ns < hi for lo, hi in ranges)))


def _hist(h, verb: str) -> tuple:
    """(sum, count) of a {kernel, verb} histogram over one verb."""
    got = [v for (_, vb), v in h.raw().items() if vb == verb]
    return (sum(v[1] for v in got), sum(v[2] for v in got))


def test_depth8_chain_converges_in_two_sweeps_and_counts_passes(monkeypatch):
    monkeypatch.setenv("SPICEDB_TPU_KERNEL", "ell")
    jx = _endpoint(_chain(8))
    kern = jx._current_graph().kernel
    sweeps0 = _hist(workload.WORKLOAD._sweep_iters, "lookup")
    calls0 = jx.stats["kernel_calls"]

    async def run():
        return [await jx.lookup_resources("pod", "view",
                                          SubjectRef("user", "u0"))
                for _ in range(3)]
    assert asyncio.run(run()) == [["p0"]] * 3
    calls = jx.stats["kernel_calls"] - calls0
    sweeps = _hist(workload.WORKLOAD._sweep_iters, "lookup")
    n = sweeps[1] - sweeps0[1]
    assert n == calls == 3
    assert (sweeps[0] - sweeps0[0]) / n <= 2
    # the compiled step's passes per sweep, set when it was jitted: the
    # namespace stage's 9 and one for each other stage
    assert _stage_of(kern, jx._current_graph().prog, "namespace",
                     "view") == 9
    passes = sum(rep for _, rep, _ in kern.stages)
    assert passes == 9 + len(kern.stages) - 1
    assert workload.WORKLOAD._stage_passes._values[()] == passes


def test_stage_passes_gated_off_with_the_sweeps():
    from spicedb_kubeapi_proxy_tpu.utils import metrics as m
    wa = workload.WorkloadAccounting(registry=m.Registry())
    wa.note_stage_passes(7)
    assert wa._stage_passes._values[()] == 7
    GATES.set("KernelIntrospect", False)
    try:
        wa.note_stage_passes(12)
    finally:
        GATES.set("KernelIntrospect", True)
    assert wa._stage_passes._values[()] == 7
    wa.note_stage_passes(1)
    assert wa._stage_passes._values[()] == 1
