"""The staged step's per-slice aux OR-tree refresh, against the host oracle.

`build_tables` orders the aux rows into slices by (reading stage, tree
level, live width rounded up to a power of two); the staged step
refreshes each slice once per pass of its stage, at its width, and tests
convergence on the state rows only.  Each case builds a small graph whose
OR-trees take one shape, and checks every LookupResources and every
check against `spicedb/evaluator.Evaluator`, and the staged step's final
state rows bit for bit against the Jacobi step, which refreshes every aux
row at full width.
"""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.models.workloads import multitenant_1m
from spicedb_kubeapi_proxy_tpu.ops.ell import K_AUX, make_ell_evaluate
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
from spicedb_kubeapi_proxy_tpu.utils import metrics as m
from spicedb_kubeapi_proxy_tpu.utils.features import GATES

SCHEMA = """
definition user {}
definition group {
  relation member: user | group#member
}
definition namespace {
  relation viewer: user | group#member
  relation creator: user
  permission view = viewer + creator
}
"""

N_USERS = 80


def _hub(n):
    """namespace:hub with `n` direct viewers; every other namespace one."""
    rels = [f"namespace:hub#viewer@user:u{i}" for i in range(n)]
    rels += [f"namespace:ns{i}#viewer@user:u{i}" for i in range(4)]
    rels += ["namespace:ns0#creator@user:u79",
             "group:g0#member@user:u5",
             "namespace:ns1#viewer@group:g0#member"]
    return rels


def _nested_group_hub():
    # group:big has 20 members, one of them a nested group: the group
    # stage has internal edges (repeat 2) and reads its own OR-tree
    rels = [f"group:big#member@user:u{i}" for i in range(19)]
    rels += ["group:big#member@group:inner#member",
             "group:inner#member@user:u40",
             "group:inner#member@group:core#member",
             "group:core#member@user:u41",
             "namespace:ns0#viewer@group:big#member",
             "namespace:ns1#viewer@user:u50"]
    return rels


def _aux_free_group_stage():
    # hubs on namespaces only: the group stage reads no aux
    rels = [f"namespace:hub#viewer@user:u{i}" for i in range(12)]
    rels += ["group:g0#member@user:u1", "group:g0#member@user:u2",
             "group:g1#member@group:g0#member",
             "namespace:ns0#viewer@group:g1#member"]
    return rels


def _no_aux():
    return ["group:g0#member@user:u1", "group:g0#member@user:u2",
            "namespace:ns0#viewer@group:g0#member",
            "namespace:ns1#viewer@user:u3",
            "namespace:ns1#creator@user:u4"]


# (rels, {(level, width)} of the built slices)
CASES = {
    "fanin3": (_hub(3), {(1, 4)}),
    "fanin9": (_hub(9), {(1, 16)}),
    "fanin17": (_hub(17), {(1, 32)}),
    "fanin33": (_hub(33), {(1, 32), (1, 1)}),
    "fanin70": (_hub(70), {(1, 32), (1, 8), (2, 4)}),
    "repeat2_stage_reads_aux": (_nested_group_hub(), {(1, 32)}),
    "stage_reads_no_aux": (_aux_free_group_stage(), {(1, 16)}),
    "no_aux_rows": (_no_aux(), set()),
}


def _pair(rels):
    # the Leopard closure index would answer this flat schema without
    # the kernel: keep it off so every query runs the staged step
    schema = sch.parse_schema(SCHEMA)
    prev = GATES.enabled("LeopardIndex")
    GATES.set("LeopardIndex", False)
    try:
        jx = JaxEndpoint(schema)
    finally:
        GATES.set("LeopardIndex", prev)
    jx.store.write([RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(r))
                    for r in rels])
    return jx, Evaluator(schema, jx.store)


def _users():
    return [SubjectRef("user", f"u{i}") for i in range(N_USERS)]


def _assert_oracle(jx, oracle):
    ids = jx.store.object_ids_of_type("namespace")

    async def run():
        for s in _users():
            want = sorted(oracle.lookup_resources("namespace", "view", s))
            got = sorted(await jx.lookup_resources("namespace", "view", s))
            assert got == want, (s, got, want)
            res = await jx.check_bulk_permissions(
                [CheckRequest(ObjectRef("namespace", oid), "view", s)
                 for oid in ids])
            for oid, r in zip(ids, res):
                assert r.allowed == oracle.check(
                    ObjectRef("namespace", oid), "view", s), (oid, s)
    asyncio.run(run())
    assert jx.stats["kernel_calls"] > 0


def _state_rows(jx, graph, stages):
    """Final state rows [0, state_size) of one evaluate over every user."""
    kern = graph.kernel
    q, _, _ = jx._encode_subjects(graph, _users())
    n_words = max(1, len(q) // 32)
    evaluate = make_ell_evaluate(
        graph.prog, kern.n_aux_rows, n_words, kern.num_iters,
        aux_passes=kern.aux_passes, stages=stages,
        aux_slices=kern.aux_slices)
    x = evaluate(jnp.asarray(q), graph.dev_main, graph.dev_aux)
    return np.asarray(x)[: graph.prog.state_size]


@pytest.mark.parametrize("case", sorted(CASES))
def test_sliced_staged_step_matches_oracle(case, monkeypatch):
    monkeypatch.setenv("SPICEDB_TPU_KERNEL", "ell")
    rels, shapes = CASES[case]
    jx, oracle = _pair(rels)
    _assert_oracle(jx, oracle)

    graph = jx._current_graph()
    kern = graph.kernel
    assert kern.stages, "the staged step serves this program"
    slices = kern.aux_slices
    assert {(lvl, w) for _, lvl, _, _, w in slices} == shapes
    # slices tile the built rows in order; the spare pool follows them
    bounds = [0] + [sl[3] for sl in slices]
    assert [sl[2] for sl in slices] == bounds[:-1]
    if slices:
        assert kern.spare_base == bounds[-1]
    else:
        assert kern.n_aux_rows == 0
    # every live child of a built row sits below its slice width
    dead = graph.prog.dead_index
    for _, _, lo, hi, w in slices:
        assert (graph.host_aux[lo:hi, w:] == dead).all()
        live = (graph.host_aux[lo:hi] != dead).sum(axis=1)
        assert (live <= w).all() and (live > w // 2).all()
    reads = [wants for _, _, wants in kern.stages]
    if case == "repeat2_stage_reads_aux":
        assert any(rep == 2 and wants for _, rep, wants in kern.stages)
        # the second pass refreshes big's tree again: u40 reaches inner in
        # the first pass and big, then ns0, in the second, so one sweep
        # propagates and one confirms
        with jx._lock:
            q, _, _ = jx._encode_subjects(graph, [SubjectRef("user", "u40")])
        assert kern.iterations(q, 1, graph.dev_main, graph.dev_aux) == 2
    if case == "stage_reads_no_aux":
        assert True in reads and False in reads
    if case == "no_aux_rows":
        assert not any(reads)

    with jx._lock:
        staged = _state_rows(jx, graph, kern.stages)
        jacobi = _state_rows(jx, graph, None)
    assert np.array_equal(staged, jacobi)


def test_refresh_slots_gauge_counts_compiled_slices(monkeypatch):
    """On a multitenant-shaped graph, authz_aux_refresh_slots{kind=slices}
    is the sum over stage passes of the stage's slices' (hi - lo) x
    width, and far below the whole-table refresh the staged step made
    before every aux-reading stage pass and once more per sweep."""
    monkeypatch.setenv("SPICEDB_TPU_KERNEL", "ell")
    w = multitenant_1m(n_tenants=10, n_users=600, n_groups=60,
                       n_namespaces=60, n_pods=1_500, n_tuples=10_000)
    schema = sch.parse_schema(w.schema_text)
    jx = JaxEndpoint(schema)
    jx.store.bulk_load([parse_relationship(r) for r in w.relationships])
    graph = jx._current_graph()
    kern = graph.kernel
    assert sum(wants for _, _, wants in kern.stages) >= 2
    kern._fns(1)  # compile: the gauge describes the compiled step

    want = sum(repeat * (hi - lo) * width
               for i, (_, repeat, _) in enumerate(kern.stages)
               for st, _, lo, hi, width in kern.aux_slices if st == i)
    gauge = m.REGISTRY.get("authz_aux_refresh_slots")
    assert gauge._values[("slices",)] == want
    assert gauge._values[("spare",)] == 0
    assert gauge._values[("table",)] == 0
    refreshes = sum(repeat for _, repeat, wants in kern.stages if wants) + 1
    whole = refreshes * kern.aux_passes * kern.n_aux_rows * K_AUX
    assert 0 < want < whole / 4, (want, whole)


@pytest.mark.parametrize("entry", ["_fns", "_pipe_fns"])
def test_entry_built_across_a_spare_rejit_is_dropped(entry, monkeypatch):
    """An entry point built from the old spare prefix while delta growth
    re-jits must not land in the new cache: it would skip a spare row
    the flushed tables read."""
    monkeypatch.setenv("SPICEDB_TPU_KERNEL", "ell")
    jx, _ = _pair(_hub(40))
    kern = jx._current_graph().kernel
    build = kern._evaluate

    def racing(n_words, **kw):
        evaluate = build(n_words, **kw)
        kern.note_spare_row(kern.spare_base, 1)  # growth meanwhile
        return evaluate

    monkeypatch.setattr(kern, "_evaluate", racing)
    getattr(kern, entry)(1)
    assert kern.spare_prefix == 1
    assert kern._jits == {}
