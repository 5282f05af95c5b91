"""Multi-chip sharding tests on the virtual 8-device CPU mesh: the sharded
kernel must agree exactly with the single-chip kernel and the host oracle."""

import numpy as np
import pytest

import jax

from spicedb_kubeapi_proxy_tpu.ops.graph_compile import compile_graph
from spicedb_kubeapi_proxy_tpu.ops.spmv import KernelCache, bucket, pad_edges
from spicedb_kubeapi_proxy_tpu.parallel.sharding import ShardedKernel, make_mesh
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    ObjectRef,
    SubjectRef,
    parse_relationship,
)

SCHEMA = """
definition user {}
definition group {
  relation member: user | group#member
}
definition tenant {
  relation admin: user
  relation member: user | group#member
  permission access = admin + member
}
definition namespace {
  relation tenant: tenant
  relation viewer: user | group#member
  permission view = viewer + tenant->access
}
definition pod {
  relation namespace: namespace
  relation creator: user
  relation banned: user
  permission view = creator + namespace->view - banned
}
"""


def build(seed=0, n_users=40, n_groups=8, n_tenants=3, n_ns=6, n_pods=60):
    import random
    rng = random.Random(seed)
    rels = set()
    for u in range(n_users):
        rels.add(f"group:g{rng.randrange(n_groups)}#member@user:u{u}")
    for g in range(n_groups):
        rels.add(f"tenant:t{g % n_tenants}#member@group:g{g}#member")
        if g % 3 == 0 and g + 1 < n_groups:
            rels.add(f"group:g{g+1}#member@group:g{g}#member")
    for t in range(n_tenants):
        rels.add(f"tenant:t{t}#admin@user:u{rng.randrange(n_users)}")
    for ns in range(n_ns):
        rels.add(f"namespace:ns{ns}#tenant@tenant:t{ns % n_tenants}")
    for p in range(n_pods):
        ns = p % n_ns
        rels.add(f"pod:ns{ns}/p{p}#namespace@namespace:ns{ns}")
        if rng.random() < 0.2:
            rels.add(f"pod:ns{ns}/p{p}#creator@user:u{rng.randrange(n_users)}")
        if rng.random() < 0.1:
            rels.add(f"pod:ns{ns}/p{p}#banned@user:u{rng.randrange(n_users)}")
    schema = sch.parse_schema(SCHEMA)
    store = TupleStore()
    store.bulk_load([parse_relationship(r) for r in sorted(rels)])
    prog = compile_graph(schema, store.read(None))
    return schema, store, prog


class TestMesh:
    def test_eight_devices_available(self):
        assert len(jax.devices()) == 8

    def test_mesh_shapes(self):
        mesh = make_mesh()
        assert mesh.shape["data"] * mesh.shape["graph"] == 8
        mesh2 = make_mesh(data=4, graph=2)
        assert mesh2.shape == {"data": 4, "graph": 2}
        with pytest.raises(ValueError):
            make_mesh(data=3, graph=3)


class TestShardedAgreement:
    @pytest.mark.parametrize("data,graph", [(1, 8), (8, 1), (2, 4), (4, 2)])
    def test_lookup_matches_single_chip_and_oracle(self, data, graph):
        schema, store, prog = build()
        oracle = Evaluator(schema, store)
        mesh = make_mesh(data=data, graph=graph)
        sharded = ShardedKernel(prog, mesh)
        s_src, s_dst = sharded.device_edges()

        single = KernelCache(prog)
        src, dst = pad_edges(prog)
        import jax.numpy as jnp
        src, dst = jnp.asarray(src), jnp.asarray(dst)

        subjects = [SubjectRef("user", f"u{i}") for i in range(16)]
        q = np.asarray([prog.subject_index(s.type, s.id, s.relation)
                        for s in subjects], np.int32)
        off, ln = prog.slot_range("pod", "view")
        got_sharded = sharded.lookup(off, ln, q, s_src, s_dst)

        qb = np.full(bucket(len(q), 8), prog.dead_index, np.int32)
        qb[: len(q)] = q
        got_single = single.lookup(off, ln, qb, src, dst)[:, : len(q)]

        ids = prog.object_ids["pod"]
        for i, s in enumerate(subjects):
            want = set(oracle.lookup_resources("pod", "view", s))
            from_sharded = {ids[j] for j in np.nonzero(got_sharded[:, i])[0]}
            from_single = {ids[j] for j in np.nonzero(got_single[:, i])[0]}
            assert from_sharded == want, f"sharded vs oracle for {s}"
            assert from_single == want, f"single vs oracle for {s}"

    def test_checks_match_oracle(self):
        schema, store, prog = build(seed=3)
        oracle = Evaluator(schema, store)
        mesh = make_mesh(data=2, graph=4)
        sharded = ShardedKernel(prog, mesh)
        s_src, s_dst = sharded.device_edges()

        subjects = [SubjectRef("user", f"u{i}") for i in range(8)]
        q = np.asarray([prog.subject_index(s.type, s.id) for s in subjects],
                       np.int32)
        pods = prog.object_ids["pod"][:20]
        gather_idx, gather_col, want = [], [], []
        for ci, s in enumerate(subjects):
            for p in pods:
                gather_idx.append(prog.state_index("pod", "view", p))
                gather_col.append(ci)
                want.append(oracle.check(ObjectRef("pod", p), "view", s))
        got = sharded.checks(q, np.asarray(gather_idx),
                             np.asarray(gather_col), s_src, s_dst)
        assert [bool(x) for x in got] == want


class TestShardedEllKernel:
    """Packed fixed-fanin kernel over the mesh (parallel/sharding.py
    ShardedEllKernel): word-sharded batch (data) x row-sharded tables
    (graph) with per-iteration all_gather."""

    @pytest.mark.parametrize("data,graph", [(1, 8), (2, 4), (8, 1)])
    def test_lookup_matches_oracle(self, data, graph):
        schema, store, prog = build(seed=11)
        mesh = make_mesh(jax.devices()[:8], data=data, graph=graph)
        from spicedb_kubeapi_proxy_tpu.parallel.sharding import ShardedEllKernel
        k = ShardedEllKernel(prog, mesh)
        oracle = Evaluator(schema, store)
        subjects = [f"u{i}" for i in range(40)]
        q = np.asarray([prog.subject_index("user", s) for s in subjects],
                       np.int32)
        off, ln = prog.slot_range("pod", "view")
        bm = k.lookup(off, ln, q)
        assert bm.shape == (ln, len(subjects))
        ids = prog.object_ids["pod"]
        for col, u in enumerate(subjects):
            want = set(oracle.lookup_resources("pod", "view",
                                               SubjectRef("user", u)))
            got = {ids[i] for i in np.nonzero(bm[:, col])[0]}
            assert got == want, (u, got ^ want)

    def test_checks_match_oracle_with_hub(self):
        # a 300-member group forces the aux OR-tree through the sharded path
        import random
        rng = random.Random(2)
        rels = [f"group:big#member@user:u{i}" for i in range(300)]
        rels += ["namespace:ns#tenant@tenant:t0",
                 "tenant:t0#member@group:big#member"]
        rels += [f"pod:ns/p{i}#namespace@namespace:ns" for i in range(20)]
        schema = sch.parse_schema(SCHEMA)
        store = TupleStore()
        store.bulk_load_text("\n".join(rels))
        prog = compile_graph(schema, store.read(None))
        mesh = make_mesh(jax.devices()[:8], data=2, graph=4)
        from spicedb_kubeapi_proxy_tpu.parallel.sharding import ShardedEllKernel
        k = ShardedEllKernel(prog, mesh)
        oracle = Evaluator(schema, store)
        subjects = [f"u{i}" for i in range(0, 300, 17)]
        q = np.asarray([prog.subject_index("user", s) for s in subjects],
                       np.int32)
        ids = prog.object_ids["pod"]
        gather_idx, gather_col, expect = [], [], []
        for j, u in enumerate(subjects):
            for oid in ids[:7]:
                gather_idx.append(prog.state_index("pod", "view", oid))
                gather_col.append(j)
                expect.append(oracle.check(ObjectRef("pod", oid), "view",
                                           SubjectRef("user", u)))
        out = k.checks(q, np.asarray(gather_idx), np.asarray(gather_col))
        assert [bool(x) for x in out] == expect
