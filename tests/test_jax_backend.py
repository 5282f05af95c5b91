"""Differential tests: jax:// kernel vs the host oracle evaluator.

The embedded evaluator is the reference oracle (SURVEY.md §4: "the
embedded:// evaluator doubles as the reference oracle for differential-
testing the jax:// kernel"); every scenario asserts exact agreement on
checks and LookupResources, including after incremental writes/deletes
(the unsorted-delta device path) and expirations.
"""

import asyncio
import random

import pytest

from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import Bootstrap
from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    CheckRequest,
    ObjectRef,
    RelationshipUpdate,
    SubjectRef,
    UpdateOp,
    parse_relationship)


@pytest.fixture(autouse=True, params=["ell", "segment"])
def kernel_kind(request, monkeypatch):
    """Run every differential scenario against BOTH device kernels: the
    bit-packed fixed-fanin default and the segment_sum fallback."""
    monkeypatch.setenv("SPICEDB_TPU_KERNEL", request.param)
    return request.param


def touch(*rels):
    return [RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(r)) for r in rels]


def delete(*rels):
    return [RelationshipUpdate(UpdateOp.DELETE, parse_relationship(r)) for r in rels]


def make_pair(schema_text, rels, clock=None):
    """(jax endpoint, oracle) over the same tuples.  Pass `clock` for
    deterministic expiry tests (the endpoint's expiry heap and the
    store's read-time filtering share it)."""
    schema = sch.parse_schema(schema_text)
    jx = JaxEndpoint(schema, store=TupleStore(clock=clock)
                     if clock is not None else None)
    if rels:
        jx.store.write(touch(*rels))
    oracle = Evaluator(schema, jx.store)
    return jx, oracle


def make_kernel_pair(schema_text, rels):
    """make_pair with the Leopard closure index off, so every check and
    lookup runs the fixpoint kernel."""
    from spicedb_kubeapi_proxy_tpu.utils.features import GATES
    prev = GATES.enabled("LeopardIndex")
    GATES.set("LeopardIndex", False)
    try:
        return make_pair(schema_text, rels)
    finally:
        GATES.set("LeopardIndex", prev)


def make_clocked_pair(schema_text, rels):
    """(jx, oracle, clk): a pair on a manual clock — set clk[0] to move
    time for deterministic expiry tests."""
    import time
    clk = [time.time()]
    jx, oracle = make_pair(schema_text, rels, clock=lambda: clk[0])
    return jx, oracle, clk


def assert_agreement(jx, oracle, resource_type, permission, subjects,
                     object_ids=None):
    """Exhaustive check+LR agreement for the given subjects."""
    ids = object_ids if object_ids is not None else \
        jx.store.object_ids_of_type(resource_type)

    async def run():
        for s in subjects:
            want = sorted(oracle.lookup_resources(resource_type, permission, s))
            got = sorted(await jx.lookup_resources(resource_type, permission, s))
            assert got == want, (
                f"LR mismatch for {s}: kernel={got} oracle={want}")
            reqs = [CheckRequest(ObjectRef(resource_type, oid), permission, s)
                    for oid in ids]
            if not reqs:
                continue
            results = await jx.check_bulk_permissions(reqs)
            for oid, res in zip(ids, results):
                want_one = oracle.check(ObjectRef(resource_type, oid),
                                        permission, s)
                assert res.allowed == want_one, (
                    f"check mismatch {resource_type}:{oid}#{permission}@{s}:"
                    f" kernel={res.allowed} oracle={want_one}")
    asyncio.run(run())


GROUPS_SCHEMA = """
definition user {}
definition group {
  relation member: user | group#member
}
definition team {
  relation member: user | group#member
}
definition namespace {
  relation viewer: user | group#member | team#member
  relation creator: user
  permission view = viewer + creator
}
"""

RBAC_DENY_SCHEMA = """
definition user {}
definition group {
  relation member: user | group#member
}
definition project {
  relation assigned: user | group#member
  relation approved: user
  relation banned: user | group#member
  permission edit = assigned & approved - banned
}
"""

ARROW_SCHEMA = """
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
"""

WILDCARD_SCHEMA = """
definition user {}
definition bot {}
definition doc {
  relation viewer: user | user:* | bot
  relation editor: user
  permission view = viewer + editor
}
"""


def users(*names):
    return [SubjectRef("user", n) for n in names]


class TestDifferentialFixed:
    def test_depth1_direct(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns1#viewer@user:alice",
            "namespace:ns2#creator@user:alice",
            "namespace:ns3#viewer@user:bob",
        ])
        assert_agreement(jx, oracle, "namespace", "view",
                         users("alice", "bob", "nobody"))

    def test_depth4_nested_groups(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "group:inner#member@user:alice",
            "group:mid#member@group:inner#member",
            "group:outer#member@group:mid#member",
            "team:t#member@group:outer#member",
            "namespace:ns#viewer@team:t#member",
            "namespace:ns2#viewer@group:mid#member",
            "group:other#member@user:bob",
        ])
        assert_agreement(jx, oracle, "namespace", "view",
                         users("alice", "bob", "carol"))

    def test_intersection_exclusion(self):
        jx, oracle = make_pair(RBAC_DENY_SCHEMA, [
            "group:devs#member@user:alice",
            "group:devs#member@user:bob",
            "group:banned-folks#member@user:bob",
            "project:p1#assigned@group:devs#member",
            "project:p1#approved@user:alice",
            "project:p1#approved@user:bob",
            "project:p1#banned@group:banned-folks#member",
            "project:p2#assigned@user:carol",
        ])
        assert_agreement(jx, oracle, "project", "edit",
                         users("alice", "bob", "carol"))

    def test_arrows(self):
        jx, oracle = make_pair(ARROW_SCHEMA, [
            "org:acme#admin@user:boss",
            "namespace:ns#org@org:acme",
            "namespace:ns#viewer@user:watcher",
            "pod:ns/p1#namespace@namespace:ns",
            "pod:ns/p1#creator@user:dev",
            "pod:ns/p2#namespace@namespace:ns",
        ])
        assert_agreement(jx, oracle, "pod", "view",
                         users("boss", "watcher", "dev", "rando"))
        assert_agreement(jx, oracle, "namespace", "view",
                         users("boss", "watcher", "dev"))

    def test_wildcard(self):
        jx, oracle = make_pair(WILDCARD_SCHEMA, [
            "doc:d1#viewer@user:*",
            "doc:d2#editor@user:eve",
            "doc:d3#viewer@user:frank",
        ])
        assert_agreement(jx, oracle, "doc", "view", users("eve", "frank", "zed"))
        # userset subjects must NOT match the wildcard
        assert_agreement(jx, oracle, "doc", "view",
                         [SubjectRef("group", "g", "member")])

    def test_userset_subject_queries(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "group:eng#member@user:alice",
            "namespace:ns#viewer@group:eng#member",
        ])
        assert_agreement(jx, oracle, "namespace", "view",
                         [SubjectRef("group", "eng", "member"),
                          SubjectRef("group", "other", "member")])

    def test_cyclic_groups(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "group:a#member@group:b#member",
            "group:b#member@group:a#member",
            "group:a#member@user:alice",
            "namespace:ns#viewer@group:b#member",
        ])
        assert_agreement(jx, oracle, "namespace", "view", users("alice", "bob"))


class TestIncrementalDeltas:
    def test_write_then_delete(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns1#viewer@user:alice",
        ])
        assert_agreement(jx, oracle, "namespace", "view", users("alice", "bob"))
        rebuilds_before = jx.stats["rebuilds"]
        # incremental adds (all ids already in universe? bob is known — alice
        # and ns1 are; bob came from queries... bob is NOT in the store, so
        # adding a tuple for bob forces a rebuild; alice->ns1 viewer delete
        # then re-add exercises the delta path)
        jx.store.write(delete("namespace:ns1#viewer@user:alice"))
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        jx.store.write(touch("namespace:ns1#viewer@user:alice"))
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        assert jx.stats["rebuilds"] == rebuilds_before, \
            "delete+readd of known ids must not rebuild"

    def test_full_row_insert_grows_aux_without_rebuild(self, kernel_kind):
        """K_MAIN=2 layout: the 3rd..Nth viewer on one namespace overflows
        the main row; add_rel must grow an OR-tree level from the spare
        aux pool instead of rebuilding (ell kernel only — the segment
        kernel has positional slack instead).  A hub seeds the aux table
        so the spare pool exists (hub-free graphs rebuild instead)."""
        rels = ["namespace:ns#viewer@user:u0"]
        # every id must be in the compiled universe, so pre-seed the users
        # on a throwaway namespace — enough of them that the seed row is a
        # hub (aux table + spare pool present)
        rels += [f"namespace:seed#viewer@user:u{i}" for i in range(1, 40)]
        jx, oracle = make_pair(GROUPS_SCHEMA, rels)
        assert_agreement(jx, oracle, "namespace", "view",
                         users(*[f"u{i}" for i in range(12)]))
        rebuilds_before = jx.stats["rebuilds"]
        for i in range(1, 12):
            jx.store.write(touch(f"namespace:ns#viewer@user:u{i}"))
        assert_agreement(jx, oracle, "namespace", "view",
                         users(*[f"u{i}" for i in range(12)]))
        if kernel_kind == "ell":
            assert jx.stats["rebuilds"] == rebuilds_before, \
                "full-row inserts must grow aux nodes, not rebuild"
            # removal after growth still works through the grown tree
            jx.store.write(delete("namespace:ns#viewer@user:u3"))
            assert_agreement(jx, oracle, "namespace", "view",
                             users(*[f"u{i}" for i in range(12)]))

    def test_insert_at_slice_width_grows_through_spare_pool(self,
                                                           kernel_kind):
        """36 viewers build two OR-tree nodes, 32 and 4 children wide, and
        fill the main row.  The staged step refreshes the 4-child node at
        width 4 only, so the 37th viewer may not take that node's dead
        column 4: it grows through the spare pool and stays exact."""
        rels = [f"namespace:ns#viewer@user:u{i}" for i in range(36)]
        rels += [f"namespace:seed{i}#viewer@user:u{i}" for i in range(36, 40)]
        jx, oracle = make_kernel_pair(GROUPS_SCHEMA, rels)
        subjects = users(*[f"u{i}" for i in range(40)])
        assert_agreement(jx, oracle, "namespace", "view", subjects)
        rebuilds = jx.stats["rebuilds"]
        if kernel_kind == "ell":
            graph = jx._graph
            narrow = [(lo, hi) for _, _, lo, hi, w in graph.kernel.aux_slices
                      if w == 4]
            assert narrow, "precondition: a built node of width 4"
            spares = len(graph._spare_aux)
        jx.store.write(touch("namespace:ns#viewer@user:u36"))
        assert_agreement(jx, oracle, "namespace", "view", subjects)
        if kernel_kind == "ell":
            assert jx.stats["rebuilds"] == rebuilds, \
                "the insert must grow through the spare pool, not rebuild"
            assert len(graph._spare_aux) == spares - 1
            dead = graph.prog.dead_index
            for lo, hi in narrow:
                assert (graph.host_aux[lo:hi, 4:] == dead).all()
            jx.store.write(delete("namespace:ns#viewer@user:u2"))
            assert_agreement(jx, oracle, "namespace", "view", subjects)

    def test_crossing_spare_prefix_rejits_once(self, kernel_kind):
        """Spare rows are taken in order and the staged step refreshes a
        power-of-two prefix of the pool: growth past the compiled prefix
        drops the compiled step once and bumps
        authz_aux_spare_rejits_total; growth inside it does neither."""
        from spicedb_kubeapi_proxy_tpu.utils import metrics as m
        rels = [f"namespace:seed#viewer@user:u{i}" for i in range(40)]
        roots = ["a", "b", "c", "d"]
        rels += [f"namespace:{r}#viewer@user:u{j}"
                 for r in roots for j in (0, 1)]
        jx, oracle = make_kernel_pair(GROUPS_SCHEMA, rels)
        subjects = users(*[f"u{i}" for i in range(6)])
        assert_agreement(jx, oracle, "namespace", "view", subjects)
        rejits = m.REGISTRY.get("authz_aux_spare_rejits_total")
        rebuilds = jx.stats["rebuilds"]
        # the k-th growth takes spare row k: prefixes 1, 2, 4, 4
        for root, prefix, crossed in zip(roots, (1, 2, 4, 4),
                                         (True, True, True, False)):
            before = rejits.value()
            if kernel_kind == "ell":
                kern = jx._graph.kernel
                compiled = dict(kern._jits)
                assert compiled, "a query compiled the step"
            jx.store.write(touch(f"namespace:{root}#viewer@user:u2"))
            jx._current_graph()
            if kernel_kind == "ell":
                assert kern.spare_prefix == prefix
                assert kern.spare_passes == 1
                assert rejits.value() == before + crossed
                assert (kern._jits == {}) == crossed
                assert (kern._jits == compiled) != crossed
            assert_agreement(jx, oracle, "namespace", "view", subjects)
        assert jx.stats["rebuilds"] == rebuilds

    def test_new_object_id_assigns_spare_without_rebuild(self):
        """A tuple naming a brand-new object/subject id claims spare rows
        (renamed in the program's id maps) instead of forcing a full
        rebuild — the dual-write create path at 1M scale must not stall
        seconds per new pod."""
        jx, oracle = make_pair(GROUPS_SCHEMA, ["namespace:ns1#viewer@user:alice"])
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        rebuilds = jx.stats["rebuilds"]
        jx.store.write(touch("namespace:brand-new#viewer@user:newbie"))
        assert_agreement(jx, oracle, "namespace", "view", users("alice", "newbie"))
        assert jx.stats["rebuilds"] == rebuilds, \
            "new ids must claim spare rows, not rebuild"
        assert jx.stats["spare_assignments"] >= 2  # object + subject
        # placeholder ids never leak from lookups
        ids = asyncio.run(jx.lookup_resources(
            "namespace", "view", SubjectRef("user", "newbie")))
        assert ids == ["brand-new"]

    def test_spare_pool_exhaustion_rebuilds_and_resizes(self, monkeypatch):
        """Draining the spare pool falls back to a rebuild whose new pool
        is sized from the (now larger) universe; correctness holds across
        the boundary.  The sizing divisor is patched to 1 so the resize
        is observable at unit-test scale."""
        from spicedb_kubeapi_proxy_tpu.ops import jax_endpoint as je
        from spicedb_kubeapi_proxy_tpu.utils.features import GATES
        monkeypatch.setattr(je, "_SPARE_DIVISOR", 1)
        # this test probes the SYNCHRONOUS exhaustion->rebuild fallback
        # (the AsyncRebuild killswitch path); the off-loop flavor is
        # covered by tests/test_rebuild_async.py
        monkeypatch.setattr(GATES._gates["AsyncRebuild"], "value", False)
        jx, oracle = make_pair(GROUPS_SCHEMA, ["namespace:ns1#viewer@user:alice"])
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        floor_pool = len(jx._spare_pool["namespace"])
        for k in range(70):  # exceeds the 64-row floor pool
            jx.store.write(touch(f"namespace:n{k}#viewer@user:alice"))
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        assert jx.stats["rebuilds"] >= 2
        # the exhaustion rebuild sized the new pool from the grown
        # universe (divisor 1: one spare per existing object > the floor)
        assert len(jx._spare_pool["namespace"]) > floor_pool
        got = sorted(asyncio.run(jx.lookup_resources(
            "namespace", "view", SubjectRef("user", "alice"))))
        assert got == sorted(["ns1"] + [f"n{k}" for k in range(70)])

    def test_unique_name_churn_reclaims_spares(self):
        """The kubernetes pod lifecycle: objects with unique generated
        names created and deleted in cycles.  Each delete that removes an
        assigned id's last tuple returns its spare row to the pool, so
        200 create+delete cycles (>> the 64-row floor pool) never force a
        rebuild."""
        jx, oracle = make_pair(GROUPS_SCHEMA, ["namespace:ns1#viewer@user:alice"])
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        rebuilds = jx.stats["rebuilds"]
        for k in range(200):
            jx.store.write(touch(f"namespace:job-{k}#viewer@user:alice"))
            # visible while alive
            if k % 50 == 0:
                got = asyncio.run(jx.lookup_resources(
                    "namespace", "view", SubjectRef("user", "alice")))
                assert f"job-{k}" in got
            jx.store.write(delete(f"namespace:job-{k}#viewer@user:alice"))
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        assert jx.stats["rebuilds"] == rebuilds, \
            "unique-name churn must recycle spare rows, not rebuild"
        assert jx.stats["spare_reclaims"] >= 190
        got = asyncio.run(jx.lookup_resources(
            "namespace", "view", SubjectRef("user", "alice")))
        assert got == ["ns1"]

    def test_unmodeled_relation_burns_no_spares(self):
        """Edgeless tuples (relations absent from the schema) must not
        consume spare rows — a stream of them used to be a no-op and must
        stay one."""
        jx, oracle = make_pair(GROUPS_SCHEMA, ["namespace:ns1#viewer@user:alice"])
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        before = jx.stats["spare_assignments"]
        rebuilds = jx.stats["rebuilds"]
        for k in range(10):
            jx.store.write(touch(f"namespace:brand-{k}#unmodeled@user:nobody"))
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        assert jx.stats["spare_assignments"] == before
        assert jx.stats["rebuilds"] == rebuilds

    def test_group_membership_revocation(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "group:eng#member@user:alice",
            "namespace:ns#viewer@group:eng#member",
            "namespace:ns2#viewer@user:alice",
        ])
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        jx.store.write(delete("group:eng#member@user:alice"))
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))

    def test_expiration_respected(self):
        jx, oracle, clk = make_clocked_pair(
            GROUPS_SCHEMA, ["namespace:ns#viewer@user:alice"])
        jx.store.write([RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(
            f"namespace:ns#viewer@user:bob[expiration:{clk[0] + 100}]"))])
        assert_agreement(jx, oracle, "namespace", "view", users("alice", "bob"))
        clk[0] += 200
        assert_agreement(jx, oracle, "namespace", "view", users("alice", "bob"))


class TestRandomizedDifferential:
    @pytest.mark.parametrize("seed", range(6))
    def test_random_graphs(self, seed):
        rng = random.Random(seed)
        n_users = rng.randint(3, 10)
        n_groups = rng.randint(2, 6)
        n_ns = rng.randint(3, 12)
        rels = []
        for g in range(n_groups):
            for u in rng.sample(range(n_users), rng.randint(0, min(3, n_users))):
                rels.append(f"group:g{g}#member@user:u{u}")
            if g > 0 and rng.random() < 0.5:
                parent = rng.randrange(g)
                rels.append(f"group:g{g}#member@group:g{parent}#member")
        for ns in range(n_ns):
            for _ in range(rng.randint(0, 4)):
                if rng.random() < 0.6:
                    rels.append(f"namespace:ns{ns}#viewer@user:u{rng.randrange(n_users)}")
                else:
                    rels.append(f"namespace:ns{ns}#viewer@group:g{rng.randrange(n_groups)}#member")
            if rng.random() < 0.3:
                rels.append(f"namespace:ns{ns}#creator@user:u{rng.randrange(n_users)}")
        rels = sorted(set(rels))
        jx, oracle = make_pair(GROUPS_SCHEMA, rels)
        subjects = users(*[f"u{i}" for i in range(n_users)])
        assert_agreement(jx, oracle, "namespace", "view", subjects)
        # mutate: random deletes + adds, re-verify (delta path)
        existing = jx.store.read(None)
        for rel in rng.sample(existing, min(3, len(existing))):
            jx.store.write([RelationshipUpdate(UpdateOp.DELETE, rel)])
        assert_agreement(jx, oracle, "namespace", "view", subjects)

    @pytest.mark.parametrize("seed", range(4))
    def test_random_delta_churn(self, seed):
        """Sustained add/delete/re-add churn over a FIXED id universe: every
        mutation stays incremental (no new ids), so this hammers the slot
        edits, spare-aux growth, and tree-walk removal paths — agreement
        with the oracle is re-asserted after every burst."""
        rng = random.Random(7000 + seed)
        n_users, n_groups, n_ns = 8, 4, 6
        # seed graph mentions every id once so the compiled universe is
        # closed under later churn
        rels = [f"group:g{g}#member@user:u{u}"
                for g in range(n_groups) for u in range(n_users)]
        rels += [f"namespace:ns{i}#viewer@user:u0" for i in range(n_ns)]
        rels += [f"namespace:ns{i}#viewer@group:g0#member"
                 for i in range(n_ns)]
        jx, oracle = make_pair(GROUPS_SCHEMA, rels)
        subjects = users(*[f"u{i}" for i in range(n_users)])
        assert_agreement(jx, oracle, "namespace", "view", subjects)

        def any_rel():
            kind = rng.random()
            if kind < 0.4:
                return (f"group:g{rng.randrange(n_groups)}#member"
                        f"@user:u{rng.randrange(n_users)}")
            if kind < 0.6:
                a, b = rng.sample(range(n_groups), 2)
                return f"group:g{a}#member@group:g{b}#member"
            if kind < 0.85:
                return (f"namespace:ns{rng.randrange(n_ns)}#viewer"
                        f"@user:u{rng.randrange(n_users)}")
            return (f"namespace:ns{rng.randrange(n_ns)}#viewer"
                    f"@group:g{rng.randrange(n_groups)}#member")

        for _ in range(5):  # bursts
            ops = []
            for _ in range(rng.randint(3, 10)):
                rel = any_rel()
                op = (UpdateOp.DELETE if rng.random() < 0.4
                      else UpdateOp.TOUCH)
                ops.append(RelationshipUpdate(op, parse_relationship(rel)))
            jx.store.write(ops)
            assert_agreement(jx, oracle, "namespace", "view", subjects)

    @pytest.mark.parametrize("seed", range(3))
    def test_random_rbac_deny(self, seed):
        rng = random.Random(1000 + seed)
        rels = []
        n_users, n_proj = 6, 5
        for g in ("devs", "ops", "blocked"):
            for u in rng.sample(range(n_users), rng.randint(1, 4)):
                rels.append(f"group:{g}#member@user:u{u}")
        for p in range(n_proj):
            rels.append(f"project:p{p}#assigned@group:devs#member")
            for u in rng.sample(range(n_users), rng.randint(0, 4)):
                rels.append(f"project:p{p}#approved@user:u{u}")
            if rng.random() < 0.6:
                rels.append(f"project:p{p}#banned@group:blocked#member")
        jx, oracle = make_pair(RBAC_DENY_SCHEMA, sorted(set(rels)))
        assert_agreement(jx, oracle, "project", "edit",
                         users(*[f"u{i}" for i in range(n_users)]))


class TestJaxEndpointBehavior:
    def test_bootstrap_dispatch(self):
        from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import create_endpoint
        ep = create_endpoint(
            "jax://",
            bootstrap=Bootstrap(
                schema_text=GROUPS_SCHEMA,
                relationships_text="namespace:ns#viewer@user:alice\n"))
        # jax:// wraps the device endpoint in the cross-request dispatcher
        # by default (spicedb/dispatch.py)
        from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import BatchingEndpoint
        assert isinstance(ep, BatchingEndpoint)
        assert isinstance(ep.inner, JaxEndpoint)

        async def run():
            r = await ep.check_permission(CheckRequest(
                ObjectRef("namespace", "ns"), "view", SubjectRef("user", "alice")))
            assert r.allowed
            assert await ep.lookup_resources(
                "namespace", "view", SubjectRef("user", "alice")) == ["ns"]
        asyncio.run(run())

    def test_unknown_resource_type_raises(self):
        jx, _ = make_pair(GROUPS_SCHEMA, ["namespace:ns#viewer@user:alice"])

        async def run():
            with pytest.raises(Exception):
                await jx.lookup_resources("ghost", "view", SubjectRef("user", "a"))
        asyncio.run(run())

    def test_stats_track_kernel_usage(self):
        # this test asserts the fixpoint kernels' own accounting; keep the
        # Leopard index out so the nested chain actually hits a kernel
        from spicedb_kubeapi_proxy_tpu.utils.features import GATES

        prev = GATES.enabled("LeopardIndex")
        GATES.set("LeopardIndex", False)
        try:
            jx, oracle = make_pair(
                GROUPS_SCHEMA, ["namespace:ns#viewer@user:alice"])
        finally:
            GATES.set("LeopardIndex", prev)
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        assert jx.stats["kernel_calls"] > 0
        assert jx.stats["rebuilds"] >= 1


class TestReviewRegressions:
    def test_wildcard_revocation_rebuilds(self):
        jx, oracle = make_pair(WILDCARD_SCHEMA, [
            "doc:d1#viewer@user:*",
            "doc:d1#editor@user:eve",
        ])
        assert_agreement(jx, oracle, "doc", "view", users("zed", "eve"))
        jx.store.write(delete("doc:d1#viewer@user:*"))
        # after revoking the wildcard, arbitrary users must lose access
        assert_agreement(jx, oracle, "doc", "view", users("zed", "eve"))

    def test_touch_adds_expiry_to_existing_tuple(self):
        jx, oracle, clk = make_clocked_pair(
            GROUPS_SCHEMA, ["namespace:ns#viewer@user:alice"])
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        # re-touch the same tuple, now with an expiration
        jx.store.write([RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(
            f"namespace:ns#viewer@user:alice[expiration:{clk[0] + 100}]"))])
        clk[0] += 200
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))

    def test_delete_then_readd_clears_stale_expiry(self):
        """Deterministic via the store's injectable clock (the endpoint's
        expiry heap reads store.now()): no wall-clock races, no sleeps."""
        jx, oracle, clk = make_clocked_pair(
            GROUPS_SCHEMA, ["namespace:ns0#viewer@user:z"])
        jx.store.write([RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(
            f"namespace:ns#viewer@user:alice[expiration:{clk[0] + 100}]"))])
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        jx.store.write(delete("namespace:ns#viewer@user:alice"))
        jx.store.write(touch("namespace:ns#viewer@user:alice"))  # no expiry
        clk[0] += 200  # stale heap entry fires; must be ignored
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))

    def test_deep_membership_chain(self):
        # 15-deep nested groups: beyond the old rewrite-depth-derived cap
        rels = [f"group:g{i+1}#member@group:g{i}#member" for i in range(15)]
        rels.append("group:g0#member@user:deep")
        rels.append("namespace:ns#viewer@group:g15#member")
        jx, oracle = make_pair(GROUPS_SCHEMA, rels)
        assert_agreement(jx, oracle, "namespace", "view", users("deep", "shallow"))

    def test_concurrent_writes_and_checks_no_deadlock(self):
        import threading
        jx, oracle = make_pair(GROUPS_SCHEMA, ["namespace:ns#viewer@user:alice"])
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))
        errors = []

        def writer():
            try:
                for i in range(30):
                    jx.store.write(touch("namespace:ns#viewer@user:alice"))
                    jx.store.write(delete("namespace:ns#viewer@user:alice"))
                    jx.store.write(touch("namespace:ns#viewer@user:alice"))
            except Exception as e:
                errors.append(e)

        def checker():
            import asyncio
            try:
                for _ in range(15):
                    asyncio.run(jx.lookup_resources(
                        "namespace", "view", SubjectRef("user", "alice")))
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=writer),
                   threading.Thread(target=checker)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "deadlock: thread did not finish"
        assert not errors, errors
        # converge: final state must agree
        assert_agreement(jx, oracle, "namespace", "view", users("alice"))


class TestHubTreeSplit:
    """Destinations whose in-degree exceeds the ELL main-row fanin are split
    into OR-tree aux nodes (ops/ell.py); these scenarios force that path and
    keep exercising it through incremental writes/deletes into the hub."""

    def test_large_group_membership(self):
        rels = [f"group:eng#member@user:u{i}" for i in range(300)]
        rels += ["namespace:ns#viewer@group:eng#member"]
        jx, oracle = make_pair(GROUPS_SCHEMA, rels)
        assert_agreement(jx, oracle, "namespace", "view",
                         users("u0", "u7", "u123", "u299", "outsider"))

    def test_delta_insert_and_remove_in_hub(self):
        rels = [f"group:eng#member@user:u{i}" for i in range(300)]
        rels += ["namespace:ns#viewer@group:eng#member"]
        jx, oracle = make_pair(GROUPS_SCHEMA, rels)
        assert_agreement(jx, oracle, "namespace", "view", users("u5"))
        # insert into the full hub (aux tree absorbs the new child or the
        # endpoint rebuilds; either way results must match the oracle)
        jx.store.write(touch("group:eng#member@user:newcomer"))
        assert_agreement(jx, oracle, "namespace", "view",
                         users("newcomer", "u5"))
        # remove a member buried in the tree
        jx.store.write(delete("group:eng#member@user:u123"))
        assert_agreement(jx, oracle, "namespace", "view",
                         users("u123", "u5", "newcomer"))

    def test_nested_hubs(self):
        rels = [f"group:g0#member@user:u{i}" for i in range(60)]
        rels += [f"group:g1#member@group:g0#member"]
        rels += [f"group:g1#member@user:v{i}" for i in range(60)]
        rels += ["namespace:ns#viewer@group:g1#member"]
        jx, oracle = make_pair(GROUPS_SCHEMA, rels)
        assert_agreement(jx, oracle, "namespace", "view",
                         users("u3", "v59", "nobody"))


class TestPhantomSubjects:
    """Subjects outside the compiled universe map onto their type's phantom
    column (zero tuples ⇒ only wildcard terms can grant), so first-contact
    users never fall back to the recursive host oracle — the round-1 cliff."""

    class _NoOracle:
        def check(self, *a, **k):
            raise AssertionError("oracle fallback used for in-schema subject")

        def lookup_resources(self, *a, **k):
            raise AssertionError("oracle fallback used for in-schema subject")

    def test_unknown_subjects_stay_on_kernel(self):
        jx, oracle = make_pair(WILDCARD_SCHEMA, [
            "doc:readme#viewer@user:*",
            "doc:secret#editor@user:alice",
        ])
        # answers must match the oracle...
        assert_agreement(jx, oracle, "doc", "view",
                         users("stranger1", "stranger2"))
        # ...and must come from the kernel, not the recursive fallback
        jx._oracle = self._NoOracle()
        assert_agreement(jx, oracle, "doc", "view",
                         users("stranger3", "stranger4"))

    def test_unknown_userset_subjects(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns#viewer@group:eng#member",
            "group:eng#member@user:alice",
        ])
        jx._oracle = self._NoOracle()
        async def run():
            # unknown group userset: no members, wildcards don't apply
            got = await jx.lookup_resources(
                "namespace", "view", SubjectRef("group", "ghosts", "member"))
            assert got == []
            res = await jx.check_permission(CheckRequest(
                ObjectRef("namespace", "ns"), "view",
                SubjectRef("group", "ghosts", "member")))
            assert not res.allowed
        asyncio.run(run())

    def test_phantom_never_leaks_from_lookup(self):
        # subject relation on the SAME type as the listed resource: the
        # phantom's own relation slot goes live, but the phantom id must
        # never appear in LookupResources output
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns#viewer@user:alice",
        ])
        async def run():
            got = await jx.lookup_resources(
                "namespace", "view", SubjectRef("namespace", "nope", "viewer"))
            assert got == []
            batch = await jx.lookup_resources_batch(
                "namespace", "view",
                [SubjectRef("namespace", "nope", "viewer"),
                 SubjectRef("user", "alice")])
            assert batch[0] == []
            assert batch[1] == ["ns"]
        asyncio.run(run())

    def test_batch_shares_phantom_column(self):
        jx, oracle = make_pair(WILDCARD_SCHEMA, ["doc:d#viewer@user:*"])
        jx._oracle = self._NoOracle()
        async def run():
            subs = [SubjectRef("user", f"stranger{i}") for i in range(40)]
            out = await jx.lookup_resources_batch("doc", "view", subs)
            assert all(x == ["d"] for x in out)
        asyncio.run(run())


class TestLockFreeKernelExecution:
    @pytest.fixture(autouse=True, params=["ell"])
    def kernel_kind(self, request, monkeypatch):
        """Timing test is ell-only: override the module fixture's params
        instead of skipping, so the default suite runs with zero skips."""
        monkeypatch.setenv("SPICEDB_TPU_KERNEL", request.param)
        return request.param

    def test_check_not_serialized_behind_slow_lookup(self, kernel_kind,
                                                     monkeypatch):
        """Device execution happens OUTSIDE the endpoint lock: a check
        issued while a (artificially slow) lookup kernel is in flight
        completes immediately instead of queueing behind it."""
        import threading
        import time as _time
        jx, _ = make_pair(GROUPS_SCHEMA, [
            "namespace:ns1#viewer@user:alice",
            "namespace:ns2#viewer@user:bob",
        ])
        # warm both paths (build graph + compile)
        jx._lookup_batch_sync("namespace", "view", users("alice"))
        jx._check_batch_sync([CheckRequest(
            resource=ObjectRef("namespace", "ns1"), permission="view",
            subject=SubjectRef("user", "alice"))])
        graph = jx._graph
        real = graph.run_lookup_packed

        def slow(*a, **k):
            _time.sleep(0.6)
            return real(*a, **k)

        monkeypatch.setattr(graph, "run_lookup_packed", slow)
        t = threading.Thread(
            target=jx._lookup_batch_sync,
            args=("namespace", "view", users("alice", "bob")))
        t.start()
        _time.sleep(0.1)  # lookup now inside the slow kernel call
        t0 = _time.perf_counter()
        out = jx._check_batch_sync([CheckRequest(
            resource=ObjectRef("namespace", "ns1"), permission="view",
            subject=SubjectRef("user", "alice"))])
        elapsed = _time.perf_counter() - t0
        t.join()
        assert out[0].permissionship.name == "HAS_PERMISSION"
        assert elapsed < 0.4, \
            f"check blocked {elapsed:.2f}s behind the lookup kernel"


class TestStaleIdViewSelfHeal:
    """Regression net for the id-view/bitmap inconsistency (VERDICT r4
    item 1): results must be complete and correct even when the captured
    id view disagrees with the kernel bitmap.  The inconsistency is
    INJECTED deterministically here (corrupted cache entry) so the
    suppress -> purge -> retry path and the double-suppression ->
    host-oracle tail are both proven, independent of whether the
    underlying race fires."""

    def _corrupt(self, jx, resource_type, victim_id):
        """Make the published cache entry show a spare placeholder at a
        LIVE object's index — exactly the stale-view shape the race
        produces."""
        with jx._lock:
            graph = jx._current_graph()
            from spicedb_kubeapi_proxy_tpu.ops import jax_endpoint as je
            arr, mask = je._object_ids_np(graph, resource_type)
            local = graph.prog.object_index[resource_type][victim_id]
            arr[local] = "\x00__spare__injected"
            mask[local] = True
        return local

    def test_injected_stale_view_self_heals(self, kernel_kind):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns1#viewer@user:alice",
            "namespace:ns2#viewer@user:alice",
            "namespace:ns3#viewer@user:bob",
        ])
        want = sorted(oracle.lookup_resources(
            "namespace", "view", SubjectRef("user", "alice")))

        async def run():
            # prime + publish the cache entry
            await jx.lookup_resources("namespace", "view",
                                      SubjectRef("user", "alice"))
            self._corrupt(jx, "namespace", "ns1")
            got = sorted(await jx.lookup_resources(
                "namespace", "view", SubjectRef("user", "alice")))
            assert got == want, f"self-heal returned truncated {got}"
            assert jx.stats.get("placeholder_suppressed", 0) >= 1
            assert jx.stats.get("suppression_oracle_fallbacks", 0) == 0
            # batch path: corrupt again (the retry purged the entry)
            await jx.lookup_resources_batch(
                "namespace", "view", users("alice"))
            self._corrupt(jx, "namespace", "ns2")
            out = await jx.lookup_resources_batch(
                "namespace", "view", users("alice", "bob"))
            assert sorted(out[0]) == want
            assert sorted(out[1]) == ["ns3"]
        asyncio.run(run())

    def test_persistent_stale_view_falls_back_to_oracle(self, kernel_kind,
                                                        monkeypatch):
        """If the re-captured view is ALSO inconsistent, the endpoint
        must return the host oracle's complete answer — never a silently
        truncated list."""
        from spicedb_kubeapi_proxy_tpu.ops import jax_endpoint as je
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns1#viewer@user:alice",
            "namespace:ns2#viewer@user:alice",
        ])
        want = sorted(oracle.lookup_resources(
            "namespace", "view", SubjectRef("user", "alice")))
        real = je._object_ids_np

        def always_stale(graph, resource_type):
            arr, mask = real(graph, resource_type)
            arr = arr.copy()
            mask = mask.copy()
            local = graph.prog.object_index[resource_type].get("ns1")
            if local is not None:
                arr[local] = "\x00__spare__persistent"
                mask[local] = True
            return arr, mask

        monkeypatch.setattr(je, "_object_ids_np", always_stale)

        async def run():
            got = sorted(await jx.lookup_resources(
                "namespace", "view", SubjectRef("user", "alice")))
            assert got == want, f"oracle fallback returned {got}"
            assert jx.stats.get("suppression_oracle_fallbacks", 0) == 1
            out = await jx.lookup_resources_batch(
                "namespace", "view", users("alice"))
            assert sorted(out[0]) == want
            assert jx.stats.get("suppression_oracle_fallbacks", 0) == 2
        asyncio.run(run())


class TestSuppressionRetryCounting:
    """The self-heal retry must not double-count placeholder_suppressed
    (or re-emit the forensic warning) for one underlying inconsistency:
    retry-attributed suppressions land in a separate counter."""

    def test_persistent_staleness_counts_first_detection_once(
            self, kernel_kind, monkeypatch):
        from spicedb_kubeapi_proxy_tpu.ops import jax_endpoint as je
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns1#viewer@user:alice",
            "namespace:ns2#viewer@user:alice",
        ])
        want = sorted(oracle.lookup_resources(
            "namespace", "view", SubjectRef("user", "alice")))
        real = je._object_ids_np

        def always_stale(graph, resource_type):
            arr, mask = real(graph, resource_type)
            arr = arr.copy()
            mask = mask.copy()
            local = graph.prog.object_index[resource_type].get("ns1")
            if local is not None:
                arr[local] = "\x00__spare__persistent"
                mask[local] = True
            return arr, mask

        monkeypatch.setattr(je, "_object_ids_np", always_stale)

        async def run():
            # single-subject path: suppress -> purge -> retry (also
            # stale) -> oracle.  ONE event: first-detection counter 1,
            # retry counter 1 — not first-detection 2.
            got = sorted(await jx.lookup_resources(
                "namespace", "view", SubjectRef("user", "alice")))
            assert got == want
            assert jx.stats.get("placeholder_suppressed", 0) == 1
            assert jx.stats.get("placeholder_suppressed_retry", 0) == 1
            assert jx.stats.get("suppression_oracle_fallbacks", 0) == 1
            # fused-batch path: same discipline through the batch tail
            out = await jx.lookup_resources_batch(
                "namespace", "view", users("alice"))
            assert sorted(out[0]) == want
            assert jx.stats.get("placeholder_suppressed", 0) == 2
            assert jx.stats.get("placeholder_suppressed_retry", 0) == 2
            assert jx.stats.get("suppression_oracle_fallbacks", 0) == 2

        asyncio.run(run())

    def test_clean_retry_counts_nothing_extra(self, kernel_kind):
        """A transient inconsistency (retry succeeds) counts exactly one
        suppression and zero retry suppressions."""
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns1#viewer@user:alice",
            "namespace:ns2#viewer@user:alice",
        ])
        want = sorted(oracle.lookup_resources(
            "namespace", "view", SubjectRef("user", "alice")))

        async def run():
            await jx.lookup_resources("namespace", "view",
                                      SubjectRef("user", "alice"))
            # corrupt the PUBLISHED cache entry once; the purge+retry
            # rebuilds it clean
            with jx._lock:
                graph = jx._current_graph()
                from spicedb_kubeapi_proxy_tpu.ops import jax_endpoint as je
                arr, mask = je._object_ids_np(graph, "namespace")
                local = graph.prog.object_index["namespace"]["ns1"]
                arr[local] = "\x00__spare__transient"
                mask[local] = True
            got = sorted(await jx.lookup_resources(
                "namespace", "view", SubjectRef("user", "alice")))
            assert got == want
            assert jx.stats.get("placeholder_suppressed", 0) == 1
            assert jx.stats.get("placeholder_suppressed_retry", 0) == 0
            assert jx.stats.get("suppression_oracle_fallbacks", 0) == 0

        asyncio.run(run())


class TestStageAuxFlip:
    def test_delta_growth_flips_aux_free_stage_annotation(self, kernel_kind):
        """A hub grown by deltas into a stage annotated aux-free at
        build time must flip the stage's wants_aux flag (so the staged
        kernel refreshes OR-trees before that stage's gather) and bump
        the visible stage_aux_flips stat — the degradation was silent
        before (ADVICE round 5)."""
        if kernel_kind != "ell":
            pytest.skip("stage annotations are an ell-kernel feature")
        # hub on `group` seeds the aux table + spare pool; namespaces
        # start with one viewer each, so the namespace stage has no aux
        # references at build time (wants_aux=False)
        rels = [f"group:hub#member@user:h{i}" for i in range(40)]
        rels += ["namespace:ns#viewer@user:u0"]
        rels += [f"namespace:seed{i}#viewer@user:u{i}" for i in range(1, 12)]
        jx, oracle = make_pair(GROUPS_SCHEMA, rels)
        subjects = users(*[f"u{i}" for i in range(12)])
        assert_agreement(jx, oracle, "namespace", "view", subjects)

        graph = jx._graph
        stages = graph.kernel.stages
        assert stages, "staged step expected on the ell kernel"
        ns_rows = {graph.prog.state_index("namespace", "viewer", "ns")}
        assert None not in ns_rows
        flags_before = {
            ranges: wants for ranges, _, wants in stages
            for (lo, hi) in ranges if any(lo <= r < hi for r in ns_rows)}
        assert set(flags_before.values()) == {False}, \
            "precondition: the namespace stage must start aux-free"

        rebuilds = jx.stats["rebuilds"]
        for i in range(1, 12):
            jx.store.write(touch(f"namespace:ns#viewer@user:u{i}"))
        assert_agreement(jx, oracle, "namespace", "view", subjects)
        assert jx.stats["rebuilds"] == rebuilds, \
            "growth must ride the spare aux pool, not rebuild"
        assert jx.stats.get("stage_aux_flips", 0) >= 1
        row = graph.prog.state_index("namespace", "viewer", "ns")
        flipped = [wants for ranges, _, wants in graph.kernel.stages
                   if any(lo <= row < hi for lo, hi in ranges)]
        assert flipped and all(flipped), "stage flag must now want aux"


class TestRebuildIdViewEviction:
    """Graph rebuilds must evict the outgoing graph's cached numpy id
    views (`_ids_np_cache`): a post-rebuild lookup must never see
    pre-rebuild ids through a stale (arr, mask) pair."""

    def test_post_rebuild_lookup_never_sees_pre_rebuild_ids(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:old1#viewer@user:alice",
            "namespace:old2#viewer@user:alice",
        ])
        alice = SubjectRef("user", "alice")

        async def run():
            out = await jx.lookup_resources("namespace", "view", alice)
            assert sorted(out) == ["old1", "old2"]
            old_graph = jx._graph
            # the lookup populated the old graph's cached id view
            assert getattr(old_graph, "_ids_np_cache", None)
            # a reset-class change (bulk_load) with a DISJOINT id universe
            # forces a full rebuild
            jx.store.delete_all()
            jx.store.bulk_load([parse_relationship(
                "namespace:new1#viewer@user:alice")])
            out = await jx.lookup_resources("namespace", "view", alice)
            assert sorted(out) == ["new1"], (
                "post-rebuild lookup leaked pre-rebuild ids")
            # the outgoing graph's id view was evicted, not carried
            assert not old_graph._ids_np_cache
            assert jx._graph is not old_graph

        asyncio.run(run())
        assert_agreement(jx, oracle, "namespace", "view", [alice])

    def test_forced_rebuild_evicts_and_refreshes_id_view(self):
        jx, oracle = make_pair(GROUPS_SCHEMA, [
            "namespace:ns1#viewer@user:alice",
        ])
        alice = SubjectRef("user", "alice")

        async def run():
            assert sorted(await jx.lookup_resources(
                "namespace", "view", alice)) == ["ns1"]
            old_graph = jx._graph
            assert old_graph._ids_np_cache
            jx.force_rebuild()
            assert not old_graph._ids_np_cache
            assert not old_graph._ids_np_published
            assert sorted(await jx.lookup_resources(
                "namespace", "view", alice)) == ["ns1"]

        asyncio.run(run())
