"""Fleet-wide distributed tracing (utils/fleet.py + the propagation
seams in proxy/server.py, spicedb/sharding/router.py,
spicedb/replication; docs/observability.md "Fleet tracing").

- pure merge unit tests: parent-hop alignment (skew-immune by
  construction), per-tier self/network attribution reconciling against
  the root duration, wall-clock fallback accounting, segment dedupe,
  serving-stage roll-ups, the merged chrome-trace, /metrics parsing;
- trace continuity over real in-process processes: one client trace id
  spans HTTP router -> shard leader with per-tier spans and hop
  parent/child linkage; a follower forwarding a dual-write (and a
  min-revision read) to its leader joins the client's trace, and the
  leader's audit events name the full tier path;
- the /debug/fleet merged view over router + shard leaders;
- the Timeline gate-off tripwire: no propagation headers leave the
  process, the receiving side mints locally, response bytes identical.
"""

import asyncio
import json
import os
import shutil
import tempfile

import pytest

from spicedb_kubeapi_proxy_tpu.config import proxyrule
from spicedb_kubeapi_proxy_tpu.kubefake.apiserver import FakeKubeApiServer
from spicedb_kubeapi_proxy_tpu.proxy.httpcore import (
    HandlerTransport,
    Headers,
    Request,
)
from spicedb_kubeapi_proxy_tpu.proxy.server import Options, ProxyServer
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import (
    Bootstrap,
    merge_internal_definitions,
)
from spicedb_kubeapi_proxy_tpu.spicedb.replication import MIN_REVISION_HEADER
from spicedb_kubeapi_proxy_tpu.spicedb.sharding import (
    PartitionMap,
    ShardRouter,
)
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    RelationshipUpdate,
    UpdateOp,
    parse_relationship,
)
from spicedb_kubeapi_proxy_tpu.utils import fleet, tracing
from spicedb_kubeapi_proxy_tpu.utils.features import GATES

SCHEMA = """
definition user {}
definition namespace {
  relation creator: user
  relation viewer: user
  permission view = viewer + creator
}
definition podns {
  relation creator: user
  permission view = creator
}
definition pod {
  relation creator: user
  relation viewer: user
  permission view = viewer + creator
}
"""

RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata: {name: get-namespaces}
match: [{apiVersion: v1, resource: namespaces, verbs: [get]}]
check: [{tpl: "namespace:{{name}}#view@user:{{user.name}}"}]
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata: {name: list-namespaces}
match: [{apiVersion: v1, resource: namespaces, verbs: [list]}]
prefilter:
- fromObjectIDNameExpr: "{{resourceId}}"
  lookupMatchingResources: {tpl: "namespace:$#view@user:{{user.name}}"}
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata: {name: create-pods}
match: [{apiVersion: v1, resource: pods, verbs: [create]}]
lock: Optimistic
check: [{tpl: "podns:{{namespace}}#view@user:{{user.name}}"}]
update:
  creates:
  - tpl: "pod:{{namespacedName}}#creator@user:{{user.name}}"
"""

PMAP_SPEC = "pod=1,podns=1"

TID = "f0" * 16
TID2 = "e1" * 16


def parsed_schema():
    return merge_internal_definitions(sch.parse_schema(SCHEMA))


@pytest.fixture(autouse=True)
def clean_state():
    tracing.RECORDER.drain()
    yield
    tracing.RECORDER.drain()
    GATES.reset()


@pytest.fixture
def tmp():
    d = tempfile.mkdtemp(prefix="fleet-test-")
    yield d
    shutil.rmtree(d, ignore_errors=True)


# -- synthetic segment builders for the pure merge tests ----------------------


def seg(tid, tier, dur_ms, start_unix=100.0, parent=None, spans=()):
    attrs = {"tier": tier, "tier_path": tier}
    if parent:
        attrs["parent_span"] = parent
    return {"trace_id": tid, "start_unix": start_unix,
            "duration_ms": dur_ms, "attrs": attrs, "spans": list(spans)}


def hop(span_id, start_ms, dur_ms, name="hop.forward"):
    return {"name": name, "start_ms": start_ms, "duration_ms": dur_ms,
            "attrs": {"span_id": span_id}}


def member(url, traces, skew=None, lag=None, flight=None):
    return {"url": url, "error": None, "traces": traces,
            "flight": flight or {}, "skew_s": skew, "lag_s": lag}


HOP_A = "aa" * 8
HOP_B = "bb" * 8


class TestMergeUnit:
    def test_two_tier_alignment_and_attribution(self):
        root = seg(TID, "router", 12.0,
                   spans=[hop(HOP_A, 2.0, 8.0, "hop.shard_forward")])
        child = seg(TID, "leader", 6.0, parent=HOP_A)
        a = fleet.assemble_trace([(member("r", []), root),
                                  (member("s0", []), child)])
        assert a["tier_count"] == 2
        assert not a["aligned_by_wall"] and a["wall_fallbacks"] == 0
        offsets = {s["tier"]: s["offset_ms"] for s in a["segments"]}
        # child anchored at the PARENT's hop start, in the parent clock
        assert offsets == {"router": 0.0, "leader": 2.0}
        assert a["tiers"]["router"]["self_ms"] == pytest.approx(4.0)
        assert a["tiers"]["leader"]["self_ms"] == pytest.approx(6.0)
        assert a["network_ms"] == pytest.approx(2.0)
        # the attribution reconciles against the root duration exactly
        assert a["attributed_ms"] == pytest.approx(a["duration_ms"])

    def test_alignment_is_skew_immune(self):
        root = seg(TID, "router", 12.0,
                   spans=[hop(HOP_A, 2.0, 8.0)])
        for skew_s in (0.0, +1000.0, -1000.0):
            child = seg(TID, "leader", 6.0, parent=HOP_A,
                        start_unix=100.0 + skew_s)
            a = fleet.assemble_trace([(member("r", []), root),
                                      (member("s0", []), child)])
            off = {s["tier"]: s["offset_ms"] for s in a["segments"]}
            # a member clock off by ±1000s moves NOTHING
            assert off["leader"] == 2.0
            assert a["attributed_ms"] == pytest.approx(12.0)

    def test_three_tier_chain(self):
        root = seg(TID, "router", 12.0, spans=[hop(HOP_A, 1.0, 10.0)])
        mid = seg(TID, "follower", 8.0, parent=HOP_A,
                  spans=[hop(HOP_B, 2.0, 5.0, "hop.forward_to_leader")])
        deep = seg(TID, "leader", 4.0, parent=HOP_B)
        a = fleet.assemble_trace([(member("r", []), root),
                                  (member("f", []), mid),
                                  (member("l", []), deep)])
        assert a["tier_count"] == 3
        off = {s["tier"]: s["offset_ms"] for s in a["segments"]}
        assert off == {"router": 0.0, "follower": 1.0, "leader": 3.0}
        assert a["tiers"]["router"]["self_ms"] == pytest.approx(2.0)
        assert a["tiers"]["follower"]["self_ms"] == pytest.approx(3.0)
        assert a["tiers"]["leader"]["self_ms"] == pytest.approx(4.0)
        assert a["network_ms"] == pytest.approx(3.0)  # (10-8) + (5-4)
        assert a["attributed_ms"] == pytest.approx(12.0)

    def test_orphan_falls_back_to_wall_clock(self):
        root = seg(TID, "router", 12.0, start_unix=100.0)
        orphan = seg(TID, "leader", 6.0, parent="cc" * 8,
                     start_unix=100.050)
        a = fleet.assemble_trace([(member("r", []), root),
                                  (member("s0", []), orphan)])
        assert a["wall_fallbacks"] == 1
        off = {s["tier"]: s["offset_ms"] for s in a["segments"]}
        assert off["leader"] == pytest.approx(50.0)

    def test_serving_stage_rollup_per_tier(self):
        child_spans = [
            {"name": "decode", "start_ms": 0.5,
             "duration_ms": 3.0},
            {"name": "filter", "start_ms": 3.5,
             "duration_ms": 2.0},
            {"name": "match", "start_ms": 0.0, "duration_ms": 1.0,
             "phase": True},
        ]
        root = seg(TID, "router", 12.0, spans=[hop(HOP_A, 2.0, 8.0)])
        child = seg(TID, "leader", 6.0, parent=HOP_A,
                    spans=child_spans)
        a = fleet.assemble_trace([(member("r", []), root),
                                  (member("s0", []), child)])
        # `match` is the span that marks the rule_match serving stage
        assert a["serving_stages_ms"]["leader"] == {
            "decode": 3.0, "filter": 2.0, "rule_match": 1.0}

    def test_merge_dedupes_and_drops_single_process(self):
        root = seg(TID, "router", 12.0, spans=[hop(HOP_A, 2.0, 8.0)])
        child = seg(TID, "leader", 6.0, parent=HOP_A)
        lonely = seg(TID2, "leader", 3.0)
        # the router aggregates itself AND shows up in its own peer
        # scrape: the duplicated segments must not double-count a tier
        merged = fleet.merge_fleet([
            member("router", [root, child, lonely]),
            member("http://s0", [child, lonely]),
        ])
        assert [t["trace_id"] for t in merged["traces"]] == [TID]
        t = merged["traces"][0]
        assert t["tier_count"] == 2
        assert t["tiers"]["leader"]["segments"] == 1
        # tier stats carry the per-trace self times
        assert merged["tiers"]["router"]["count"] == 1
        assert merged["tiers"]["network"]["p50_ms"] == pytest.approx(2.0)

    def test_chrome_trace_one_track_per_tier_process(self):
        root = seg(TID, "router", 12.0, spans=[hop(HOP_A, 2.0, 8.0)])
        child = seg(TID, "leader", 6.0, parent=HOP_A)
        merged = fleet.merge_fleet([member("router", [root]),
                                    member("http://s0", [child])])
        ct = merged["chrome_trace"]
        names = [e for e in ct["traceEvents"] if e["ph"] == "M"]
        slices = [e for e in ct["traceEvents"] if e["ph"] == "X"]
        assert len(names) == 2          # (router, router) + (leader, s0)
        assert ct["otherData"]["tracks"] == 2
        assert any(e["cat"] == "request" for e in slices)
        # slice ts is µs on the merged (aligned) timeline
        leader_pid = next(e["pid"] for e in names
                          if "leader" in e["args"]["name"])
        leader_req = next(e for e in slices
                          if e["pid"] == leader_pid
                          and e["cat"] == "request")
        assert leader_req["ts"] == pytest.approx(2000.0)

    def test_slo_and_member_rollup(self):
        merged = fleet.merge_fleet([
            member("http://f0", [], skew=0.012, lag=1.5,
                   flight={"burning": [{"slo": "latency_p99"}]}),
            {"url": "http://dead", "error": "GET /metrics: boom",
             "traces": [], "flight": {}, "skew_s": None, "lag_s": None},
        ])
        assert merged["slo_burning"] == [
            {"url": "http://f0", "slo": {"slo": "latency_p99"}}]
        by_url = {m["url"]: m for m in merged["members"]}
        assert by_url["http://f0"]["skew_s"] == 0.012
        assert by_url["http://dead"]["error"].startswith("GET /metrics")

    def test_parse_metric(self):
        text = ("# HELP authz_clock_skew_seconds skew\n"
                "authz_clock_skew_seconds -0.025\n"
                "authz_replica_lag_seconds 1.75\n")
        assert fleet.parse_metric(text, fleet._SKEW_RE) == -0.025
        assert fleet.parse_metric(text, fleet._LAG_RE) == 1.75
        assert fleet.parse_metric("", fleet._SKEW_RE) is None


# -- real processes: router -> shard leaders ----------------------------------


class CapturingTransport:
    """Transport wrapper recording every forwarded request (the gate-off
    tripwire inspects the exact header set that crossed the hop)."""

    def __init__(self, inner):
        self.inner = inner
        self.seen = []

    async def round_trip(self, req):
        self.seen.append(req)
        return await self.inner.round_trip(req)


def make_shard_leader(tmp, subdir, seed_rels):
    kube = FakeKubeApiServer()
    kube.seed("", "v1", "namespaces", {"metadata": {"name": "team-a"}})
    proxy = ProxyServer(Options(
        spicedb_endpoint="embedded://",
        bootstrap=Bootstrap(schema_text=SCHEMA),
        rules_yaml=RULES,
        upstream_transport=HandlerTransport(kube),
        data_dir=os.path.join(tmp, subdir),
        wal_fsync="never",
    ))
    if seed_rels and proxy.endpoint.store.revision == 0:
        proxy.endpoint.store.bulk_load(
            [parse_relationship(r) for r in seed_rels])
    proxy.enable_dual_writes()
    return proxy


def make_router(tmp):
    s0 = make_shard_leader(tmp, "s0",
                           ["namespace:team-a#creator@user:alice"])
    s1 = make_shard_leader(tmp, "s1",
                           ["podns:team-a#creator@user:alice"])
    pm = PartitionMap.parse(PMAP_SPEC, n_shards=2)
    cap0 = CapturingTransport(HandlerTransport(s0.handler))
    cap1 = CapturingTransport(HandlerTransport(s1.handler))
    router = ShardRouter(
        pm, [cap0, cap1],
        rule_configs=proxyrule.parse(RULES), schema=parsed_schema(),
        fleet_peers=["http://s0.test", "http://s1.test"],
        fleet_transports={
            "http://s0.test": HandlerTransport(s0.handler),
            "http://s1.test": HandlerTransport(s1.handler)})
    return router, s0, s1, cap0, cap1


async def router_req(router, method, target, user="alice", body=None,
                     headers=()):
    h = Headers(list(headers))
    if user:
        h.set("X-Remote-User", user)
    h.set("Accept", "application/json")
    data = b""
    if body is not None:
        data = json.dumps(body).encode()
        h.set("Content-Type", "application/json")
    return await router.handle(Request(method=method, target=target,
                                       headers=h, body=data))


def segments_for(tid):
    return [t for t in tracing.RECORDER.snapshot()
            if t["trace_id"] == tid]


class TestRouterContinuity:
    def test_one_trace_spans_router_and_shard_leader(self, tmp):
        router, s0, _s1, cap0, _cap1 = make_router(tmp)

        async def go():
            resp = await router_req(
                router, "GET", "/api/v1/namespaces/team-a",
                headers=[(tracing.TRACE_ID_HEADER, TID)])
            assert resp.status == 200, resp.body
            # the client's id is echoed back from the ROUTER tier
            assert resp.headers.get(tracing.TRACE_ID_HEADER) == TID
            segs = segments_for(TID)
            by_tier = {t["attrs"].get("tier"): t for t in segs}
            assert set(by_tier) == {"router", "leader"}
            # hop parent/child linkage: the leader's whole request is a
            # child of the router's client-side hop span
            hop_sp = next(sp for sp in by_tier["router"]["spans"]
                          if sp["name"] == "hop.shard_forward")
            assert by_tier["leader"]["attrs"]["parent_span"] == \
                hop_sp["attrs"]["span_id"]
            assert by_tier["leader"]["attrs"]["tier_path"] == \
                "router>leader"
            # the propagation headers crossed the wire
            fwd = cap0.seen[-1]
            assert fwd.headers.get(tracing.PROP_TRACE_HEADER) == TID
            assert fwd.headers.get(tracing.PROP_TIER_PATH_HEADER) == \
                "router"
            # the leader recorded serving-stage spans inside the trace
            stage_names = {sp["name"]
                           for sp in by_tier["leader"]["spans"]}
            assert "authn" in stage_names

        asyncio.run(go())

    def test_fleet_merged_view_reconciles(self, tmp):
        router, _s0, _s1, _c0, _c1 = make_router(tmp)

        async def go():
            r1 = await router_req(
                router, "GET", "/api/v1/namespaces/team-a",
                headers=[(tracing.TRACE_ID_HEADER, TID)])
            assert r1.status == 200
            r2 = await router_req(
                router, "POST", "/api/v1/namespaces/team-a/pods",
                body={"apiVersion": "v1", "kind": "Pod",
                      "metadata": {"name": "p1", "namespace": "team-a"}},
                headers=[(tracing.TRACE_ID_HEADER, TID2)])
            assert r2.status in (200, 201), r2.body

            resp = await router_req(router, "GET", "/debug/fleet")
            assert resp.status == 200
            merged = json.loads(resp.body)
            assert merged["enabled"] and merged["tier"] == "router"
            traces = {t["trace_id"]: t for t in merged["traces"]}
            assert {TID, TID2} <= set(traces)
            for t in (traces[TID], traces[TID2]):
                assert t["tier_count"] >= 2
                assert {"router", "leader"} <= set(t["tiers"])
                assert not t["aligned_by_wall"]
                # per-tier self + network reconciles against the
                # client-observed (router) duration by construction
                assert t["attributed_ms"] == pytest.approx(
                    t["duration_ms"], abs=0.05)
                assert "authn" in t["serving_stages_ms"].get(
                    "leader", {})
            ct = merged["chrome_trace"]
            assert ct["otherData"]["tracks"] >= 2
            assert any(e["ph"] == "X" for e in ct["traceEvents"])
            assert "router" in merged["tiers"]
            assert "leader" in merged["tiers"]

        asyncio.run(go())

    def test_fleet_requires_identity(self, tmp):
        router, _s0, _s1, _c0, _c1 = make_router(tmp)

        async def go():
            resp = await router_req(router, "GET", "/debug/fleet",
                                    user="")
            assert resp.status == 401
            resp = await router_req(router, "GET", "/debug/traces",
                                    user="")
            assert resp.status == 401

        asyncio.run(go())

    def test_gate_off_no_headers_and_byte_identical(self, tmp):
        router, _s0, _s1, cap0, _c1 = make_router(tmp)

        async def go():
            on = await router_req(router, "GET",
                                  "/api/v1/namespaces/team-a")
            assert on.status == 200
            assert cap0.seen[-1].headers.get(
                tracing.PROP_TRACE_HEADER)

            GATES.set("Timeline", False)
            tracing.RECORDER.drain()
            off = await router_req(router, "GET",
                                   "/api/v1/namespaces/team-a")
            assert off.status == 200
            # tripwire: the router ATTACHED no fleet headers of its own
            fwd = cap0.seen[-1]
            assert not fwd.headers.get(tracing.PROP_TRACE_HEADER)
            assert not fwd.headers.get(tracing.PROP_PARENT_HEADER)
            assert not fwd.headers.get(tracing.PROP_TIER_PATH_HEADER)

            # a client-injected propagation header passes through the
            # gate-off router VERBATIM (transparent proxy), but the
            # receiving side never reads it: it mints locally and no
            # tier attribution leaks into the trace
            tracing.RECORDER.drain()
            off2 = await router_req(
                router, "GET", "/api/v1/namespaces/team-a",
                headers=[(tracing.PROP_TRACE_HEADER, TID),
                         (tracing.PROP_PARENT_HEADER, HOP_A),
                         (tracing.PROP_TIER_PATH_HEADER, "router")])
            assert off2.status == 200
            assert cap0.seen[-1].headers.get(
                tracing.PROP_TRACE_HEADER) == TID  # untouched bytes
            assert segments_for(TID) == []
            for t in tracing.RECORDER.snapshot():
                assert "tier" not in t["attrs"]
            # the response BYTES are identical to the gate-on run
            assert off.body == on.body
            # the echoed trace id is the LEADER's locally-minted one
            # (X-Trace-Id echo predates fleet tracing), not the
            # injected fleet id
            assert off2.headers.get(tracing.TRACE_ID_HEADER) != TID

        asyncio.run(go())


# -- real processes: follower -> leader forwards ------------------------------


class LeaderLink:
    def __init__(self, proxy):
        self.proxy = proxy

    async def round_trip(self, req):
        return await self.proxy.handler(req)


def make_leader(tmp):
    kube = FakeKubeApiServer()
    for i in range(4):
        kube.seed("", "v1", "namespaces",
                  {"metadata": {"name": f"ns{i}"}})
    leader = ProxyServer(Options(
        spicedb_endpoint="embedded://",
        bootstrap=Bootstrap(schema_text=SCHEMA),
        rules_yaml=RULES,
        upstream_transport=HandlerTransport(kube),
        data_dir=os.path.join(tmp, "leader"), wal_fsync="never"))
    leader.endpoint.store.bulk_load(
        [parse_relationship(f"namespace:ns{i}#creator@user:alice")
         for i in range(4)]
        + [parse_relationship("podns:ns0#creator@user:alice")])
    return leader, kube


def make_follower(leader, kube, **opt_kw):
    return ProxyServer(Options(
        spicedb_endpoint="embedded://",
        bootstrap=Bootstrap(schema_text=SCHEMA),
        rules_yaml=RULES,
        upstream_transport=HandlerTransport(kube),
        replicate_from="http://leader.test",
        leader_transport=LeaderLink(leader), **opt_kw))


class TestFollowerContinuity:
    def test_forwarded_dual_write_joins_trace_and_audit(self, tmp):
        leader, kube = make_leader(tmp)
        follower = make_follower(leader, kube)

        async def go():
            await follower.replication.sync_once()
            leader.enable_dual_writes()
            client = follower.get_embedded_client("alice")
            resp = await client.post(
                "/api/v1/namespaces/ns0/pods",
                {"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": "p1", "namespace": "ns0"}},
                headers=[(tracing.TRACE_ID_HEADER, TID)])
            assert resp.status in (200, 201), resp.body
            assert resp.headers.get("X-Authz-Forwarded-To") == "leader"

            segs = segments_for(TID)
            by_tier = {t["attrs"].get("tier"): t for t in segs}
            assert set(by_tier) == {"follower", "leader"}
            assert by_tier["follower"]["attrs"]["tier_path"] == \
                "follower"
            assert by_tier["leader"]["attrs"]["tier_path"] == \
                "follower>leader"
            hop_sp = next(sp for sp in by_tier["follower"]["spans"]
                          if sp["name"] == "hop.forward_to_leader")
            assert by_tier["leader"]["attrs"]["parent_span"] == \
                hop_sp["attrs"]["span_id"]
            # audit provenance: the LEADER's decision events name the
            # full hop chain of the forwarded dual-write
            forwarded = [e for e in leader.audit.recent()
                         if e.get("tier_path") == "follower>leader"]
            assert forwarded, leader.audit.recent()
            assert any(e["trace_id"] == TID for e in forwarded)

        asyncio.run(go())

    def test_min_revision_forward_joins_trace(self, tmp):
        leader, kube = make_leader(tmp)
        follower = make_follower(leader, kube, replica_wait_ms=30.0)

        async def go():
            await follower.replication.sync_once()
            rev = await leader.endpoint.write_relationships([
                RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(
                    "namespace:ns1#viewer@user:zed"))])
            client = follower.get_embedded_client("zed")
            resp = await client.get(
                "/api/v1/namespaces",
                headers=[(MIN_REVISION_HEADER, str(rev)),
                         (tracing.TRACE_ID_HEADER, TID)])
            assert resp.status == 200, resp.body
            assert resp.headers.get("X-Authz-Forwarded-To") == "leader"
            by_tier = {t["attrs"].get("tier"): t
                       for t in segments_for(TID)}
            # the stale follower forwarded the read: same trace id on
            # both sides of the hop, leader as the child tier
            assert set(by_tier) == {"follower", "leader"}
            assert by_tier["leader"]["attrs"]["tier_path"] == \
                "follower>leader"

        asyncio.run(go())

    def test_follower_fleet_view_over_leader(self, tmp):
        leader, kube = make_leader(tmp)
        follower = make_follower(
            leader, kube,
            fleet_peers=["http://leader.test"],
            peer_transports={"http://leader.test": LeaderLink(leader)})

        async def go():
            await follower.replication.sync_once()
            leader.enable_dual_writes()
            client = follower.get_embedded_client("alice")
            resp = await client.post(
                "/api/v1/namespaces/ns0/pods",
                {"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": "p2", "namespace": "ns0"}},
                headers=[(tracing.TRACE_ID_HEADER, TID)])
            assert resp.status in (200, 201), resp.body

            resp = await client.get("/debug/fleet")
            assert resp.status == 200
            merged = json.loads(resp.body)
            assert merged["enabled"] and merged["tier"] == "follower"
            traces = {t["trace_id"]: t for t in merged["traces"]}
            assert TID in traces
            t = traces[TID]
            assert {"follower", "leader"} <= set(t["tiers"])
            assert t["attributed_ms"] == pytest.approx(
                t["duration_ms"], abs=0.05)
            # the member scrape lifts the leader's clock-skew gauge
            # slot (None here: a leader exports no skew)
            by_url = {m["url"]: m for m in merged["members"]}
            assert by_url["http://leader.test"]["error"] is None
            assert by_url["http://leader.test"]["traces"] >= 1

        asyncio.run(go())
