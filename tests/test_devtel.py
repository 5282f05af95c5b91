"""Device telemetry & flight recorder (utils/devtel.py).

Covers the four surfaces: HBM ledger byte-exactness across rebuilds and
warm starts (the leak-detection contract), jit-cache/recompile-storm
accounting, batch-occupancy recording on the real kernel path, and the
flight recorder's window snapshots + SLO burn-rate math (asserting the
worked example documented in docs/observability.md), plus the uniform
/debug surface handling in the proxy server.
"""

import asyncio
import json
import time

import pytest

from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    CheckRequest,
    ObjectRef,
    RelationshipUpdate,
    SubjectRef,
    UpdateOp,
    parse_relationship,
)
from spicedb_kubeapi_proxy_tpu.utils import devtel
from spicedb_kubeapi_proxy_tpu.utils import metrics as m

SCHEMA = """
definition user {}

definition doc {
    relation viewer: user
    permission view = viewer
}
"""


def touch(*rels):
    return [RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(r))
            for r in rels]


def run(coro):
    return asyncio.run(coro)


def make_endpoint(n_docs=6):
    schema = sch.parse_schema(SCHEMA)
    ep = JaxEndpoint(schema)
    ep.store.write(touch(*[f"doc:d{i}#viewer@user:u{i % 3}"
                           for i in range(n_docs)]))
    return ep


# -- HBM ledger ---------------------------------------------------------------


class TestHbmLedger:
    def test_register_unregister_accounting(self):
        led = devtel.HbmLedger(registry=m.Registry())
        led.register("tables", 1000, generation=1, name="main")
        led.register("tables", 500, generation=1, name="aux")
        led.register("id_view", 200, generation=1, name="ids:doc")
        assert led.total() == 1700
        assert led.totals() == {"id_view": 200, "tables": 1500}
        assert led.generation_bytes(1) == 1700
        # re-registration replaces (delta accounting), never double-counts
        led.register("tables", 800, generation=1, name="main")
        assert led.total() == 1500
        assert led.unregister("id_view", generation=1, name="ids:doc") == 200
        assert led.total() == 1300
        # unregistering an unknown buffer is a no-op, not an error
        assert led.unregister("id_view", generation=9, name="nope") == 0

    def test_defer_retire_reaped_by_next_operation(self):
        """Graph finalizers must not take the ledger lock (they run
        inside gc on a thread that may already hold it): defer_retire
        only queues, and the next ledger operation reaps."""
        led = devtel.HbmLedger(registry=m.Registry())
        led.register("tables", 1000, generation=1)
        led.register("tables", 500, generation=2)
        led.defer_retire(1)   # lock-free: safe from a finalizer
        assert led.total() == 500  # reaped on entry
        assert led.generation_bytes(1) == 0
        led.defer_retire(2)
        led.register("tables", 64, generation=3)
        assert led.totals() == {"tables": 64}

    def test_retire_generation_and_peak(self):
        led = devtel.HbmLedger(registry=m.Registry())
        led.register("tables", 1000, generation=1)
        led.register("tables", 2000, generation=2)
        assert led.peak == 3000
        assert led.retire_generation(1) == 1000
        assert led.total() == 2000
        assert led.peak == 3000  # high-water survives the retire
        assert led.generation_bytes(1) == 0

    def test_scratch_replaces_not_accumulates(self):
        led = devtel.HbmLedger(registry=m.Registry())
        led.note_scratch(4096)
        led.note_scratch(1024)
        assert led.totals() == {"scratch": 1024}
        assert led.peak == 4096

    def test_gate_blocks_additions_but_not_cleanup(self):
        """The DeviceTelemetry killswitch stops new recording, but
        unregister/retire always run so toggling the gate never strands
        ledger entries."""
        from spicedb_kubeapi_proxy_tpu.utils.features import GATES
        led = devtel.HbmLedger(registry=m.Registry())
        led.register("tables", 1000, generation=1)
        GATES.set("DeviceTelemetry", False)
        try:
            led.register("tables", 500, generation=2)
            led.note_scratch(4096)
            assert led.total() == 1000  # additions gated off
            assert led.retire_generation(1) == 1000  # cleanup still runs
            assert led.total() == 0
        finally:
            GATES.set("DeviceTelemetry", True)


def flush_dead_generations():
    """Endpoints are reference-cyclic (store->listener->endpoint), so
    prior tests' graphs die at an arbitrary later gc — firing the
    ledger's auto-retire finalizers mid-assertion.  Collect NOW so the
    totals captured below only move through this test's actions."""
    import gc
    gc.collect()


class TestLedgerRebuildRegression:
    """The rebuild contract: after a graph rebuild the ledger total must
    equal (old total − old generation + new generation) byte-exactly —
    i.e. a retained old-generation buffer is immediately visible."""

    def test_rebuild_returns_ledger_to_exact_total(self):
        ep = make_endpoint()
        # warm: build the graph and materialize an id view
        run(ep.lookup_resources("doc", "view", SubjectRef("user", "u0")))
        flush_dead_generations()
        gen1 = ep._devtel_gen
        assert gen1 >= 1
        g1_bytes = devtel.LEDGER.generation_bytes(gen1)
        assert g1_bytes > 0
        total_before = devtel.LEDGER.total()

        ep.force_rebuild()
        # re-materialize the id view on the new generation too
        run(ep.lookup_resources("doc", "view", SubjectRef("user", "u0")))
        gen2 = ep._devtel_gen
        assert gen2 > gen1
        g2_bytes = devtel.LEDGER.generation_bytes(gen2)
        assert g2_bytes > 0
        assert devtel.LEDGER.generation_bytes(gen1) == 0, \
            "old generation retained buffers after rebuild"
        assert devtel.LEDGER.total() == total_before - g1_bytes + g2_bytes

    def test_warm_start_registers_generation(self):
        ep = make_endpoint()
        flush_dead_generations()
        before = devtel.LEDGER.total()
        ep.warm_start()
        gen = ep._devtel_gen
        assert gen >= 1
        g = devtel.LEDGER.generation_bytes(gen)
        assert g > 0
        assert devtel.LEDGER.total() == before + g
        # warm_start is idempotent: no duplicate registration
        total = devtel.LEDGER.total()
        ep.warm_start()
        assert devtel.LEDGER.total() == total

    def test_delta_rebuild_accounts_exactly(self):
        """A rebuild forced by a delta outside the compiled universe
        (wildcard) follows the same exact-accounting contract."""
        ep = make_endpoint()
        run(ep.check_permission(CheckRequest(
            ObjectRef("doc", "d0"), "view", SubjectRef("user", "u0"))))
        flush_dead_generations()
        gen1 = ep._devtel_gen
        g1_bytes = devtel.LEDGER.generation_bytes(gen1)
        total_before = devtel.LEDGER.total()
        ep.store.write(touch("doc:d0#viewer@user:*"))
        run(ep.check_permission(CheckRequest(
            ObjectRef("doc", "d0"), "view", SubjectRef("user", "zz"))))
        # the wildcard delta quarantines its pairs and rebuilds OFF-LOOP
        # (AsyncRebuild default): the old generation keeps serving until
        # the candidate installs, so wait for the swap instead of racing
        # the background executor
        deadline = time.time() + 10.0
        while ep._devtel_gen == gen1 and time.time() < deadline:
            time.sleep(0.01)
            run(ep.check_permission(CheckRequest(
                ObjectRef("doc", "d0"), "view",
                SubjectRef("user", "zz"))))
        gen2 = ep._devtel_gen
        assert gen2 > gen1
        assert devtel.LEDGER.generation_bytes(gen1) == 0
        assert devtel.LEDGER.total() == (
            total_before - g1_bytes + devtel.LEDGER.generation_bytes(gen2))


# -- kernel & compile accounting ----------------------------------------------


class TestKernelAccounting:
    def test_hit_miss_and_storm_detection(self, caplog):
        ka = devtel.KernelAccounting(registry=m.Registry())
        t0 = 1000.0
        ka.note_compile(64, now=t0)
        ka.note_jit_hit(64)
        ka.note_jit_hit(64)
        snap = ka.snapshot()
        assert snap["hits"] == 2 and snap["misses"] == 1
        assert snap["storms"] == 0
        # recompiles of ONE bucket inside the window: the threshold+1'th
        # raises the storm counter and a slow-log line
        for i in range(devtel.STORM_THRESHOLD):
            ka.note_compile(64, now=t0 + i)
        assert ka.snapshot()["storms"] == 1
        # compiles outside the window never count toward a storm
        ka.note_compile(128, now=t0)
        ka.note_compile(128, now=t0 + devtel.STORM_WINDOW_S + 1)
        assert ka.snapshot()["storms"] == 1

    def test_entries_gauge_tracks_live_caches(self):
        ka = devtel.KernelAccounting(registry=m.Registry())

        class FakeCache:
            def __init__(self):
                self._jits = {}

        c = FakeCache()
        ka.track(c)
        assert ka.snapshot()["entries"] == 0
        c._jits[8] = object()
        c._jits[16] = object()
        assert ka.snapshot()["entries"] == 2
        del c  # dropped cache disappears from the count (weakref)
        assert ka.snapshot()["entries"] == 0

    def test_real_kernel_populates_accounting(self):
        ep = make_endpoint()
        before = devtel.KERNELS.snapshot()
        stages = m.REGISTRY.get("authz_dispatch_stage_seconds")
        ready_before = stages.count(stage="ready", kind="lookup")
        s = SubjectRef("user", "u0")
        run(ep.lookup_resources("doc", "view", s))
        run(ep.lookup_resources("doc", "view", s))  # same bucket: a hit
        after = devtel.KERNELS.snapshot()
        assert after["misses"] > before["misses"]
        assert after["hits"] > before["hits"]
        # per-call device time is the `ready` stage span's, one
        # observation per kernel call
        assert stages.count(stage="ready", kind="lookup") \
            == ready_before + 2, "kernel calls recorded no device time"


# -- batch occupancy ----------------------------------------------------------


class TestBatchOccupancy:
    def test_record_and_mean(self):
        occ = devtel.BatchOccupancy(registry=m.Registry())
        occ.record("lookup", 3, 29)   # 3 useful lanes in a 32-wide bucket
        occ.record("lookup", 32, 0)
        occ.note_collapsed(5)
        snap = occ.snapshot()
        assert snap["batches"] == 2
        assert snap["useful"] == 35 and snap["padded"] == 29
        assert snap["collapsed"] == 5
        assert snap["mean"] == round(35 / 64, 4)

    def test_kernel_path_records_occupancy(self):
        before = devtel.OCCUPANCY.snapshot()
        ep = make_endpoint()
        run(ep.lookup_resources_batch(
            "doc", "view", [SubjectRef("user", f"u{i}") for i in range(3)]))
        run(ep.check_bulk_permissions([
            CheckRequest(ObjectRef("doc", "d0"), "view",
                         SubjectRef("user", "u0"))]))
        after = devtel.OCCUPANCY.snapshot()
        assert after["batches"] > before["batches"]
        assert after["padded"] > before["padded"], \
            "pow-2 bucketing produced no measured padding"

    def test_singleflight_collapse_counted(self):
        from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import (
            BatchingEndpoint)
        before = devtel.OCCUPANCY.snapshot()["collapsed"]
        ep = BatchingEndpoint(make_endpoint())
        s = SubjectRef("user", "u0")

        async def go():
            return await asyncio.gather(*[
                ep.lookup_resources("doc", "view", s) for _ in range(4)])

        results = run(go())
        assert all(sorted(r) == sorted(results[0]) for r in results)
        # at least the duplicates queued behind the first leader collapse
        assert devtel.OCCUPANCY.snapshot()["collapsed"] > before


# -- snapshot / diff ----------------------------------------------------------


class TestSnapshotDiff:
    def test_diff_snapshot_subtracts_counters(self):
        a = {"hbm_bytes": {}, "hbm_total_bytes": 10, "hbm_peak_bytes": 20,
             "jit": {"hits": 1, "misses": 2, "storms": 0, "entries": 2},
             "occupancy": {"batches": 1, "useful": 10, "padded": 22,
                           "collapsed": 0, "mean": 0.3125}}
        b = {"hbm_bytes": {"ell_main": 100}, "hbm_total_bytes": 100,
             "hbm_peak_bytes": 120,
             "jit": {"hits": 5, "misses": 3, "storms": 1, "entries": 3},
             "occupancy": {"batches": 3, "useful": 42, "padded": 54,
                           "collapsed": 4, "mean": 0.4375}}
        d = devtel.diff_snapshot(a, b)
        assert d["jit_hits"] == 4 and d["recompiles"] == 1
        assert d["recompile_storms"] == 1
        assert d["hbm_peak_bytes"] == 120
        assert d["batches"] == 2
        assert d["mean_batch_occupancy"] == 0.5  # (42-10)/(32+32)
        assert d["collapsed_duplicates"] == 4
        assert d["jit_entries"] == 3


# -- flight recorder + SLO burn rates ----------------------------------------


def make_http_registry():
    reg = m.Registry()
    lat = reg.histogram("proxy_http_request_seconds", "", labels=("verb",),
                        buckets=(0.1, 0.25, 0.5, 1.0))
    codes = reg.counter("proxy_http_requests_total", "",
                        labels=("verb", "code"))
    phases = reg.histogram("authz_request_phase_seconds", "",
                           labels=("phase",), buckets=(0.1, 0.25, 0.5, 1.0))
    return reg, lat, codes, phases


class TestFlightRecorder:
    def test_windows_and_quantiles(self):
        reg, _lat, _codes, phases = make_http_registry()
        fr = devtel.FlightRecorder(window_s=1.0, capacity=4, registry=reg)
        fr.capture(now=time.time())
        for _ in range(90):
            fr.observe_request(0.05, 200)
            phases.observe(0.05, phase="execute")
        for _ in range(10):
            fr.observe_request(0.4, 200)
            phases.observe(0.4, phase="execute")
        snap = fr.capture(now=time.time())
        assert snap["http"]["requests"] == 100
        assert snap["http"]["error_rate"] == 0.0
        # http quantiles come from the exact per-window sample
        assert snap["http"]["latency_p50_ms"] == 50.0
        assert snap["http"]["latency_p99_ms"] == 400.0
        # phase quantiles come from histogram-bucket deltas
        assert snap["phases"]["execute"]["count"] == 100
        assert 250 <= snap["phases"]["execute"]["p99_ms"] <= 500
        # ring serves newest first, internal tallies stripped
        out = fr.snapshots()
        assert len(out) == 2
        assert out[0]["ts"] >= out[1]["ts"]
        assert all(not k.startswith("_") for s in out for k in s)

    def test_first_window_does_not_inherit_process_history(self):
        """The delta baseline is primed at construction: cumulative
        metrics observed BEFORE the recorder exists must not be billed
        to window 1."""
        reg, _lat, _codes, phases = make_http_registry()
        for _ in range(500):
            phases.observe(0.05, phase="execute")
        fr = devtel.FlightRecorder(window_s=1.0, capacity=4, registry=reg)
        snap = fr.capture()
        assert snap["phases"] == {}, snap["phases"]
        assert snap["http"]["requests"] == 0
        phases.observe(0.05, phase="execute")
        snap = fr.capture()
        assert snap["phases"]["execute"]["count"] == 1

    def test_burn_rate_worked_example(self):
        """The docs/observability.md example: target p99 250ms with a 1%
        budget; a window where 5% of requests exceed 250ms burns at 5x."""
        reg, _lat, _codes, _ = make_http_registry()
        slo = devtel.Slo("latency_p99", "latency", objective=0.01,
                         threshold_s=0.25)
        err = devtel.Slo("error_rate", "error", objective=0.001)
        fr = devtel.FlightRecorder(window_s=1.0, capacity=8,
                                   slos=(slo, err), registry=reg,
                                   long_windows=4)
        fr.capture()
        for _ in range(95):
            fr.observe_request(0.05, 200)
        for _ in range(5):
            fr.observe_request(0.6, 500)
        snap = fr.capture()
        assert snap["slo"]["latency_p99"]["short"] == pytest.approx(5.0)
        assert snap["slo"]["latency_p99"]["burning"] is True
        # 5% errors against a 0.1% budget burns at 50x
        assert snap["slo"]["error_rate"]["short"] == pytest.approx(50.0)
        burning = {b["slo"] for b in fr.burning()}
        assert burning == {"latency_p99", "error_rate"}
        # burn-rate gauges exported with slo= and window= labels
        text = reg.render()
        assert 'authz_slo_burn_rate{slo="latency_p99",window="short"} 5' \
            in text
        # a clean window recovers the short horizon; the long horizon
        # still remembers the burn (multi-window evaluation)
        for _ in range(100):
            fr.observe_request(0.05, 200)
        snap = fr.capture()
        assert snap["slo"]["latency_p99"]["short"] == 0.0
        assert snap["slo"]["latency_p99"]["long"] == pytest.approx(2.5)
        assert snap["slo"]["latency_p99"]["burning"] is False

    def test_long_horizon_clamped_to_ring_capacity(self):
        """A small --flight-windows ring must not silently promise a
        12-window long horizon it cannot hold."""
        reg = m.Registry()
        fr = devtel.FlightRecorder(window_s=1.0, capacity=4,
                                   long_windows=12, registry=reg)
        assert fr.long_windows == 4

    def test_observe_request_exact_threshold(self):
        """SLO intake counts at the exact threshold (no histogram-bucket
        snapping): a request exactly AT the target is good."""
        slo = devtel.Slo("latency_p99", "latency", objective=0.5,
                         threshold_s=0.25)
        fr = devtel.FlightRecorder(window_s=1.0, capacity=4, slos=(slo,),
                                   registry=m.Registry())
        fr.observe_request(0.25, 200)   # at the target: good
        fr.observe_request(0.2501, 200)  # over: bad
        snap = fr.capture()
        assert snap["_slo_tallies"]["latency_p99"] == (1, 2)


# -- /debug surfaces + readyz -------------------------------------------------


SERVER_SCHEMA = """
definition user {}

definition pod {
    relation creator: user
    permission view = creator
}
"""

SERVER_RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata: {name: get-pods}
match: [{apiVersion: v1, resource: pods, verbs: [get]}]
check: [{tpl: "pod:{{namespacedName}}#view@user:{{user.name}}"}]
"""


def make_server(**extra):
    from spicedb_kubeapi_proxy_tpu.kubefake.apiserver import FakeKubeApiServer
    from spicedb_kubeapi_proxy_tpu.proxy.httpcore import HandlerTransport
    from spicedb_kubeapi_proxy_tpu.proxy.server import Options, ProxyServer
    from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import Bootstrap

    kube = FakeKubeApiServer()
    kube.seed("", "v1", "pods",
              {"metadata": {"name": "p0", "namespace": "team-a"}})
    server = ProxyServer(Options(
        spicedb_endpoint="embedded://",
        bootstrap=Bootstrap(schema_text=SERVER_SCHEMA),
        rules_yaml=SERVER_RULES,
        upstream_transport=HandlerTransport(kube),
        slo_check_p99_ms=250.0,
        **extra))
    server.endpoint.store.write(touch("pod:team-a/p0#creator@user:alice"))
    return server


class TestDebugSurfaces:
    def test_index_enumerates_all_surfaces(self):
        server = make_server()
        alice = server.get_embedded_client(user="alice")

        async def go():
            resp = await alice.get("/debug")
            assert resp.status == 200
            surfaces = json.loads(resp.body)["surfaces"]
            assert set(surfaces) == {"/debug/traces", "/debug/decisions",
                                     "/debug/flight", "/debug/timeline",
                                     "/debug/replication",
                                     "/debug/sharding", "/debug/fleet",
                                     "/debug/tail", "/debug/workload",
                                     "/debug/profile"}
            for desc in surfaces.values():
                assert isinstance(desc, str) and desc
        run(go())

    def test_unknown_surface_uniform_404(self):
        server = make_server()
        alice = server.get_embedded_client(user="alice")

        async def go():
            resp = await alice.get("/debug/bogus")
            assert resp.status == 404
            body = json.loads(resp.body)
            assert body["reason"] == "NotFound"
        run(go())

    def test_surfaces_unauthenticated_401(self):
        server = make_server()
        anon = server.get_embedded_client()

        async def go():
            for path in ("/debug", "/debug/traces", "/debug/decisions",
                         "/debug/flight", "/debug/timeline",
                         "/debug/workload", "/debug/profile"):
                resp = await anon.get(path)
                assert resp.status == 401, path
        run(go())

    def test_flight_serves_windows_after_capture(self):
        server = make_server()
        alice = server.get_embedded_client(user="alice")

        async def go():
            await alice.get("/api/v1/namespaces/team-a/pods/p0")
            server.flight.capture()
            server.flight.capture()
            resp = await alice.get("/debug/flight")
            assert resp.status == 200
            flight = json.loads(resp.body)
            assert flight["enabled"] is True
            assert len(flight["windows"]) == 2
            assert flight["slos"][0]["name"] == "latency_p99"
            newest = flight["windows"][0]
            for field in ("http", "phases", "hbm", "occupancy", "jit",
                          "slo", "cache", "queues"):
                assert field in newest
        run(go())

    def test_readyz_surfaces_burning_slo(self):
        server = make_server()
        alice = server.get_embedded_client(user="alice")

        async def go():
            resp = await alice.get("/readyz")
            assert resp.status == 200 and resp.body == b"ok"
            # force a burn: every request slower than the 250ms target
            server.flight.capture()
            for _ in range(10):
                server.flight.observe_request(0.9, 200)
            server.flight.capture()
            resp = await alice.get("/readyz")
            assert resp.status == 200
            assert b"slo latency_p99 burning" in resp.body
        run(go())

    def test_health_and_introspection_do_not_dilute_slo(self):
        """Health probes and /metrics//debug scrapes are untraced and
        must not feed the SLO tallies — only proxied API requests do."""
        server = make_server()
        alice = server.get_embedded_client(user="alice")

        async def go():
            server.flight.capture()
            for _ in range(20):
                await alice.get("/readyz")
                await alice.get("/metrics")
                await alice.get("/debug/flight")
                await alice.get("/debug/")  # index via trailing slash
            resp = await alice.get("/api/v1/namespaces/team-a/pods/p0")
            assert resp.status == 200
            snap = server.flight.capture()
            _bad, total = snap["_slo_tallies"]["latency_p99"]
            assert total == 1, (
                f"probe/scrape traffic leaked into the SLO base: {total}")
            # the window's http stats are proxied-only too
            assert snap["http"]["requests"] == 1
        run(go())

    def test_flight_reports_gate_state(self):
        from spicedb_kubeapi_proxy_tpu.utils.features import GATES
        server = make_server()
        alice = server.get_embedded_client(user="alice")
        GATES.set("DeviceTelemetry", False)
        try:
            async def go():
                resp = await alice.get("/debug/flight")
                flight = json.loads(resp.body)
                assert flight["enabled"] is False
                assert "gate" in flight["reason"]
            run(go())
        finally:
            GATES.set("DeviceTelemetry", True)
