"""Dispatch timeline profiler internals (utils/timeline.py): ring-bound
eviction under churn, chrome-trace schema validity (Perfetto contract),
overlap-ratio math on synthetic interleavings, derived telemetry
(stalls, bandwidth, roofline, worst dispatch), the kernel-span hook,
zero-allocation behavior behind the Timeline gate, and the end-to-end
jax:// pipeline emitting every stage."""

import asyncio
import threading

import pytest

from spicedb_kubeapi_proxy_tpu.utils import metrics as m
from spicedb_kubeapi_proxy_tpu.utils import timeline, tracing
from spicedb_kubeapi_proxy_tpu.utils.features import GATES
from spicedb_kubeapi_proxy_tpu.utils.timeline import (
    Timeline,
    TimelineEvent,
    overlap_stats,
)


def make_timeline(**kw):
    """Isolated instance: fresh registry so metric registration never
    collides with the module singleton's."""
    kw.setdefault("registry", m.Registry())
    return Timeline(**kw)


def ev(stage, start, end, batch=None, track="device", nbytes=0):
    return TimelineEvent(stage, track, start, end, 0, batch, None,
                         nbytes, None)


# -- overlap-ratio math on synthetic interleavings ----------------------------


class TestOverlapMath:
    def test_no_events_is_none(self):
        assert overlap_stats([]) is None

    def test_no_transfer_time_is_none(self):
        assert overlap_stats([ev("kernel", 0.0, 1.0, batch=1)]) is None

    def test_partial_overlap(self):
        # transfer of batch 1 spans [0, 10]; batch 2's kernel covers
        # [2, 6] of it -> 4/10
        st = overlap_stats([ev("transfer", 0.0, 10.0, batch=1),
                            ev("kernel", 2.0, 6.0, batch=2)])
        assert st["ratio"] == pytest.approx(0.4)
        assert st["transfer_s"] == pytest.approx(10.0)
        assert st["overlap_s"] == pytest.approx(4.0)

    def test_same_batch_kernel_is_serialization_not_overlap(self):
        st = overlap_stats([ev("transfer", 0.0, 10.0, batch=1),
                            ev("kernel", 0.0, 10.0, batch=1)])
        assert st["ratio"] == 0.0

    def test_overlapping_kernels_not_double_counted(self):
        # kernels [2,6] and [4,8] union to [2,8] -> 6/10, not 8/10
        st = overlap_stats([ev("transfer", 0.0, 10.0, batch=1),
                            ev("kernel", 2.0, 6.0, batch=2),
                            ev("kernel", 4.0, 8.0, batch=3)])
        assert st["ratio"] == pytest.approx(0.6)

    def test_transpose_counts_as_transfer_side(self):
        st = overlap_stats([ev("transpose", 0.0, 4.0, batch=1),
                            ev("kernel", 0.0, 4.0, batch=2)])
        assert st["ratio"] == pytest.approx(1.0)

    def test_perfect_double_buffer_scores_one(self):
        # batch N's transfer fully hidden behind batch N+1's kernel
        events = []
        for n in range(4):
            t0 = float(n)
            events.append(ev("kernel", t0, t0 + 0.8, batch=n))
            events.append(ev("transfer", t0 + 1.0, t0 + 1.5, batch=n))
        # shift kernels to cover the previous batch's transfer window
        events += [ev("kernel", n + 1.0, n + 1.8, batch=n + 1)
                   for n in range(4)]
        st = overlap_stats(events)
        assert st["ratio"] == pytest.approx(1.0)


# -- ring bounds under churn --------------------------------------------------


class TestRingBounds:
    def test_eviction_under_threaded_churn(self):
        tl = make_timeline(capacity=64)
        errors = []

        def writer(i):
            try:
                for k in range(200):
                    t0 = timeline.now()
                    tl.record("pack", "host", t0, t0 + 1e-6,
                              batch=i * 1000 + k, nbytes=64)
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)

        def reader():
            try:
                for _ in range(50):
                    tl.summary()
                    tl.chrome_trace()
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)

        threads = ([threading.Thread(target=writer, args=(i,))
                    for i in range(8)]
                   + [threading.Thread(target=reader) for _ in range(2)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(tl.events()) == 64  # bounded: oldest evicted
        assert tl.snapshot()["events_total"] == 8 * 200
        # the retained events are the NEWEST per writer
        batches = sorted(e.batch for e in tl.events())
        assert batches[0] >= 100  # every writer's early events evicted

    def test_since_filter(self):
        tl = make_timeline(capacity=16)
        tl.record("pack", "host", 1.0, 2.0)
        tl.record("pack", "host", 10.0, 11.0)
        assert len(tl.events(since=5.0)) == 1
        assert len(tl.events()) == 2


# -- chrome-trace schema ------------------------------------------------------


def assert_valid_chrome_trace(trace):
    """Every event has ph/ts/pid/tid; X events carry dur; B/E pairs
    balance per (pid, tid).  Independent hand-kept copy of
    scripts/devtel_smoke.py's validator (that script's module level
    sets env vars and imports jax, so it must not be imported here);
    schema changes must land in both."""
    assert isinstance(trace["traceEvents"], list)
    depth = {}
    for e in trace["traceEvents"]:
        for field in ("ph", "ts", "pid", "tid"):
            assert field in e, f"event missing {field}: {e}"
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0
        elif e["ph"] == "B":
            depth[(e["pid"], e["tid"])] = (
                depth.get((e["pid"], e["tid"]), 0) + 1)
        elif e["ph"] == "E":
            key = (e["pid"], e["tid"])
            depth[key] = depth.get(key, 0) - 1
            assert depth[key] >= 0, "E without open B"
    assert not any(depth.values()), f"unbalanced B/E: {depth}"


class TestChromeTrace:
    def test_schema_and_tracks(self):
        tl = make_timeline(capacity=32)
        b = tl.next_batch()
        t0 = timeline.now()
        tl.record("pack", "host", t0, t0 + 0.001, batch=b, bucket=64,
                  nbytes=256)
        tl.record("kernel", "device", t0, t0 + 0.01, batch=b, bucket=64,
                  nbytes=1 << 20)
        tl.record("rebuild", "rebuild", t0, t0 + 0.5, nbytes=1 << 24)
        tl.record("fused", "dispatcher", t0, t0 + 0.02, bucket=8)
        trace = tl.chrome_trace()
        assert_valid_chrome_trace(trace)
        import json
        json.dumps(trace)  # JSON-serializable end to end
        events = trace["traceEvents"]
        names = {e["name"] for e in events}
        assert {"pack", "kernel", "rebuild", "fused"} <= names
        # named tracks: metadata rows for host/dispatcher/device/rebuild
        threads = {e["args"]["name"] for e in events
                   if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"host", "dispatcher", "device", "rebuild"} <= threads
        # rebuild exports as a B/E pair, pipeline stages as X
        phs = {e["name"]: e["ph"] for e in events if e["ph"] != "M"}
        assert phs["rebuild"] == "E"  # last rebuild record is the E
        assert phs["pack"] == "X"
        # args carry the correlation ids
        kernel = next(e for e in events
                      if e["ph"] == "X" and e["name"] == "kernel")
        assert kernel["args"]["batch"] == b
        assert kernel["args"]["bucket"] == 64
        assert kernel["args"]["bytes"] == 1 << 20

    def test_summary_rides_other_data(self):
        tl = make_timeline(capacity=8)
        tl.record("pack", "host", 0.0, 1.0)
        od = tl.chrome_trace()["otherData"]
        assert od["summary"]["events"] == 1
        assert od["capacity"] == 8


# -- derived telemetry --------------------------------------------------------


class TestDerivedTelemetry:
    def test_stall_attribution_and_counters(self):
        reg = m.Registry()
        tl = make_timeline(capacity=32, registry=reg)
        tl.record("pack", "host", 0.0, 0.5)
        tl.record("transpose", "device", 0.0, 0.25)
        tl.record("rebuild", "rebuild", 0.0, 2.0)
        tl.record("compact", "rebuild", 0.0, 1.0)   # rebuild-family
        tl.record("warm_start", "rebuild", 0.0, 4.0)
        tl.record("compile", "device", 0.0, 0.125)
        tl.record("kernel", "device", 0.0, 9.0)     # NOT a stall
        s = tl.summary()
        assert s["stall_s"]["pack"] == pytest.approx(0.5)
        assert s["stall_s"]["transpose"] == pytest.approx(0.25)
        assert s["stall_s"]["rebuild"] == pytest.approx(7.0)
        assert s["stall_s"]["compile"] == pytest.approx(0.125)
        assert "kernel" not in s["stall_s"]
        # the ring itself observes only the stages no span marks
        # (rebuild family, compile) into authz_dispatch_stage_seconds
        h = reg.get("authz_dispatch_stage_seconds")
        raw = h.raw()
        assert raw[("rebuild", "")][1:] == (pytest.approx(7.0), 3)
        assert raw[("compile", "")][1:] == (pytest.approx(0.125), 1)
        assert ("pack", "") not in raw and ("kernel", "") not in raw
        # pack (like every stage of a fused call) is observed once by
        # the span that marks it, with the call's kind
        glob = m.REGISTRY.get("authz_dispatch_stage_seconds")
        before = glob.count(stage="pack", kind="check")
        with tracing.span("pack", fused="check"):
            pass
        assert glob.count(stage="pack", kind="check") == before + 1

    def test_bandwidth_and_roofline(self):
        tl = make_timeline(capacity=32, hbm_peak_gbps=1.0)  # 1 GB/s peak
        # 0.5 GB moved in 1s on the kernel stage -> 0.5 of peak
        tl.record("kernel", "device", 0.0, 1.0, batch=1,
                  nbytes=500_000_000)
        s = tl.summary()
        assert s["bandwidth_bytes_per_s"]["kernel"] == pytest.approx(5e8)
        assert s["roofline_fraction"] == pytest.approx(0.5)
        assert s["hbm_peak_gbps"] == pytest.approx(1.0)

    def test_roofline_none_without_peak(self):
        tl = make_timeline(capacity=8)
        tl._hbm_peak_detected = 0.0  # force "unknown platform"
        tl.record("kernel", "device", 0.0, 1.0, nbytes=1000)
        assert tl.summary()["roofline_fraction"] is None

    def test_no_platform_detection_before_any_device_event(self):
        # summary()/scrapes on a jax-less server must never trigger
        # platform detection (jax import + jax.devices() would stall
        # the event loop on backend init); detection arms only once a
        # device-track event proves the backend is already up
        tl = make_timeline(capacity=8)
        tl.record("pack", "host", 0.0, 1.0, nbytes=64)  # host-only load
        assert tl.hbm_peak_bytes_per_s() == 0.0
        assert tl._hbm_peak_detected is None  # detection never ran
        tl.summary()
        tl.chrome_trace()
        assert tl._hbm_peak_detected is None
        tl.record("kernel", "device", 0.0, 1.0, nbytes=64)
        tl.hbm_peak_bytes_per_s()
        assert tl._hbm_peak_detected is not None  # armed by the event

    def test_worst_dispatch_exemplar(self):
        tl = make_timeline(capacity=32)
        tl.record("pack", "host", 0.0, 0.1, batch=1)
        tl.record("kernel", "device", 0.1, 0.2, batch=1)
        tl.record("pack", "host", 0.0, 0.1, batch=2)
        tl.record("kernel", "device", 0.1, 3.0, batch=2)  # the slow one
        w = tl.summary()["worst_dispatch"]
        assert w["batch"] == 2
        assert w["stages_ms"]["kernel"] == pytest.approx(2900.0)
        assert w["total_ms"] == pytest.approx(3000.0)

    def test_time_first_call_records_one_compile(self):
        tl = make_timeline(capacity=8)
        calls = []
        wrapped = tl.time_first_call(lambda x: calls.append(x) or x + 1,
                                     bucket=64)
        assert wrapped(1) == 2 and wrapped(2) == 3 and wrapped(3) == 4
        compiles = [e for e in tl.events() if e.stage == "compile"]
        assert len(compiles) == 1
        assert compiles[0].bucket == 64
        assert calls == [1, 2, 3]

    def test_time_first_call_per_static_key(self):
        # jit static_argnums: every NEW static prefix recompiles and
        # must record its own compile slice (a lookup kernel compiles
        # per (slot_offset, slot_length), not just once ever)
        tl = make_timeline(capacity=16)
        wrapped = tl.time_first_call(lambda off, ln, x: x, static_args=2)
        for off, ln in ((0, 10), (0, 10), (5, 20), (0, 10), (5, 20),
                        (7, 3)):
            wrapped(off, ln, "q")
        compiles = [e for e in tl.events() if e.stage == "compile"]
        assert len(compiles) == 3  # (0,10), (5,20), (7,3)

    def test_compile_contaminated_kernel_excluded_from_roofline(self):
        # the first execution of a fresh bucket compiles INSIDE the
        # kernel span: that kernel event is tagged and must not feed
        # bandwidth/roofline with a compile-inflated duration
        tl = make_timeline(capacity=16, hbm_peak_gbps=1.0)
        t0 = 100.0
        tl.record("compile", "device", t0 + 0.1, t0 + 5.0)
        # kernel window [t0, t0+6] contains the compile slice
        tl.record("kernel", "device", t0, t0 + 6.0, batch=1,
                  nbytes=1_000_000)
        evs = tl.events()
        contaminated = [e for e in evs if e.stage == "kernel"]
        assert contaminated[0].attrs.get("compile") is True
        s = tl.summary()
        assert "kernel" not in s["bandwidth_bytes_per_s"]
        assert s["roofline_fraction"] is None
        # a clean kernel event afterwards feeds them again
        tl.record("kernel", "device", t0 + 10.0, t0 + 11.0, batch=2,
                  nbytes=500_000_000)
        s = tl.summary()
        assert s["bandwidth_bytes_per_s"]["kernel"] == pytest.approx(5e8)
        assert s["roofline_fraction"] == pytest.approx(0.5)

    def test_rebuild_bytes_are_not_a_bandwidth(self):
        reg = m.Registry()
        tl = make_timeline(capacity=8, registry=reg)
        tl.record("rebuild", "rebuild", 0.0, 2.0, nbytes=1 << 30)
        assert "rebuild" not in tl.summary()["bandwidth_bytes_per_s"]
        g = reg.get("authz_dispatch_bandwidth_bytes_per_sec")
        assert 'stage="rebuild"' not in "\n".join(g.render())


# -- the tracing.span hook ---------------------------------------------------


class TestKernelSpanHook:
    def test_kernel_span_lands_on_device_track(self):
        mark = timeline.now()
        with tracing.span("ready", fused="check", bucket=64) as a:
            a["batch_id"] = 424242
            a["nbytes"] = 4096
        evs = [e for e in timeline.TIMELINE.events(since=mark)
               if e.batch == 424242]
        assert len(evs) == 1
        assert evs[0].stage == "kernel" and evs[0].track == "device"
        assert evs[0].nbytes == 4096 and evs[0].bucket == 64

    def test_timeline_stage_override(self):
        # a host word-transpose is marked by its own span name and lands
        # as the timeline's transpose stage, not as a transfer
        mark = timeline.now()
        with tracing.span("transpose", fused="lookup") as a:
            a["batch_id"] = 434343
        evs = [e for e in timeline.TIMELINE.events(since=mark)
               if e.batch == 434343]
        assert [e.stage for e in evs] == ["transpose"]

    def test_unmapped_kernel_span_is_ignored(self):
        mark = timeline.now()
        with tracing.request_trace():
            with tracing.span("kernel.oracle", rows=1):
                pass
        assert [e for e in timeline.TIMELINE.events(since=mark)
                if e.stage == "kernel.oracle"] == []


# -- gate off: zero allocation ------------------------------------------------


class TestGateOff:
    def test_gated_off_records_nothing_and_allocates_no_spans(self):
        tl = make_timeline(capacity=8)
        tl.record("pack", "host", 0.0, 1.0)
        GATES.set("Timeline", False)
        try:
            before = tl.snapshot()
            n = len(tl.events())
            for _ in range(100):
                tl.record("pack", "host", 0.0, 1.0, nbytes=1 << 20)
            # span() hands back ONE shared null context: no per-call
            # event/generator allocation while gated off
            s1 = tl.span("pack", "host")
            s2 = tl.span("kernel", "device", nbytes=5)
            assert s1 is s2
            with s1 as attrs:
                attrs2 = attrs
            assert attrs2 == {}
            assert len(tl.events()) == n
            assert tl.snapshot() == before
        finally:
            GATES.set("Timeline", True)
        # back on: recording resumes
        tl.record("pack", "host", 0.0, 1.0)
        assert len(tl.events()) == n + 1

    def test_gated_off_chrome_trace_still_valid(self):
        tl = make_timeline(capacity=8)
        GATES.set("Timeline", False)
        try:
            assert_valid_chrome_trace(tl.chrome_trace())
        finally:
            GATES.set("Timeline", True)


# -- end to end: the jax:// pipeline emits every stage ------------------------


SCHEMA = """
definition user {}
definition doc {
  relation viewer: user
  permission view = viewer
}
"""


class TestEndpointPipeline:
    def test_lookup_and_check_emit_pipeline_stages(self):
        from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import (
            Bootstrap, create_endpoint)
        from spicedb_kubeapi_proxy_tpu.spicedb.types import (
            CheckRequest, ObjectRef, SubjectRef, parse_relationship)

        ep = create_endpoint("jax://?dispatch=direct",
                             Bootstrap(schema_text=SCHEMA))
        ep.store.bulk_load([parse_relationship(f"doc:d{i}#viewer@user:u1")
                            for i in range(8)])
        mark = timeline.now()

        async def go():
            await ep.check_bulk_permissions([CheckRequest(
                ObjectRef("doc", "d0"), "view", SubjectRef("user", "u1"))])
            return await ep.lookup_resources_batch(
                "doc", "view", [SubjectRef("user", "u1"),
                                SubjectRef("user", "u2")])

        results = asyncio.run(go())
        assert sorted(results[0]) == [f"d{i}" for i in range(8)]
        evs = timeline.TIMELINE.events(since=mark)
        stages = {e.stage for e in evs}
        # host pack + device kernel + host extract on both verbs; the
        # packed lookup's result movement shows as transfer/transpose;
        # the fresh graph's first kernel calls record compile slices;
        # the initial graph build records a rebuild-track span
        assert {"pack", "kernel", "extract", "compile"} <= stages
        assert stages & {"transfer", "transpose"}
        assert stages & {"rebuild", "compact"}
        # fused-batch ids correlate one dispatch's slices across tracks
        kernel_batches = {e.batch for e in evs if e.stage == "kernel"}
        pack_batches = {e.batch for e in evs if e.stage == "pack"}
        assert kernel_batches and kernel_batches <= pack_batches
        # and the whole thing renders as a loadable chrome trace
        assert_valid_chrome_trace(timeline.chrome_trace(since=mark))
        s = timeline.summary(since=mark)
        assert s["events"] == len(evs)
        assert s["worst_dispatch"] is not None
        assert "pack" in s["stall_s"]


# -- flight-recorder evidence links ------------------------------------------


class TestFlightEvidenceLinks:
    def test_window_embeds_slow_traces_and_timeline(self, monkeypatch):
        from spicedb_kubeapi_proxy_tpu.utils import devtel

        # isolated recorder: the global one retains the 32 SLOWEST
        # traces of the whole suite run, which would starve this test's
        # microsecond trace out of the exemplar heap
        monkeypatch.setattr(tracing, "RECORDER",
                            tracing.SlowTraceRecorder(capacity=8))
        fr = devtel.FlightRecorder(window_s=0.05, capacity=4,
                                   registry=m.REGISTRY)
        tr = tracing.Trace(op="evidence")
        tr.finish()
        tracing.RECORDER.record(tr)
        timeline.record("pack", "host", timeline.now() - 0.001)
        snap = fr.capture()
        assert any(x["trace_id"] == tr.trace_id
                   for x in snap["slow_traces"])
        assert snap["timeline"] is not None
        assert snap["timeline"]["events"] >= 1
        # the internal SLO tallies stay private; the evidence links are
        # served at /debug/flight
        served = fr.snapshots()[0]
        assert "slow_traces" in served and "timeline" in served

    def test_window_timeline_none_when_gate_off(self):
        from spicedb_kubeapi_proxy_tpu.utils import devtel

        fr = devtel.FlightRecorder(window_s=0.05, capacity=4,
                                   registry=m.REGISTRY)
        GATES.set("Timeline", False)
        try:
            snap = fr.capture()
            assert snap["timeline"] is None
        finally:
            GATES.set("Timeline", True)

    def test_exemplars_filter_by_start(self):
        rec = tracing.SlowTraceRecorder(capacity=8)
        t_old = tracing.Trace()
        t_old.wall_start -= 1000.0  # started long ago
        t_old.finish()
        rec.record(t_old)
        t_new = tracing.Trace()
        t_new.finish()
        rec.record(t_new)
        import time
        recent = rec.exemplars(k=5, since_unix=time.time() - 60)
        assert [x["trace_id"] for x in recent] == [t_new.trace_id]
        assert len(rec.exemplars(k=5)) == 2
        assert len(rec.exemplars(k=1)) == 1
