"""Kernel introspection & workload cost attribution.

Covers the measured sweep-telemetry plane (utils/workload.py + the
introspection-threaded kernels in ops/ell.py and ops/spmv.py), the
per-(type, permission) cost-attribution accounting behind
/debug/workload, the Leopard-candidate nesting detector, the sampling
profiler (utils/profiler.py), and the perf-regression sentinel
(scripts/benchdiff.py + the bench.py --baseline gate).

Honesty contracts asserted here:

- measured kernel bytes (iterations x one-sweep traffic) are always at
  least the modeled one-sweep floor the roofline used to assume;
- the KernelIntrospect killswitch off builds byte-identical
  pre-introspection jitted functions and records nothing;
- serial and pipelined dispatch observe the same sweep histogram for
  the same traffic (the telemetry must not depend on the dispatch mode);
- an injected slowdown in the dispatch drain trips the benchdiff gate
  with the offending config named (the check.sh tripwire).
"""

import asyncio
import importlib.util
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.ops.graph_compile import compile_graph
from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
from spicedb_kubeapi_proxy_tpu.ops.spmv import KernelCache, bucket, pad_edges
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    CheckRequest,
    ObjectRef,
    RelationshipUpdate,
    SubjectRef,
    UpdateOp,
    parse_relationship,
)
from spicedb_kubeapi_proxy_tpu.utils import metrics as m
from spicedb_kubeapi_proxy_tpu.utils import profiler, timeline, workload
from spicedb_kubeapi_proxy_tpu.utils.features import GATES

ROOT = Path(__file__).resolve().parents[1]

# a userset-recursive schema: group membership nests through
# group#member, so deep chains force multi-sweep fixpoint propagation
NESTED_SCHEMA = """
definition user {}
definition group {
    relation member: user | group#member
}
definition doc {
    relation viewer: user | group#member
    permission view = viewer
}
"""

FLAT_SCHEMA = """
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


def chain_rels(depth):
    """doc:d0 viewable by user:deep only through `depth` nested groups
    (plus user:flat directly) — the fixpoint needs ~depth sweeps."""
    rels = ["doc:d0#viewer@group:g0#member",
            "doc:d0#viewer@user:flat"]
    for i in range(depth - 1):
        rels.append(f"group:g{i}#member@group:g{i + 1}#member")
    rels.append(f"group:g{depth - 1}#member@user:deep")
    return rels


def build_prog(schema_text, rels):
    schema = sch.parse_schema(schema_text)
    store = TupleStore()
    store.bulk_load([parse_relationship(r) for r in rels])
    return schema, store, compile_graph(schema, store.read(None))


def segment_lookup_iterations(schema_text, rels, users=("deep", "flat")):
    """Run one segment-kernel lookup and return its decoded sweep
    record (KernelCache.lookup stashes it thread-locally)."""
    import jax.numpy as jnp
    _, _, prog = build_prog(schema_text, rels)
    k = KernelCache(prog)
    src, dst = pad_edges(prog)
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    q = np.asarray([prog.subject_index("user", u, "") for u in users],
                   np.int32)
    qb = np.full(bucket(len(q), 8), prog.dead_index, np.int32)
    qb[: len(q)] = q
    off, ln = prog.slot_range("doc", "view")
    workload.take_last_sweep()  # drop any stale record
    out = k.lookup(off, ln, qb, src, dst)
    rec = workload.take_last_sweep()
    return out[:, : len(q)], rec


def make_endpoint(depth=7):
    schema = sch.parse_schema(NESTED_SCHEMA)
    # these tests measure the fixpoint kernels' own telemetry: keep the
    # Leopard index out so the nested chain actually sweeps
    prev = GATES.enabled("LeopardIndex")
    GATES.set("LeopardIndex", False)
    try:
        ep = JaxEndpoint(schema)
    finally:
        GATES.set("LeopardIndex", prev)
    ep.store.write(touch(*chain_rels(depth)))
    return ep


def check_reqs(n=8):
    """A kernel-eligible check batch: every subject against doc:d0."""
    subs = [SubjectRef("user", "deep"), SubjectRef("user", "flat")]
    subs += [SubjectRef("user", f"u{i}") for i in range(n - 2)]
    return [CheckRequest(ObjectRef("doc", "d0"), "view", s) for s in subs]


def kernel_events(since):
    return [e for e in timeline.TIMELINE.events(since=since)
            if e.stage == "kernel" and e.nbytes > 0]


# -- measured sweep telemetry -------------------------------------------------


class TestSweepTelemetry:
    def test_segment_kernel_records_measured_iterations(self):
        assert GATES.enabled("KernelIntrospect")
        before = workload.WORKLOAD._sweep_iters.count(
            kernel="segment", verb="lookup")
        out, rec = segment_lookup_iterations(NESTED_SCHEMA, chain_rels(7))
        assert rec is not None and rec.kernel == "segment"
        assert rec.verb == "lookup"
        # the nested chain cannot converge in one sweep, and the trace
        # carries exactly one frontier delta per iteration
        assert rec.iterations >= 2
        assert len(rec.deltas) == rec.iterations
        assert rec.deltas[0] > 0
        assert workload.WORKLOAD._sweep_iters.count(
            kernel="segment", verb="lookup") == before + 1
        # the lookup result itself is still correct alongside telemetry
        assert out.any()

    def test_nested_chain_sweeps_deeper_than_flat(self):
        _, deep = segment_lookup_iterations(NESTED_SCHEMA, chain_rels(7))
        _, flat = segment_lookup_iterations(
            FLAT_SCHEMA, ["doc:d0#viewer@user:flat"], users=("flat",))
        assert deep.iterations > flat.iterations

    def test_frontier_decay_histogram_observed(self):
        h = workload.WORKLOAD._decay
        before = h.count(kernel="segment", verb="lookup")
        _, rec = segment_lookup_iterations(NESTED_SCHEMA, chain_rels(7))
        # one decay ratio per successive-iteration pair with a live
        # previous frontier
        expect = sum(1 for prev in rec.deltas[:-1] if prev > 0)
        assert h.count(kernel="segment", verb="lookup") == before + expect

    def test_ell_endpoint_attributes_checks_to_pair(self):
        workload.WORKLOAD.reset()
        ep = make_endpoint()
        run(ep.check_bulk_permissions(check_reqs()))
        payload = workload.WORKLOAD.payload()
        rows = {(r["resource_type"], r["permission"]): r
                for r in payload["rows"]}
        row = rows[("doc", "view")]
        assert row["kernel_rows"] + row["oracle_rows"] >= len(check_reqs())
        if row["kernel_rows"]:
            assert row["mean_sweep_depth"] is None \
                or row["mean_sweep_depth"] >= 1

    def test_measured_bytes_at_least_modeled_floor(self):
        """The roofline's kernel bytes with introspection on are
        measured iterations x one-sweep traffic; they can never fall
        below the modeled one-sweep lower bound the gate-off build
        reports for the same traffic."""
        reqs = check_reqs()

        GATES.set("KernelIntrospect", False)
        try:
            ep_off = make_endpoint()
            run(ep_off.check_bulk_permissions(reqs))  # warm (compile)
            mark = time.perf_counter()
            run(ep_off.check_bulk_permissions(reqs))
            modeled_evs = kernel_events(mark)
            assert modeled_evs, "no kernel event with modeled bytes"
            assert all(not (e.attrs or {}).get("measured")
                       for e in modeled_evs)
            modeled = max(e.nbytes for e in modeled_evs)
        finally:
            GATES.set("KernelIntrospect", True)

        ep_on = make_endpoint()
        run(ep_on.check_bulk_permissions(reqs))  # warm (compile)
        mark = time.perf_counter()
        run(ep_on.check_bulk_permissions(reqs))
        measured_evs = [e for e in kernel_events(mark)
                        if (e.attrs or {}).get("measured")]
        assert measured_evs, "no measured-basis kernel event"
        assert max(e.nbytes for e in measured_evs) >= modeled

    def test_serial_and_pipelined_observe_same_histogram(self):
        """The sweep histogram must not depend on the dispatch mode:
        the same traffic through the serial path and the device-resident
        pipeline lands the same (kernel, verb) observations."""
        h = workload.WORKLOAD._sweep_iters

        def observe(pipelined):
            GATES.set("DevicePipeline", pipelined)
            try:
                ep = make_endpoint()
                run(ep.check_bulk_permissions(check_reqs()))  # warm
                before = h.raw()
                run(ep.check_bulk_permissions(check_reqs()))
                # the pipelined readback decodes the trace on a pool
                # thread; give it a beat to land
                key = ("ell", "check")
                for _ in range(100):
                    after = h.raw()
                    if (after.get(key, ([], 0, 0))[2]
                            > before.get(key, ([], 0, 0))[2]):
                        break
                    time.sleep(0.01)
                else:
                    raise AssertionError("no ell/check sweep observed")
                b = before.get(key, ([0] * len(h.buckets + (0,)), 0.0, 0))
                a = after[key]
                return (a[1] - b[1], a[2] - b[2])  # (sum, count) delta
            finally:
                GATES.set("DevicePipeline", True)

        serial = observe(False)
        piped = observe(True)
        assert serial[1] >= 1 and piped[1] >= 1
        # identical traffic, identical fixpoint: same total iterations
        assert serial == piped

    def test_pipeline_depth_does_not_change_histogram(self):
        """Pipeline depth 1 vs 3 through the batching dispatcher lands
        the same sweep observations — how many batches are kept in
        flight must not change what each batch measures."""
        from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import (
            BatchingEndpoint)
        h = workload.WORKLOAD._sweep_iters
        key = ("ell", "check")

        def observe(depth):
            ep = BatchingEndpoint(make_endpoint(), max_batch=4,
                                  pipeline_depth=depth)
            reqs = check_reqs()

            async def go():
                return await asyncio.gather(
                    *[ep.check_permission(r) for r in reqs])

            run(go())  # warm
            before = h.raw().get(key, ([], 0.0, 0))
            run(go())
            for _ in range(100):
                after = h.raw().get(key, ([], 0.0, 0))
                if after[2] > before[2]:
                    break
                time.sleep(0.01)
            else:
                raise AssertionError("no ell/check sweep observed")
            return (after[1] - before[1], after[2] - before[2])

        assert observe(1) == observe(3)


# -- killswitch: off must mean byte-identical inert ---------------------------


class TestGateOffTripwire:
    def test_gate_off_builds_pre_introspection_jits(self):
        import jax.numpy as jnp
        GATES.set("KernelIntrospect", False)
        try:
            _, _, prog = build_prog(NESTED_SCHEMA, chain_rels(5))
            k = KernelCache(prog)
            assert k._intro is False
            src, dst = pad_edges(prog)
            src, dst = jnp.asarray(src), jnp.asarray(dst)
            q = np.full(bucket(1, 8), prog.dead_index, np.int32)
            q[0] = prog.subject_index("user", "deep", "")
            off, ln = prog.slot_range("doc", "view")
            before = workload.WORKLOAD._sweep_iters.raw()
            out = k.lookup(off, ln, q, src, dst)
            # a plain array result, no sweep record, no observation
            assert isinstance(out, np.ndarray)
            assert workload.take_last_sweep() is None
            assert workload.WORKLOAD._sweep_iters.raw() == before
        finally:
            GATES.set("KernelIntrospect", True)

    def test_gate_off_accounting_is_inert(self):
        reg = m.Registry()
        wa = workload.WorkloadAccounting(registry=reg)
        GATES.set("KernelIntrospect", False)
        try:
            assert wa.note_sweep("ell", "check", np.asarray([2, 3, 1])) \
                is None
            wa.note_batch([("doc", "view", 4)], "check", iterations=3)
            wa.note_device_time([("doc", "view", 4)], "ready", 0.01)
            wa.note_oracle([("doc", "view", 1)])
            wa.note_cache("doc", "view", 2, 1)
            payload = wa.payload()
            assert payload["rows"] == []
            assert payload["total_device_s"] == 0.0
            # zero observations: the families render no samples at all
            text = reg.render()
            assert "authz_sweep_iterations_bucket" not in text
            assert "authz_frontier_decay_bucket" not in text
        finally:
            GATES.set("KernelIntrospect", True)


# -- cost-attribution accounting ----------------------------------------------


class TestWorkloadAccounting:
    def test_device_time_split_by_row_share(self):
        wa = workload.WorkloadAccounting(registry=m.Registry())
        wa.note_device_time([("doc", "view", 3), ("doc", "edit", 1)],
                            "ready", 0.04)
        payload = wa.payload()
        rows = {(r["resource_type"], r["permission"]): r
                for r in payload["rows"]}
        assert rows[("doc", "view")]["device_s"] == pytest.approx(0.03)
        assert rows[("doc", "edit")]["device_s"] == pytest.approx(0.01)
        assert payload["attribution_ratio"] == pytest.approx(1.0)

    def test_unattributed_span_still_counts_toward_total(self):
        """Spans with no composition (warmup, rebuild flushes) must
        show up in the reconciliation denominator, not vanish."""
        wa = workload.WorkloadAccounting(registry=m.Registry())
        wa.note_device_time(None, "ready", 0.02)
        payload = wa.payload()
        assert payload["total_device_s"] == pytest.approx(0.02)
        assert payload["attributed_device_s"] == 0.0

    def test_non_device_phase_ignored(self):
        wa = workload.WorkloadAccounting(registry=m.Registry())
        wa.note_device_time([("doc", "view", 1)], "h2d.slices", 0.5)
        assert wa.payload()["total_device_s"] == 0.0

    def test_oracle_fraction_and_cache_hit_rate(self):
        wa = workload.WorkloadAccounting(registry=m.Registry())
        wa.note_batch([("doc", "view", 6)], "check", iterations=4,
                      occupancy=0.75)
        wa.note_oracle([("doc", "view", 2)])
        wa.note_cache("doc", "view", 3, 1)
        row = wa.payload()["rows"][0]
        assert row["oracle_fraction"] == pytest.approx(2 / 8)
        assert row["cache_hit_rate"] == pytest.approx(0.75)
        assert row["mean_sweep_depth"] == pytest.approx(4.0)
        assert row["mean_occupancy"] == pytest.approx(0.75)

    def test_devtel_hook_feeds_same_seconds(self):
        """The /debug/workload device-time total is fed by the same
        `ready` span seconds as authz_dispatch_stage_seconds{stage=
        "ready"} (the endpoint forwards the span's duration), so the two
        reconcile by construction."""
        from spicedb_kubeapi_proxy_tpu.utils import metrics as m_
        ep = make_endpoint()
        run(ep.check_bulk_permissions(check_reqs()))  # warm (compile)
        hist = m_.REGISTRY.get("authz_dispatch_stage_seconds")

        def ready_s():
            return sum(s for (stage, _k), (_c, s, _n) in hist.raw().items()
                       if stage == "ready")

        before = (workload.WORKLOAD.payload()["total_device_s"], ready_s())
        run(ep.check_bulk_permissions(check_reqs()))
        after = (workload.WORKLOAD.payload()["total_device_s"], ready_s())
        assert after[1] > before[1]
        assert after[0] - before[0] == pytest.approx(after[1] - before[1],
                                                     abs=1e-5)


# -- Leopard-candidate detection ----------------------------------------------


class TestLeopardDetector:
    def _accounted(self, schema_text, depth):
        wa = workload.WorkloadAccounting(registry=m.Registry())
        wa.note_schema(sch.parse_schema(schema_text))
        wa.note_batch([("doc", "view", 4)], "check", iterations=depth,
                      occupancy=0.5)
        return wa.leopard_candidates()

    def test_deep_nested_pair_flagged(self):
        cands = self._accounted(NESTED_SCHEMA, depth=8)
        assert [c["resource_type"] for c in cands] == ["doc"]
        assert cands[0]["permission"] == "view"
        assert cands[0]["mean_sweep_depth"] == pytest.approx(8.0)

    def test_flat_schema_never_flagged(self):
        # even at absurd measured depth a flat footprint has no userset
        # cycle — a Leopard index cannot help it
        assert self._accounted(FLAT_SCHEMA, depth=50) == []

    def test_shallow_depth_not_flagged(self):
        assert self._accounted(
            NESTED_SCHEMA, depth=workload.LEOPARD_DEPTH - 1) == []


# -- sampling profiler --------------------------------------------------------


def _spin(stop):
    x = 0
    while not stop.is_set():
        for i in range(1000):
            x = (x * 31 + i) % 1000003
    return x


class TestProfiler:
    def test_capture_collapsed_stacks_and_trace(self):
        stop = threading.Event()
        t = threading.Thread(target=_spin, args=(stop,), name="spinner")
        t.start()
        try:
            out = profiler.capture(0.2)
        finally:
            stop.set()
            t.join()
        assert out["samples"] > 0
        assert out["threads"] >= 1
        assert out["collapsed"], "no collapsed stacks captured"
        # collapsed-stack format: "frame;frame;... count"
        stack, count = out["collapsed"][0].rsplit(" ", 1)
        assert ";" in stack or stack
        assert int(count) >= 1
        assert any("_spin" in line for line in out["collapsed"])
        evs = out["chrome_trace"]["traceEvents"]
        assert evs and evs[0]["ph"] == "X"

    def test_second_concurrent_capture_rejected(self):
        errs = []
        started = threading.Event()

        def long_capture():
            started.set()
            profiler.capture(0.5)

        t = threading.Thread(target=long_capture)
        t.start()
        started.wait()
        time.sleep(0.05)  # let it take the busy lock
        try:
            with pytest.raises(profiler.ProfilerBusy):
                profiler.capture(0.1)
        finally:
            t.join()
        assert not errs

    def test_gate_off_raises(self):
        GATES.set("Profiler", False)
        try:
            with pytest.raises(profiler.ProfilerDisabled):
                profiler.capture(0.1)
        finally:
            GATES.set("Profiler", True)

    def test_duration_clamped_to_hard_cap(self, monkeypatch):
        monkeypatch.setattr(profiler, "HARD_CAP_S", 0.2)
        t0 = time.perf_counter()
        out = profiler.capture(99.0)
        assert time.perf_counter() - t0 < 2.0
        assert out["seconds"] <= 0.5


# -- perf-regression sentinel -------------------------------------------------


def _benchdiff():
    spec = importlib.util.spec_from_file_location(
        "benchdiff", ROOT / "scripts" / "benchdiff.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _artifact(cal, medians, jitter=0.0):
    cfgs = {}
    for name, med in medians.items():
        per_round = [med * (1 + jitter * ((i % 3) - 1)) for i in range(5)]
        cfgs[name] = {"median_s": med, "per_round_s": per_round}
    return {"calibration_s": cal, "configs": cfgs}


class TestBenchdiff:
    def test_clean_comparison_passes(self):
        bd = _benchdiff()
        base = _artifact(0.01, {"a": 0.010, "b": 0.100})
        cur = _artifact(0.01, {"a": 0.011, "b": 0.095})
        v = bd.compare(base, cur)
        assert v["regressions"] == []
        assert all(not r["regression"] for r in v["rows"])

    def test_regression_named(self):
        bd = _benchdiff()
        base = _artifact(0.01, {"a": 0.010, "b": 0.100})
        cur = _artifact(0.01, {"a": 0.050, "b": 0.100})
        v = bd.compare(base, cur)
        assert v["regressions"] == ["a"]
        row = next(r for r in v["rows"] if r["config"] == "a")
        assert row["ratio"] == pytest.approx(5.0, rel=0.01)

    def test_calibration_normalizes_machine_speed(self):
        """A uniformly 2x-slower box (calibration AND medians doubled)
        is not a regression — the gate compares work per calibrated
        unit, not wall seconds."""
        bd = _benchdiff()
        base = _artifact(0.01, {"a": 0.010})
        cur = _artifact(0.02, {"a": 0.020})
        v = bd.compare(base, cur)
        assert v["regressions"] == []
        assert v["rows"][0]["ratio"] == pytest.approx(1.0)
        assert v["calibration_ratio"] == pytest.approx(2.0)

    def test_unpaired_configs_reported_not_failed(self):
        bd = _benchdiff()
        base = _artifact(0.01, {"a": 0.010, "gone": 0.005})
        cur = _artifact(0.01, {"a": 0.010, "new": 0.007})
        v = bd.compare(base, cur)
        assert v["regressions"] == []
        assert v["unpaired"] == ["gone", "new"]

    def test_noisy_runs_earn_wider_threshold(self):
        bd = _benchdiff()
        tight = bd.compare(_artifact(0.01, {"a": 0.01}),
                           _artifact(0.01, {"a": 0.01}))
        noisy = bd.compare(_artifact(0.01, {"a": 0.01}, jitter=0.4),
                           _artifact(0.01, {"a": 0.01}, jitter=0.4))
        assert noisy["rows"][0]["threshold"] > tight["rows"][0]["threshold"]
        assert tight["rows"][0]["threshold"] == bd.DEFAULT_FLOOR


class TestBenchdiffGate:
    def test_injected_slowdown_trips_gate(self):
        """The check.sh tripwire: an armed per-drain sleep MUST turn the
        cpu-microbench + --baseline gate red, naming the config."""
        env = dict(os.environ, SPICEDB_TPU_BENCHDIFF_INJECT_MS="25")
        proc = subprocess.run(
            [sys.executable, str(ROOT / "bench.py"),
             "--config", "cpu-microbench",
             "--baseline", str(ROOT / "scripts/benchdiff_baseline.json")],
            env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 1, proc.stderr
        assert "dispatch-check" in proc.stderr
        assert "REGRESSION" in proc.stderr
