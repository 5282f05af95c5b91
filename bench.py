#!/usr/bin/env python
"""Benchmark harness: authz checks/sec, jax:// kernel vs the python oracle.

Prints ONE JSON line on stdout, ALWAYS (a global watchdog and a top-level
exception handler both emit the line with an "error" field rather than
dying with a traceback):

  {"metric": ..., "value": N, "unit": "checks/s", "vs_baseline": N,
   "p99_list_filter_ms": N, "platform": ..., "baseline": "python-oracle", ...}

The headline config follows BASELINE.json config 5: filtering list requests
against a 1M-tuple multi-tenant depth-4 graph, 256 *concurrent list
requests* fused by the cross-request dispatcher (spicedb/dispatch.py) —
i.e. the exact path production `jax://` traffic takes.  The direct
batched-kernel number is reported alongside as `direct_batch_checks_per_s`.

Honesty note (VERDICT r2 weak-1): `vs_baseline` compares against THIS
repo's single-threaded pure-Python oracle evaluator — NOT the reference's
embedded Go SpiceDB, which cannot run in this image.  The payload carries
`baseline: "python-oracle"` and a `baseline_note` so nobody mistakes the
multiple for the BASELINE.md ">=50x vs embedded SpiceDB" target.

Device: the run is on the TPU, or on the CPU only when JAX_PLATFORMS=cpu
asks for it by name.  With neither, it exits non-zero; no path turns a
missing TPU into a CPU result.  Every payload names the platform.

All progress/diagnostics go to stderr; stdout carries only the JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

_T0 = time.time()
_STATE: dict = {"stage": "start", "partial": {}}
_EMITTED = threading.Event()

BASELINE_NOTE = (
    "vs_baseline compares against this repo's single-threaded pure-Python "
    "oracle evaluator, NOT the reference's embedded Go SpiceDB (not runnable "
    "in this image). The BASELINE.md '>=50x vs embedded SpiceDB' target is "
    "not established by this multiple."
)


def p99(times: list) -> float:
    """Nearest-rank p99: ceil(0.99*n)-th order statistic — for n < 100
    that is the max, never silently the p90."""
    import math
    return sorted(times)[math.ceil(0.99 * len(times)) - 1]


def log(msg: str) -> None:
    print(f"[{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def stage(name: str) -> None:
    _STATE["stage"] = name
    log(f"== stage: {name}")


def emit(payload: dict) -> None:
    """Print the one JSON line exactly once."""
    if _EMITTED.is_set():
        return
    _EMITTED.set()
    print(json.dumps(payload), flush=True)


def emit_error(msg: str) -> None:
    p = _STATE["partial"]
    out = {
        "metric": _STATE.get("metric", "authz checks/sec"),
        "value": p.get("value", 0.0),
        "unit": "checks/s",
        "vs_baseline": p.get("vs_baseline", 0.0),
        "p99_list_filter_ms": p.get("p99_list_filter_ms", 0.0),
        "platform": _STATE.get("platform", "unknown"),
        "baseline": "python-oracle",
        "error": f"{msg} (stage={_STATE['stage']})",
    }
    out.update({k: v for k, v in p.items() if k not in out})
    emit(out)


def start_watchdog(deadline_s: float) -> None:
    def fire():
        log(f"WATCHDOG: deadline {deadline_s:.0f}s exceeded at stage "
            f"{_STATE['stage']!r}; emitting partial result")
        emit_error(f"deadline {deadline_s:.0f}s exceeded")
        sys.stdout.flush()
        os._exit(0)

    t = threading.Timer(deadline_s, fire)
    t.daemon = True
    t.start()


def devtel_snapshot():
    """Cumulative device-telemetry counters (utils/devtel.py); None when
    the package (or jax) is unavailable so the bench never dies on it."""
    try:
        from spicedb_kubeapi_proxy_tpu.utils import devtel
        return devtel.snapshot()
    except Exception:
        return None


def devtel_delta(before):
    """End-of-run device-telemetry view for one config: HBM peak/by-kind
    bytes, recompile + jit-hit counts, mean batch occupancy, per-bucket
    kernel time — the numbers later kernel PRs are judged by."""
    after = devtel_snapshot()
    if before is None or after is None:
        return None
    from spicedb_kubeapi_proxy_tpu.utils import devtel
    return devtel.diff_snapshot(before, after)


def timeline_mark():
    """Monotonic mark delimiting one config's dispatch-timeline window;
    None when the package (or jax) is unavailable."""
    try:
        from spicedb_kubeapi_proxy_tpu.utils import timeline
        return timeline.now()
    except Exception:
        return None


def timeline_summary(mark):
    """End-of-run dispatch-timeline condensate for one config (overlap
    ratio, roofline fraction, stall-cause breakdown, worst-dispatch
    exemplar — utils/timeline.py): the numbers ROADMAP item 1's
    double-buffering work is judged by, riding every BENCH artifact."""
    try:
        from spicedb_kubeapi_proxy_tpu.utils import timeline
        if not timeline.enabled():
            return None
        return timeline.summary(since=mark)
    except Exception:
        return None


def timeline_headline(tl_sum) -> dict:
    """Promote the three device-resident-pipeline judgment numbers to
    headline columns (ISSUE 7): overlap ratio (0 = serialized), modeled
    roofline fraction, and the host transfer+transpose wall time the
    pipeline exists to hide.  Riding every config entry of every sweep
    artifact, so the BENCH trajectory shows the before/after directly
    instead of burying it inside timeline_summary."""
    if not tl_sum:
        return {}
    stage_ms = tl_sum.get("stage_ms") or {}
    return {
        "overlap_ratio": tl_sum.get("overlap_ratio"),
        "roofline_fraction": tl_sum.get("roofline_fraction"),
        "transfer_transpose_ms": round(
            stage_ms.get("transfer", 0.0) + stage_ms.get("transpose", 0.0),
            3),
    }


def build_endpoint(workload, kind: str):
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import EmbeddedEndpoint

    schema = sch.parse_schema(workload.schema_text)
    t0 = time.time()
    ep = (JaxEndpoint(schema) if kind == "jax" else EmbeddedEndpoint(schema))
    # columnar bulk path: native parse -> store base layer, no per-tuple
    # Python objects
    ep.store.bulk_load_text("\n".join(workload.relationships))
    log(f"loaded {len(workload.relationships)} relationship lines "
        f"in {time.time() - t0:.1f}s (columnar)")
    return ep


def warmup_tiny() -> None:
    """Compile + run the kernel on a tiny graph first: separates 'backend
    comes up / kernel compiles' from 'the 1M-tuple config is slow'."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.models import workloads as wl
    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef

    stage("tiny-warmup (graph build + first XLA compile)")
    t0 = time.time()
    workload = wl.pods_depth1(n_pods=64, n_users=8, n_tuples=256)
    ep = build_endpoint(workload, "jax")
    out = asyncio.run(ep.lookup_resources_batch(
        workload.resource_type, workload.permission,
        [SubjectRef("user", s) for s in workload.subjects[:8]]))
    log(f"tiny warmup ok in {time.time() - t0:.1f}s "
        f"(allowed sizes sample {[len(x) for x in out[:4]]})")


def bench_jax(workload, batch: int, rounds: int, ep=None) -> dict:
    """Direct batched-kernel path: one lookup_resources_batch per round."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef

    if ep is None:
        stage("jax graph build + load")
        ep = build_endpoint(workload, "jax")
    subjects = [s for s in workload.subjects]

    def batch_subjects(r):
        base = (r * batch) % max(1, len(subjects) - batch)
        return [SubjectRef("user", subjects[(base + i) % len(subjects)])
                for i in range(batch)]

    async def run():
        stage("jax warmup (real-config compile + first batch)")
        t0 = time.time()
        first = await ep.lookup_resources_batch(
            workload.resource_type, workload.permission, batch_subjects(0))
        warm = time.time() - t0
        n_obj = len(ep.store.object_ids_of_type(workload.resource_type))
        log(f"jax warmup {warm:.1f}s; {n_obj} objects of type "
            f"{workload.resource_type}; first batch allowed sizes sample "
            f"{[len(x) for x in first[:4]]}")
        stage("jax timed rounds (direct batch)")
        times = []
        for r in range(rounds):
            t0 = time.time()
            await ep.lookup_resources_batch(
                workload.resource_type, workload.permission,
                batch_subjects(r + 1))
            times.append(time.time() - t0)
            log(f"round {r + 1}/{rounds}: {times[-1] * 1000:.1f} ms")
        per_batch = statistics.median(times)
        checks = batch * n_obj
        return {
            "per_batch_s": per_batch,
            "p99_s": p99(times),
            "checks_per_s": checks / per_batch,
            "objects": n_obj,
            "warmup_s": warm,
            "endpoint": ep,
        }

    return asyncio.run(run())


def bench_concurrent(workload, batch: int, rounds: int) -> dict:
    """BASELINE config-5 shape (the HEADLINE): `batch` concurrent list
    requests, each issuing a single-subject LookupResources, fused by the
    cross-request dispatcher (spicedb/dispatch.py) into device batches —
    the exact path production `jax://` traffic takes."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import BatchingEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef

    stage("jax concurrent-dispatch build + load")
    inner = build_endpoint(workload, "jax")
    ep = BatchingEndpoint(inner)
    subjects = workload.subjects

    async def one_round(r):
        async def caller(i):
            s = SubjectRef("user", subjects[(r * batch + i) % len(subjects)])
            return await ep.lookup_resources(
                workload.resource_type, workload.permission, s)
        t0 = time.time()
        await asyncio.gather(*[caller(i) for i in range(batch)])
        return time.time() - t0

    async def run():
        stage("dispatcher warmup (compile + first fused round)")
        await one_round(0)
        stage("dispatcher timed rounds (concurrent list requests)")
        times = []
        for r in range(rounds):
            times.append(await one_round(r + 1))
            log(f"round {r + 1}/{rounds}: {times[-1] * 1000:.1f} ms")
        n_obj = len(ep.store.object_ids_of_type(workload.resource_type))
        per_round = statistics.median(times)
        log(f"dispatch stats: {ep.stats}")
        return {
            "per_round_s": per_round,
            "per_batch_s": per_round,
            "p99_s": p99(times),
            "checks_per_s": batch * n_obj / per_round,
            "objects": n_obj,
            "fused_lookups": ep.stats["fused_lookups"],
            "endpoint": inner,
        }

    return asyncio.run(run())


def bench_oracle(workload, queries: int) -> dict:
    import asyncio

    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef

    stage("oracle baseline build + load")
    ep = build_endpoint(workload, "embedded")

    async def run():
        n_obj = len(ep.store.object_ids_of_type(workload.resource_type))
        stage("oracle timed queries")
        times = []
        for i in range(queries):
            s = SubjectRef("user", workload.subjects[i % len(workload.subjects)])
            t0 = time.time()
            await ep.lookup_resources(workload.resource_type,
                                      workload.permission, s)
            times.append(time.time() - t0)
            log(f"oracle query {i + 1}/{queries}: {times[-1] * 1000:.0f} ms")
        per_query = statistics.median(times)
        return {
            "per_query_s": per_query,
            "checks_per_s": n_obj / per_query,
            "objects": n_obj,
        }

    return asyncio.run(run())


def roofline_probe(ep, workload, batch: int) -> dict:
    """Roofline/efficiency accounting for the ELL kernel (VERDICT r3 item
    4): measured device time + executed while_loop iterations + a bytes-
    moved MODEL per iteration -> modeled achieved HBM GB/s and fraction of
    the chip's peak.  The model counts, per iteration, each gather's
    output bytes (K reads of the packed state per table row) plus one
    state write and the gather-table reads; random-access amplification is
    NOT modeled, so the achieved number is a lower bound on true traffic.
    Also decomposes one lookup into device / transfer+unpack / id-
    materialize stages (the parts behind the reported p99)."""
    import jax.numpy as jnp
    import numpy as np

    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef

    with ep._lock:
        graph = ep._current_graph()
    if not hasattr(graph, "dev_main"):
        return {"skipped": "roofline probe needs the single-chip ELL graph"}
    prog = graph.prog
    rng_slot = prog.slot_range(workload.resource_type, workload.permission)
    subjects = [SubjectRef("user", workload.subjects[i % len(workload.subjects)])
                for i in range(batch)]
    with ep._lock:
        q_arr, cols, _ = ep._encode_subjects(graph, subjects)
    n_words = max(1, len(q_arr) // 32)
    kern = graph.kernel
    _, run_lookup, intro = kern._fns(n_words)
    if intro:
        # KernelIntrospect builds return (out, sweep_telemetry); the
        # probe times the raw jitted fn, so strip telemetry here
        _rl = run_lookup
        run_lookup = lambda *a: _rl(*a)[0]  # noqa: E731
    args = [rng_slot[0], rng_slot[1], jnp.asarray(q_arr),
            graph.dev_main, graph.dev_aux]
    if kern.planes:
        args.append(graph.dev_cav)
    import jax

    run_lookup(*args).block_until_ready()  # warm/compile
    # dispatch/sync round-trip floor: a trivial jitted op timed the same
    # way; subtracting it separates "kernel compute" from dispatch
    tiny = jax.jit(lambda v: v + 1)
    z = jnp.zeros(8, jnp.uint32)
    tiny(z).block_until_ready()
    r0 = time.perf_counter()
    tiny(z).block_until_ready()
    rtt = time.perf_counter() - r0

    # Detect-and-retime (VERDICT r4 item 7): repeat the forced-execution
    # timing until two consecutive measurements agree within tolerance;
    # record the residual disagreement as timing_confidence instead of
    # publishing a labeled guess.
    tol = 0.15
    samples = []
    for _i in range(6):
        t0 = time.perf_counter()
        run_lookup(*args).block_until_ready()
        samples.append(time.perf_counter() - t0)
        if (len(samples) >= 2
                and abs(samples[-1] - samples[-2]) / max(samples[-1],
                                                         samples[-2]) < tol):
            break
    device_s = (samples[-1] + samples[-2]) / 2 if len(samples) >= 2 \
        else samples[-1]
    timing_confidence = (1.0 - abs(samples[-1] - samples[-2])
                         / max(samples[-1], samples[-2])
                         if len(samples) >= 2 else 0.0)
    out = run_lookup(*args)
    t1 = time.perf_counter()
    # production extraction path: packed transpose + per-column word ops
    # (ops/jax_endpoint._lookup_batch_sync)
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import (
        _object_ids_np, _word_col_indices)
    packed = np.ascontiguousarray(out)
    packed_T = np.ascontiguousarray(packed.T)
    t2 = time.perf_counter()
    ids_np, _mask = _object_ids_np(graph, workload.resource_type)
    _ = [ids_np[_word_col_indices(packed_T[c // 32], c % 32)].tolist()
         for c in range(min(len(cols), 8))]  # sample of id materialization
    t3 = time.perf_counter()

    iters = kern.iterations(q_arr, n_words, graph.dev_main, graph.dev_aux,
                            graph.dev_cav if kern.planes else None)
    n = prog.state_size
    a = graph.dev_aux.shape[0]
    nt = n + a
    # fanin widths from the ACTUAL tables (K layout is env-tunable)
    k_main = int(graph.dev_main.shape[1])
    k_cav = int(graph.dev_cav.shape[1]) if kern.planes else 0
    w_total = 2 * n_words if kern.planes else n_words
    state_bytes = nt * w_total * 4
    # aux slots the compiled step gathers per outer iteration: each
    # slice once per pass of its stage (staged step), or the whole table
    # aux_passes times (Jacobi step)
    aux_slots = sum(kern.aux_refresh_slots().values())
    gather_bytes = 4 * w_total * (n * (k_main + 1) + aux_slots)
    if kern.planes:
        gather_bytes += 4 * w_total * nt * (k_cav + 1)
    table_bytes = 4 * (n * k_main + aux_slots
                       + (nt * k_cav if kern.planes else 0))
    per_iter = gather_bytes + 2 * state_bytes + table_bytes
    total_bytes = per_iter * max(iters, 1)
    from spicedb_kubeapi_proxy_tpu.utils.device import hbm_peak_gbps
    peak = hbm_peak_gbps(jax.devices()[0].device_kind)
    # device_s came from converged block_until_ready timing above.  When
    # the kernel is too small to separate from the dispatch round trip
    # (device_s - rtt within jitter), the net-of-rtt rates are
    # meaningless: null them instead of publishing absurd GB/s.
    compute_s = device_s - rtt
    rtt_dominated = compute_s < max(0.1 * device_s, 1e-4)
    compute_s = max(compute_s, 1e-6)
    achieved = total_bytes / max(device_s, 1e-6) / 1e9
    achieved_net = (None if rtt_dominated
                    else total_bytes / compute_s / 1e9)

    # Measured attainable floor for THIS access pattern: XLA's TPU
    # row-gather lowering costs a per-row constant independent of index
    # locality (scripts/probe_step_breakdown.py), so chip-peak HBM GB/s
    # is not reachable by any index layout.  Time one amortized gather
    # of the state shape and scale to the kernel's per-sweep gather
    # rows; kernel_vs_gather_floor ≈ 1 means the kernel is at the
    # lowering floor and further wins must cut sweeps or rows.
    idx_probe = jnp.arange(nt, dtype=jnp.int32)

    @jax.jit
    def _gather_loop(x):
        return jax.lax.fori_loop(
            0, 20, lambda i, v: v[idx_probe] + jnp.uint32(1), x)

    xs = jnp.zeros((nt, w_total), jnp.uint32)
    _gather_loop(xs).block_until_ready()
    g0 = time.perf_counter()
    _gather_loop(xs).block_until_ready()
    gather_pass_s = max((time.perf_counter() - g0 - rtt) / 20, 1e-9)
    ns_per_row = gather_pass_s / nt * 1e9
    # per-sweep gather rows: K_MAIN over state + aux refreshes
    sweep_rows = n * k_main + aux_slots
    floor_s = sweep_rows * (ns_per_row / 1e9) * max(iters, 1)
    return {
        "state_rows": nt,
        "state_bytes": state_bytes,
        "packed_words_per_plane": n_words,
        "bitplanes": 2 if kern.planes else 1,
        "iterations_executed": iters,
        "iteration_cap": kern.num_iters,
        "modeled_bytes_per_iteration": per_iter,
        "device_time_ms": round(device_s * 1e3, 3),
        "dispatch_rtt_ms": round(rtt * 1e3, 3),
        "kernel_compute_ms": round(compute_s * 1e3, 3),
        "rtt_dominated": rtt_dominated,
        "timing_basis": "block_until_ready, converged",
        "timing_confidence": round(timing_confidence, 3),
        "timing_samples_ms": [round(s * 1e3, 1) for s in samples],
        "kernel_transfer_pipeline_ms": round((t2 - t1) * 1e3, 3),
        # the pipeline window contains a full kernel execution; the
        # transfer estimate subtracts the separately-forced kernel time
        "transfer_est_ms": round(max((t2 - t1) - device_s, 0.0) * 1e3, 3),
        "id_materialize_sample_ms": round((t3 - t2) * 1e3, 3),
        "modeled_achieved_hbm_gbps": round(achieved, 2),
        "modeled_achieved_hbm_gbps_net_of_rtt": (
            round(achieved_net, 2) if achieved_net is not None else None),
        "hbm_peak_gbps": peak,
        "modeled_peak_fraction": (round(achieved / peak, 4)
                                  if peak else None),
        "modeled_peak_fraction_net_of_rtt": (
            round(achieved_net / peak, 4)
            if peak and achieved_net is not None else None),
        "gather_ns_per_row_measured": round(ns_per_row, 2),
        "gather_floor_ms": round(floor_s * 1e3, 3),
        "kernel_vs_gather_floor": round(compute_s / max(floor_s, 1e-9), 2),
        "model_note": ("bytes model counts gather outputs + state "
                       "read/write + table reads (lower bound). "
                       "modeled_peak_fraction vs chip HBM peak is NOT the "
                       "efficiency story: XLA's row-gather lowering costs "
                       "gather_ns_per_row regardless of locality (measured "
                       "in-situ), so gather_floor/kernel_vs_gather_floor is "
                       "the attainable-efficiency measure; dispatch_rtt (a "
                       "trivial-op round trip) is subtracted for net-of-rtt "
                       "numbers"),
    }


def sharded_comm_model(ep, workload, batch: int,
                       n_data: int = 2, n_graph: int = 4) -> dict:
    """Analytic per-iteration ICI traffic for the v5e-8 sharded layout
    (VERDICT r3 item 10), computed from the REAL headline graph's table
    shapes via the canonical model in parallel/sharding.py."""
    from spicedb_kubeapi_proxy_tpu.parallel.sharding import comm_model

    with ep._lock:
        graph = ep._current_graph()
    if not hasattr(graph, "dev_main"):
        return {"skipped": "needs the ELL graph"}
    out = comm_model(graph.prog.state_size, graph.dev_aux.shape[0],
                     n_data, n_graph, batch,
                     planes=bool(getattr(graph, "has_cav", False)),
                     aux_passes=getattr(graph.kernel, "aux_passes", 1))
    out["note"] = ("per-iteration tiled all_gather over ICI reassembles "
                   "row blocks; measured wall time for this layout is "
                   "recorded by dryrun_multichip (MULTICHIP artifact)")
    return out


def v5e8_projection(ep, workload, batch: int, roofline: dict) -> dict:
    """Predicted v5e-8 throughput from the measured single-chip roofline
    (VERDICT r4 item 4) — formula + inputs recorded in the artifact."""
    from spicedb_kubeapi_proxy_tpu.parallel.sharding import (
        predict_v5e8_checks_per_s)

    with ep._lock:
        graph = ep._current_graph()
    if not hasattr(graph, "dev_main") or "kernel_compute_ms" not in roofline:
        return {"skipped": "needs the ELL graph + a measured roofline"}
    iters = max(roofline.get("iterations_executed", 1), 1)
    iter_s = roofline["kernel_compute_ms"] / 1e3 / iters
    # fixed overhead: extraction + dispatch; D2H of the packed result
    # modeled at 8 GB/s PCIe
    n_words = roofline.get("packed_words_per_plane", 8)
    d2h_s = workload.expected_objects * n_words * 4 / 8e9
    fixed = d2h_s + roofline.get("id_materialize_sample_ms", 0) / 1e3
    return predict_v5e8_checks_per_s(
        graph.prog.state_size, graph.dev_aux.shape[0], 2, 4, batch,
        objects=workload.expected_objects,
        single_chip_iter_s=iter_s, iters=iters,
        planes=bool(getattr(graph, "has_cav", False)),
        aux_passes=getattr(graph.kernel, "aux_passes", 1),
        fixed_overhead_s=fixed)


def _cache_chain(workload, cache_on: bool):
    """Production proxy-chain wiring for the cache benches:
    jax:// -> BatchingEndpoint -> (DecisionCacheEndpoint when on)."""
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.decision_cache import (
        DecisionCacheEndpoint)
    from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import BatchingEndpoint

    schema = sch.parse_schema(workload.schema_text)
    inner = JaxEndpoint(schema)
    inner.store.bulk_load_text("\n".join(workload.relationships))
    ep = BatchingEndpoint(inner)
    if cache_on:
        ep = DecisionCacheEndpoint(ep)
    return ep, inner


def _cache_workload():
    from spicedb_kubeapi_proxy_tpu.models import workloads as wl
    return wl.pods_depth1(n_pods=10_000, n_users=100, n_tuples=30_000)


def bench_warm_repeat_list(args) -> dict:
    """Decision-cache headline: the SAME user lists 10k pods N times
    (no interleaved writes), cache on vs off — the repeat-list is the
    production hot path the cache exists for.  Reports proxy-chain
    filter throughput both ways plus the on/off speedup (acceptance:
    >=5x) and the cache hit rate."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef

    workload = _cache_workload()
    lists = 32
    subject = SubjectRef("user", workload.subjects[0])
    out = {}
    for cache_on in (False, True):
        label = "on" if cache_on else "off"
        stage(f"warm-repeat-list cache={label}")
        ep, inner = _cache_chain(workload, cache_on)

        async def run():
            # warmup: compile + first frontier (and the one cache fill)
            first = await ep.lookup_resources(
                workload.resource_type, workload.permission, subject)
            t0 = time.time()
            for _ in range(lists):
                got = await ep.lookup_resources(
                    workload.resource_type, workload.permission, subject)
            elapsed = time.time() - t0
            assert sorted(got) == sorted(first)
            return len(first), elapsed

        n_allowed, elapsed = asyncio.run(run())
        n_obj = workload.expected_objects
        out[f"cache_{label}_lists_per_s"] = round(lists / elapsed, 2)
        out[f"cache_{label}_checks_per_s"] = round(
            lists * n_obj / elapsed, 1)
        if cache_on:
            st = ep.cache.stats
            probes = st["hits"] + st["misses"]
            out["hit_rate"] = round(st["hits"] / max(probes, 1), 4)
        log(f"warm-repeat-list cache={label}: "
            f"{lists / elapsed:.1f} lists/s ({n_allowed} allowed ids)")
    out["speedup"] = round(out["cache_on_lists_per_s"]
                           / max(out["cache_off_lists_per_s"], 1e-9), 2)
    out["objects"] = workload.expected_objects
    log(f"warm-repeat-list speedup (on/off): {out['speedup']}x "
        f"(acceptance >=5x), hit rate {out.get('hit_rate')}")
    return out


def bench_delta_churn(args) -> dict:
    """Decision-cache correctness under interleaved writes: every round
    commits a write (touch/delete of viewer tuples), then the cache-on
    chain's lookups are refereed against the host oracle over the SAME
    store.  Divergences must be zero; the hit rate shows the
    relation-scoped invalidation keeping unrelated entries warm."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
    from spicedb_kubeapi_proxy_tpu.spicedb.types import (
        RelationshipUpdate, SubjectRef, UpdateOp, parse_relationship)

    workload = _cache_workload()
    stage("delta-churn build")
    ep, inner = _cache_chain(workload, cache_on=True)
    oracle = Evaluator(inner.schema, inner.store)
    rounds = 12
    subjects = [SubjectRef("user", workload.subjects[i % len(workload.subjects)])
                for i in range(3)]
    divergences = 0

    async def run():
        nonlocal divergences
        stage("delta-churn warmup")
        for s in subjects:
            await ep.lookup_resources(workload.resource_type,
                                      workload.permission, s)
        stage("delta-churn rounds (interleaved writes)")
        chain_s = 0.0
        n_lists = 0
        for r in range(rounds):
            op = UpdateOp.TOUCH if r % 2 == 0 else UpdateOp.DELETE
            rel = parse_relationship(
                f"pod:p{r % 7}#viewer@user:{workload.subjects[0]}")
            await ep.write_relationships([RelationshipUpdate(op=op, rel=rel)])
            for s in subjects:
                # the revision is frozen between writes: one oracle
                # frontier referees BOTH passes (pass 2 serves
                # unchanged-footprint entries from cache)
                want = sorted(oracle.lookup_resources(
                    workload.resource_type, workload.permission, s))
                for _pass in range(2):
                    t0 = time.time()
                    got = sorted(await ep.lookup_resources(
                        workload.resource_type, workload.permission, s))
                    chain_s += time.time() - t0
                    n_lists += 1
                    if got != want:
                        divergences += 1
        return n_lists, chain_s

    n_lists, elapsed = asyncio.run(run())
    st = ep.cache.stats
    probes = st["hits"] + st["misses"]
    out = {
        "divergences": divergences,
        "rounds": rounds,
        "lists_per_s": round(n_lists / elapsed, 2),
        "hit_rate": round(st["hits"] / max(probes, 1), 4),
        "invalidations": st["invalidations"],
    }
    log(f"delta-churn: {divergences} divergences over {n_lists} refereed "
        f"lists, hit rate {out['hit_rate']}, "
        f"{st['invalidations']} invalidations")
    return out


def bench_recovery(args) -> dict:
    """Durable-store restart cost (ISSUE 4): time-to-serve after a
    restart at 1M tuples — checkpoint load + WAL tail replay + warm
    graph rebuild, measured separately and summed — plus the WAL-on vs
    WAL-off write-path overhead (the price every live write pays for
    durability)."""
    import asyncio
    import shutil
    import tempfile

    from spicedb_kubeapi_proxy_tpu.models import workloads as wl
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.persist import PersistenceManager
    from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore
    from spicedb_kubeapi_proxy_tpu.spicedb.types import (
        CheckRequest,
        RelationshipUpdate,
        SubjectRef,
        UpdateOp,
        parse_relationship,
    )

    workload = wl.multitenant_1m()
    rel_text = "\n".join(workload.relationships)
    write_rounds, batch = 50, 50
    tail_rounds = 25

    def churn_batch(i):
        # touch/delete EXISTING workload tuples so every write is
        # schema-valid and the device graph replays them cleanly
        ups = []
        for j in range(batch):
            line = workload.relationships[(i * batch + j)
                                          % len(workload.relationships)]
            op = UpdateOp.DELETE if (i + j) % 2 else UpdateOp.TOUCH
            ups.append(RelationshipUpdate(op, parse_relationship(line)))
        return ups

    def time_writes(store, rounds, start=0):
        t0 = time.time()
        for i in range(start, start + rounds):
            store.write(churn_batch(i))
        return time.time() - t0

    tmp = tempfile.mkdtemp(prefix="persist-bench-")
    out = {"tuples": len(workload.relationships)}
    try:
        stage("recovery: seed + journal (WAL on)")
        mgr = PersistenceManager(tmp, fsync="interval")
        store = mgr.recover()
        mgr.attach(store)
        store.bulk_load_text(rel_text)
        wal_on_s = time_writes(store, write_rounds)
        stage("recovery: checkpoint + WAL tail")
        mgr.checkpoint()
        time_writes(store, tail_rounds, start=write_rounds)
        seed_revision = store.revision
        mgr.close()

        stage("recovery: WAL-off write baseline")
        bare = TupleStore()
        bare.bulk_load_text(rel_text)
        wal_off_s = time_writes(bare, write_rounds)
        del bare

        stage("recovery: restart (checkpoint + tail replay)")
        mgr2 = PersistenceManager(tmp, fsync="interval")
        recovered = mgr2.recover()
        assert recovered.revision == seed_revision
        info = mgr2.recovery_info

        stage("recovery: warm graph rebuild")
        schema = sch.parse_schema(workload.schema_text)
        ep = JaxEndpoint(schema, store=recovered)
        probe = next(parse_relationship(line)
                     for line in workload.relationships
                     if line.startswith(workload.resource_type + ":"))
        t0 = time.time()
        ep.warm_start()
        # first kernel answer = "serving": includes jit compile
        asyncio.run(ep.check_permission(CheckRequest(
            probe.resource, workload.permission,
            SubjectRef("user", workload.subjects[0]))))
        rebuild_s = time.time() - t0

        out.update({
            "checkpoint_load_s": info["checkpoint_load_s"],
            "wal_replay_s": info["wal_replay_s"],
            "wal_tail_records": info["replayed_records"],
            "graph_rebuild_s": round(rebuild_s, 3),
            "time_to_serve_s": round(
                info["total_s"] + rebuild_s, 3),
            "wal_on_batch_ms": round(wal_on_s / write_rounds * 1e3, 3),
            "wal_off_batch_ms": round(wal_off_s / write_rounds * 1e3, 3),
            "wal_overhead_pct": round(
                (wal_on_s - wal_off_s) / max(wal_off_s, 1e-9) * 100, 1),
        })
        log(f"recovery: time-to-serve {out['time_to_serve_s']}s at "
            f"{out['tuples']} tuples (ckpt {out['checkpoint_load_s']}s + "
            f"replay {out['wal_replay_s']}s [{out['wal_tail_records']} "
            f"records] + rebuild {out['graph_rebuild_s']}s); WAL write "
            f"overhead {out['wal_overhead_pct']}% "
            f"({out['wal_on_batch_ms']} vs {out['wal_off_batch_ms']} "
            f"ms/batch)")
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_pipeline_depth(args) -> dict:
    """Device-resident pipeline A/B (ISSUE 7): the headline 1M-tuple
    10k-pod concurrent-list shape, run with the DevicePipeline gate OFF
    (the exact pre-PR host-pack serial path) and then gate ON at
    dispatch depths 1, 2, and 4.  Each mode records checks/s, the
    overlap ratio, and the stall{pack|transpose|transfer} attribution,
    so the BENCH artifact carries the before/after for ROADMAP item 1
    directly: `stall_reduction_x` = (host-pack pack+transpose+transfer
    stall) / (depth-2 same), `checks_per_s_gain` = depth-2 / host-pack
    throughput."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.models import workloads as wl
    from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import BatchingEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef
    from spicedb_kubeapi_proxy_tpu.utils.features import GATES

    stage("pipeline-depth sweep build + load (multitenant-1m)")
    workload = wl.multitenant_1m()
    inner = build_endpoint(workload, "jax")
    batch = args.batch
    rounds = max(3, args.rounds // 2)
    subjects = workload.subjects
    # max_batch splits each round into ~4 fused batches: with one
    # monolithic batch per round the drain has nothing to keep in
    # flight and every depth degenerates to serial
    max_batch = max(1, batch // 4)
    modes = (
        ("host-pack", False, 1),   # gate off: pre-PR serial baseline
        ("depth-1", True, 1),      # device pack, serial dispatch
        ("depth-2", True, 2),      # the --pipeline-depth default
        ("depth-4", True, 4),
    )
    out: dict = {"modes": {}, "batch": batch, "rounds": rounds,
                 "max_batch": max_batch}
    eps = {name: BatchingEndpoint(inner, max_batch=max_batch,
                                  pipeline_depth=depth)
           for name, _gate, depth in modes}
    acc = {name: {"times": [], "stall": {}, "transfer_s": 0.0,
                  "overlap_s": 0.0, "tt_ms": 0.0}
           for name, _gate, _depth in modes}

    async def one_round(ep, r):
        async def caller(i):
            s = SubjectRef(
                "user", subjects[(r * batch + i) % len(subjects)])
            return await ep.lookup_resources(
                workload.resource_type, workload.permission, s)
        t0 = time.time()
        await asyncio.gather(*[caller(i) for i in range(batch)])
        return time.time() - t0

    try:
        # interleaved A/B (the same methodology the gate-off parity
        # claim uses): mode order rotates inside every round, so
        # allocator drift / process aging lands on all modes equally
        # instead of flattering whichever ran first
        stage("pipeline-depth interleaved rounds")
        for name, gate, _depth in modes:
            GATES.set("DevicePipeline", gate)
            asyncio.run(one_round(eps[name], 0))  # warm: compiles+arenas
        for r in range(rounds):
            for name, gate, _depth in modes:
                GATES.set("DevicePipeline", gate)
                mark = timeline_mark()
                dt = asyncio.run(one_round(eps[name], r + 1))
                tl = timeline_summary(mark) or {}
                a = acc[name]
                a["times"].append(dt)
                for cause, v in (tl.get("stall_s") or {}).items():
                    a["stall"][cause] = a["stall"].get(cause, 0.0) + v
                ov = tl.get("overlap") or {}
                a["transfer_s"] += ov.get("transfer_s", 0.0)
                a["overlap_s"] += ov.get("overlap_s", 0.0)
                a["tt_ms"] += timeline_headline(tl).get(
                    "transfer_transpose_ms", 0.0)
    finally:
        GATES.set("DevicePipeline", True)

    n_obj = len(inner.store.object_ids_of_type(workload.resource_type))
    for name, _gate, _depth in modes:
        a = acc[name]
        per_round = statistics.median(a["times"])
        host_stall = (a["stall"].get("pack", 0.0)
                      + a["stall"].get("transpose", 0.0)
                      + a["stall"].get("transfer", 0.0))
        mode = {
            "checks_per_s": round(batch * n_obj / per_round, 1),
            "per_round_ms": round(per_round * 1e3, 2),
            "p99_ms": round(p99(a["times"]) * 1e3, 2),
            "stall_s": {c: round(v, 6) for c, v in sorted(
                a["stall"].items())},
            "stall_pack_transpose_transfer_s": round(host_stall, 6),
            "overlap_ratio": (round(a["overlap_s"] / a["transfer_s"], 4)
                              if a["transfer_s"] > 0 else None),
            "transfer_transpose_ms": round(a["tt_ms"], 3),
        }
        out["modes"][name] = mode
        log(f"pipeline {name}: {mode['checks_per_s']:.3g} checks/s, "
            f"overlap={mode.get('overlap_ratio')}, "
            f"host stalls={mode['stall_pack_transpose_transfer_s']}s")
    base = out["modes"].get("host-pack", {})
    d2 = out["modes"].get("depth-2", {})
    if base and d2:
        denom = max(d2.get("stall_pack_transpose_transfer_s") or 0.0, 1e-9)
        out["stall_reduction_x"] = round(
            (base.get("stall_pack_transpose_transfer_s") or 0.0) / denom, 2)
        out["checks_per_s_gain"] = round(
            d2["checks_per_s"] / max(base["checks_per_s"], 1e-9), 3)
        log(f"pipeline-depth: stall reduction "
            f"{out['stall_reduction_x']}x, checks/s gain "
            f"{out['checks_per_s_gain']}x (depth-2 vs host-pack)")
    return out


REPLICA_WORKER_SPEC = {
    "n_pods": 10_000, "n_users": 100, "n_tuples": 30_000,
    "lookup_batch": 32, "measure_s": 4.0,
}


def replica_worker(spec_json: str) -> None:
    """`bench.py --replica-worker <spec-json>` subprocess: one follower
    tailing the leader's replication API over real HTTP and serving
    batched filtered-list reads from its own device graph.  Protocol
    on stdio: print READY after warm; each `RUN` line on stdin runs one
    measured window and prints `DONE <json>`; `EXIT` quits.  A separate
    process per follower is the point — N proxy replicas behind a load
    balancer are separate processes, and the GIL would serialize
    in-process reader threads into an anti-measurement."""
    import asyncio

    spec = json.loads(spec_json)
    from spicedb_kubeapi_proxy_tpu.models import workloads as wl
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
    from spicedb_kubeapi_proxy_tpu.proxy.httpcore import H11Transport
    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.replication import ReplicaFollower
    from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore
    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef

    workload = wl.pods_depth1(n_pods=spec["n_pods"],
                              n_users=spec["n_users"],
                              n_tuples=spec["n_tuples"])
    schema = sch.parse_schema(workload.schema_text)
    store = TupleStore()
    repl = ReplicaFollower(store, H11Transport(spec["leader"]),
                           identity=spec["identity"])
    ep = JaxEndpoint(schema, store=store)
    lookup_batch = spec["lookup_batch"]

    def subjects(base):
        return [SubjectRef("user", workload.subjects[
            (base + k) % len(workload.subjects)])
            for k in range(lookup_batch)]

    async def measured_window(seconds: float) -> dict:
        await repl.sync_once()  # catch up the backlog untimed
        lists = 0
        lags: list = []
        base = 0
        stop = asyncio.Event()

        async def tail():
            # the tail runs CONCURRENTLY with reads, exactly like the
            # server's follower task — reads never block on leader RTT.
            # Lag is sampled just BEFORE each sync: the staleness a
            # read arriving at that moment would actually observe.
            while not stop.is_set():
                lags.append(repl.lag_revisions())
                try:
                    await repl.sync_once()
                except Exception:
                    pass  # transient leader hiccup; lag keeps counting
                await asyncio.sleep(0.05)

        tail_task = asyncio.ensure_future(tail())
        t0 = time.time()
        while time.time() - t0 < seconds:
            await ep.lookup_resources_batch(
                workload.resource_type, workload.permission,
                subjects(base))
            base += lookup_batch
            lists += lookup_batch
        elapsed = time.time() - t0
        stop.set()
        await tail_task
        lags.sort()

        def pct(p):
            return (float(lags[min(len(lags) - 1, int(p * len(lags)))])
                    if lags else 0.0)

        return {"lists": lists, "elapsed_s": round(elapsed, 3),
                "lists_per_s": round(lists / elapsed, 1),
                "lag_p50": pct(0.5), "lag_p99": pct(0.99),
                "lag_samples": len(lags),
                "applied_records": repl.stats["applied_records"]}

    async def main_loop():
        await repl.sync_once()
        await ep.lookup_resources_batch(
            workload.resource_type, workload.permission, subjects(0))
        print("READY", flush=True)
        loop = asyncio.get_running_loop()
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            if not line or line.strip() == "EXIT":
                return
            if line.strip() == "RUN":
                res = await measured_window(spec["measure_s"])
                print("DONE " + json.dumps(res), flush=True)

    asyncio.run(main_loop())


def bench_replica_scale(args) -> dict:
    """WAL-shipping read-replica scaling (ISSUE 9): one leader taking
    write churn, its WAL served over real localhost HTTP by the
    replication hub, and N follower PROCESSES (replica_worker above —
    one process per replica, as deployed) each bootstrapping, tailing,
    and serving batched filtered-list reads from its own device graph.
    Reports aggregate filtered-list throughput at 1/2/4 followers plus
    per-follower lag percentiles; headline column
    `replica_read_scaling` = 2-follower aggregate over 1-follower
    (acceptance >= 1.7x on CPU — note the hardware ceiling: aggregate
    scaling cannot exceed the machine's core count)."""
    import asyncio
    import shutil
    import tempfile

    from spicedb_kubeapi_proxy_tpu.models import workloads as wl
    from spicedb_kubeapi_proxy_tpu.proxy.httpcore import (
        HttpServer,
        json_response,
    )
    from spicedb_kubeapi_proxy_tpu.utils.topology import (
        WorkerFleet,
        cpu_pair_ceiling,
    )
    from spicedb_kubeapi_proxy_tpu.spicedb.persist import PersistenceManager
    from spicedb_kubeapi_proxy_tpu.spicedb.replication import ReplicationHub
    from spicedb_kubeapi_proxy_tpu.spicedb.types import (
        RelationshipUpdate,
        UpdateOp,
        parse_relationship,
    )

    spec = dict(REPLICA_WORKER_SPEC)
    fleet_sizes = (1, 2, 4)
    workload = wl.pods_depth1(n_pods=spec["n_pods"],
                              n_users=spec["n_users"],
                              n_tuples=spec["n_tuples"])

    tmp = tempfile.mkdtemp(prefix="replica-bench-")
    stage("replica-scale: leader build + journal")
    mgr = PersistenceManager(tmp, fsync="never")
    leader_store = mgr.recover()
    mgr.attach(leader_store)
    leader_store.bulk_load_text("\n".join(workload.relationships))
    hub = ReplicationHub(leader_store, mgr)
    hub.attach()

    async def hub_handler(req):
        path = req.path
        if path == "/replication/manifest":
            return await hub.serve_manifest(req)
        if path.startswith("/replication/segment/"):
            return await hub.serve_segment(req, path.rsplit("/", 1)[1])
        if path.startswith("/replication/checkpoint/"):
            return await hub.serve_checkpoint(req, path.rsplit("/", 1)[1])
        return json_response(404, {"message": f"unknown {path}"})

    # leader HTTP serving + churn run on a dedicated thread's loop so
    # the measured follower processes see a live leader throughout
    ready = threading.Event()
    stop = threading.Event()
    port_box: dict = {}

    def leader_thread():
        async def run():
            server = HttpServer(hub_handler)
            port_box["port"] = await server.start("127.0.0.1", 0)
            ready.set()
            # ~50 writes/s of churn: enough to keep every follower's
            # tail busy without the (unpinned) leader thread eating the
            # fixed per-replica core budgets it is refereeing
            i = 0
            while not stop.is_set():
                line = workload.relationships[
                    i % len(workload.relationships)]
                op = UpdateOp.DELETE if i % 2 else UpdateOp.TOUCH
                leader_store.write([RelationshipUpdate(
                    op, parse_relationship(line))])
                i += 1
                await asyncio.sleep(0.02)
            await server.stop()

        asyncio.run(run())

    lt = threading.Thread(target=leader_thread, daemon=True)
    lt.start()
    ready.wait(10)
    leader_url = f"http://127.0.0.1:{port_box['port']}"

    out: dict = {"fleet": {}, "measure_s": spec["measure_s"],
                 "lookup_batch": spec["lookup_batch"],
                 "tuples": len(workload.relationships),
                 "cores": os.cpu_count()}
    # fixed per-replica CPU budget (1 core, single-threaded XLA) via
    # the shared harness: production replicas are separate nodes, so
    # the scaling claim is "aggregate throughput grows as replicas are
    # added at a constant per-replica budget" — without the pin, one
    # XLA intra-op pool eats every local core and the baseline is
    # already machine-saturated, measuring contention, not scaling
    fleet = WorkerFleet(name="replica-scale")
    try:
        stage(f"replica-scale: spawn + warm {max(fleet_sizes)} follower "
              f"processes")
        for i in range(max(fleet_sizes)):
            wspec = dict(spec, leader=leader_url, identity=f"replica-{i}")
            fleet.spawn(
                [sys.executable, os.path.abspath(__file__),
                 "--replica-worker", json.dumps(wspec)],
                pin=i, label=f"replica-{i}")
        fleet.wait_ready()

        def window(n):
            return fleet.run_window(n)

        # interleaved rounds, median per fleet size (same methodology
        # as the pipeline-depth A/B): this box's background load drifts
        # minute to minute, and sequential one-shot windows would hand
        # whichever fleet size ran during a quiet patch a fake win
        rounds = 3
        acc: dict = {n: [] for n in fleet_sizes}
        for r in range(rounds):
            for n in fleet_sizes:
                stage(f"replica-scale round {r + 1}/{rounds}: {n} "
                      f"follower process(es) under churn")
                acc[n].append(window(n))
        for n in fleet_sizes:
            aggs = [sum(res["lists_per_s"] for res in results)
                    for results in acc[n]]
            agg = statistics.median(aggs)
            flat = [res for results in acc[n] for res in results]
            lag_p50 = statistics.median(res["lag_p50"] for res in flat)
            lag_p99 = max(res["lag_p99"] for res in flat)
            out["fleet"][str(n)] = {
                "aggregate_lists_per_s": round(agg, 1),
                "aggregate_lists_per_s_rounds": [round(a, 1)
                                                 for a in aggs],
                "aggregate_checks_per_s": round(
                    agg * workload.expected_objects, 1),
                "per_follower_lists_per_s": round(agg / n, 1),
                "lag_revisions_p50": lag_p50,
                "lag_revisions_p99": lag_p99,
                "lag_samples": sum(res["lag_samples"] for res in flat),
            }
            log(f"replica-scale n={n}: {agg:.1f} lists/s aggregate "
                f"(median of {aggs}), lag p50/p99 = "
                f"{lag_p50}/{lag_p99} revisions")
    finally:
        fleet.shutdown()
        stop.set()
        lt.join(10)
        mgr.close()
        shutil.rmtree(tmp, ignore_errors=True)

    stage("replica-scale: CPU pair-scaling ceiling probe")
    out["cpu_pair_scaling_ceiling"] = cpu_pair_ceiling()

    # scaling is estimated from PAIRED per-round ratios (windows inside
    # one round are adjacent in time), because ambient load on a shared
    # box drifts across rounds by more than the effect being measured;
    # the n=1 round spread is recorded so a reader can judge the noise
    base_rounds = [sum(res["lists_per_s"] for res in results)
                   for results in acc[1]]
    out["noise_spread_1x"] = round(
        max(base_rounds) / max(min(base_rounds), 1e-9), 2)
    for n in fleet_sizes[1:]:
        ratios = [
            sum(res["lists_per_s"] for res in results) / max(b, 1e-9)
            for results, b in zip(acc[n], base_rounds)]
        out[f"scaling_{n}x"] = round(statistics.median(ratios), 2)
        out[f"scaling_{n}x_rounds"] = [round(r, 2) for r in ratios]
    out["replica_read_scaling"] = out.get("scaling_2x", 0.0)
    ceiling = out["cpu_pair_scaling_ceiling"]
    out["replica_read_scaling_normalized"] = round(
        out["replica_read_scaling"] / max(ceiling, 1e-9), 2)
    log(f"replica-scale: read scaling at 2 followers = "
        f"{out['replica_read_scaling']}x raw (acceptance >= 1.7x on >=2 "
        f"free cores), {out['replica_read_scaling_normalized']}x of this "
        f"box's measured pair ceiling {ceiling}x; at 4 = "
        f"{out.get('scaling_4x')}x on {out['cores']} cores "
        f"(n=1 round noise spread {out['noise_spread_1x']}x)")
    return out


# -- partitioned write scale-out (ISSUE 15) -----------------------------------

# four independent co-location classes — (kube resource, namespace-like
# parent type, tuple type) — so a 4-shard partition map can spread them
# 1:1 and a 2-shard map packs two classes per shard.  Every class is
# symmetric: the per-class dual-write cost is identical, so aggregate
# throughput differences between fleet sizes measure sharding, not
# workload skew.
SHARD_CLASSES = (
    ("pods", "podns", "pod"),
    ("configmaps", "cfgns", "configmap"),
    ("secrets", "secns", "secret"),
    ("services", "svcns", "service"),
)

SHARD_SCHEMA = "definition user {}\n" + "\n".join(
    f"definition {t} {{\n  relation creator: user\n"
    f"  permission view = creator\n}}"
    for _res, ns, typ in SHARD_CLASSES for t in (ns, typ))

_SHARD_RULE_TPL = """\
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata: {{name: create-{res}}}
match: [{{apiVersion: v1, resource: {res}, verbs: [create]}}]
lock: Optimistic
check: [{{tpl: "{ns}:{{{{namespace}}}}#view@user:{{{{user.name}}}}"}}]
update:
  creates:
  - tpl: "{typ}:{{{{namespacedName}}}}#creator@user:{{{{user.name}}}}"
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata: {{name: delete-{res}}}
match: [{{apiVersion: v1, resource: {res}, verbs: [delete]}}]
lock: Optimistic
update:
  deleteByFilter:
  - tpl: "{typ}:{{{{namespacedName}}}}#$resourceRelation@$subjectType:$subjectID"
"""

SHARD_RULES = "\n---\n".join(
    _SHARD_RULE_TPL.format(res=res, ns=ns, typ=typ)
    for res, ns, typ in SHARD_CLASSES)

SHARD_WORKER_SPEC = {
    "measure_s": 4.0, "inflight": 6, "wal_fsync": "always",
}


def shard_leader_worker(spec_json: str) -> None:
    """`bench.py --shard-worker <spec-json>` subprocess: ONE shard
    leader — an unmodified embedded proxy (rules engine, dual-write
    workflow engine, its own WAL under `data_dir` with the spec'd fsync
    policy) taking kube-style create/delete dual-writes through the
    in-process client, exactly the per-shard write path behind the
    router (spicedb/sharding/router.py).  Protocol on stdio: READY
    after warm; each `RUN {"tag":..,"resources":[..]}` line runs one
    measured churn window over those resources and prints
    `DONE <json>`; `EXIT` quits.  A separate pinned process per shard
    leader is the point: each has its own GIL, event loop, and WAL —
    the deployment unit the partition map scales."""
    import asyncio

    spec = json.loads(spec_json)
    from spicedb_kubeapi_proxy_tpu.kubefake.apiserver import FakeKubeApiServer
    from spicedb_kubeapi_proxy_tpu.proxy.httpcore import HandlerTransport
    from spicedb_kubeapi_proxy_tpu.proxy.server import Options, ProxyServer
    from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import Bootstrap
    from spicedb_kubeapi_proxy_tpu.spicedb.types import parse_relationship

    kube = FakeKubeApiServer()
    kube.seed("", "v1", "namespaces", {"metadata": {"name": "team-a"}})
    opts = Options(
        spicedb_endpoint="embedded://",
        bootstrap=Bootstrap(schema_text=SHARD_SCHEMA),
        rules_yaml=SHARD_RULES,
        upstream_transport=HandlerTransport(kube),
        workflow_database_path="",  # in-memory dual-write journal
    )
    opts.data_dir = spec["data_dir"]
    opts.wal_fsync = spec["wal_fsync"]
    proxy = ProxyServer(opts)
    if proxy.endpoint.store.revision == 0:
        proxy.endpoint.store.bulk_load([
            parse_relationship(f"{ns}:team-a#creator@user:alice")
            for _res, ns, _typ in SHARD_CLASSES])
    proxy.enable_dual_writes()
    client = proxy.get_embedded_client(user="alice")
    ident = spec["identity"]

    async def one_create(res: str, name: str) -> float:
        t0 = time.perf_counter()
        resp = await client.post(
            f"/api/v1/namespaces/team-a/{res}",
            {"apiVersion": "v1", "metadata": {"name": name,
                                              "namespace": "team-a"}})
        assert resp.status in (200, 201), (res, name, resp.status,
                                           resp.body)
        return time.perf_counter() - t0

    async def one_delete(res: str, name: str) -> float:
        t0 = time.perf_counter()
        resp = await client.delete(
            f"/api/v1/namespaces/team-a/{res}/{name}")
        assert resp.status in (200, 404), (res, name, resp.status,
                                           resp.body)
        return time.perf_counter() - t0

    async def window(tag: str, resources: list, seconds: float) -> dict:
        lat: list = []
        done = 0
        deadline = time.perf_counter() + seconds

        async def loop(lane: int):
            nonlocal done
            i = 0
            recent: list = []
            while time.perf_counter() < deadline:
                res = resources[i % len(resources)]
                # churn profile: 3 creates then a delete of the oldest
                # pending create — bounded store growth, both dual-write
                # verbs (create = check + precondition + create tuple;
                # delete = delete-by-filter), unique names across
                # windows via the round tag
                if len(recent) >= 3:
                    lat.append(await one_delete(*recent.pop(0)))
                else:
                    name = f"{ident}-{tag}-l{lane}-{i}"
                    lat.append(await one_create(res, name))
                    recent.append((res, name))
                done += 1
                i += 1

        t0 = time.perf_counter()
        await asyncio.gather(*(loop(k) for k in range(spec["inflight"])))
        elapsed = time.perf_counter() - t0
        lat.sort()

        def pct(p):
            return round(
                lat[min(len(lat) - 1, int(p * len(lat)))] * 1000, 3)

        return {"writes": done, "elapsed_s": round(elapsed, 3),
                "writes_per_s": round(done / elapsed, 1),
                "p50_ms": pct(0.5), "p99_ms": pct(0.99),
                "store_revision": proxy.endpoint.store.revision}

    async def main_loop():
        # warm every rule/template path before READY so compilation
        # never lands inside a measured window
        for res, _ns, _typ in SHARD_CLASSES:
            await one_create(res, f"{ident}-warm")
            await one_delete(res, f"{ident}-warm")
        print("READY", flush=True)
        loop = asyncio.get_running_loop()
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            if not line or line.strip() == "EXIT":
                return
            if line.startswith("RUN "):
                cmd = json.loads(line[4:])
                res = await window(cmd["tag"], cmd["resources"],
                                   spec["measure_s"])
                print("DONE " + json.dumps(res), flush=True)

    asyncio.run(main_loop())


def bench_write_shard_scale(args) -> dict:
    """Partitioned write scale-out (ISSUE 15): aggregate dual-write
    throughput + p99 at 1/2/4 shard-leader PROCESSES (shard_leader_worker
    above — each an unmodified embedded proxy with its own WAL,
    fsync=always, pinned to a core) under the create/delete churn
    profile.  The parent plays the thin stateless router: it owns the
    PartitionMap, footprint-validates the schema against it per fleet
    size (the SL007 startup gate), and assigns each co-location class to
    its shard — routers are horizontally scalable, so routing cost rides
    the client, not a one-process bottleneck that would cap the thing
    being measured.  Headline `write_shard_scaling` = 2-shard aggregate
    over 1-shard (acceptance >= 1.5x — same hardware ceiling caveat as
    replica-scale: scaling cannot exceed the box's measured pair
    ceiling, recorded alongside)."""
    import shutil
    import tempfile

    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import (
        merge_internal_definitions,
    )
    from spicedb_kubeapi_proxy_tpu.spicedb.sharding import PartitionMap
    from spicedb_kubeapi_proxy_tpu.utils.topology import (
        WorkerFleet,
        cpu_pair_ceiling,
    )

    spec = dict(SHARD_WORKER_SPEC)
    fleet_sizes = (1, 2, 4)
    schema = merge_internal_definitions(sch.parse_schema(SHARD_SCHEMA))

    # the partition maps the parent-as-router would serve each fleet
    # with: class c -> shard c % n.  Footprint-validate each one — the
    # same hard startup gate the real router applies (SL007): 0 errors
    # proves every class's closure is shard-local for every fleet size.
    maps: dict = {}
    for n in fleet_sizes:
        assignments = {}
        for c, (_res, ns, typ) in enumerate(SHARD_CLASSES):
            assignments[ns] = c % n
            assignments[typ] = c % n
        pmap = PartitionMap(n, assignments)
        errors, _warnings = pmap.validate_schema(schema)
        if errors:
            raise AssertionError(
                f"write-shard-scale partition map for {n} shard(s) "
                f"fails footprint validation: {errors}")
        maps[n] = pmap

    tmp = tempfile.mkdtemp(prefix="shard-bench-")
    out: dict = {"fleet": {}, "measure_s": spec["measure_s"],
                 "inflight_per_shard": spec["inflight"],
                 "wal_fsync": spec["wal_fsync"],
                 "partition_map_4": maps[4].describe(),
                 "cores": os.cpu_count()}
    # same fixed per-process budget as replica-scale, via the shared
    # harness: production shard leaders are separate nodes, so the
    # claim is "aggregate write throughput grows as shards are added
    # at a constant per-shard budget"
    fleet = WorkerFleet(name="write-shard-scale")
    try:
        stage(f"write-shard-scale: spawn + warm {max(fleet_sizes)} "
              f"shard-leader processes")
        for i in range(max(fleet_sizes)):
            wspec = dict(spec, identity=f"shard{i}",
                         data_dir=os.path.join(tmp, f"shard-{i}"))
            fleet.spawn(
                [sys.executable, os.path.abspath(__file__),
                 "--shard-worker", json.dumps(wspec)],
                pin=i, label=f"shard-{i}")
        fleet.wait_ready()

        def window(n: int, tag: str) -> list:
            # ownership split the fleet-n partition map prescribes:
            # worker i writes the kube resources of classes c%n == i
            pmap = maps[n]
            payloads = []
            for i in range(n):
                resources = [res for res, _ns, typ in SHARD_CLASSES
                             if pmap.shard_for_type(typ) == i]
                payloads.append({"tag": tag, "resources": resources})
            return fleet.run_window(n, payloads=payloads)

        # interleaved rounds, median per fleet size, paired per-round
        # scaling ratios — the replica-scale methodology (ambient load
        # on a shared box drifts by more than the effect measured)
        rounds = 3
        acc: dict = {n: [] for n in fleet_sizes}
        for r in range(rounds):
            for n in fleet_sizes:
                stage(f"write-shard-scale round {r + 1}/{rounds}: {n} "
                      f"shard leader(s) under churn")
                acc[n].append(window(n, f"r{r}n{n}"))
        for n in fleet_sizes:
            aggs = [sum(res["writes_per_s"] for res in results)
                    for results in acc[n]]
            agg = statistics.median(aggs)
            flat = [res for results in acc[n] for res in results]
            out["fleet"][str(n)] = {
                "aggregate_writes_per_s": round(agg, 1),
                "aggregate_writes_per_s_rounds": [round(a, 1)
                                                  for a in aggs],
                "per_shard_writes_per_s": round(agg / n, 1),
                "dual_write_p50_ms": statistics.median(
                    res["p50_ms"] for res in flat),
                # conservative: the slowest shard's p99 across rounds
                "dual_write_p99_ms": max(res["p99_ms"] for res in flat),
                "writes": sum(res["writes"] for res in flat),
            }
            log(f"write-shard-scale n={n}: {agg:.1f} dual-writes/s "
                f"aggregate (median of {aggs}), p99 "
                f"{out['fleet'][str(n)]['dual_write_p99_ms']}ms")
    finally:
        fleet.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)

    stage("write-shard-scale: CPU pair-scaling ceiling probe")
    out["cpu_pair_scaling_ceiling"] = cpu_pair_ceiling()

    base_rounds = [sum(res["writes_per_s"] for res in results)
                   for results in acc[1]]
    out["noise_spread_1x"] = round(
        max(base_rounds) / max(min(base_rounds), 1e-9), 2)
    for n in fleet_sizes[1:]:
        ratios = [
            sum(res["writes_per_s"] for res in results) / max(b, 1e-9)
            for results, b in zip(acc[n], base_rounds)]
        out[f"scaling_{n}x"] = round(statistics.median(ratios), 2)
        out[f"scaling_{n}x_rounds"] = [round(r, 2) for r in ratios]
    out["write_shard_scaling"] = out.get("scaling_2x", 0.0)
    ceiling = out["cpu_pair_scaling_ceiling"]
    out["write_shard_scaling_normalized"] = round(
        out["write_shard_scaling"] / max(ceiling, 1e-9), 2)
    out["dual_write_p99_ms"] = out["fleet"]["2"]["dual_write_p99_ms"]
    log(f"write-shard-scale: write scaling at 2 shards = "
        f"{out['write_shard_scaling']}x raw (acceptance >= 1.5x on >=2 "
        f"free cores), {out['write_shard_scaling_normalized']}x of this "
        f"box's measured pair ceiling {ceiling}x; at 4 = "
        f"{out.get('scaling_4x')}x on {out['cores']} cores "
        f"(n=1 round noise spread {out['noise_spread_1x']}x)")
    return out


def _scenario_chain(workload, clock, cache_on: bool):
    """jax:// endpoint over a FAKE-clock store (+ DecisionCacheEndpoint
    when the scenario exercises the cache seam) and its oracle."""
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
    from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore

    schema = sch.parse_schema(workload.schema_text)
    store = TupleStore(clock=clock.now)
    inner = JaxEndpoint(schema, store=store)
    store.bulk_load_text("\n".join(workload.relationships))
    ep = inner
    if cache_on:
        from spicedb_kubeapi_proxy_tpu.spicedb.decision_cache import (
            DecisionCacheEndpoint)
        ep = DecisionCacheEndpoint(inner)
    return ep, inner, Evaluator(schema, store)


def _scenario_bench(name, args, churn_fn, cache_on=False, rounds=None,
                    extra=None):
    """Shared scenario runner with the HOST-ORACLE PARITY REFEREE:
    every round applies scenario churn, referees N subjects' frontiers
    and a check-bulk sample against the recursive evaluator over the
    SAME store at the SAME revision, and measures device throughput.
    Churn rounds scale with --rounds (the default 10 maps to 6 rounds,
    --rounds 20 to 12, ...).  Divergence acceptance for every scenario
    config: 0."""
    if rounds is None:
        rounds = max(2, args.rounds * 6 // 10)
    import asyncio
    import random as _random

    from spicedb_kubeapi_proxy_tpu.fuzz.delta_gen import FakeClock
    from spicedb_kubeapi_proxy_tpu.fuzz.scenarios import SCENARIO_WORKLOADS
    from spicedb_kubeapi_proxy_tpu.spicedb.types import (
        CheckRequest, ObjectRef, SubjectRef)

    clock = FakeClock()
    wl_kw = {"now": clock.now()} if name == "ephemeral-grants" else {}
    workload = SCENARIO_WORKLOADS[name](**wl_kw)
    stage(f"{name} build ({len(workload.relationships)} tuples)")
    ep, inner, oracle = _scenario_chain(workload, clock, cache_on)
    rng = _random.Random(99)
    rt, perm = workload.resource_type, workload.permission
    subjects = [SubjectRef("user", workload.subjects[i * 7
                                                     % len(workload.subjects)])
                for i in range(4)]
    divergences = 0
    refereed = 0
    check_s = 0.0
    n_checks = 0
    list_s = 0.0
    n_lists = 0

    async def run():
        nonlocal divergences, refereed, check_s, n_checks, list_s, n_lists
        # warmup: pay first-use jit compiles outside the timed rounds
        await ep.lookup_resources(rt, perm, subjects[0])
        ids0 = inner.store.object_ids_of_type(rt)[:64]
        await ep.check_bulk_permissions(
            [CheckRequest(ObjectRef(rt, o), perm, subjects[0])
             for o in ids0])
        for r in range(rounds):
            churn_fn(inner.store, clock, rng, r)
            # referee: frontier parity per subject at the pinned
            # revision — twice when the cache rides the chain, so the
            # SECOND pass referees a cache-served answer too
            for s in subjects:
                want = sorted(oracle.lookup_resources(rt, perm, s))
                for _pass in range(2 if cache_on else 1):
                    t0 = time.time()
                    got = sorted(await ep.lookup_resources(rt, perm, s))
                    list_s += time.time() - t0
                    n_lists += 1
                    refereed += 1
                    if got != want:
                        divergences += 1
            # referee: tri-state check parity on a sampled id block
            ids = inner.store.object_ids_of_type(rt)
            sample = ids[:: max(1, len(ids) // 128)][:128]
            reqs = [CheckRequest(ObjectRef(rt, o), perm, s)
                    for o in sample for s in subjects[:2]]
            t0 = time.time()
            res = await ep.check_bulk_permissions(reqs)
            check_s += time.time() - t0
            n_checks += len(reqs)
            p3 = {"NO_PERMISSION": 0, "CONDITIONAL_PERMISSION": 1,
                  "HAS_PERMISSION": 2}
            for req, cr in zip(reqs, res):
                refereed += 1
                if p3[cr.permissionship.name] != oracle.check3(
                        req.resource, req.permission, req.subject):
                    divergences += 1

    asyncio.run(run())
    out = {
        "divergences": divergences,
        "refereed_answers": refereed,
        "rounds": rounds,
        "checks_per_s": round(n_checks / max(check_s, 1e-9), 1),
        "lists_per_s": round(n_lists / max(list_s, 1e-9), 2),
        "objects": workload.expected_objects,
        "tuples": len(workload.relationships),
        "kernel_calls": inner.stats["kernel_calls"],
        "oracle_residual_checks": inner.stats["oracle_residual_checks"],
        "rebuilds": inner.stats["rebuilds"],
    }
    if cache_on:
        st = ep.cache.stats
        probes = st["hits"] + st["misses"]
        out["hit_rate"] = round(st["hits"] / max(probes, 1), 4)
        out["cache_invalidations"] = st["invalidations"]
    if extra:
        out.update(extra(inner))
    log(f"{name}: {divergences} divergences over {refereed} refereed "
        f"answers, {out['checks_per_s']} checks/s, "
        f"{out['rebuilds']} rebuilds")
    return out


def bench_scenario_caveat_heavy(args) -> dict:
    """CEL-caveated tuples at scale (ROADMAP item 5): decided-true /
    decided-false / undecidable contexts churned every round; the
    artifact records WHICH side decided the caveats (`caveat_path`) —
    the tri-state device bitplanes or the host-oracle post-filter."""

    def churn(store, clock, rng, r):
        from spicedb_kubeapi_proxy_tpu.spicedb.types import (
            RelationshipUpdate, UpdateOp, parse_relationship)
        ops = []
        for _ in range(24):
            d = rng.randrange(3000)
            u = rng.randrange(400)
            roll = rng.random()
            if roll < 0.4:
                ctx = '{"used": 1, "quota": 5}' if rng.random() < 0.5 \
                    else '{"used": 1}'
                ops.append(RelationshipUpdate(
                    UpdateOp.TOUCH, parse_relationship(
                        f"doc:d{d}#assigned@user:u{u}"
                        f"[caveat:within_quota:{ctx}]")))
            elif roll < 0.7:
                lvl = rng.randrange(6)
                ops.append(RelationshipUpdate(
                    UpdateOp.TOUCH, parse_relationship(
                        f"doc:d{d}#approved@user:u{u}"
                        f'[caveat:min_level:{{"level": {lvl}}}]')))
            else:
                ops.append(RelationshipUpdate(
                    UpdateOp.DELETE, parse_relationship(
                        f"doc:d{d}#assigned@user:u{u}")))
        store.write(ops)

    def caveat_path(inner):
        graph = inner._graph
        bitplane = bool(getattr(graph, "has_cav", False))
        residual = inner.stats["oracle_residual_checks"]
        return {"caveat_path": ("device-bitplane" if bitplane and not
                                residual else
                                "device-bitplane+host-residual" if bitplane
                                else "host-postfilter"),
                "caveat_bitplanes": bitplane}

    return _scenario_bench("caveat-heavy", args, churn, extra=caveat_path)


def bench_scenario_wildcard_public(args) -> dict:
    """Wildcard-heavy public resources: `user:*` grants FLIP on and off
    every round — the delta class the device graph cannot absorb in
    place, so the rebuild path (sync or background per the AsyncRebuild
    gate) carries the churn while the referee holds parity."""

    def churn(store, clock, rng, r):
        from spicedb_kubeapi_proxy_tpu.spicedb.types import (
            RelationshipUpdate, UpdateOp, parse_relationship)
        ops = []
        for _ in range(8):
            d = rng.randrange(4000)
            op = (UpdateOp.DELETE if rng.random() < 0.5 else UpdateOp.TOUCH)
            ops.append(RelationshipUpdate(
                op, parse_relationship(f"doc:d{d}#public@user:*")))
        for _ in range(8):
            d = rng.randrange(4000)
            u = rng.randrange(400)
            ops.append(RelationshipUpdate(
                UpdateOp.TOUCH,
                parse_relationship(f"doc:d{d}#viewer@user:u{u}")))
        store.write(ops)

    return _scenario_bench("wildcard-public", args, churn)


def bench_scenario_ephemeral_grants(args) -> dict:
    """PAuth-style task-scoped ephemeral grants: short-TTL expiring
    tuples at high churn against the store's fake clock, with the
    DecisionCache ON — every round grants expire mid-stream, so the
    PR 3 expiry heap must invalidate cached frontiers exactly when the
    clock crosses each instant (the referee proves it)."""

    def churn(store, clock, rng, r):
        from spicedb_kubeapi_proxy_tpu.spicedb.types import (
            RelationshipUpdate, UpdateOp, parse_relationship)
        ops = []
        for _ in range(32):
            d = rng.randrange(3000)
            u = rng.randrange(300)
            ttl = 5.0 + 25.0 * rng.random()
            exp = clock.now() + ttl
            ops.append(RelationshipUpdate(
                UpdateOp.TOUCH, parse_relationship(
                    f"doc:d{d}#grant@user:u{u}[expiration:{exp}]")))
        store.write(ops)
        # cross a swath of TTL instants: earlier rounds' grants lapse
        clock.advance(12.0)

    return _scenario_bench("ephemeral-grants", args, churn, cache_on=True)


def bench_scenario_group_explosion(args) -> dict:
    """Leopard materialized group index A/B (ISSUE 19): 100k groups in
    disjoint depth-8 membership chains, docs shared with chain HEADS —
    the shape where every check pays `depth` HBM sweep iterations
    without the index and ONE closure-plane probe with it.  Two
    endpoints over the SAME store: LeopardIndex gate ON at construction
    (indexed) and OFF (iterative kernel sweeps), churned with tail-user
    moves (insert propagation + delete quarantine -> background
    re-close) under the host-oracle parity referee on BOTH endpoints.
    Acceptance: 0 divergences, indexed >= 5x iterative checks/s, and
    measured mean sweep depth ~1 on the indexed pairs."""
    import asyncio
    import random as _random

    from spicedb_kubeapi_proxy_tpu.fuzz.delta_gen import FakeClock
    from spicedb_kubeapi_proxy_tpu.fuzz.scenarios import SCENARIO_WORKLOADS
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
    from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore
    from spicedb_kubeapi_proxy_tpu.spicedb.types import (
        CheckRequest, ObjectRef, RelationshipUpdate, SubjectRef, UpdateOp,
        parse_relationship)
    from spicedb_kubeapi_proxy_tpu.utils import workload as wk
    from spicedb_kubeapi_proxy_tpu.utils.features import GATES

    depth = 8
    workload = SCENARIO_WORKLOADS["group-explosion"](depth=depth)
    stage(f"group-explosion build ({len(workload.relationships)} tuples)")
    schema = sch.parse_schema(workload.schema_text)
    clock = FakeClock()
    store = TupleStore(clock=clock.now)
    store.bulk_load_text("\n".join(workload.relationships))
    # the LeopardIndex gate is captured at endpoint construction, so the
    # indexed and iterative endpoints coexist over the same store
    prev = GATES.enabled("LeopardIndex")
    try:
        GATES.set("LeopardIndex", True)
        ep_on = JaxEndpoint(schema, store=store)
        GATES.set("LeopardIndex", False)
        ep_off = JaxEndpoint(schema, store=store)
    finally:
        GATES.set("LeopardIndex", prev)
    oracle = Evaluator(schema, store)
    rng = _random.Random(199)

    # doc -> (head group, tail user) straight from the tuples, so the
    # check mix carries known depth-8 positives without generator coupling
    doc_user = {}
    for r in workload.relationships:
        if r.startswith("doc:"):
            rel = parse_relationship(r)
            head = int(rel.subject.id[1:])
            doc_user[rel.resource.id] = f"u{(head // depth) % 2000}"
    docs = sorted(doc_user)

    def check_reqs(n):
        reqs = []
        for _ in range(n):
            d = docs[rng.randrange(len(docs))]
            u = (doc_user[d] if rng.random() < 0.5
                 else f"u{rng.randrange(2000)}")
            reqs.append(CheckRequest(ObjectRef("doc", d), "view",
                                     SubjectRef("user", u)))
        return reqs

    rounds = max(2, args.rounds * 4 // 10)
    n_chains = 100_000 // depth
    divergences = 0
    refereed = 0
    p3 = {"NO_PERMISSION": 0, "CONDITIONAL_PERMISSION": 1,
          "HAS_PERMISSION": 2}

    def churn(r):
        # move a few tail users between chains: the DELETE leg drives
        # the quarantine -> background re-close path, the TOUCH leg the
        # bounded-frontier insert propagation
        ops = []
        for _ in range(4):
            c = rng.randrange(n_chains)
            tail = c * depth + depth - 1
            ops.append(RelationshipUpdate(UpdateOp.DELETE,
                       parse_relationship(f"group:g{tail}#member"
                                          f"@user:u{c % 2000}")))
            ops.append(RelationshipUpdate(UpdateOp.TOUCH,
                       parse_relationship(f"group:g{tail}#member"
                                          f"@user:u{rng.randrange(2000)}")))
        store.write(ops)

    async def referee():
        nonlocal divergences, refereed
        subjects = [SubjectRef("user", doc_user[docs[rng.randrange(
            len(docs))]]) for _ in range(2)]
        for s in subjects:
            want = sorted(oracle.lookup_resources("doc", "view", s))
            for ep in (ep_on, ep_off):
                got = sorted(await ep.lookup_resources("doc", "view", s))
                refereed += 1
                if got != want:
                    divergences += 1
        reqs = check_reqs(64)
        want3 = [oracle.check3(q.resource, q.permission, q.subject)
                 for q in reqs]
        for ep in (ep_on, ep_off):
            res = await ep.check_bulk_permissions(reqs)
            for w, cr in zip(want3, res):
                refereed += 1
                if p3[cr.permissionship.name] != w:
                    divergences += 1

    async def measure(ep):
        # depth attribution reads the sweep-telemetry singleton, so each
        # phase starts from a clean accounting slate
        wk.WORKLOAD.reset()
        reqs = check_reqs(args.batch)
        await ep.check_bulk_permissions(reqs)  # pay compiles untimed
        t0 = time.time()
        n = 0
        for _ in range(max(4, args.rounds)):
            await ep.check_bulk_permissions(reqs)
            n += len(reqs)
        check_s = time.time() - t0
        t0 = time.time()
        n_lists = 0
        for _ in range(8):
            s = SubjectRef("user", doc_user[docs[rng.randrange(len(docs))]])
            await ep.lookup_resources("doc", "view", s)
            n_lists += 1
        list_s = time.time() - t0
        mean_depth = None
        for row in wk.WORKLOAD.payload()["rows"]:
            if (row["resource_type"], row["permission"]) == ("doc", "view"):
                mean_depth = row["mean_sweep_depth"]
        return {"checks_per_s": round(n / max(check_s, 1e-9), 1),
                "lists_per_s": round(n_lists / max(list_s, 1e-9), 2),
                "mean_sweep_depth": mean_depth}

    async def run():
        for r in range(rounds):
            churn(r)
            await referee()
        # drain background re-closes so the indexed phase measures the
        # closure-plane fast path, not the quarantine kernel fallback
        ep_on.wait_rebuilds()
        ep_off.wait_rebuilds()
        await referee()
        return await measure(ep_on), await measure(ep_off)

    indexed, iterative = asyncio.run(run())
    lp = ep_on._leopard
    statuses = lp.status_map() if lp is not None else {}
    out = {
        "divergences": divergences,
        "refereed_answers": refereed,
        "rounds": rounds,
        "depth": depth,
        "tuples": len(workload.relationships),
        "checks_per_s": indexed["checks_per_s"],
        "indexed": indexed,
        "iterative": iterative,
        "indexed_speedup": round(indexed["checks_per_s"]
                                 / max(iterative["checks_per_s"], 1e-9), 2),
        "index_fragments": lp.fragment_count() if lp is not None else 0,
        "index_bytes": lp.nbytes if lp is not None else 0,
        "index_statuses": statuses,
        "leopard_checks": ep_on.stats["leopard_checks"],
        "leopard_lookups": ep_on.stats["leopard_lookups"],
        "leopard_recloses": ep_on.stats["leopard_recloses"],
    }
    log(f"group-explosion: {divergences} divergences over {refereed} "
        f"refereed answers, indexed {indexed['checks_per_s']} vs "
        f"iterative {iterative['checks_per_s']} checks/s "
        f"({out['indexed_speedup']}x), depth {indexed['mean_sweep_depth']}"
        f" vs {iterative['mean_sweep_depth']}")
    return out


# scenario matrix configs (ISSUE 12 / ROADMAP item 5): the three
# workload shapes the sweep was missing, each with a host-oracle parity
# referee (docs/performance.md "Scenario matrix")
def bench_sweep_telemetry(args) -> dict:
    """KernelIntrospect A/B (ISSUE 17): the 1M-tuple depth-4 headline
    shape run with the sweep-telemetry gate OFF (byte-identical
    pre-introspection jits) and ON (iteration counter + frontier trace
    threaded through the fixpoint carry), interleaved so allocator
    drift lands on both modes equally.  Reports the per-round overhead
    of the telemetry (acceptance: within run-to-run noise), the
    measured-basis roofline from a dedicated introspect-on window
    (`kernel_bytes_basis` must read "measured"), and the /debug/workload
    attribution payload the traffic produced."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.models import workloads as wl
    from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import BatchingEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef
    from spicedb_kubeapi_proxy_tpu.utils import workload as wk
    from spicedb_kubeapi_proxy_tpu.utils.features import GATES

    workload = wl.multitenant_1m()
    batch = args.batch
    rounds = max(3, args.rounds // 2)
    max_batch = max(1, batch // 4)
    subjects = workload.subjects
    modes = (("introspect-off", False), ("introspect-on", True))
    out: dict = {"modes": {}, "batch": batch, "rounds": rounds,
                 "max_batch": max_batch}
    eps: dict = {}
    acc = {name: [] for name, _gate in modes}

    async def one_round(ep, r):
        async def caller(i):
            s = SubjectRef(
                "user", subjects[(r * batch + i) % len(subjects)])
            return await ep.lookup_resources(
                workload.resource_type, workload.permission, s)
        t0 = time.time()
        await asyncio.gather(*[caller(i) for i in range(batch)])
        return time.time() - t0

    try:
        # `introspect` is resolved at jit BUILD time, so each mode gets
        # its own endpoint, built and warmed under its gate state — the
        # off mode runs the exact pre-introspection functions
        for name, gate in modes:
            GATES.set("KernelIntrospect", gate)
            stage(f"sweep-telemetry build + load + warm ({name})")
            inner = build_endpoint(workload, "jax")
            eps[name] = BatchingEndpoint(inner, max_batch=max_batch,
                                         pipeline_depth=2)
            asyncio.run(one_round(eps[name], 0))  # warm: compiles+arenas
        stage("sweep-telemetry interleaved rounds")
        for r in range(rounds):
            for name, gate in modes:
                GATES.set("KernelIntrospect", gate)
                acc[name].append(asyncio.run(one_round(eps[name], r + 1)))
        # dedicated introspect-on window for the measured-basis roofline:
        # only introspect-built kernels dispatch inside it, so the
        # summary's kernel byte tags are all iterations x one-sweep
        GATES.set("KernelIntrospect", True)
        mark = timeline_mark()
        asyncio.run(one_round(eps["introspect-on"], rounds + 1))
        tl = timeline_summary(mark) or {}
    finally:
        GATES.set("KernelIntrospect", True)

    n_obj = len(eps["introspect-on"].inner.store.object_ids_of_type(
        workload.resource_type))
    for name, _gate in modes:
        per_round = statistics.median(acc[name])
        out["modes"][name] = {
            "checks_per_s": round(batch * n_obj / per_round, 1),
            "per_round_ms": round(per_round * 1e3, 2),
            "p99_ms": round(p99(acc[name]) * 1e3, 2),
        }
    off_med = statistics.median(acc["introspect-off"])
    on_med = statistics.median(acc["introspect-on"])
    noise = (statistics.stdev(acc["introspect-off"])
             if len(acc["introspect-off"]) > 1 else 0.0)
    out["overhead_pct"] = round((on_med / off_med - 1) * 100, 2)
    out["noise_pct"] = round(noise / off_med * 100, 2) if off_med else None
    out["overhead_within_noise"] = bool(abs(on_med - off_med)
                                        <= max(2 * noise, 0.02 * off_med))
    out["roofline_fraction"] = tl.get("roofline_fraction")
    out["kernel_bytes_basis"] = tl.get("kernel_bytes_basis")
    out["workload_attribution"] = wk.WORKLOAD.payload()
    log(f"sweep-telemetry: overhead={out['overhead_pct']}% "
        f"(noise {out['noise_pct']}%), basis={out['kernel_bytes_basis']}, "
        f"roofline={out['roofline_fraction']}")
    return out


def bench_cpu_microbench(args) -> dict:
    """Deterministic pure-python microbench for the perf-regression
    sentinel (scripts/benchdiff.py + the check.sh gate): NO jax import,
    fixed seeds and fixed work, per-round wall times recorded so the
    comparator can derive noise-aware thresholds, and a pure-python
    calibration loop riding the artifact so two runs on
    differently-loaded machines compare ratio-normalized.  Exercises
    the dispatch drain hot loop (spicedb/dispatch.py) and the recursive
    oracle — the two CPU paths a slowdown is most likely to hide in."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import BatchingEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import EmbeddedEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb.types import (
        CheckRequest, ObjectRef, SubjectRef)

    schema_text = """
definition user {}
definition team {
  relation member: user | team#member
  permission view = member
}
definition doc {
  relation owner: user
  relation reader: user | team#member
  permission view = owner + reader
}
"""
    n_docs, n_users, n_teams = 120, 24, 6
    rels = []
    for t in range(n_teams):
        for u in range(t, n_users, n_teams):
            rels.append(f"team:t{t}#member@user:u{u}")
        if t:
            rels.append(f"team:t{t}#member@team:t{t - 1}#member")
    for d in range(n_docs):
        rels.append(f"doc:d{d}#owner@user:u{d % n_users}")
        rels.append(f"doc:d{d}#reader@team:t{d % n_teams}#member")
    inner = EmbeddedEndpoint(sch.parse_schema(schema_text))
    inner.store.bulk_load_text("\n".join(rels))
    ep = BatchingEndpoint(inner, max_batch=8)

    def calib() -> float:
        t0 = time.perf_counter()
        x = 0
        for i in range(200_000):
            x = (x * 31 + i) % 1_000_003
        return time.perf_counter() - t0

    calibration_s = min(calib() for _ in range(3))
    rounds = max(5, args.rounds)
    batch = min(args.batch, 64)

    async def check_round(r):
        reqs = [CheckRequest(ObjectRef("doc", f"d{(r * batch + i) % n_docs}"),
                             "view", SubjectRef("user", f"u{i % n_users}"))
                for i in range(batch)]
        await asyncio.gather(*[ep.check_permission(q) for q in reqs])

    async def lookup_round(r):
        await asyncio.gather(*[
            ep.lookup_resources("doc", "view",
                                SubjectRef("user", f"u{(r + i) % n_users}"))
            for i in range(batch)])

    async def oracle_round(r):
        reqs = [CheckRequest(ObjectRef("doc", f"d{(r * batch + i) % n_docs}"),
                             "view", SubjectRef("user", f"u{i % n_users}"))
                for i in range(batch)]
        await inner.check_bulk_permissions(reqs)

    configs: dict = {}
    for name, fn in (("dispatch-check", check_round),
                     ("dispatch-lookup", lookup_round),
                     ("oracle-eval", oracle_round)):
        asyncio.run(fn(0))  # warm
        times = []
        for r in range(rounds):
            t0 = time.perf_counter()
            asyncio.run(fn(r + 1))
            times.append(time.perf_counter() - t0)
        configs[name] = {
            "per_round_s": [round(t, 6) for t in times],
            "median_s": round(statistics.median(times), 6),
        }
        log(f"cpu-microbench {name}: median "
            f"{configs[name]['median_s'] * 1e3:.2f} ms/round")
    return {"calibration_s": round(calibration_s, 6), "rounds": rounds,
            "batch": batch, "tuples": len(rels), "configs": configs}


def bench_mesh_scale(args) -> dict:
    """Multi-chip mesh scaling, MEASURED (not projected) on the local
    device set: the same depth-4 workload served by the single-chip
    kernels and by sharded 1x1 / 1x2 / 1x4 (data x graph) meshes, with
    rounds interleaved across all modes so allocator and load drift
    land on every mode equally.  Reports per-mode lookup-round medians
    and paired scaling ratios vs the single-chip baseline, the
    per-device HBM ledger rows each mesh registered, and the pipelined
    dispatch overlap measured on the largest sharded graph.  On a CPU
    host (forced virtual devices) the numbers measure STRUCTURAL
    scaling — partition, collective, and dispatch overheads are real,
    FLOPS scaling is not; on a TPU slice the same config measures the
    physical thing.  Needs >= 2 devices; single-device hosts record a
    skip marker instead of projecting."""
    import asyncio

    import jax

    from spicedb_kubeapi_proxy_tpu.models import workloads as wl
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
    from spicedb_kubeapi_proxy_tpu.parallel.sharding import make_mesh
    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import BatchingEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef
    from spicedb_kubeapi_proxy_tpu.utils import devtel

    devices = jax.devices()
    sizes = [n for n in (1, 2, 4) if n <= len(devices)]
    if len(devices) < 2:
        return {"skipped": True,
                "reason": f"mesh-scale needs >= 2 devices, have "
                          f"{len(devices)} (force a virtual mesh with "
                          f"XLA_FLAGS=--xla_force_host_platform_device_"
                          f"count=N)"}
    workload = wl.nested_groups(n_pods=4_000, n_users=1_000, n_groups=120,
                                n_teams=24, n_namespaces=60)
    schema = sch.parse_schema(workload.schema_text)
    batch = args.batch
    rounds = max(3, args.rounds // 2)
    subjects = workload.subjects
    modes = [("single", None)] + [(f"mesh-1x{n}", n) for n in sizes]

    def batch_subjects(r):
        return [SubjectRef("user", subjects[(r * batch + i) % len(subjects)])
                for i in range(batch)]

    async def one_round(ep, r):
        t0 = time.perf_counter()
        await ep.lookup_resources_batch(
            workload.resource_type, workload.permission, batch_subjects(r))
        return time.perf_counter() - t0

    eps: dict = {}
    shard_rows: dict = {}
    acc = {name: [] for name, _n in modes}
    for name, n in modes:
        stage(f"mesh-scale build + load + warm ({name})")
        before = dict(devtel.LEDGER.device_totals())
        mesh = (make_mesh(devices[:n], data=1, graph=n)
                if n is not None else None)
        ep = JaxEndpoint(schema, kernel="ell", mesh=mesh)
        ep.store.bulk_load_text("\n".join(workload.relationships))
        asyncio.run(one_round(ep, 0))  # warm: build + compiles + arenas
        after = devtel.LEDGER.device_totals()
        shard_rows[name] = {
            f"{kind}:d{dev}": b - before.get((kind, dev), 0)
            for (kind, dev), b in sorted(after.items())
            if b - before.get((kind, dev), 0) > 0}
        eps[name] = ep

    stage("mesh-scale interleaved rounds")
    for r in range(rounds):
        for name, _n in modes:
            acc[name].append(asyncio.run(one_round(eps[name], r + 1)))

    n_obj = len(eps["single"].store.object_ids_of_type(
        workload.resource_type))
    out: dict = {"devices": len(devices), "batch": batch, "rounds": rounds,
                 "basis": "measured", "modes": {}, "scaling": {}}
    single_med = statistics.median(acc["single"])
    for name, _n in modes:
        med = statistics.median(acc[name])
        out["modes"][name] = {
            "per_round_ms": round(med * 1e3, 2),
            "p99_ms": round(p99(acc[name]) * 1e3, 2),
            "checks_per_s": round(batch * n_obj / med, 1),
            "device_shard_bytes": shard_rows[name],
        }
        if name != "single":
            out["scaling"][name] = round(single_med / med, 3)

    # pipelined dispatch on the largest sharded graph: concurrent
    # per-subject lists must fan into overlapping fused batches (the
    # acceptance is overlap > 0 — the sharded kernels keep the PR 7
    # pipelined drain instead of degrading to serial dispatch)
    big = f"mesh-1x{sizes[-1]}"
    bep = BatchingEndpoint(eps[big], max_batch=max(8, batch // 8),
                           pipeline_depth=2)

    async def wave(r):
        await asyncio.gather(*[
            bep.lookup_resources(
                workload.resource_type, workload.permission,
                SubjectRef("user", subjects[(r + i) % len(subjects)]))
            for i in range(batch)])

    overlap = None
    for attempt in range(6):
        mark = timeline_mark()
        asyncio.run(wave(attempt))
        tl = timeline_summary(mark) or {}
        overlap = tl.get("overlap_ratio")
        if overlap:
            break
    out["pipelined_overlap_ratio"] = overlap
    out["mesh_scaling"] = out["scaling"].get(big, 0.0)
    log(f"mesh-scale: {out['scaling']} vs single-chip "
        f"(basis=measured, overlap={overlap})")
    return out


SCENARIO_CONFIGS = {
    "caveat-heavy": bench_scenario_caveat_heavy,
    "wildcard-public": bench_scenario_wildcard_public,
    "ephemeral-grants": bench_scenario_ephemeral_grants,
    "group-explosion": bench_scenario_group_explosion,
}

# device-resident pipeline A/B (ISSUE 7): same contract as CACHE_CONFIGS
PIPELINE_CONFIGS = {
    "pipeline-depth": bench_pipeline_depth,
}

# kernel introspection & regression sentinel (ISSUE 17): sweep-telemetry
# needs jax; cpu-microbench deliberately does NOT (it short-circuits in
# main() before the device check so the check.sh benchdiff gate stays
# fast and deterministic)
OBS_CONFIGS = {
    "sweep-telemetry": bench_sweep_telemetry,
    "cpu-microbench": bench_cpu_microbench,
}

# WAL-shipping replication scale-out (ISSUE 9): same contract
REPLICATION_CONFIGS = {
    "replica-scale": bench_replica_scale,
}

# partitioned write scale-out (ISSUE 15): same contract
SHARDING_CONFIGS = {
    "write-shard-scale": bench_write_shard_scale,
}

# multi-chip mesh execution (ISSUE 18): measured shard_map scaling on
# the local device set (virtual CPU mesh in CI, physical on TPU)
MESH_CONFIGS = {
    "mesh-scale": bench_mesh_scale,
}

# composed fleet topology (ISSUE 20): real multi-process fleets (shard
# leaders x follower fan-out trees x the CLI router) under open-loop
# load, via the shared harness (utils/topology.py) + scripts/
# fleet_bench.py.  The parent never imports jax (members run embedded
# endpoints), so these dispatch BEFORE the device check like
# cpu-microbench.  Excluded from --all like OBS_CONFIGS: a fleet boot
# is minutes of wall clock and its artifact is FLEET_rNN.json, not
# BENCH.  Values are fleet_bench.py section names.
FLEET_CONFIGS = {
    "fleet-read-scale": "read_scale",
    "fleet-write-scale": "write_scale",
    "fleet-chaos": "chaos",
    "fleet-topology": "full",
}

# decision-cache bench configs (ISSUE 3): run standalone via --config or
# appended to the --all sweep artifact
CACHE_CONFIGS = {
    "warm-repeat-list": bench_warm_repeat_list,
    "delta-churn": bench_delta_churn,
}

# durable-store bench configs (ISSUE 4): same contract as CACHE_CONFIGS
PERSIST_CONFIGS = {
    "recovery": bench_recovery,
}

CONFIGS = {
    "namespace-baseline": ("namespace_baseline", {}),
    "pods-depth1": ("pods_depth1", {}),
    "nested-groups-depth4": ("nested_groups", {}),
    "rbac-deny": ("rbac_deny", {}),
    "multitenant-1m": ("multitenant_1m", {}),
    # VERDICT r1 item 7: half the querying subjects have zero tuples; the
    # phantom-column path must show no cliff vs multitenant-1m
    "multitenant-1m-cold-users": ("multitenant_1m", {"cold_subjects": 0.5}),
    # VERDICT r3 item 5: caveat-heavy RBAC — tri-state bitplane path; must
    # be within ~10x of the definite rbac-deny throughput
    "caveats-rbac": ("caveated_rbac", {}),
}


def _config_registry() -> dict:
    """Every runnable --config, grouped; the source of truth for both
    validation and the unknown-config listing."""
    return {
        "workload sweep (CONFIGS)": list(CONFIGS),
        "decision cache": list(CACHE_CONFIGS),
        "durable store": list(PERSIST_CONFIGS),
        "device pipeline": list(PIPELINE_CONFIGS),
        "replication": list(REPLICATION_CONFIGS),
        "write sharding": list(SHARDING_CONFIGS),
        "multi-chip mesh": list(MESH_CONFIGS),
        "fleet topology": list(FLEET_CONFIGS),
        "scenario matrix": list(SCENARIO_CONFIGS),
        "observability": list(OBS_CONFIGS),
    }


def _reject_unknown_config(name: str) -> None:
    """Unknown --config: print the grouped registry and exit 2 (never a
    traceback — ISSUE 12 satellite)."""
    groups = _config_registry()
    if any(name in names for names in groups.values()):
        return
    print(f"bench.py: unknown --config {name!r}; registered configs:",
          file=sys.stderr)
    for group, names in groups.items():
        print(f"  {group}:", file=sys.stderr)
        for n in names:
            print(f"    {n}", file=sys.stderr)
    sys.exit(2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="multitenant-1m",
                    metavar="NAME",
                    help="one of the registered configs (an unknown "
                         "name prints the grouped registry and exits 2)")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--oracle-queries", type=int, default=2)
    ap.add_argument("--deadline", type=float,
                    default=float(os.environ.get("BENCH_DEADLINE_S", "2100")),
                    help="hard wall-clock cap; the JSON line is emitted "
                         "with partial results when it expires")
    ap.add_argument("--all", action="store_true", default=True,
                    help="run every config (the default since round 4: the "
                         "BENCH artifact must carry the whole BASELINE "
                         "sweep); headline metric stays the default config")
    ap.add_argument("--single", dest="all", action="store_false",
                    help="headline config only (smoke runs)")
    ap.add_argument("--no-cold-users", action="store_true",
                    help="skip the cold-users side-measurement")
    ap.add_argument("--direct-only", action="store_true",
                    help="headline = direct batched call instead of the "
                         "concurrent dispatcher path")
    ap.add_argument("--baseline", default="", metavar="ARTIFACT",
                    help="compare this run's artifact against a prior "
                         "bench JSON via scripts/benchdiff.py and exit "
                         "with its verdict (0 ok, 1 regression); "
                         "currently honored by --config cpu-microbench")
    ap.add_argument("--replica-worker", default="", help=argparse.SUPPRESS)
    ap.add_argument("--shard-worker", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    _reject_unknown_config(args.config)

    if args.replica_worker:
        # replica-scale follower subprocess: no device check, no watchdog —
        # the parent bench owns the lifecycle (see replica_worker)
        replica_worker(args.replica_worker)
        return
    if args.shard_worker:
        # write-shard-scale shard-leader subprocess: same contract
        shard_leader_worker(args.shard_worker)
        return

    start_watchdog(args.deadline)

    if args.config == "cpu-microbench":
        # perf-regression sentinel config: pure python, runs BEFORE the
        # device check / jax import so the check.sh benchdiff gate is
        # fast, deterministic, and immune to device bring-up weather
        stage("cpu-microbench (no jax)")
        _STATE["metric"] = "cpu-microbench"
        res = bench_cpu_microbench(args)
        payload = {
            "metric": "cpu-microbench",
            "value": res["configs"]["dispatch-check"]["median_s"],
            "unit": "s/round", "platform": "cpu-python",
            "baseline": "committed benchdiff baseline artifact "
                        "(scripts/benchdiff_baseline.json)",
            **res}
        emit(payload)
        if args.baseline:
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "benchdiff",
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "scripts", "benchdiff.py"))
            bd = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(bd)
            with open(args.baseline) as f:
                base = json.load(f)
            verdict = bd.compare(base, payload)
            bd.print_report(verdict, file=sys.stderr)
            sys.exit(1 if verdict["regressions"] else 0)
        return

    if args.config in FLEET_CONFIGS:
        # composed-fleet config: multi-process members, no jax in the
        # parent — dispatch before the device check (cpu-microbench
        # precedent) and delegate to the fleet_bench section runner
        stage(f"fleet config {args.config} (multi-process, no jax in "
              f"parent)")
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "fleet_bench",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "fleet_bench.py"))
        fb = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(fb)
        _STATE["metric"] = f"fleet {args.config}"
        res = fb.run_section(FLEET_CONFIGS[args.config])
        emit({"metric": _STATE["metric"],
              "value": res.get("headline", 0.0),
              "unit": res.get("headline_unit", "x"),
              "platform": "cpu-multiprocess",
              "baseline": "smallest fleet of the same shape under the "
                          "same open-loop schedule (paired rounds)",
              **res})
        return

    path_desc = (f"{args.batch}-subject direct batched call"
                 if args.direct_only else
                 f"{args.batch} concurrent list requests, batched dispatch")
    _STATE["metric"] = f"authz checks/sec ({args.config}, {path_desc})"

    # -- device: the TPU, or the CPU only when asked for by name ------------
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from spicedb_kubeapi_proxy_tpu.utils.device import configure_compile_cache
    log(f"compile cache: {configure_compile_cache()}")

    stage("jax import + device init")
    import jax
    devs = jax.devices()
    _STATE["platform"] = devs[0].platform
    log(f"devices: {devs}")
    if (devs[0].platform != "tpu"
            and os.environ.get("JAX_PLATFORMS", "") != "cpu"):
        emit_error(f"no TPU found (jax platform {devs[0].platform!r}); "
                   f"set JAX_PLATFORMS=cpu to run on the CPU by name")
        sys.exit(1)

    warmup_tiny()

    if args.config in CACHE_CONFIGS:
        # standalone decision-cache config: its own headline metric
        stage(f"cache config {args.config}")
        tel_before = devtel_snapshot()
        tl_mark = timeline_mark()
        res = CACHE_CONFIGS[args.config](args)
        tel = devtel_delta(tel_before)
        if tel:
            res["device_telemetry"] = tel
        tl_sum = timeline_summary(tl_mark)
        if tl_sum:
            res["timeline_summary"] = tl_sum
        res.update(timeline_headline(tl_sum))
        value = (res.get("cache_on_checks_per_s")
                 or res.get("lists_per_s", 0.0))
        _STATE["metric"] = f"decision-cache {args.config}"
        emit({"metric": _STATE["metric"], "value": value,
              "unit": ("checks/s" if "cache_on_checks_per_s" in res
                       else "lists/s"),
              "platform": _STATE["platform"],
              "baseline": "cache-off proxy chain", **res})
        return

    if args.config in PIPELINE_CONFIGS:
        # standalone pipeline A/B: depth-2 checks/s is the headline
        # value, the gate-off host-pack serial path is the baseline
        stage(f"pipeline config {args.config}")
        tel_before = devtel_snapshot()
        res = PIPELINE_CONFIGS[args.config](args)
        tel = devtel_delta(tel_before)
        if tel:
            res["device_telemetry"] = tel
        _STATE["metric"] = f"device-pipeline {args.config}"
        d2 = res.get("modes", {}).get("depth-2", {})
        emit({"metric": _STATE["metric"],
              "value": d2.get("checks_per_s", 0.0), "unit": "checks/s",
              "platform": _STATE["platform"],
              "baseline": "DevicePipeline gate off (host-pack serial "
                          "dispatch, the pre-PR path)",
              **res})
        return

    if args.config in OBS_CONFIGS:
        # kernel-introspection A/B: the headline value is the telemetry
        # overhead (acceptance: within noise), the gate-off byte-
        # identical jits are the baseline
        stage(f"observability config {args.config}")
        tel_before = devtel_snapshot()
        res = OBS_CONFIGS[args.config](args)
        tel = devtel_delta(tel_before)
        if tel:
            res["device_telemetry"] = tel
        _STATE["metric"] = f"kernel-introspection {args.config}"
        emit({"metric": _STATE["metric"],
              "value": res.get("overhead_pct", 0.0), "unit": "%",
              "platform": _STATE["platform"],
              "baseline": "KernelIntrospect gate off (byte-identical "
                          "pre-introspection jits, interleaved rounds)",
              **res})
        return

    if args.config in REPLICATION_CONFIGS:
        # standalone replication config: 2-follower read scaling is the
        # headline, single-follower aggregate is the baseline
        stage(f"replication config {args.config}")
        tel_before = devtel_snapshot()
        res = REPLICATION_CONFIGS[args.config](args)
        tel = devtel_delta(tel_before)
        if tel:
            res["device_telemetry"] = tel
        _STATE["metric"] = f"replication {args.config}"
        emit({"metric": _STATE["metric"],
              "value": res.get("replica_read_scaling", 0.0), "unit": "x",
              "platform": _STATE["platform"],
              "baseline": "single follower aggregate filtered-list "
                          "throughput (same churn, same graph)",
              **res})
        return

    if args.config in SHARDING_CONFIGS:
        # standalone write-sharding config: 2-shard write scaling is the
        # headline, single shard-leader aggregate is the baseline
        stage(f"sharding config {args.config}")
        tel_before = devtel_snapshot()
        res = SHARDING_CONFIGS[args.config](args)
        tel = devtel_delta(tel_before)
        if tel:
            res["device_telemetry"] = tel
        _STATE["metric"] = f"write-sharding {args.config}"
        emit({"metric": _STATE["metric"],
              "value": res.get("write_shard_scaling", 0.0), "unit": "x",
              "platform": _STATE["platform"],
              "baseline": "single shard-leader aggregate dual-write "
                          "throughput (same churn profile, same "
                          "per-process core budget)",
              **res})
        return

    if args.config in MESH_CONFIGS:
        # standalone mesh config: largest-mesh paired scaling vs the
        # single-chip kernels is the headline (measured, not projected)
        stage(f"mesh config {args.config}")
        tel_before = devtel_snapshot()
        res = MESH_CONFIGS[args.config](args)
        tel = devtel_delta(tel_before)
        if tel:
            res["device_telemetry"] = tel
        _STATE["metric"] = f"mesh {args.config}"
        emit({"metric": _STATE["metric"],
              "value": res.get("mesh_scaling", 0.0), "unit": "x",
              "platform": _STATE["platform"],
              "baseline": "single-chip ell kernels over the same store "
                          "(interleaved rounds, same batches)",
              **res})
        return

    if args.config in SCENARIO_CONFIGS:
        # standalone scenario config: refereed divergences must be 0;
        # the headline value is the device check throughput under churn
        stage(f"scenario config {args.config}")
        tel_before = devtel_snapshot()
        tl_mark = timeline_mark()
        res = SCENARIO_CONFIGS[args.config](args)
        tel = devtel_delta(tel_before)
        if tel:
            res["device_telemetry"] = tel
        tl_sum = timeline_summary(tl_mark)
        if tl_sum:
            res["timeline_summary"] = tl_sum
        res.update(timeline_headline(tl_sum))
        _STATE["metric"] = f"scenario {args.config}"
        emit({"metric": _STATE["metric"],
              "value": res.get("checks_per_s", 0.0), "unit": "checks/s",
              "platform": _STATE["platform"],
              "baseline": "host-oracle referee over the same store "
                          "(parity acceptance: divergences == 0)",
              **res})
        return

    if args.config in PERSIST_CONFIGS:
        # standalone durable-store config: time-to-serve after restart
        stage(f"persist config {args.config}")
        tel_before = devtel_snapshot()
        tl_mark = timeline_mark()
        res = PERSIST_CONFIGS[args.config](args)
        tel = devtel_delta(tel_before)
        if tel:
            res["device_telemetry"] = tel
        tl_sum = timeline_summary(tl_mark)
        if tl_sum:
            res["timeline_summary"] = tl_sum
        res.update(timeline_headline(tl_sum))
        _STATE["metric"] = f"durable-store {args.config}"
        emit({"metric": _STATE["metric"],
              "value": res.get("time_to_serve_s", 0.0), "unit": "s",
              "platform": _STATE["platform"],
              "baseline": "in-memory proxy (full bootstrap re-ingest "
                          "on every restart, post-bootstrap writes lost)",
              **res})
        return

    from spicedb_kubeapi_proxy_tpu.models import workloads as wl

    def load_workload(name):
        fn_name, kw = CONFIGS[name]
        workload = getattr(wl, fn_name)(**kw)
        log(f"== config {name}: {len(workload.relationships)} tuples, "
            f"{len(workload.subjects)} subjects ==")
        return workload

    def run_one(name, with_oracle=True, rounds=None):
        workload = load_workload(name)
        tel_before = devtel_snapshot()
        tl_mark = timeline_mark()
        r = rounds if rounds is not None else args.rounds
        if args.direct_only:
            head = bench_jax(workload, args.batch, r)
            direct = head
        else:
            head = bench_concurrent(workload, args.batch, r)
            # re-use the already-built+compiled endpoint for the direct run
            direct = bench_jax(workload, args.batch, max(3, r // 2),
                               ep=head["endpoint"])
        log(f"{name} (dispatcher): {head['checks_per_s']:.3g} checks/s "
            f"({head['per_batch_s'] * 1000:.1f} ms / {args.batch} requests, "
            f"p99 {head['p99_s'] * 1000:.1f} ms)")
        log(f"{name} direct batch: {direct['checks_per_s']:.3g} checks/s "
            f"({direct['per_batch_s'] * 1000:.1f} ms, "
            f"p99 {direct['p99_s'] * 1000:.1f} ms)")
        # end-of-run device-telemetry snapshot rides the artifact for
        # EVERY config (HBM peak, recompiles, occupancy, per-bucket
        # kernel time), so BENCH_r*.json carries device numbers
        # alongside throughput
        tel = devtel_delta(tel_before)
        tl_sum = timeline_summary(tl_mark)
        if tl_sum:
            log(f"{name} timeline: overlap={tl_sum.get('overlap_ratio')} "
                f"roofline={tl_sum.get('roofline_fraction')} "
                f"stalls_s={tl_sum.get('stall_s')}")
        if name == args.config:
            # watchdog partials must only ever carry the headline config's
            # numbers — a sweep config's value under the headline metric
            # label would misattribute the workload
            _STATE["partial"].update({
                "value": round(head["checks_per_s"], 1),
                "p99_list_filter_ms": round(head["p99_s"] * 1000, 2),
                "direct_batch_checks_per_s": round(direct["checks_per_s"], 1),
                **({"device_telemetry": tel} if tel else {}),
                **({"timeline_summary": tl_sum} if tl_sum else {}),
                **timeline_headline(tl_sum),
            })
        else:
            # sweep numbers land in the artifact too (VERDICT r3 item 3)
            _STATE["partial"].setdefault("configs", {})[name] = {
                "checks_per_s": round(head["checks_per_s"], 1),
                "p99_ms": round(head["p99_s"] * 1000, 2),
                "direct_checks_per_s": round(direct["checks_per_s"], 1),
                "objects": head["objects"],
                **({"device_telemetry": tel} if tel else {}),
                **({"timeline_summary": tl_sum} if tl_sum else {}),
                **timeline_headline(tl_sum),
            }
        oracle_res = None
        if with_oracle:
            oracle_res = bench_oracle(workload, args.oracle_queries)
            log(f"oracle: {oracle_res['checks_per_s']:.3g} checks/s"
                f" ({oracle_res['per_query_s'] * 1000:.1f} ms / query)")
        return workload, head, direct, oracle_res

    cold_users_planned = (args.config == "multitenant-1m"
                          and not args.no_cold_users)

    # headline FIRST: if the watchdog fires mid-sweep, the partial payload
    # already carries the headline numbers (VERDICT r3 item 3 reordering)
    workload, head, direct, oracle_res = run_one(args.config)
    speedup = head["checks_per_s"] / max(oracle_res["checks_per_s"], 1e-9)
    payload = {
        "metric": _STATE["metric"],
        "value": round(head["checks_per_s"], 1),
        "unit": "checks/s",
        "vs_baseline": round(speedup, 2),
        "p99_list_filter_ms": round(head["p99_s"] * 1000, 2),
        "platform": _STATE["platform"],
        "objects": head["objects"],
        "batch": args.batch,
        "fused_lookups": head.get("fused_lookups"),
        "direct_batch_checks_per_s": round(direct["checks_per_s"], 1),
        "direct_batch_p99_ms": round(direct["p99_s"] * 1000, 2),
        "oracle_checks_per_s": round(oracle_res["checks_per_s"], 1),
        "baseline": "python-oracle",
        "baseline_note": BASELINE_NOTE,
    }
    if _STATE["partial"].get("device_telemetry"):
        payload["device_telemetry"] = _STATE["partial"]["device_telemetry"]
    if _STATE["partial"].get("timeline_summary"):
        # headline dispatch-timeline condensate: overlap fraction,
        # modeled roofline fraction, stall breakdown, worst dispatch
        payload["timeline_summary"] = _STATE["partial"]["timeline_summary"]
        payload.update(timeline_headline(payload["timeline_summary"]))
    # dispatcher overhead = headline round time minus the bare device batch
    payload["latency_breakdown_ms"] = {
        "dispatcher_round": round(head["per_batch_s"] * 1e3, 2),
        "direct_batch": round(direct["per_batch_s"] * 1e3, 2),
        "dispatcher_overhead": round(
            (head["per_batch_s"] - direct["per_batch_s"]) * 1e3, 2),
    }

    # roofline accounting on the headline endpoint (VERDICT r3 item 4)
    ep_head = head.get("endpoint") or direct.get("endpoint")
    if ep_head is not None:
        try:
            stage("roofline probe")
            payload["roofline"] = roofline_probe(ep_head, workload,
                                                 args.batch)
            payload["latency_breakdown_ms"].update({
                k: payload["roofline"][k]
                for k in ("device_time_ms", "transfer_est_ms",
                          "id_materialize_sample_ms")
                if k in payload["roofline"]})
            log(f"roofline: {payload['roofline']}")
        except Exception as e:
            log(f"roofline probe failed: {e!r}")
            payload["roofline"] = {"error": repr(e)}
        try:
            payload["sharded_comm_model"] = sharded_comm_model(
                ep_head, workload, args.batch)
        except Exception as e:
            payload["sharded_comm_model"] = {"error": repr(e)}
        try:
            payload["v5e8_projection"] = v5e8_projection(
                ep_head, workload, args.batch,
                payload.get("roofline", {}))
        except Exception as e:
            payload["v5e8_projection"] = {"error": repr(e)}
        ep_head = None  # release: the pops below are no-ops while this lives

    # -- sweep: every other config, fewer rounds, no oracle ------------------
    if args.all:
        # drop the headline endpoint before the sweep so its (possibly
        # 1M-tuple) graph doesn't stay live while sweep graphs build;
        # each sweep run's endpoint is scoped to its run_one call
        head.pop("endpoint", None)
        direct.pop("endpoint", None)
        for name in CONFIGS:
            if name == args.config:
                continue
            if name == "multitenant-1m-cold-users" and cold_users_planned:
                continue  # measured once, as the side-measurement below
            try:
                run_one(name, with_oracle=False,
                        rounds=max(3, args.rounds // 2))
            except Exception as e:  # keep the headline alive
                log(f"config {name} failed: {e!r}")
                _STATE["partial"].setdefault("configs", {})[name] = {
                    "error": repr(e)}
        # decision-cache + durable-store configs ride the sweep artifact
        # too (hit rate, on/off speedup, churn divergences, and the
        # restart time-to-serve + WAL write-overhead columns)
        for name, fn in {**CACHE_CONFIGS, **PERSIST_CONFIGS,
                         **PIPELINE_CONFIGS, **REPLICATION_CONFIGS,
                         **SHARDING_CONFIGS, **MESH_CONFIGS,
                         **SCENARIO_CONFIGS}.items():
            try:
                tel_before = devtel_snapshot()
                tl_mark = timeline_mark()
                res = fn(args)
                tel = devtel_delta(tel_before)
                if tel:
                    res["device_telemetry"] = tel
                tl_sum = timeline_summary(tl_mark)
                if tl_sum:
                    res["timeline_summary"] = tl_sum
                res.update(timeline_headline(tl_sum))
                _STATE["partial"].setdefault("configs", {})[name] = res
            except Exception as e:
                log(f"config {name} failed: {e!r}")
                _STATE["partial"].setdefault("configs", {})[name] = {
                    "error": repr(e)}
        payload["configs"] = _STATE["partial"].get("configs", {})
        # caveat-path health: within ~10x of the definite rbac path
        # (the headline config's number lives in payload["value"], not
        # the sweep table — read whichever slot holds each config)
        cfgs = payload["configs"]

        def value_of(name):
            if name == args.config:
                return payload["value"]
            return cfgs.get(name, {}).get("checks_per_s")

        definite, caveated = value_of("rbac-deny"), value_of("caveats-rbac")
        if definite and caveated:
            ratio = definite / max(caveated, 1e-9)
            payload["definite_over_caveated_ratio"] = round(ratio, 2)
            log(f"definite/caveated throughput ratio: {ratio:.2f} "
                f"(target <~10)")

    # VERDICT r2 item 9: measure the cold-users config (50% of querying
    # subjects have zero tuples) and record the warm/cold ratio — the
    # phantom-column path must show no cliff.
    if cold_users_planned:
        try:
            # free the warm 1M graph before building the cold one — holding
            # both doubles peak memory for nothing
            head.pop("endpoint", None)
            direct.pop("endpoint", None)
            cold_wl = load_workload("multitenant-1m-cold-users")
            cold = bench_jax(cold_wl, args.batch, max(3, args.rounds // 2))
            cold.pop("endpoint", None)
            ratio = direct["per_batch_s"] / max(cold["per_batch_s"], 1e-9)
            log(f"cold-users: {cold['checks_per_s']:.3g} checks/s "
                f"(warm/cold per-batch ratio {ratio:.2f}; "
                f"1.0 = no cliff)")
            payload["cold_users_checks_per_s"] = round(cold["checks_per_s"], 1)
            payload["cold_users_p99_ms"] = round(cold["p99_s"] * 1000, 2)
            payload["warm_over_cold_batch_time"] = round(ratio, 3)
        except Exception as e:
            log(f"cold-users run failed: {e!r}")
            payload["cold_users_error"] = repr(e)

    emit(payload)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException as e:  # never die without the JSON line
        import traceback
        traceback.print_exc(file=sys.stderr)
        emit_error(f"{type(e).__name__}: {e}")
        sys.exit(1)
