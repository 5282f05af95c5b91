"""Write-path soak: sustained mixed workload on the jax:// endpoint —
unique-name pod create/delete cycles (the normal kubernetes lifecycle),
fused lookups, bulk checks, and a live watch — tracking spare-pool
occupancy, rebuilds (now off-loop: sync vs background vs preemptive),
quarantined stale pairs, suppressions, RSS, and p99 drift per window.
Writes SOAK_r06.json by default.

Profiles:
  default        the r05 mix (2 writers, 3 lookers, 1 bulk checker)
  --churn        tail-latency hardening gate (ROADMAP item 4): heavier
                 sustained write churn (4 writers, no inter-op sleeps on
                 the write side) + list-heavy read traffic, sized to
                 drive the spare pool through preemptive background
                 rebuilds.

Pass/fail mode (--assert-slo): per-window p99 must stay within
max(2 x p50, --p99-floor-ms) and NO window may exceed --p99-cap-ms
(default 1000) — the "no rebuild-coincident multi-second spike"
acceptance gate.  The floor exists because at sub-ms p50 a 2x ratio is
noise, not a tail; the cap is absolute.

Run (real TPU):  PYTHONPATH=/root/repo python scripts/soak.py 1800
30-min churn:    python scripts/soak.py 1800 --churn --assert-slo
Quick CPU gate:  JAX_PLATFORMS=cpu python scripts/soak.py 24 --churn \
                     --graph small --window 6 --assert-slo --out /tmp/s.json

Every lookup/check runs inside a request trace (utils/tracing.py) and
each window dumps its slowest traces with per-phase span breakdowns
(queue_wait vs. kernel vs. extraction), so a p99 spike in a window is
attributable from the soak output alone.
"""

import argparse
import asyncio
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spicedb_kubeapi_proxy_tpu.models import workloads as wl
from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import Bootstrap, create_endpoint
from spicedb_kubeapi_proxy_tpu.utils import timeline, tracing
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    CheckRequest,
    ObjectRef,
    RelationshipUpdate,
    SubjectRef,
    UpdateOp,
    parse_relationship,
)


def rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def parse_args():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("duration", nargs="?", type=float, default=1800.0,
                   help="soak duration in seconds (default 1800)")
    p.add_argument("--churn", action="store_true",
                   help="sustained write churn + list-heavy profile "
                        "(tail-latency hardening gate)")
    p.add_argument("--graph", choices=["1m", "small"], default="1m",
                   help="workload: multitenant-1m (default) or the "
                        "small pods-depth1 graph for the fast CPU gate")
    p.add_argument("--window", type=float,
                   default=float(os.environ.get("SOAK_WINDOW_S", 300.0)),
                   help="reporting window seconds (default 300)")
    p.add_argument("--open-loop", action="store_true",
                   help="coordinated-omission-free read side: lookups "
                        "fire on a FIXED precomputed schedule (same "
                        "average rate as the closed-loop lookers) and "
                        "each latency is charged from the INTENDED "
                        "send time, so a stall's queued victims are "
                        "measured instead of silently delayed; "
                        "scheduler lag is exported as "
                        "authz_loadgen_lag_seconds "
                        "(docs/performance.md \"Fleet topology bench\")")
    p.add_argument("--assert-slo", action="store_true",
                   help="exit 1 unless every window holds p99 <= "
                        "max(2 x p50, --p99-floor-ms) and "
                        "p99 <= --p99-cap-ms, with zero worker errors")
    p.add_argument("--p99-floor-ms", type=float, default=50.0,
                   help="absolute floor under which the 2x-p50 ratio "
                        "check is waived (sub-ms p50s make the ratio "
                        "noise, not a tail)")
    p.add_argument("--p99-cap-ms", type=float, default=1000.0,
                   help="no window may exceed this p99 (ms)")
    p.add_argument("--out", default=os.environ.get("SOAK_OUT",
                                                   "SOAK_r06.json"),
                   help="output artifact path (default SOAK_r06.json)")
    return p.parse_args()


def main():
    args = parse_args()
    w = wl.multitenant_1m() if args.graph == "1m" else wl.pods_depth1()
    t0 = time.time()
    ep = create_endpoint("jax://", Bootstrap(schema_text=w.schema_text))
    ep.store.bulk_load([parse_relationship(r) for r in w.relationships])
    inner = getattr(ep, "inner", ep)
    # warm start BEFORE the workload: the initial graph compile and the
    # pow-2 bucket-ladder jit compiles are startup cost in production
    # (server warm start does exactly this) — without it window 1 just
    # measures compile latency instead of steady-state tails
    t_warm = time.time()
    inner.warm_start(prewarm=True)
    print(f"loaded {len(w.relationships)} tuples in {time.time()-t0:.1f}s "
          f"(warm start {time.time()-t_warm:.1f}s, "
          f"profile={'churn' if args.churn else 'default'} "
          f"graph={args.graph})", flush=True)

    n_writers = 4 if args.churn else 2
    n_lookers = 6 if args.churn else 3
    write_pause = 0.0 if args.churn else 0.05
    look_pause = 0.05 if args.churn else 0.2

    stop = asyncio.Event()
    lookup_lat: list = []      # seconds within current window
    windows: list = []
    counters = {"creates": 0, "deletes": 0, "lookups": 0, "checks": 0,
                "watch_events": 0, "errors": 0}
    min_pool: dict = {}

    def pool_snapshot():
        with inner._lock:
            for t, pool in inner._spare_pool.items():
                free = len(pool)
                if t not in min_pool or free < min_pool[t]:
                    min_pool[t] = free

    async def writer(wid: int):
        k = 0
        while not stop.is_set():
            name = f"soak-{wid}-{k}"
            try:
                await ep.write_relationships([RelationshipUpdate(
                    UpdateOp.TOUCH, parse_relationship(
                        f"pod:ns{k % 2000}/{name}#creator@user:u{wid}"))])
                counters["creates"] += 1
                await asyncio.sleep(0.02)
                await ep.write_relationships([RelationshipUpdate(
                    UpdateOp.DELETE, parse_relationship(
                        f"pod:ns{k % 2000}/{name}#creator@user:u{wid}"))])
                counters["deletes"] += 1
            except Exception as e:
                counters["errors"] += 1
                print(f"writer error: {e!r}", flush=True)
            pool_snapshot()
            k += 1
            await asyncio.sleep(write_pause)

    async def looker(i: int):
        while not stop.is_set():
            sub = SubjectRef("user", w.subjects[(i * 37) % len(w.subjects)])
            t = time.perf_counter()
            try:
                with tracing.request_trace(op="lookup", subject=sub.id) as tr:
                    ids = await ep.lookup_resources("pod", "view", sub)
                tracing.RECORDER.record(tr)
                lookup_lat.append(time.perf_counter() - t)
                counters["lookups"] += 1
                assert not any("\x00" in x for x in ids)
            except Exception as e:
                counters["errors"] += 1
                print(f"looker error: {e!r}", flush=True)
            await asyncio.sleep(look_pause)

    sched_lag = {"max_ms": 0.0, "scheduled": 0}

    async def open_loop_driver():
        """--open-loop replacement for the lookers: the whole read-side
        schedule is laid out up front (loadgen.WorkloadSpec, zipfian
        subject fan-in) and each lookup fires at its intended time
        whether or not earlier ones returned — latency is charged from
        the INTENDED send, the coordinated-omission fix."""
        from spicedb_kubeapi_proxy_tpu.utils import loadgen

        # offered rate matched to the closed-loop lookers' upper bound
        # so the two modes are comparable on the same profile
        rate = n_lookers / max(look_pause, 0.02)
        spec = loadgen.WorkloadSpec(
            seed=7, duration_s=args.duration, rate_per_s=rate,
            users=max(2, len(w.subjects)),
            verb_mix=(("filter", 1.0),))
        schedule = spec.schedule()
        sched_lag["scheduled"] = len(schedule)
        print(f"open-loop: {len(schedule)} lookups scheduled at "
              f"{rate:.1f}/s", flush=True)

        async def one(ev, intended):
            sub = SubjectRef("user", w.subjects[
                (int(ev["user"][1:]) - 1) % len(w.subjects)])
            try:
                with tracing.request_trace(op="lookup",
                                           subject=sub.id) as tr:
                    ids = await ep.lookup_resources("pod", "view", sub)
                tracing.RECORDER.record(tr)
                lookup_lat.append(time.perf_counter() - intended)
                counters["lookups"] += 1
                assert not any("\x00" in x for x in ids)
            except Exception as e:
                counters["errors"] += 1
                print(f"looker error: {e!r}", flush=True)

        t0 = time.perf_counter()
        tasks: list = []
        for ev in schedule:
            if stop.is_set():
                break
            delay = t0 + ev["t"] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            intended = t0 + ev["t"]
            lag = max(0.0, time.perf_counter() - intended)
            if lag * 1e3 > sched_lag["max_ms"]:
                sched_lag["max_ms"] = round(lag * 1e3, 3)
            loadgen.LAG_GAUGE.set(lag)
            tasks.append(asyncio.ensure_future(one(ev, intended)))
            tasks = [t for t in tasks if not t.done()]
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)

    async def checker():
        while not stop.is_set():
            try:
                reqs = [CheckRequest(
                    ObjectRef("pod", f"ns{j % 2000}/p{j}"), "view",
                    SubjectRef("user", w.subjects[j % len(w.subjects)]))
                    for j in range(16)]
                with tracing.request_trace(op="check_bulk", batch=16) as tr:
                    await ep.check_bulk_permissions(reqs)
                tracing.RECORDER.record(tr)
                counters["checks"] += 16
            except Exception as e:
                counters["errors"] += 1
                print(f"checker error: {e!r}", flush=True)
            await asyncio.sleep(0.5)

    async def watcher():
        wtc = ep.watch(["pod"])
        try:
            while not stop.is_set():
                upd = await wtc.next(timeout=1.0)
                if upd is not None:
                    counters["watch_events"] += len(upd.updates)
        finally:
            wtc.close()

    async def reporter():
        start = time.time()
        last = start
        window_mark = timeline.now()
        while not stop.is_set():
            await asyncio.sleep(min(5, args.window / 3))
            now = time.time()
            if now - last >= args.window or (stop.is_set() and lookup_lat):
                lat = sorted(lookup_lat)
                lookup_lat.clear()
                last = now
                # per-window dispatch-timeline condensate: overlap
                # fraction, roofline fraction, stall-cause breakdown,
                # worst dispatch — a p99 spike window names its stall
                # (rebuild vs transfer vs compile) from the soak output
                tl_sum = timeline.summary(since=window_mark)
                window_mark = timeline.now()
                st = dict(inner.stats)
                windows.append({
                    "t_s": round(now - start, 1),
                    "lookups": len(lat),
                    "p50_ms": round(lat[len(lat) // 2] * 1e3, 1) if lat else None,
                    "p99_ms": round(lat[int(len(lat) * 0.99)] * 1e3, 1) if lat else None,
                    "rss_mb": round(rss_mb(), 1),
                    "rebuilds": st.get("rebuilds"),
                    "bg_rebuilds": st.get("bg_rebuilds"),
                    "preemptive_rebuilds": st.get("preemptive_rebuilds"),
                    "rebuild_failures": st.get("rebuild_failures"),
                    "stale_pair_marks": st.get("stale_pair_marks"),
                    "stale_routed": st.get("stale_routed"),
                    "spare_assignments": st.get("spare_assignments"),
                    "spare_reclaims": st.get("spare_reclaims"),
                    "placeholder_suppressed": st.get("placeholder_suppressed", 0),
                    "suppression_oracle_fallbacks": st.get(
                        "suppression_oracle_fallbacks", 0),
                    "counters": dict(counters),
                    # the window's slowest op traces, spans included —
                    # a p99 spike names its own phase (queue vs kernel
                    # vs extraction) instead of needing a re-run
                    "slow_traces": tracing.RECORDER.drain()[:3],
                    "timeline": tl_sum,
                })
                print(f"window {len(windows)}: {windows[-1]}", flush=True)

    async def run():
        read_side = ([open_loop_driver()] if args.open_loop
                     else [looker(i) for i in range(n_lookers)])
        tasks = [asyncio.ensure_future(x) for x in (
            *[writer(i) for i in range(n_writers)],
            *read_side,
            checker(), watcher(), reporter())]
        await asyncio.sleep(args.duration)
        stop.set()
        await asyncio.gather(*tasks, return_exceptions=True)

    t_run = time.time()
    asyncio.run(run())
    # quiesce in-flight background rebuilds before the final stats read
    wait = getattr(inner, "wait_rebuilds", None)
    if wait is not None:
        wait(timeout=60)
    st = dict(inner.stats)
    warmup_rebuilds = windows[0]["rebuilds"] if windows else st.get("rebuilds")

    slo_failures = []
    if args.assert_slo:
        for i, win in enumerate(windows):
            p50, p99 = win["p50_ms"], win["p99_ms"]
            if p99 is None:
                slo_failures.append(f"window {i + 1}: no lookups completed")
                continue
            if p99 > args.p99_cap_ms:
                slo_failures.append(
                    f"window {i + 1}: p99 {p99}ms > cap {args.p99_cap_ms}ms")
            if p99 > max(2 * (p50 or 0.0), args.p99_floor_ms):
                slo_failures.append(
                    f"window {i + 1}: p99 {p99}ms > "
                    f"max(2 x p50 {p50}ms, floor {args.p99_floor_ms}ms)")
        if not windows:
            slo_failures.append("no windows recorded (duration too short "
                                "for --window?)")
        if counters["errors"]:
            slo_failures.append(f"{counters['errors']} worker errors")

    import jax  # already initialized by the jax:// endpoint

    final = {
        "duration_s": round(time.time() - t_run, 1),
        "platform": jax.devices()[0].platform,
        "profile": "churn" if args.churn else "default",
        "graph": args.graph,
        "window_s": args.window,
        "open_loop": args.open_loop,
        "loadgen": ({"scheduled": sched_lag["scheduled"],
                     "max_sched_lag_ms": sched_lag["max_ms"]}
                    if args.open_loop else None),
        "windows": windows,
        "final_stats": {k: v for k, v in st.items()
                        if isinstance(v, (int, float))},
        "min_spare_pool_free": min_pool,
        "counters": counters,
        "rss_mb_final": round(rss_mb(), 1),
        # whole-run dispatch-timeline condensate (ring-bounded: covers
        # the most recent events; per-window views live in windows[])
        "timeline_summary": timeline.summary(),
        "verdict": {
            "rebuilds_after_warmup": (st.get("rebuilds", 0)
                                      - (warmup_rebuilds or 0)),
            "bg_rebuilds": st.get("bg_rebuilds", 0),
            "preemptive_rebuilds": st.get("preemptive_rebuilds", 0),
            "rebuild_failures": st.get("rebuild_failures", 0),
            "stale_pair_marks": st.get("stale_pair_marks", 0),
            "placeholder_suppressed": st.get("placeholder_suppressed", 0),
            "suppression_oracle_fallbacks": st.get(
                "suppression_oracle_fallbacks", 0),
            "errors": counters["errors"],
            "rss_flat": (len(windows) < 2
                         or windows[-1]["rss_mb"] - windows[1]["rss_mb"]
                         < 256),
            "slo_pass": not slo_failures if args.assert_slo else None,
            "slo_failures": slo_failures,
        },
    }
    with open(args.out, "w") as f:
        json.dump(final, f, indent=1)
    print(json.dumps(final["verdict"]), flush=True)
    print(f"wrote {args.out}", flush=True)
    if args.assert_slo and slo_failures:
        print("soak: SLO GATE FAILED:\n  " + "\n  ".join(slo_failures),
              file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
