"""Device-telemetry smoke: start the proxy, drive traffic, scrape
/metrics + /debug/flight, and fail loudly on any missing telemetry
family (wired into scripts/check.sh; fast, CPU-only, no TPU).

What it proves end to end:
- the server starts with the flight recorder + SLO tracker wired;
- `/metrics` carries the device-telemetry families (`authz_device_bytes`,
  `authz_batch_occupancy`, `authz_jit_cache_*`, `authz_slo_burn_rate`);
- `/debug/flight` returns >= 2 windows of snapshots after a warm-up;
- `/debug/timeline` serves valid chrome-trace JSON (every event has
  ph/ts/pid/tid, B/E pairing balanced) with >= 1 dispatch slice;
- the `/debug` index enumerates every debug surface uniformly;
- with the device-resident pipeline enabled (DevicePipeline gate on,
  `jax://?pipeline_depth=3`), concurrent per-user list requests fan
  into multiple fused batches and `authz_dispatch_overlap_ratio` goes
  positive, while `stall{cause=pack|transpose}` stays ~0 relative to
  kernel time (the host encode/word-transpose moved on-device);
- kernel introspection & workload attribution: after real mixed
  traffic the measured sweep histograms carry samples, `/debug/workload`
  attributes device seconds per (type, permission) and its total
  reconciles with the cumulative `ready` stage of
  `authz_dispatch_stage_seconds` within 5%,
  and `/debug/profile` returns non-empty collapsed stacks;
- admission control (second server, `--shed-queue-depth` +
  `jax://?max_queue_depth=`): driving concurrent read waves past the
  queue bound yields kube-style 429 Status responses carrying a
  `Retry-After` header, `authz_admission_rejected_total` increments,
  and `/readyz` reports the shedding as degraded-but-200 (docs/
  performance.md "Overload & rebuild behavior").
"""

import asyncio
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from spicedb_kubeapi_proxy_tpu.kubefake.apiserver import (  # noqa: E402
    FakeKubeApiServer)
from spicedb_kubeapi_proxy_tpu.proxy.httpcore import (  # noqa: E402
    HandlerTransport)
from spicedb_kubeapi_proxy_tpu.proxy.server import (  # noqa: E402
    Options, ProxyServer)
from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import Bootstrap  # noqa: E402
from spicedb_kubeapi_proxy_tpu.spicedb.types import (  # noqa: E402
    parse_relationship)
from spicedb_kubeapi_proxy_tpu.utils.features import GATES  # noqa: E402

# This smoke measures the device pipeline itself (fused-batch overlap,
# admission shedding against real kernel windows): keep the Leopard
# index out so nested lookups sweep instead of serving from the closure
# plane.  The /debug/workload leopard field still surfaces through the
# detector fallback (candidate | ineligible(unplanned)); the indexed
# path is exercised by tests/test_leopard.py and the live e2e driver.
GATES.set("LeopardIndex", False)

SCHEMA = """
definition user {}

definition namespace {
    relation creator: user
    permission view = creator
}

definition pod {
    relation creator: user
    relation namespace: namespace
    permission view = creator + namespace->view
}
"""

RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata: {name: list-pods}
match: [{apiVersion: v1, resource: pods, verbs: [list]}]
prefilter:
- fromObjectIDNameExpr: "{{split_name(resourceId)}}"
  fromObjectIDNamespaceExpr: "{{split_namespace(resourceId)}}"
  lookupMatchingResources: {tpl: "pod:$#view@user:{{user.name}}"}
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata: {name: get-pods}
match: [{apiVersion: v1, resource: pods, verbs: [get]}]
check: [{tpl: "pod:{{namespacedName}}#view@user:{{user.name}}"}]
"""

REQUIRED_FAMILIES = (
    "authz_device_bytes",
    "authz_device_bytes_peak",
    "authz_batch_occupancy",
    "authz_jit_cache_hits_total",
    "authz_jit_cache_misses_total",
    "authz_jit_cache_entries",
    "authz_slo_burn_rate",
    # stages of each fused device call (utils/tracing.py)
    "authz_dispatch_stage_seconds",
    # dispatch timeline (utils/timeline.py)
    "authz_dispatch_bandwidth_bytes_per_sec",
    "authz_roofline_fraction",
    "authz_dispatch_overlap_ratio",
    # kernel introspection & workload attribution (utils/workload.py)
    "authz_sweep_iterations",
    "authz_frontier_decay",
)

# stages that prove a real device dispatch landed on the timeline
DISPATCH_SLICES = ("kernel", "transfer", "transpose", "pack")


def validate_chrome_trace(trace: dict) -> list:
    """Chrome trace-event schema check: every event needs ph/ts/pid/tid
    (X additionally dur), and B/E pairs must balance per (pid, tid).
    Returns the dispatch-stage slices.  tests/test_timeline.py keeps an
    independent copy BY HAND (this script's module level sets env vars
    and imports jax — importing it from the test suite would drag those
    side effects in); schema changes must land in both."""
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        fail(f"/debug/timeline has no traceEvents list: {list(trace)}")
    depth: dict = {}
    slices = []
    for ev in events:
        for field in ("ph", "ts", "pid", "tid"):
            if field not in ev:
                fail(f"timeline event missing {field!r}: {ev}")
        if ev["ph"] == "X":
            if "dur" not in ev:
                fail(f"X event missing dur: {ev}")
            if ev["name"] in DISPATCH_SLICES:
                slices.append(ev)
        elif ev["ph"] == "B":
            depth[(ev["pid"], ev["tid"])] = (
                depth.get((ev["pid"], ev["tid"]), 0) + 1)
        elif ev["ph"] == "E":
            key = (ev["pid"], ev["tid"])
            depth[key] = depth.get(key, 0) - 1
            if depth[key] < 0:
                fail(f"unbalanced E event (no open B) on {key}")
    open_tracks = {k: v for k, v in depth.items() if v}
    if open_tracks:
        fail(f"unbalanced B/E pairs at end of trace: {open_tracks}")
    return slices


def fail(msg: str) -> None:
    print(f"devtel_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


async def main() -> None:
    kube = FakeKubeApiServer()
    for i in range(8):
        kube.seed("", "v1", "pods",
                  {"metadata": {"name": f"p{i}", "namespace": "team-a"}})
    # max_batch=4 + pipeline_depth=3: the concurrent per-user wave below
    # must split into several fused batches so the drain keeps started
    # batches in flight (the overlap assertion needs >= 2 batches whose
    # kernel/readback windows can interleave)
    server = ProxyServer(Options(
        spicedb_endpoint="jax://?max_batch=4&pipeline_depth=3",
        bootstrap=Bootstrap(schema_text=SCHEMA),
        rules_yaml=RULES,
        upstream_transport=HandlerTransport(kube),
        flight_window_s=0.15,
        flight_windows=16,
        slo_check_p99_ms=250.0,
        slo_objective=0.01,
    ))
    users = [f"u{j}" for j in range(12)]
    rels = ["namespace:team-a#creator@user:alice"] + [
        f"pod:team-a/p{i}#creator@user:alice" for i in range(0, 8, 2)] + [
        f"pod:team-a/p{i}#creator@user:{u}"
        for i in range(8) for u in users[i % 3::3]] + [
        # graph ballast (not in the fake kube, filtered from responses):
        # widens the lookup slot so each fused kernel's window is long
        # enough for the overlap assertion below to be deterministic on
        # the CPU backend — without it the sub-ms kernels finish before
        # the drain can dispatch the next batch
        f"pod:team-a/ballast{i}#creator@user:{users[i % len(users)]}"
        for i in range(30_000)]
    server.endpoint.store.bulk_load([parse_relationship(r) for r in rels])

    await server.start("127.0.0.1", 0)
    try:
        alice = server.get_embedded_client(user="alice")
        for _ in range(6):
            resp = await alice.get("/api/v1/pods")
            assert resp.status == 200, resp.body
        resp = await alice.get("/api/v1/namespaces/team-a/pods/p0")
        assert resp.status == 200, resp.body
        # >= 2 flight windows after the warm-up
        await asyncio.sleep(0.5)

        resp = await alice.get("/metrics")
        if resp.status != 200:
            fail(f"/metrics -> {resp.status}")
        text = resp.body.decode()
        missing = [f for f in REQUIRED_FAMILIES
                   if f"# TYPE {f} " not in text]
        if missing:
            fail(f"/metrics missing device-telemetry families: {missing}")
        if "authz_device_bytes{" not in text:
            fail("authz_device_bytes has no kind-labeled samples "
                 "(HBM ledger never registered a buffer)")
        if 'authz_slo_burn_rate{slo="latency_p99"' not in text:
            fail("authz_slo_burn_rate has no latency_p99 samples "
                 "(SLO evaluator never ran)")

        resp = await alice.get("/debug/flight")
        if resp.status != 200:
            fail(f"/debug/flight -> {resp.status}")
        flight = json.loads(resp.body)
        if len(flight.get("windows", [])) < 2:
            fail(f"/debug/flight returned "
                 f"{len(flight.get('windows', []))} windows, want >= 2")
        newest = flight["windows"][0]
        for field in ("http", "hbm", "occupancy", "jit", "slo"):
            if field not in newest:
                fail(f"flight window missing {field!r}: {newest}")
        if newest["hbm"]["total"] <= 0:
            fail("flight window reports an empty HBM ledger after "
                 "kernel traffic")

        resp = await alice.get("/debug/timeline")
        if resp.status != 200:
            fail(f"/debug/timeline -> {resp.status}")
        trace = json.loads(resp.body)
        slices = validate_chrome_trace(trace)
        if not slices:
            fail(f"/debug/timeline has no dispatch slices "
                 f"({len(trace.get('traceEvents', []))} events, none named "
                 f"{DISPATCH_SLICES})")
        summary = trace.get("otherData", {}).get("summary", {})
        if not summary.get("events"):
            fail(f"/debug/timeline summary is empty: {summary}")
        win = flight["windows"][0]
        if "timeline" not in win or "slow_traces" not in win:
            fail(f"flight window missing timeline/slow_traces evidence "
                 f"links: {sorted(win)}")

        # -- device-resident pipeline: overlap > 0, pack/transpose ~ 0 --
        # waves of concurrent per-user lists (distinct subjects, so the
        # singleflight dedup can't collapse them) fan into >= 3 fused
        # batches at max_batch=4; the pipelined drain keeps started
        # batches in flight, so some batch's readback must land inside
        # another batch's kernel window.  A couple of retry waves absorb
        # scheduler noise on the tiny CPU smoke graph.
        clients = [server.get_embedded_client(user=u) for u in users]
        overlap = 0.0
        for _ in range(6):
            waved = await asyncio.gather(
                *[c.get("/api/v1/pods") for c in clients])
            for r in waved:
                assert r.status == 200, r.body
            resp = await alice.get("/metrics")
            text = resp.body.decode()
            for line in text.splitlines():
                if line.startswith("authz_dispatch_overlap_ratio "):
                    overlap = float(line.split()[1])
            if overlap > 0:
                break
        if overlap <= 0:
            fail("authz_dispatch_overlap_ratio stayed 0 after 6 "
                 "concurrent waves with the pipeline enabled — the "
                 "pipelined drain is not overlapping readback with the "
                 "next batch's kernel")
        resp = await alice.get("/debug/timeline")
        summary = json.loads(resp.body).get("otherData", {}).get(
            "summary", {})
        stalls = summary.get("stall_s", {})
        kernel_ms = summary.get("stage_ms", {}).get("kernel", 0.0)
        if kernel_ms <= 0:
            fail(f"timeline summary has no kernel stage time: {summary}")
        host_prep = stalls.get("pack", 0.0) + stalls.get("transpose", 0.0)
        if host_prep > 0.2 * kernel_ms / 1e3:
            fail(f"stall{{cause=pack|transpose}} = {host_prep:.4f}s vs "
                 f"kernel {kernel_ms:.1f}ms — host query prep crept back "
                 f"onto the hot path (device-resident pipeline regression; "
                 f"see lint M003)")

        # -- workload attribution & profiling ------------------------
        # the waves above pushed real check + lookup traffic through
        # the kernels: the measured sweep histograms must carry samples
        resp = await alice.get("/metrics")
        text = resp.body.decode()
        if "authz_sweep_iterations_bucket{" not in text:
            fail("authz_sweep_iterations has no samples after kernel "
                 "traffic (sweep telemetry never read back a trace)")
        resp = await alice.get("/debug/workload")
        if resp.status != 200:
            fail(f"/debug/workload -> {resp.status}")
        wl = json.loads(resp.body)
        if not wl.get("enabled"):
            fail(f"/debug/workload reports disabled: {wl}")
        pairs = {(r["resource_type"], r["permission"]): r
                 for r in wl.get("rows", [])}
        pod_view = pairs.get(("pod", "view"))
        if not pod_view:
            fail(f"/debug/workload has no (pod, view) row: {sorted(pairs)}")
        if pod_view["kernel_rows"] + pod_view["oracle_rows"] <= 0:
            fail(f"(pod, view) row attributes no routed rows: {pod_view}")
        # every row must carry the Leopard per-pair status verdict
        # (`indexed | indexed(quarantined) | candidate |
        # ineligible(reason)` — ops/leopard.py status_map plus the
        # detector fallback), and the candidate list must be present
        for row in wl.get("rows", []):
            leo = row.get("leopard")
            if not (leo in ("indexed", "indexed(quarantined)", "candidate")
                    or (isinstance(leo, str)
                        and leo.startswith("ineligible("))):
                fail(f"/debug/workload row has no actionable leopard "
                     f"status: {row}")
        if "leopard_candidates" not in wl:
            fail(f"/debug/workload payload missing leopard_candidates: "
                 f"{sorted(wl)}")
        # total device seconds must reconcile with the cumulative
        # `ready` stage of the dispatch-stage histogram (the same span's
        # seconds) within 5%
        metric_s = 0.0
        for line in text.splitlines():
            if (line.startswith("authz_dispatch_stage_seconds_sum{")
                    and 'stage="ready"' in line):
                metric_s += float(line.split()[-1])
        total_s = wl.get("total_device_s", 0.0)
        if metric_s <= 0 or total_s <= 0:
            fail(f"no device seconds to reconcile (metric {metric_s}, "
                 f"workload {total_s})")
        if abs(total_s - metric_s) > 0.05 * metric_s:
            fail(f"/debug/workload total_device_s {total_s:.4f}s does not "
                 f"reconcile with authz_dispatch_stage_seconds{{stage="
                 f"\"ready\"}} {metric_s:.4f}s "
                 f"(> 5% apart)")
        resp = await alice.get("/debug/profile?seconds=0.2")
        if resp.status != 200:
            fail(f"/debug/profile -> {resp.status}")
        prof = json.loads(resp.body)
        if not prof.get("enabled"):
            fail(f"/debug/profile reports disabled: {prof}")
        if prof.get("samples", 0) <= 0 or not prof.get("collapsed"):
            fail(f"/debug/profile captured nothing: samples="
                 f"{prof.get('samples')}, "
                 f"{len(prof.get('collapsed', []))} collapsed stacks")
        if not prof.get("chrome_trace", {}).get("traceEvents"):
            fail("/debug/profile chrome_trace is empty")

        resp = await alice.get("/debug")
        if resp.status != 200:
            fail(f"/debug -> {resp.status}")
        surfaces = json.loads(resp.body).get("surfaces", {})
        for path in ("/debug/traces", "/debug/decisions", "/debug/flight",
                     "/debug/timeline", "/debug/workload", "/debug/profile"):
            if path not in surfaces:
                fail(f"/debug index missing {path}: {surfaces}")
        resp = await alice.get("/debug/nonesuch")
        if resp.status != 404:
            fail(f"/debug/nonesuch -> {resp.status}, want uniform 404")
        resp = await alice.get("/readyz")
        if resp.status != 200 or not resp.body.startswith(b"ok"):
            fail(f"/readyz -> {resp.status} {resp.body!r}")
    finally:
        await server.stop()

    rejected = await overload_smoke(kube)
    print("devtel_smoke: OK (device-telemetry families present, "
          f"{len(flight['windows'])} flight windows, "
          f"{len(slices)} timeline dispatch slices, "
          f"pipeline overlap {overlap:.3f}, "
          f"workload attribution reconciled "
          f"({total_s:.4f}s vs {metric_s:.4f}s), "
          f"{prof['samples']} profile samples, "
          f"{rejected} overload rejections)")


def _metric_value(text: str, prefix: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(prefix):
            total += float(line.split()[-1])
    return total


async def overload_smoke(kube) -> int:
    """Drive a bounded-queue proxy past capacity: reads must shed with
    429 + Retry-After (never hang), the admission counter must count
    every rejection, and /readyz must report degraded-but-200."""
    server = ProxyServer(Options(
        # tight bounds so a 12-wide concurrent wave reliably overflows:
        # each fused batch carries at most 2 queries and at most 4 more
        # may queue (the 4-deep backlog persists across several kernel
        # windows, giving the door shedder a visible depth); the
        # shedder additionally rejects reads at the door once anything
        # is queued
        spicedb_endpoint="jax://?max_batch=2&max_queue_depth=4",
        bootstrap=Bootstrap(schema_text=SCHEMA),
        rules_yaml=RULES,
        upstream_transport=HandlerTransport(kube),
        shed_queue_depth=1,
        shed_retry_after_s=7.0,
    ))
    users = [f"u{j}" for j in range(12)]
    rels = ["namespace:team-a#creator@user:alice"] + [
        # ballast widens the kernel window so queue depth actually
        # builds while a batch is in flight (same trick as above)
        f"pod:team-a/ballast{i}#creator@user:{users[i % len(users)]}"
        for i in range(30_000)]
    server.endpoint.store.bulk_load([parse_relationship(r) for r in rels])
    await server.start("127.0.0.1", 0)
    try:
        alice = server.get_embedded_client(user="alice")
        base = _metric_value(
            (await alice.get("/metrics")).body.decode(),
            "authz_admission_rejected_total")
        clients = [server.get_embedded_client(user=u) for u in users]
        shed = []
        door_shed = 0
        for _ in range(8):
            # two staggered waves: the first saturates the dispatcher
            # queues (its overflow 429s exercise the queue bound), the
            # second arrives while the first is still queued so its
            # door checks see non-zero depth and the LOAD SHEDDER
            # rejects before any authorization work (what /readyz
            # below must report)
            first = [asyncio.ensure_future(c.get("/api/v1/pods"))
                     for c in clients]
            await asyncio.sleep(0.01)
            second = [asyncio.ensure_future(c.get("/api/v1/pods"))
                      for c in clients]
            waved = await asyncio.wait_for(
                asyncio.gather(*first, *second), timeout=60)
            for r in waved:
                if r.status == 429:
                    shed.append(r)
                elif r.status != 200:
                    fail(f"overload wave: unexpected status {r.status}: "
                         f"{r.body[:200]}")
            door_shed = server.shedder.snapshot()["shed_total"]
            if shed and door_shed:
                break
        if not shed:
            fail("8 staggered double read waves against "
                 "max_queue_depth=4 + shed_queue_depth=1 produced no "
                 "429 — admission control is not engaging")
        if not door_shed:
            fail("429s came only from the dispatcher queue bound; the "
                 "load shedder never rejected at the door "
                 "(shed_queue_depth=1 with requests queued)")
        for r in shed:
            ra = r.headers.get("Retry-After")
            if not ra or int(ra) < 1:
                fail(f"429 without a usable Retry-After header: {ra!r}")
            status = json.loads(r.body)
            if (status.get("kind") != "Status"
                    or status.get("reason") != "TooManyRequests"
                    or status.get("code") != 429):
                fail(f"429 body is not a kube TooManyRequests Status: "
                     f"{status}")
        text = (await alice.get("/metrics")).body.decode()
        now = _metric_value(text, "authz_admission_rejected_total")
        if now - base < len(shed):
            fail(f"authz_admission_rejected_total rose {now - base:.0f} "
                 f"but {len(shed)} requests were rejected")
        resp = await alice.get("/readyz")
        if resp.status != 200:
            fail(f"/readyz during shedding -> {resp.status}, want "
                 "degraded-but-200 (shedding is backpressure, not an "
                 "outage)")
        body = resp.body.decode()
        if "shedding" not in body:
            fail(f"/readyz does not report recent shedding: {body!r}")
        # the system must drain, not wedge: a quiet follow-up succeeds
        await asyncio.sleep(0.2)
        resp = await alice.get("/api/v1/pods")
        if resp.status != 200:
            fail(f"post-overload request -> {resp.status}, want 200 "
                 "(queues must drain after the wave passes)")
        return len(shed)
    finally:
        await server.stop()


if __name__ == "__main__":
    asyncio.run(main())
