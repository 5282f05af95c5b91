"""The stage spans of one fused device call (utils/tracing.py `span`,
docs/observability.md "Dispatch stages"): each stage of a fused lookup
and a fused check is observed once per call into
authz_dispatch_stage_seconds under the call's kind, however many
requests rode the call, and lands in every co-batched request's trace;
every span enters a profiler TraceAnnotation named after its stage."""

import asyncio
import os

from spicedb_kubeapi_proxy_tpu.spicedb.dispatch import BatchingEndpoint
from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import (
    Bootstrap,
    create_endpoint,
)
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    CheckRequest,
    ObjectRef,
    SubjectRef,
    parse_relationship,
)
from spicedb_kubeapi_proxy_tpu.utils import metrics as m
from spicedb_kubeapi_proxy_tpu.utils import timeline, tracing
from spicedb_kubeapi_proxy_tpu.utils.features import GATES

SCHEMA = """
definition user {}
definition doc {
  relation viewer: user
  relation editor: user
  permission view = viewer + editor
}
"""

N_CALLERS = 5

# the stages a pipelined fused call passes through, per kind
LOOKUP_STAGES = ("pack", "launch", "ready", "transfer", "wait", "extract")
CHECK_STAGES = ("pack", "launch", "ready", "transfer", "wait", "assemble")


def make_endpoint():
    """jax:// behind the dispatcher, with the closure-plane index off so
    every lookup and check runs the fixpoint kernel."""
    prev = GATES.enabled("LeopardIndex")
    GATES.set("LeopardIndex", False)
    try:
        ep = create_endpoint("jax://", Bootstrap(schema_text=SCHEMA))
    finally:
        GATES.set("LeopardIndex", prev)
    assert isinstance(ep, BatchingEndpoint)
    ep.store.bulk_load([parse_relationship(f"doc:d{i}#viewer@user:u{i % 4}")
                        for i in range(32)])
    return ep


def stage_counts(kind):
    hist = m.REGISTRY.get("authz_dispatch_stage_seconds")
    return {stage: hist.count(stage=stage, kind=kind)
            for stage in tracing.DISPATCH_STAGES}


def run_traced(calls):
    """Run one coroutine factory per caller concurrently, each in its own
    request trace; -> (results, traces)."""
    async def one(call):
        with tracing.request_trace() as tr:
            return await call(), tr

    async def go():
        return await asyncio.gather(*[one(c) for c in calls])

    out = asyncio.run(go())
    return [r for r, _ in out], [tr for _, tr in out]


def assert_once_per_call(ep, kind, stages, stat, calls):
    before = stage_counts(kind)
    fused_before = ep.stats[stat]
    results, traces = run_traced(calls)
    fused = ep.stats[stat] - fused_before
    after = stage_counts(kind)
    assert fused == 1, f"{N_CALLERS} callers rode {fused} fused calls"
    for stage in tracing.DISPATCH_STAGES:
        want = fused if stage in stages else 0
        assert after[stage] - before[stage] == want, (kind, stage)
    for tr in traces:
        names = [s.name for s in tr.spans]
        for stage in stages:
            assert names.count(stage) == 1, (stage, names)
        assert all(s.attrs.get("kind") == kind for s in tr.spans
                   if s.name in stages)
    return results


def test_fused_lookup_observes_each_stage_once_per_call():
    ep = make_endpoint()
    subs = [SubjectRef("user", f"u{i}") for i in range(N_CALLERS)]
    asyncio.run(ep.lookup_resources("doc", "view", subs[0]))  # compile
    results = assert_once_per_call(
        ep, "lookup", LOOKUP_STAGES, "fused_lookups",
        [lambda s=s: ep.lookup_resources("doc", "view", s) for s in subs])
    assert sorted(results[1]) == sorted(
        f"d{i}" for i in range(32) if i % 4 == 1)
    assert results[4] == []


def test_fused_check_observes_each_stage_once_per_call():
    ep = make_endpoint()

    def req(i):
        return CheckRequest(ObjectRef("doc", f"d{i}"), "view",
                            SubjectRef("user", f"u{i % 4}"))

    asyncio.run(ep.check_permission(req(0)))  # compile
    results = assert_once_per_call(
        ep, "check", CHECK_STAGES, "fused_checks",
        [lambda i=i: ep.check_permission(req(i)) for i in range(N_CALLERS)])
    assert all(r.allowed for r in results)


def test_stage_spans_feed_the_timeline_ring():
    ep = make_endpoint()
    asyncio.run(ep.lookup_resources("doc", "view", SubjectRef("user", "u1")))
    mark = timeline.now()
    asyncio.run(ep.lookup_resources("doc", "view", SubjectRef("user", "u2")))
    evs = timeline.TIMELINE.events(since=mark)
    by_stage = {e.stage: e for e in evs if e.batch is not None}
    # pack / ready / transfer / extract map to the ring's stages
    assert {"pack", "kernel", "transfer", "extract"} <= set(by_stage)
    assert by_stage["kernel"].track == "device"
    assert len({by_stage[s].batch for s in
                ("pack", "kernel", "transfer", "extract")}) == 1


class RecordingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation."""
    entered: list = []
    exited: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        RecordingAnnotation.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        RecordingAnnotation.exited.append(self.name)
        return False


def test_span_enters_a_trace_annotation_named_after_its_stage(monkeypatch):
    monkeypatch.setattr(tracing, "_trace_me", RecordingAnnotation)
    RecordingAnnotation.entered = []
    RecordingAnnotation.exited = []
    with tracing.span("transfer", fused="check"):
        assert RecordingAnnotation.entered == ["transfer"]
        assert RecordingAnnotation.exited == []
    # untraced request spans are annotated too: the profiler names a
    # device idle gap after whatever the host was doing
    with tracing.span("decode", nbytes=10):
        pass
    assert RecordingAnnotation.entered == ["transfer", "decode"]
    assert RecordingAnnotation.exited == ["transfer", "decode"]


def test_stage_lands_on_the_profilers_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("assemble", fused="check"):
            pass
    finally:
        jax.profiler.stop_trace()
    names = set()
    for root, _dirs, files in os.walk(tmp_path):
        for f in files:
            if f.endswith(".xplane.pb"):
                pd = ProfileData.from_file(os.path.join(root, f))
                for plane in pd.planes:
                    if plane.name.startswith("/host:"):
                        for line in plane.lines:
                            names.update(ev.name for ev in line.events)
    assert "assemble" in names
