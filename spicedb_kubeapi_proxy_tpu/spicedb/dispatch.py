"""Cross-request batched dispatch (SURVEY.md §2, parallelism table).

The reference fans each HTTP request's checks into one
`CheckBulkPermissions` RPC (pkg/authz/check.go:23-48) but batches only
*within* a request.  On TPU the batch IS the kernel invocation, so this
wrapper also coalesces across concurrent requests: concurrent
check/LookupResources callers enqueue work, and a drain loop issues fused
calls to the inner endpoint.

Policy ("natural batching"): when no inner call is in flight, the queue
flushes immediately — single-caller latency is one kernel call, same as
direct dispatch.  While a call is in flight, new arrivals accumulate and go
out together on the next drain, so high concurrency (BASELINE config 5: 256
simultaneous list requests) produces device-sized batches without a tuning
knob.  `max_batch` caps one drain's fused size.

Failure isolation: if a fused inner call raises, each member request is
retried individually so one malformed query (e.g. unknown definition, which
the endpoint surfaces as an error like the reference does) cannot poison
unrelated co-batched callers.

Pipelining (`pipeline_depth`, docs/performance.md "Device-resident
pipeline"): when the inner endpoint exposes two-phase start/finish pairs
(jax://), the drain loop keeps up to depth-1 started fused batches in
flight — checks and lookups both — so the host encode + upload + kernel
dispatch of batch N+1 overlap batch N's device execution and async D2H
readback.  The DevicePipeline feature gate is the killswitch.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import os
import time
from typing import Iterable, Optional

from ..utils import admission, devtel, timeline, tracing
from ..utils.failpoints import fail_point
from .endpoints import PermissionsEndpoint
from .store import Watcher
from .types import (
    CheckRequest,
    Precondition,
    RelationshipFilter,
    RelationshipUpdate,
    SubjectRef,
)


# regression-sentinel proof hook (scripts/check.sh): a per-drain sleep
# armed via env var injects a deterministic slowdown into the dispatch
# hot loop so the benchdiff gate can be shown to catch one.  Read once
# at import; 0 in any real deployment.
_BENCHDIFF_INJECT_S = (
    float(os.environ.get("SPICEDB_TPU_BENCHDIFF_INJECT_MS", "0") or 0)
    / 1e3)


def _trace_ctx() -> Optional[dict]:
    """Per-caller dispatch trace context, captured at enqueue time; the
    drain loop stamps exec_start/exec_end into it so the caller can
    attribute queue wait separately from fused execution.  None when the
    request is untraced (zero overhead)."""
    trace = tracing.current_trace()
    if trace is None:
        return None
    return {"trace": trace, "enq": time.perf_counter()}


def _record_waiter_spans(tc: Optional[dict]) -> None:
    """queue_wait (enqueue -> drain pickup) and execute (fused inner
    call, kernel included) phase spans for one dispatch caller."""
    if not tc:
        return
    now = time.perf_counter()
    exec_start = tc.get("exec_start", now)
    trace = tc["trace"]
    trace.add_span("queue_wait", tc["enq"], exec_start, phase=True)
    trace.add_span("execute", exec_start, tc.get("exec_end", now), phase=True)


def _mark_exec_start(waiters: list) -> None:
    t0 = time.perf_counter()
    for w in waiters:
        if w[2] is not None:
            w[2].setdefault("exec_start", t0)


def _mark_exec_end(waiters: list) -> None:
    t1 = time.perf_counter()
    for w in waiters:
        if w[2] is not None:
            w[2]["exec_end"] = t1


def _follow(leader: asyncio.Future, loop) -> asyncio.Future:
    """A caller-facing future mirroring an internal singleflight leader.
    The leader is never handed to callers, so one caller's cancellation
    can never poison the co-flighted others; results are shared (the
    fused extract path already hands the SAME id list to every waiter of
    a column, so sharing is the established contract)."""
    fut = loop.create_future()

    def _copy(lf: asyncio.Future) -> None:
        if fut.done():
            return
        if lf.cancelled():
            fut.set_exception(
                RuntimeError("singleflight leader cancelled"))
        elif lf.exception() is not None:
            fut.set_exception(lf.exception())
        else:
            fut.set_result(lf.result())

    leader.add_done_callback(_copy)
    return fut


@contextlib.contextmanager
def _activate_batch_trace(waiters: list):
    """Activate the co-batched callers' traces (fanned out) for the
    duration of a fused inner call, so spans the backend records (e.g.
    the jax:// dispatch stages) land in EVERY member request's trace.

    Always overrides the contextvar — the drain task was created from
    some caller's _kick() and INHERITED that caller's trace context, so
    an all-untraced batch must actively null the sink or its kernel
    spans would leak into the unrelated kicking request's trace."""
    traces: list = []
    seen: set = set()
    for w in waiters:
        tc = w[2]
        if tc is not None and id(tc["trace"]) not in seen:
            seen.add(id(tc["trace"]))
            traces.append(tc["trace"])
    sink = (None if not traces
            else traces[0] if len(traces) == 1
            else tracing.FanoutTrace(traces))
    token = tracing.activate(sink)
    try:
        yield
    finally:
        tracing.deactivate(token)


class BatchingEndpoint(PermissionsEndpoint):
    # Retry-After hint on queue-bound rejections: one drain cycle is the
    # natural unit of backoff (the queue that rejected will have turned
    # over at least once by then)
    RETRY_AFTER_S = 1.0

    def __init__(self, inner: PermissionsEndpoint, max_batch: int = 4096,
                 pipeline_depth: int = 2, max_queue_depth: int = 0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        if max_queue_depth < 0:
            raise ValueError(
                f"max_queue_depth must be >= 0, got {max_queue_depth}")
        self.inner = inner
        self.max_batch = max_batch
        # admission control (utils/admission.py, --max-queue-depth):
        # bound on EACH of the check and LR queues; an enqueue that
        # would exceed it raises AdmissionRejectedError instead of
        # queueing (0 = unbounded, the pre-admission behavior).
        # Exempt callers (dual-write authorization, admission.exempt())
        # and singleflight followers (they add no queue entry) always
        # admit.
        self.max_queue_depth = max_queue_depth
        if max_queue_depth:
            # only a configured bound publishes the gauge: endpoints
            # constructed later with the default 0 (bench sweeps, test
            # fixtures) must not reset the serving proxy's exported
            # limit to "unbounded"
            admission.set_queue_limit(max_queue_depth)
        # fused batches allowed in flight at once (device-resident
        # pipeline, --pipeline-depth): depth N keeps N-1 STARTED batches
        # pending, so batch N+1's host encode + H2D upload + kernel
        # dispatch overlap batch N's device execution and async D2H
        # readback.  1 = fully serial; the DevicePipeline feature gate
        # off reproduces the pre-pipeline behavior (single-slot lookup
        # window, serial checks) regardless of depth.
        self.pipeline_depth = pipeline_depth
        # waiters are (item, Future, trace-ctx-or-None) triples
        self._check_queue: list = []   # [(CheckRequest, Future, tc)]
        self._lr_queue: dict = {}      # (type, perm) -> [(SubjectRef, Future, tc)]
        # fair service order across LR keys: every queued (type, perm)
        # key appears exactly once; the drain serves the head and a key
        # with remaining waiters rejoins at the TAIL, so one hot lookup
        # key cannot monopolize the drain while others starve
        self._lr_rotation: collections.deque = collections.deque()
        # live LR queue depth (all keys), maintained incrementally so
        # the admission bound check stays O(1) per enqueue
        self._lr_depth = 0
        # in-flight singleflight index: (type, perm, subject) -> the
        # QUEUED leader future.  Entries are removed at drain pickup, so
        # arrivals during execution start a fresh query (a write may have
        # committed since the executing batch drained deltas, and a later
        # arrival must observe it — full consistency).
        self._lr_pending: dict = {}
        # per-pending-key follower counts: how many duplicate callers a
        # queued leader collapsed, drained into the batch-occupancy
        # histogram (utils/devtel.py) at pickup
        self._sf_counts: dict = {}
        self._inflight: list = []      # waiters of the batch being executed
        self._drain_task: Optional[asyncio.Task] = None
        # explain_bypass pre-seeded so InstrumentedEndpoint's one-shot
        # gauge registration sees the key
        self._stats = {"drains": 0, "fused_checks": 0, "fused_lookups": 0,
                       "max_fused_batch": 0, "explain_bypass": 0,
                       "singleflight_hits": 0, "admission_rejected": 0}

    def queue_depth(self) -> int:
        """Total queued (not in-flight) entries across both queues —
        O(1), allocation-free; the load shedder's door check reads this
        on every read-only request (proxy/server.py)."""
        return len(self._check_queue) + self._lr_depth

    @property
    def stats(self) -> dict:
        """Own dispatch counters merged over the inner backend's stats,
        plus live queue-depth / current-fused-batch gauges (sampled at
        scrape time through InstrumentedEndpoint's stats callbacks)."""
        inner_stats = getattr(self.inner, "stats", None)
        out = dict(inner_stats) if isinstance(inner_stats, dict) else {}
        out.update(self._stats)
        out["check_queue_depth"] = len(self._check_queue)
        out["lr_queue_depth"] = sum(len(v) for v in self._lr_queue.values())
        out["inflight_batch"] = len(self._inflight)
        out["pipeline_depth"] = self.pipeline_depth
        out["queue_limit"] = self.max_queue_depth
        return out

    # -- queue plumbing ------------------------------------------------------

    def _admit(self, queue_depth: int, adding: int, which: str) -> None:
        """Reject an enqueue that would push `which` queue past the
        bound (fail fast instead of queueing unboundedly).  The bound
        limits BACKLOG, not request size: a bulk arriving at an empty
        queue always admits whole — otherwise any batch larger than the
        bound would be rejected forever, idle or not, and retrying
        could never succeed.  Worst-case resident depth is therefore
        bound + one batch.  Exempt callers — dual-write authorization
        runs under admission.exempt() — always pass, as does everything
        when the AdmissionControl gate (killswitch) is off."""
        if not self.max_queue_depth:
            return
        if queue_depth == 0 or queue_depth + adding <= self.max_queue_depth:
            return
        if admission.is_exempt() or not admission.enabled():
            return
        self._stats["admission_rejected"] += 1
        admission.note_rejected("queue_limit")
        raise admission.AdmissionRejectedError(
            f"{which} queue at depth {queue_depth} (bound "
            f"{self.max_queue_depth}); retry after "
            f"{self.RETRY_AFTER_S:.1f}s",
            reason="queue_limit", retry_after_s=self.RETRY_AFTER_S)

    def _kick(self) -> None:
        if self._drain_task is None or self._drain_task.done():
            self._drain_task = asyncio.get_running_loop().create_task(
                self._drain())

    async def _drain(self) -> None:
        # Pipelined dispatch: when the inner endpoint exposes two-phase
        # start/finish pairs (jax://), batch N+1's kernel is DISPATCHED
        # (start) before batch N's readback+extraction (finish) blocks,
        # so the device computes N+1 while N's result streams to the
        # host.  `pending` holds up to (pipeline_depth - 1) started
        # batches — checks and lookups share the window, finished
        # strictly FIFO — bounding snapshot retention to the depth.
        # With the DevicePipeline gate off the loop reproduces the
        # pre-pipeline behavior exactly: lookups keep the single-slot
        # two-phase window, checks run serially.
        from ..utils.features import pipeline_enabled
        pending: collections.deque = collections.deque()
        two_lr = (hasattr(self.inner, "lookup_resources_batch_start")
                  and hasattr(self.inner, "lookup_resources_batch_finish"))
        two_ck = (hasattr(self.inner, "check_bulk_permissions_start")
                  and hasattr(self.inner, "check_bulk_permissions_finish"))
        if pipeline_enabled():
            window = self.pipeline_depth - 1
            two_lr = two_lr and window > 0
            two_ck = two_ck and window > 0
        else:
            window = 1 if two_lr else 0
            two_ck = False
        try:
            while self._check_queue or self._lr_queue or pending:
                fail_point("dispatchDrain")
                if _BENCHDIFF_INJECT_S > 0:
                    await asyncio.sleep(_BENCHDIFF_INJECT_S)
                self._stats["drains"] += 1
                # alternate which queue goes first each iteration so
                # sustained traffic on one verb cannot push the other
                # behind it in every drain cycle (fairness, half of the
                # hot-key rotation below)
                order = (("ck", "lr") if self._stats["drains"] % 2
                         else ("lr", "ck"))
                for side in order:
                    if side == "ck" and self._check_queue:
                        batch = self._check_queue[: self.max_batch]
                        del self._check_queue[: len(batch)]
                        self._inflight = batch
                        if two_ck:
                            started = await self._start_checks(batch)
                            self._inflight = []
                            if started:
                                pending.append(started)
                        else:
                            await self._run_checks(batch)
                            self._inflight = []
                    elif side == "lr" and self._lr_queue:
                        key, waiters = self._next_lr_key()
                        rest = waiters[self.max_batch:]
                        waiters = waiters[: self.max_batch]
                        if rest:
                            # remainder rejoins at the BACK of the
                            # rotation: a hot key yields the drain to
                            # every other queued key between its batches
                            self._lr_queue[key] = rest
                            self._lr_rotation.append(key)
                        self._lr_depth -= len(waiters)
                        self._unregister_pending(key, waiters)
                        self._inflight = waiters
                        if two_lr:
                            # `started` joins `pending` BEFORE any
                            # blocking finish, so a drain death during
                            # that await still knows about every
                            # started batch
                            started = await self._start_lookups(key, waiters)
                            self._inflight = []
                            if started:
                                pending.append(started)
                        else:
                            await self._run_lookups(key, waiters)
                            self._inflight = []
                while pending and (len(pending) > window
                                   or not (self._check_queue
                                           or self._lr_queue)):
                    fail_point("dispatchDrainBeforeFinish")
                    kind, waiters, started = pending.popleft()
                    self._inflight = waiters
                    if kind == "lr":
                        await self._finish_lookups(waiters, started)
                    else:
                        await self._finish_checks(waiters, started)
                    self._inflight = []
        except BaseException as e:
            # A cancelled/dying drain task must FAIL its waiters — queued,
            # in-flight, and started-but-unfinished — or every caller
            # awaiting a future hangs forever (ADVICE round-5 finding).
            failure = (RuntimeError("batch dispatch drain task cancelled")
                       if isinstance(e, asyncio.CancelledError) else e)
            stranded = list(self._inflight)
            self._inflight = []
            for _kind, ws, _started in pending:
                stranded.extend(ws)
            stranded.extend(self._check_queue)
            del self._check_queue[:]
            for ws in self._lr_queue.values():
                stranded.extend(ws)
            self._lr_queue.clear()
            self._lr_rotation.clear()
            self._lr_depth = 0
            self._lr_pending.clear()
            self._sf_counts.clear()
            for w in stranded:
                if not w[1].done():
                    w[1].set_exception(failure)
            raise

    def _next_lr_key(self) -> tuple:
        """Pop the next (type, perm) key in fair rotation order and its
        full waiter list.  Invariant: a key is in the rotation exactly
        once iff it has a queue entry, so the popleft loop's guard is
        defensive only."""
        while self._lr_rotation:
            key = self._lr_rotation.popleft()
            waiters = self._lr_queue.pop(key, None)
            if waiters is not None:
                return key, waiters
        # defensive resync (should be unreachable): serve dict order
        key = next(iter(self._lr_queue))
        return key, self._lr_queue.pop(key)

    def _unregister_pending(self, key: tuple, waiters: list) -> None:
        """Close the singleflight window for a batch being picked up:
        identical queries arriving from now on must start fresh (the
        batch's delta drain happens at pickup, not at their arrival)."""
        resource_type, permission = key
        collapsed = 0
        for w in waiters:
            k = (resource_type, permission, w[0])
            if self._lr_pending.get(k) is w[1]:
                del self._lr_pending[k]
                collapsed += self._sf_counts.pop(k, 0)
        devtel.OCCUPANCY.note_collapsed(collapsed)

    def _enqueue_lookup(self, resource_type: str, permission: str,
                        subject: SubjectRef, tc,
                        pre_admitted: bool = False) -> asyncio.Future:
        """Queue one lookup, singleflight-deduped: an identical query
        already QUEUED shares its waiter (one kernel column, one cache
        fill upstream) through an internal leader future; the returned
        future is always caller-private (see _follow).  `pre_admitted`:
        lookup_resources_batch already admitted the WHOLE batch — a
        second per-leader check here would reject mid-batch (the
        batch's own leaders raise the depth past the bound), stranding
        the already-enqueued members and breaking the admit-whole
        guarantee."""
        loop = asyncio.get_running_loop()
        k = (resource_type, permission, subject)
        leader = self._lr_pending.get(k)
        if leader is None:
            # only a NEW leader adds queue depth; followers below join
            # an existing column for free, so under overload identical
            # queries collapse instead of rejecting
            if not pre_admitted:
                self._admit(self._lr_depth, 1, "lookup")
            leader = loop.create_future()
            self._lr_pending[k] = leader
            qkey = (resource_type, permission)
            q = self._lr_queue.get(qkey)
            if q is None:
                q = self._lr_queue[qkey] = []
                self._lr_rotation.append(qkey)
            q.append((subject, leader, tc))
            self._lr_depth += 1
        else:
            self._stats["singleflight_hits"] += 1
            self._sf_counts[k] = self._sf_counts.get(k, 0) + 1
        return _follow(leader, loop)

    async def _retry_individually(self, waiters: list, single_call) -> None:
        """Per-member fallback after a fused call failed (concurrently —
        a poison request must not serialize the drain loop) so one
        malformed query can't fail unrelated co-batched callers."""
        async def retry_one(w):
            item, fut, tc = w
            if fut.done():
                return
            # each retry is ONE member's work: activate that member's
            # trace (or none), never the fused batch fanout — gather's
            # tasks copy the ambient context, so this reset is needed
            # even when called inside _activate_batch_trace
            token = tracing.activate(tc["trace"] if tc else None)
            try:
                res = await single_call(item)
            except Exception as e:
                if not fut.done():  # caller may cancel during the await
                    fut.set_exception(e)
            else:
                if not fut.done():
                    fut.set_result(res)
            finally:
                tracing.deactivate(token)

        await asyncio.gather(*[retry_one(w) for w in waiters])

    @staticmethod
    def _resolve(waiters: list, results: list) -> None:
        for w, res in zip(waiters, results):
            if not w[1].done():
                w[1].set_result(res)

    async def _run_fused(self, waiters: list, stat: str, fused_call,
                         single_call) -> None:
        """One fused inner call for `waiters` ([(item, Future, tc)]); on
        failure, retry members individually."""
        items = [w[0] for w in waiters]
        self._stats[stat] += 1
        self._stats["max_fused_batch"] = max(self._stats["max_fused_batch"],
                                            len(items))
        _mark_exec_start(waiters)
        t0 = timeline.now()
        try:
            with _activate_batch_trace(waiters):
                try:
                    results = await fused_call(items)
                except Exception:
                    await self._retry_individually(waiters, single_call)
                    return
            self._resolve(waiters, results)
        finally:
            _mark_exec_end(waiters)
            # dispatcher-track slice: how long this fused call occupied
            # the drain loop (overlaps the device track's kernel slices
            # in the /debug/timeline view)
            timeline.record("fused", "dispatcher", t0, bucket=len(items),
                            kind=stat)

    async def _run_checks(self, batch: list) -> None:
        await self._run_fused(
            batch, "fused_checks",
            self.inner.check_bulk_permissions,
            self.inner.check_permission)

    async def _run_lookups(self, key: tuple, waiters: list) -> None:
        resource_type, permission = key
        await self._run_fused(
            waiters, "fused_lookups",
            lambda subjects: self.inner.lookup_resources_batch(
                resource_type, permission, subjects),
            lambda subject: self.inner.lookup_resources(
                resource_type, permission, subject))

    async def _start_lookups(self, key: tuple, waiters: list):
        """Phase 1 of a pipelined fused lookup: dispatch the kernel +
        async D2H.  On failure, degrade to the classic fused call with
        per-member retry; returns None so the drain loop has nothing to
        finish."""
        resource_type, permission = key
        self._stats["fused_lookups"] += 1
        self._stats["max_fused_batch"] = max(self._stats["max_fused_batch"],
                                            len(waiters))
        _mark_exec_start(waiters)
        t0 = timeline.now()
        try:
            with _activate_batch_trace(waiters):
                ctx = await self.inner.lookup_resources_batch_start(
                    resource_type, permission, [w[0] for w in waiters])
        except Exception:
            self._stats["fused_lookups"] -= 1  # _run_fused recounts
            await self._run_lookups(key, waiters)
            return None
        timeline.record("fused_start", "dispatcher", t0,
                        batch=ctx.get("batch_id") if isinstance(ctx, dict)
                        else None, bucket=len(waiters))
        return ("lr", waiters, (key, ctx))

    async def _start_checks(self, batch: list):
        """Phase 1 of a pipelined fused check: dispatch the kernel +
        async readback.  On failure, degrade to the classic fused call
        with per-member retry; returns None so the drain loop has
        nothing to finish."""
        self._stats["fused_checks"] += 1
        self._stats["max_fused_batch"] = max(self._stats["max_fused_batch"],
                                            len(batch))
        _mark_exec_start(batch)
        t0 = timeline.now()
        try:
            with _activate_batch_trace(batch):
                ctx = await self.inner.check_bulk_permissions_start(
                    [w[0] for w in batch])
        except Exception:
            self._stats["fused_checks"] -= 1  # _run_checks recounts
            await self._run_checks(batch)
            return None
        timeline.record("fused_start", "dispatcher", t0,
                        batch=ctx.get("batch_id") if isinstance(ctx, dict)
                        else None, bucket=len(batch), kind="fused_checks")
        return ("ck", batch, ctx)

    async def _finish_checks(self, waiters: list, ctx) -> None:
        """Phase 2: blocking readback + result assembly; per-member
        retry on failure (same isolation contract as _run_fused)."""
        t0 = timeline.now()
        try:
            with _activate_batch_trace(waiters):
                try:
                    results = await self.inner.check_bulk_permissions_finish(
                        ctx)
                except Exception:
                    await self._retry_individually(
                        waiters, self.inner.check_permission)
                    return
            self._resolve(waiters, results)
        finally:
            _mark_exec_end(waiters)
            timeline.record("fused_finish", "dispatcher", t0,
                            batch=ctx.get("batch_id")
                            if isinstance(ctx, dict) else None,
                            bucket=len(waiters), kind="fused_checks")

    async def _finish_lookups(self, waiters: list, started) -> None:
        """Phase 2: blocking transfer + extraction; per-member retry on
        failure (same isolation contract as _run_fused)."""
        key, ctx = started
        resource_type, permission = key
        t0 = timeline.now()
        try:
            with _activate_batch_trace(waiters):
                try:
                    results = await self.inner.lookup_resources_batch_finish(ctx)
                except Exception:
                    await self._retry_individually(
                        waiters, lambda s: self.inner.lookup_resources(
                            resource_type, permission, s))
                    return
            self._resolve(waiters, results)
        finally:
            _mark_exec_end(waiters)
            timeline.record("fused_finish", "dispatcher", t0,
                            batch=ctx.get("batch_id")
                            if isinstance(ctx, dict) else None,
                            bucket=len(waiters))

    # -- batched verbs -------------------------------------------------------

    async def check_permission(self, req: CheckRequest):
        self._admit(len(self._check_queue), 1, "check")
        tc = _trace_ctx()
        fut = asyncio.get_running_loop().create_future()
        self._check_queue.append((req, fut, tc))
        self._kick()
        try:
            return await fut
        finally:
            _record_waiter_spans(tc)

    async def check_bulk_permissions(self, reqs: list) -> list:
        if not reqs:
            return []
        # admit or reject the bulk WHOLE: partially enqueueing one
        # caller's batch and then rejecting the rest would run half its
        # checks for an answer the caller never sees
        self._admit(len(self._check_queue), len(reqs), "check")
        loop = asyncio.get_running_loop()
        tc = _trace_ctx()  # one shared ctx: the bulk is one caller
        futs = []
        for r in reqs:
            fut = loop.create_future()
            self._check_queue.append((r, fut, tc))
            futs.append(fut)
        self._kick()
        try:
            return list(await asyncio.gather(*futs))
        finally:
            _record_waiter_spans(tc)

    async def lookup_resources(self, resource_type: str, permission: str,
                               subject: SubjectRef) -> list:
        tc = _trace_ctx()
        fut = self._enqueue_lookup(resource_type, permission, subject, tc)
        self._kick()
        try:
            return await fut
        finally:
            _record_waiter_spans(tc)

    async def lookup_resources_batch(self, resource_type: str, permission: str,
                                     subjects: list) -> list:
        if not subjects:
            return []
        # whole-batch admission (conservative: duplicates that would
        # collapse into followers still count) — enqueueing half a
        # caller's batch then rejecting the rest wastes kernel lanes on
        # an answer the caller never sees
        self._admit(self._lr_depth, len(subjects), "lookup")
        tc = _trace_ctx()  # one shared ctx: the batch is one caller
        futs = [self._enqueue_lookup(resource_type, permission, s, tc,
                                     pre_admitted=True)
                for s in subjects]
        self._kick()
        try:
            return list(await asyncio.gather(*futs))
        finally:
            _record_waiter_spans(tc)

    # -- passthrough verbs ---------------------------------------------------

    def explain_check(self, resource, permission, subject):
        """Witness capture bypasses the fused queue: an explain is a
        targeted re-check on a rare debug path — co-batching it would
        make the captured iterate depend on whatever requests it fused
        with, and a queue backlog would stall the audit event it feeds."""
        self._stats["explain_bypass"] += 1
        fn = getattr(self.inner, "explain_check", None)
        if fn is not None:
            return fn(resource, permission, subject)
        from ..authz.explain import witness_for
        return witness_for(self.inner, resource, permission, subject)

    async def read_relationships(self, flt: RelationshipFilter) -> list:
        return await self.inner.read_relationships(flt)

    async def write_relationships(self, updates: Iterable[RelationshipUpdate],
                                  preconditions: Iterable[Precondition] = ()) -> int:
        return await self.inner.write_relationships(updates, preconditions)

    async def delete_relationships(self, flt: RelationshipFilter,
                                   preconditions: Iterable[Precondition] = ()) -> int:
        return await self.inner.delete_relationships(flt, preconditions)

    def watch(self, object_types=None) -> Watcher:
        return self.inner.watch(object_types)

    async def close(self) -> None:
        task = self._drain_task
        if task is not None and not task.done():
            await task
        await self.inner.close()

    def __getattr__(self, name):
        return getattr(self.inner, name)
