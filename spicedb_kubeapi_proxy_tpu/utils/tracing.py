"""End-to-end request tracing with per-phase latency attribution.

The round-5 soak showed multi-second p99 spikes that endpoint-boundary
aggregates (utils/metrics.py) cannot explain: a slow request could be
stuck in authn, rule matching, the dispatch queue, the TPU kernel, or
response filtering, and the aggregates cannot tell which.  This module
is the dependency-free tracing core that makes the gap attributable:

- `Trace`: one per proxied request, carrying monotonic-clock `Span`s.
  Propagated via a contextvar through the whole handler chain — and,
  because the jax:// endpoint runs device work in executor threads and
  the dispatcher fuses work from MANY requests into one kernel call,
  two extra pieces:

  * `FanoutTrace` lets the dispatch drain loop record one fused-batch
    span into every co-batched request's trace;
  * callers that hop threads copy the context (`contextvars.copy_context`)
    so `current_trace()` still resolves off-loop.

- Spans marked `phase=True` are the request's latency attribution: they
  are chosen to tile the request wall time without overlapping (authn,
  resolve, match, queue_wait, execute, upstream, respfilter, workflow),
  feed the `authz_request_phase_seconds{phase=...,verb=...}` histogram,
  and sum to ~wall time.  Unmarked spans (decode, filter, serialize,
  kernel.oracle, workflow.<activity>, ...) are forensic detail and may
  overlap phases.

- `span` is the one primitive that marks a stage.  Besides recording
  into the active trace it enters `jax.profiler.TraceAnnotation`, so a
  profile captured with `jax.profiler.trace` carries the program's stage
  names on the profiler's own clock (the jax import is never triggered
  here: the annotation binds once jax is loaded).  A span given
  `fused=<kind>` is a stage of one fused device call (`DISPATCH_STAGES`)
  and is observed once per call into
  `authz_dispatch_stage_seconds{stage,kind}`, traced or not.  Span exits
  also feed the dispatch timeline ring (utils/timeline.py).

- `SlowTraceRecorder`: a bounded recorder retaining the N slowest
  traces, served at the authenticated `/debug/traces` endpoint and
  drained per window by scripts/soak.py so a soak run explains its own
  p99 spikes.

Thread-safe: spans are recorded from asyncio handlers and executor
threads concurrently.
"""

from __future__ import annotations

import contextlib
import contextvars
import heapq
import sys
import threading
import time
import uuid
from typing import Iterable, Optional

from . import metrics as m

TRACE_ID_HEADER = "X-Trace-Id"

# fleet-internal trace propagation (docs/observability.md "Fleet
# tracing").  Every internal hop — router -> shard leader, follower ->
# leader forward, replication control calls — carries these so the
# receiving proxy JOINS the caller's trace instead of minting a fresh
# one.  The Timeline feature gate is the killswitch: off, no headers
# are sent and receivers mint locally, byte-identical to the
# single-process behavior.
PROP_TRACE_HEADER = "X-Authz-Trace-Id"
PROP_PARENT_HEADER = "X-Authz-Parent-Span"
PROP_TIER_PATH_HEADER = "X-Authz-Tier-Path"

# tier vocabulary for per-tier latency attribution (authz_tier_seconds)
TIERS = ("router", "leader", "follower", "hub")

# per-trace span cap: a runaway loop recording spans must not grow a
# request's memory without bound (the slowest traces are retained)
_MAX_SPANS = 512

_current: contextvars.ContextVar = contextvars.ContextVar(
    "authz_request_trace", default=None)


class Span:
    __slots__ = ("name", "start", "end", "phase", "attrs")

    def __init__(self, name: str, start: float, end: float,
                 phase: bool = False, attrs: Optional[dict] = None):
        self.name = name
        self.start = start
        self.end = end
        self.phase = phase
        self.attrs = attrs or {}

    @property
    def duration(self) -> float:
        return self.end - self.start


class SpanSink:
    """Anything spans can be recorded into (Trace or FanoutTrace).
    Record via the module-level span() context manager or add_span()
    directly."""

    def add_span(self, name: str, start: float, end: float,
                 phase: bool = False, **attrs) -> None:
        raise NotImplementedError


class Trace(SpanSink):
    """One request's spans, on the monotonic clock (perf_counter)."""

    def __init__(self, trace_id: Optional[str] = None, **attrs):
        self.trace_id = trace_id or uuid.uuid4().hex
        self.attrs: dict = dict(attrs)
        self.wall_start = time.time()
        self.t0 = time.perf_counter()
        self.duration: Optional[float] = None
        self.spans: list = []
        self._lock = threading.Lock()

    def add_span(self, name: str, start: float, end: float,
                 phase: bool = False, **attrs) -> None:
        sp = Span(name, start, end, phase=phase, attrs=attrs or None)
        with self._lock:
            if len(self.spans) < _MAX_SPANS:
                self.spans.append(sp)

    def finish(self) -> float:
        """Freeze the trace duration (idempotent); returns seconds."""
        if self.duration is None:
            self.duration = time.perf_counter() - self.t0
        return self.duration

    def phase_durations(self) -> dict:
        """Summed seconds per phase-marked span name — the request's
        latency attribution (feeds authz_request_phase_seconds)."""
        with self._lock:
            spans = list(self.spans)
        out: dict = {}
        for sp in spans:
            if sp.phase:
                out[sp.name] = out.get(sp.name, 0.0) + sp.duration
        return out

    def to_dict(self) -> dict:
        with self._lock:
            spans = list(self.spans)
        dur = (self.duration if self.duration is not None
               else time.perf_counter() - self.t0)
        out_spans = []
        for sp in spans:
            d = {"name": sp.name,
                 "start_ms": round((sp.start - self.t0) * 1e3, 3),
                 "duration_ms": round(sp.duration * 1e3, 3)}
            if sp.phase:
                d["phase"] = True
            if sp.attrs:
                d["attrs"] = dict(sp.attrs)
            out_spans.append(d)
        return {"trace_id": self.trace_id,
                "start_unix": round(self.wall_start, 6),
                "duration_ms": round(dur * 1e3, 3),
                "attrs": dict(self.attrs),
                "spans": out_spans}


class FanoutTrace(SpanSink):
    """Multiplexes span records to several traces: the dispatch drain
    loop activates one of these around a fused inner call so the call's
    stage spans land in EVERY co-batched request's trace."""

    def __init__(self, traces: Iterable[SpanSink]):
        self.traces = tuple(traces)

    def add_span(self, name: str, start: float, end: float,
                 phase: bool = False, **attrs) -> None:
        for tr in self.traces:
            tr.add_span(name, start, end, phase=phase, **attrs)


# -- context propagation -----------------------------------------------------

def current_trace() -> Optional[SpanSink]:
    return _current.get()


def activate(sink: Optional[SpanSink]):
    """Set (or, with None, null out) the active trace; returns a token
    for deactivate."""
    return _current.set(sink)


def deactivate(token) -> None:
    _current.reset(token)


def start_trace(trace_id: Optional[str] = None, **attrs):
    """Create + activate a trace; returns (trace, token)."""
    tr = Trace(trace_id=trace_id, **attrs)
    return tr, _current.set(tr)


def end_trace(token) -> None:
    _current.reset(token)


@contextlib.contextmanager
def request_trace(trace_id: Optional[str] = None, **attrs):
    """Trace the enclosed block as one request (finished on exit)."""
    tr, token = start_trace(trace_id=trace_id, **attrs)
    try:
        yield tr
    finally:
        tr.finish()
        _current.reset(token)


# the stages that tile one fused device call, in order (docs/
# observability.md "Dispatch stages"): host packing, the jitted call's
# async dispatch, launch end -> device result ready (device-stream
# queueing plus the call's own kernel), ready -> host array in hand,
# the finishing thread blocked on the readback, and the host-side id
# extraction (lookups) or result assembly (checks)
DISPATCH_STAGES = ("pack", "launch", "ready", "transfer", "wait",
                   "extract", "assemble")


def stage_histogram(registry: Optional[m.Registry] = None) -> m.Histogram:
    """`authz_dispatch_stage_seconds{stage,kind}`: one observation per
    fused call per stage (kind lookup/check; "" for a stall no call
    owns, such as a background rebuild)."""
    return (registry or m.REGISTRY).histogram(
        "authz_dispatch_stage_seconds",
        "Seconds per stage of one fused device call (pack, launch, ready, "
        "transfer, wait, extract, assemble) and per dispatch stall "
        "(transpose, rebuild, compile), by call kind",
        labels=("stage", "kind"))


_STAGE_SECONDS = stage_histogram()

_trace_me = None  # jax.profiler.TraceAnnotation once jax is loaded


def _annotation(name: str):
    """A profiler TraceMe for `name`, or None while jax is not loaded
    (no profile session can be active then, and a span must never pay
    for importing jax)."""
    global _trace_me
    if _trace_me is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation
            _trace_me = TraceAnnotation
        except Exception:
            _trace_me = False
    return _trace_me(name) if _trace_me else None


_timeline_note = None  # resolved lazily; False => timeline unavailable


def _note_timeline(name: str, attrs: dict, start: float, end: float) -> None:
    """Feed a closed span into the dispatch timeline ring (utils/
    timeline.py maps span names to its stages and tracks) — lazy-bound
    so this module keeps no import of the timeline."""
    global _timeline_note
    if _timeline_note is None:
        try:
            from .timeline import note_span
            _timeline_note = note_span
        except Exception:
            _timeline_note = False
    if _timeline_note:
        try:
            _timeline_note(name, attrs, start, end)
        except Exception:
            pass


class _SpanScope:
    """Context manager behind span(); `.seconds` holds the duration once
    the block has closed."""

    __slots__ = ("name", "phase", "fused", "attrs", "start", "seconds",
                 "_trace", "_ann")

    def __init__(self, name: str, phase: bool, fused: Optional[str],
                 attrs: dict):
        self.name = name
        self.phase = phase
        self.fused = fused
        self.attrs = attrs
        self.seconds = 0.0

    def __enter__(self) -> dict:
        self._trace = _current.get()
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.start = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.seconds = end - self.start
        tr = self._trace
        if tr is not None:
            tr.add_span(self.name, self.start, end, phase=self.phase,
                        **self.attrs)
        if self.fused is not None:
            _STAGE_SECONDS.observe(self.seconds, stage=self.name,
                                   kind=self.fused)
        if tr is not None or self.fused is not None:
            _note_timeline(self.name, self.attrs, self.start, end)
        return False


def span(name: str, phase: bool = False, fused: Optional[str] = None,
         **attrs) -> _SpanScope:
    """Mark the enclosed block as stage `name`: recorded into the active
    trace (fanned out to every co-batched trace inside a fused call),
    annotated on the profiler's host plane, and fed to the timeline ring.
    `fused` (the call kind, "lookup" or "check") makes it a stage of one
    fused device call, observed once per call into
    authz_dispatch_stage_seconds whether or not a request is traced.
    Yields the attrs dict so callers can enrich it before the span
    closes.  Without an active trace and without `fused`, only the
    annotation remains (a no-op outside a profile session)."""
    if fused is not None:
        attrs["kind"] = fused
    return _SpanScope(name, phase, fused, attrs)


def clean_trace_id(raw: str) -> Optional[str]:
    """Sanitize a caller-supplied trace id (header): short, printable,
    no quotes/whitespace — anything else is replaced by a fresh id."""
    raw = (raw or "").strip()
    if not raw or len(raw) > 64:
        return None
    if any(c.isspace() or c in '"\\' or not c.isprintable() for c in raw):
        return None
    return raw


# -- fleet propagation (cross-process trace continuity) ----------------------

_gates_enabled = None  # resolved lazily; False => gates unavailable


def propagation_enabled() -> bool:
    """True when fleet trace propagation is on (the `Timeline` feature
    gate doubles as the killswitch — one flag turns off both the
    serving-stage spans and the cross-process headers).  Fails open:
    this module stays importable standalone."""
    global _gates_enabled
    if _gates_enabled is None:
        try:
            from .features import GATES
            _gates_enabled = GATES.enabled
        except Exception:
            _gates_enabled = False
    if _gates_enabled:
        try:
            return _gates_enabled("Timeline")
        except Exception:
            return True
    return True


_TIER_PATH_OK = frozenset(
    "abcdefghijklmnopqrstuvwxyz0123456789_->|,")


def clean_tier_path(raw: str) -> str:
    """Sanitize a caller-supplied tier path header: bounded, lowercase
    tier names joined by `>` — anything else is dropped (the path is
    advisory provenance, not a trust input)."""
    raw = (raw or "").strip().lower()
    if not raw or len(raw) > 128:
        return ""
    if any(c not in _TIER_PATH_OK for c in raw):
        return ""
    return raw


def propagation_headers(default_tier: str = "") -> dict:
    """Headers an outbound *fleet-internal* hop should carry so the
    receiving proxy joins this trace instead of minting its own.
    Empty when propagation is gated off; without an active trace the
    tier path still travels (background hops such as follower sync
    keep provenance even though they have no request trace)."""
    if not propagation_enabled():
        return {}
    headers = {}
    tr = _current.get()
    trace_id = getattr(tr, "trace_id", "") if tr is not None else ""
    if trace_id:
        headers[PROP_TRACE_HEADER] = trace_id
    path = ""
    if tr is not None:
        attrs = getattr(tr, "attrs", None)
        if isinstance(attrs, dict):
            path = str(attrs.get("tier_path") or "")
    path = path or default_tier
    if path:
        headers[PROP_TIER_PATH_HEADER] = path
    return headers


class _Hop:
    """Yielded by hop_span: `.headers` is what the caller copies onto
    the outbound request; `.span_id` is the client-side span a
    downstream trace names as its parent."""
    __slots__ = ("headers", "span_id")

    def __init__(self, headers: dict, span_id: str = ""):
        self.headers = headers
        self.span_id = span_id


_NULL_HOP = _Hop({})


@contextlib.contextmanager
def hop_span(name: str, tier: str = "", **attrs):
    """Client-side span around ONE outbound internal HTTP hop (router ->
    shard leader, follower -> leader forward, ...).  Yields a `_Hop`
    whose `.headers` carry X-Authz-Trace-Id / X-Authz-Parent-Span /
    X-Authz-Tier-Path for the outbound request.  The recorded span's
    `span_id` attr is what the downstream trace names as its parent, so
    the fleet merge (utils/fleet.py) aligns the child trace inside this
    hop and attributes hop network time separately from downstream
    server time.  Degrades to a no-op with empty headers when
    propagation is gated off or no trace is active."""
    tr = _current.get()
    if tr is None or not propagation_enabled():
        yield _NULL_HOP
        return
    span_id = uuid.uuid4().hex[:16]
    headers = propagation_headers(default_tier=tier)
    headers[PROP_PARENT_HEADER] = span_id
    t0 = time.perf_counter()
    try:
        yield _Hop(headers, span_id)
    finally:
        tr.add_span(name, t0, time.perf_counter(), span_id=span_id,
                    **attrs)


# -- slow-trace retention ----------------------------------------------------

class SlowTraceRecorder:
    """Bounded min-heap of the N slowest finished traces (as dicts, so
    retention never pins request objects).  `snapshot` serves
    /debug/traces; `drain` gives scripts/soak.py a per-window view."""

    def __init__(self, capacity: int = 32):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._heap: list = []  # (duration_s, seq, trace_dict)
        self._seq = 0

    def record(self, trace: Trace) -> None:
        dur = trace.duration if trace.duration is not None else trace.finish()
        with self._lock:
            self._seq += 1
            entry = (dur, self._seq, trace.to_dict())
            if len(self._heap) < self.capacity:
                heapq.heappush(self._heap, entry)
            elif self._heap and dur > self._heap[0][0]:
                heapq.heapreplace(self._heap, entry)

    def _sorted(self) -> list:
        return [d for _, _, d in
                sorted(self._heap, key=lambda e: e[0], reverse=True)]

    def snapshot(self) -> list:
        """Slowest-first list of retained trace dicts (non-destructive)."""
        with self._lock:
            return self._sorted()

    def drain(self) -> list:
        """Snapshot + reset — per-window retention for soak runs."""
        with self._lock:
            out = self._sorted()
            self._heap = []
            return out

    def exemplars(self, k: int = 3,
                  since_unix: Optional[float] = None) -> list:
        """Top-k slowest retained traces as lightweight exemplar refs
        (trace id + duration + wall start), optionally restricted to
        traces that STARTED at/after `since_unix` — the flight recorder
        embeds these per window so a burning SLO window at /debug/flight
        links straight to /debug/traces + /debug/timeline evidence."""
        with self._lock:
            dicts = self._sorted()
        out = []
        for d in dicts:
            if (since_unix is not None
                    and d.get("start_unix", 0.0) < since_unix):
                continue
            out.append({"trace_id": d["trace_id"],
                        "duration_ms": d["duration_ms"],
                        "start_unix": d["start_unix"]})
            if len(out) >= k:
                break
        return out


RECORDER = SlowTraceRecorder()
