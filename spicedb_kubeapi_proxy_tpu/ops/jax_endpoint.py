"""`jax://` endpoint: the TPU execution backend for checks and lookups.

Same host tuple store as `embedded://` (source of truth, watch, durable
semantics), but CheckPermission / CheckBulkPermissions / LookupResources
execute on device as batched boolean reachability over the compiled
relation graph (ops/graph_compile.py).  Two interchangeable kernels:

- **ell** (default): bit-packed fixed-fanin gather kernel (ops/ell.py) —
  state is uint32 bitmask words, adjacency is destination-major fixed-width
  tables with hub rows split into OR-trees; no scatter in the iteration.
- **segment**: float32 gather + segment_sum kernel (ops/spmv.py) — the
  straightforward SpMV lowering, kept as a differential/debug fallback and
  for the edge-sharded multi-chip path (select with
  SPICEDB_TPU_KERNEL=segment).

The device graph is a cache over the host store:

- full (re)builds lower the current tuple snapshot;
- store deltas (dual-writes, watch traffic) are applied incrementally —
  row-slot edits in the ELL tables / padded-slack scatter in the segment
  edge arrays — and a rebuild is only forced when a new object id or a
  wildcard appears or slack runs out;
- relationship expiration is enforced lazily: expired tuples are
  delta-removed before the next query.

Reads are fully consistent w.r.t. the store (reference check.go:41-45 uses
FullyConsistent): every query first drains pending deltas under the graph
lock, so the device graph always reflects the committed store revision.

Device-resident pipeline (DevicePipeline gate, docs/performance.md):
the per-batch query preparation that used to run on the host — bitplane
packing, the word transpose of the lookup result, and the blocking D2H
sync — is folded into the jitted sweep (ops/ell.py `_pipe_fns`), the
iteration state rides donated per-bucket arenas so it updates in place,
and results read back asynchronously on a waiter pool so the dispatcher
(spicedb/dispatch.py, --pipeline-depth) can overlap batch N+1's encode +
upload + kernel with batch N's readback.  Gate off reproduces the
serial host-pack path exactly.
"""

from __future__ import annotations

import asyncio
import collections
import contextvars
import heapq
import logging
import os
import threading
import time
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.features import leopard_enabled as _leopard_on
from ..utils.features import pipeline_enabled as _pipeline_on
from ..utils.failpoints import fail_point

from ..spicedb import schema as sch
from ..utils import devtel, timeline, tracing, workload
from ..spicedb.endpoints import (
    Bootstrap,
    DEFAULT_BOOTSTRAP_SCHEMA,
    PermissionsEndpoint,
)
from ..spicedb.evaluator import Evaluator
from ..spicedb.store import TupleStore, Watcher
from ..spicedb.types import (
    AnnotatedIds,
    CheckRequest,
    CheckResult,
    ObjectRef,
    Permissionship,
    Precondition,
    Relationship,
    RelationshipFilter,
    RelationshipUpdate,
    SubjectRef,
    UpdateOp,
    WatchUpdate,
    WILDCARD,
)
from .ell import (
    EllKernelCache,
    aux_row_widths,
    batch_words,
    build_tables,
)
from .graph_compile import (GraphProgram, caveat_affected_pairs,
                            compile_graph, compile_graph_columnar)
from .spmv import KernelCache, bucket, pad_edges, pad_scatter

_MIN_EDGE_BUCKET = 256
_MIN_BATCH_BUCKET = 8


_warned_min_batch_words: set = set()


def _min_batch_words() -> int:
    """Floor for the packed batch width (env-tunable, read per call so
    tests can flip it).  Malformed values are loudly rejected (once per
    value) instead of silently ignored, and the floor is rounded up to a
    power of two so batch_words' doubling from it keeps producing
    power-of-two word buckets — non-pow2 floors would fragment the jit
    cache that bucketing exists to bound."""
    raw = os.environ.get("SPICEDB_TPU_MIN_BATCH_WORDS", "1")
    try:
        v = int(raw)
        if v < 1:
            raise ValueError(raw)
    except ValueError:
        if raw not in _warned_min_batch_words:
            _warned_min_batch_words.add(raw)
            _log.error("ignoring malformed SPICEDB_TPU_MIN_BATCH_WORDS=%r "
                       "(expected a positive integer); using 1", raw)
        return 1
    p = 1
    while p < v:
        p <<= 1
    if p != v and raw not in _warned_min_batch_words:
        _warned_min_batch_words.add(raw)
        _log.warning("SPICEDB_TPU_MIN_BATCH_WORDS=%d is not a power of two; "
                     "rounding up to %d (non-pow2 floors fragment the jit "
                     "bucket cache)", v, p)
    return p

# One synthetic zero-tuple subject per type is compiled into every graph:
# a subject that appears in no tuple can differ from any other zero-tuple
# subject of its type only through wildcard terms, which key on the subject
# TYPE — so every unknown query subject maps onto its type's phantom column
# instead of falling back to the recursive host oracle (the round-1
# "oracle cliff": multi-second LR per first-contact user).  The id contains
# NUL, which can never appear in a stored relationship id.
PHANTOM_ID = "\x00__phantom__"

# Spare object rows (VERDICT-r4 follow-through on the rebuild cliff):
# every type's compiled universe reserves a pool of placeholder ids; a
# dual-write that creates a BRAND-NEW object id claims one by renaming it
# in the program's id maps instead of forcing a multi-second full rebuild
# of the 1M-row graph.  The prefix contains NUL, which can never appear
# in a stored relationship id.
_SPARE_PREFIX = "\x00__spare__"
# pool sizing: max(floor, universe // divisor) placeholder rows per type
_SPARE_FLOOR = 64
_SPARE_DIVISOR = 64


def _object_ids_np(graph, resource_type: str) -> tuple:
    """(ids array, placeholder mask) view of the program's id list,
    cached per graph: object-dtype numpy ids for C-speed fancy-indexed
    materialization, plus a bool mask of internal NUL-prefixed ids
    (phantom + spare placeholders) so result filtering never needs a
    per-id Python scan."""
    cache = getattr(graph, "_ids_np_cache", None)
    if cache is None:
        cache = graph._ids_np_cache = {}
        graph._ids_np_published = set()
    entry = cache.get(resource_type)
    if entry is None:
        lst = graph.prog.object_ids[resource_type]
        arr = np.asarray(lst, dtype=object)
        mask = np.fromiter(("\x00" in i for i in lst), dtype=bool,
                           count=len(lst))
        entry = cache[resource_type] = (arr, mask)
        gen = getattr(graph, "_devtel_gen", 0)
        if gen:
            # id-pool views ride the graph generation in the HBM ledger
            # (host-resident, but generation-scoped exactly like the
            # device tables — a retained one is the same leak class)
            devtel.LEDGER.register("id_view",
                                   int(arr.nbytes) + int(mask.nbytes),
                                   generation=gen,
                                   name=f"ids:{resource_type}")
    # the pair escapes the lock with the caller: renames must now
    # copy-on-write instead of patching it in place (see _rename_row)
    graph._ids_np_published.add(resource_type)
    return entry


def _evict_id_views(graph) -> None:
    """Drop an outgoing graph generation's cached numpy id views: a
    stale (arr, mask) pair must never outlive its graph, and clearing
    releases the O(universe) object arrays immediately.  In-flight
    lookups that already captured a pair under the lock keep their own
    references — clear() empties the dict, never the arrays."""
    if graph is None:
        return
    cache = getattr(graph, "_ids_np_cache", None)
    if cache is not None:
        gen = getattr(graph, "_devtel_gen", 0)
        if gen:
            for rt in list(cache):
                devtel.LEDGER.unregister("id_view", generation=gen,
                                         name=f"ids:{rt}")
        cache.clear()
        graph._ids_np_published.clear()


_DEVTEL_GRAPH_BUFFERS = (
    ("dev_main", "ell_main"), ("dev_aux", "ell_aux"),
    ("dev_cav", "ell_cav"), ("edge_src", "segment_edges"),
    ("edge_dst", "segment_edges"))


def _register_graph_buffers(graph, gen: int) -> int:
    """Register one graph generation's device buffers with the HBM
    ledger (utils/devtel.py); returns the generation's byte total.
    Flush swaps same-shape arrays, so sizes registered at build stay
    exact for the generation's whole lifetime.  A finalizer retires the
    generation when the graph itself is collected, so an endpoint
    dropped without a rebuild (bench sweeps, tests) never leaves dead
    generations inflating the ledger.  The finalizer DEFERS (lock-free
    deque append): it runs inside whatever gc some allocation triggered,
    possibly on a thread already holding the ledger lock — retiring
    inline would self-deadlock."""
    import weakref
    total = 0
    graph._devtel_gen = gen
    for attr, kind in _DEVTEL_GRAPH_BUFFERS:
        a = getattr(graph, attr, None)
        nb = int(getattr(a, "nbytes", 0) or 0)
        if nb:
            devtel.LEDGER.register(kind, nb, generation=gen, name=attr)
            total += nb
    # donated state arenas (device-resident pipeline) allocate lazily on
    # the kernel cache and register under the SAME generation, so the
    # wholesale retirement below covers them; donation itself never
    # changes the registered bytes (in-place aliasing neither allocates
    # nor frees)
    kern = getattr(graph, "kernel", None)
    if kern is not None and hasattr(kern, "mesh"):
        # sharded mesh graph: tables live on the kernel as row-sharded
        # NamedSharding arrays.  Register one row PER ADDRESSABLE SHARD,
        # keyed by device id — the per-shard sum is the true physical
        # footprint (data-axis replication really does hold one copy per
        # replica), and each row also feeds the per-device gauge
        # (authz_device_shard_bytes) so placement is observable.
        for attr, kind in (("idx_main", "ell_main"),
                           ("idx_aux", "ell_aux"),
                           ("idx_cav", "ell_cav")):
            a = getattr(kern, attr, None)
            for sh in getattr(a, "addressable_shards", ()):
                nb = int(sh.data.nbytes)
                devtel.LEDGER.register(kind, nb, generation=gen,
                                       name=f"{attr}:d{sh.device.id}",
                                       device=sh.device.id)
                total += nb
    if kern is not None and hasattr(kern, "devtel_generation"):
        kern.devtel_generation = gen
    # the segment graph creates its kernel caches lazily (sorted vs
    # unsorted edge variants): stamp the graph so _kernel() propagates
    # the generation onto caches created after this registration too
    if hasattr(graph, "devtel_generation"):
        graph.devtel_generation = gen
        for k in getattr(graph, "_kernels", {}).values():
            k.devtel_generation = gen
    weakref.finalize(graph, devtel.LEDGER.defer_retire, gen)
    return total


def _sweep_bytes(graph, lanes: int) -> int:
    """Modeled HBM bytes for ONE fixpoint sweep of `graph` at `lanes`
    query lanes.  Counts each gather slot's packed-state read plus one
    state write per row, scaled by the batch width — the same accounting
    as bench.py's roofline model.  With KernelIntrospect on, the kernels
    read back the EXECUTED iteration count and the timeline's kernel
    byte tag becomes measured `iterations x this value` (basis
    "measured" in `/debug/timeline`); gate off — or on paths without a
    readback trace (sharded kernel, pre-first-readback) — this one-sweep
    value is used alone and the resulting bandwidth keeps its historical
    strict-lower-bound semantics (basis "modeled").  The static row
    factor is cached on the graph (shapes are fixed per generation)."""
    cached = getattr(graph, "_timeline_sweep", None)
    if cached is None:
        if hasattr(graph, "dev_main"):
            n, km = graph.dev_main.shape
            a_rows = graph.dev_aux.shape[0]
            # aux slots the compiled step gathers (its row writes are
            # left out: the model stays a lower bound)
            rows = n * (km + 1) + sum(
                graph.kernel.aux_refresh_slots().values())
            if getattr(graph, "dev_cav", None) is not None:
                rows += (n + a_rows) * (graph.dev_cav.shape[1] + 1)
            cached = (rows, True)   # packed: 4 bytes per 32 lanes
        elif hasattr(graph, "edge_src"):
            # segment kernel: one gather read + segment write per edge
            cached = (int(graph.edge_src.shape[0]) * 2, False)
        elif getattr(getattr(graph, "kernel", None), "idx_main", None) \
                is not None:
            # sharded mesh graph: tables live on the kernel (padded row
            # counts include the n_graph row padding — the padded rows
            # really are swept on device, so they belong in the model)
            kern = graph.kernel
            n, km = kern.idx_main.shape
            a_rows, ka = kern.idx_aux.shape
            ap = getattr(kern, "aux_passes", 1)
            rows = n * (km + 1) + ap * a_rows * (ka + 1)
            if getattr(kern, "idx_cav", None) is not None:
                rows += (n + a_rows) * (kern.idx_cav.shape[1] + 1)
            cached = (rows, True)
        else:
            cached = (0, True)
        graph._timeline_sweep = cached
    rows, packed = cached
    width = max(1, lanes // 32) * 4 if packed else lanes * 4
    if packed and getattr(graph, "has_cav", False):
        width *= 2  # definite + maybe bitplanes
    return rows * width


def _word_col_indices(wcol: np.ndarray, bit: int) -> np.ndarray:
    """Allowed slot indices from one packed uint32 word column (bit b of
    word w = query column w*32+b) — no bool bitmap, no 51MB transpose."""
    return np.nonzero((wcol >> np.uint32(bit)) & np.uint32(1))[0]


_log = logging.getLogger(__name__)


# -- async D2H readback (device-resident pipeline) ----------------------------
# The pipelined entry points return un-materialized device arrays; a
# small waiter pool parks one thread per in-flight batch on the
# completed future (block_until_ready), which is the only host-visible
# instant the device window closes — that gives the call an honest
# `ready` stage under async dispatch (the dispatching call itself is
# launch-only) — then drains the D2H as the `transfer` stage.  Sized
# above any sane --pipeline-depth; excess submissions just queue.

_READBACK_POOL = None
_READBACK_POOL_LOCK = threading.Lock()


def _readback_pool():
    global _READBACK_POOL
    if _READBACK_POOL is None:
        with _READBACK_POOL_LOCK:
            if _READBACK_POOL is None:
                import concurrent.futures
                _READBACK_POOL = concurrent.futures.ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix="authz-readback")
    return _READBACK_POOL


# -- off-loop rebuild executor (docs/performance.md "Overload & rebuild
# behavior") ------------------------------------------------------------------
# Background graph rebuilds run here, NOT on the event loop's default
# executor: a 1M-tuple compile must never occupy a thread the query
# paths (_off_loop) are waiting on.  Two workers so two coexisting
# endpoints (bench sweeps) can rebuild concurrently; each endpoint
# serializes its own rebuilds with an in-flight flag.

_REBUILD_POOL = None
_REBUILD_POOL_LOCK = threading.Lock()


def _rebuild_pool():
    global _REBUILD_POOL
    if _REBUILD_POOL is None:
        with _REBUILD_POOL_LOCK:
            if _REBUILD_POOL is None:
                import concurrent.futures
                _REBUILD_POOL = concurrent.futures.ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="authz-rebuild")
    return _REBUILD_POOL


class _GenState:
    """One device-graph generation's full host-side state, built as a
    candidate OFF the endpoint lock and installed atomically under it
    (the off-loop rebuild's unit of swap).  Field names mirror the
    endpoint's live attributes so the delta-application machinery
    (`_apply_batches` and friends) runs identically on the live
    generation (`st=self`) and on a candidate mid-replay."""

    __slots__ = ("_graph", "_graph_revision", "_spare_pool",
                 "_assigned_refs", "_spare_seq", "_caveated_pairs",
                 "_caveat_affected", "_caveated_keys", "_expiry_heap",
                 "_expiry_meta", "_stale_pairs", "_leopard")

    def __init__(self):
        self._graph = None
        self._graph_revision = 0
        self._spare_pool: dict = {}
        self._assigned_refs: dict = {}
        self._spare_seq = 0
        self._caveated_pairs: set = set()
        self._caveat_affected: set = set()
        self._caveated_keys: set = set()
        self._expiry_heap: list = []
        self._expiry_meta: dict = {}
        self._stale_pairs: set = set()
        self._leopard = None


def _start_readback(dev, batch_id, bucket: int, sweep_bytes: int,
                    kind: str, on_error=None, tel=None, comp=None,
                    kernel="ell"):
    """Submit the async readback of a dispatched device result; returns
    a concurrent.futures.Future resolving to the host numpy array.
    `on_error` (e.g. discarding the donated arena chain) runs before the
    exception propagates to the waiter.

    The waiter marks two stages of the `kind` call (lookup/check):
    `ready` (launch end -> block_until_ready returns: device-stream
    queueing plus the call's own kernel) and `transfer` (ready -> host
    array in hand: the sweep-trace read and the D2H copy).  It runs in
    the submitter's context, so both land in every co-batched trace.

    `tel` (KernelIntrospect) is the sweep-trace device array the
    pipelined kernels return alongside the result: read after the
    device window closes, it turns the kernel slice's byte tag from the
    modeled one-sweep floor into measured `iterations x sweep_bytes`.
    `comp` (the batch's (type, permission, rows) composition) carries
    the device window into the workload cost-attribution plane."""
    ctx = contextvars.copy_context()

    def wait_and_fetch():
        try:
            # kill-matrix site (tests/test_faultmatrix.py): a waiter
            # dying here must fail its batch fast, discard the donated
            # arena via on_error, and leave the ledger consistent
            fail_point("readbackWaiter")
            ready = tracing.span("ready", fused=kind, batch_id=batch_id,
                                 bucket=bucket, nbytes=sweep_bytes)
            with ready:
                dev.block_until_ready()
            workload.note_device_time(comp, "ready", ready.seconds)
            with tracing.span("transfer", fused=kind, batch_id=batch_id,
                              bucket=bucket) as a:
                if tel is not None:
                    rec = workload.note_sweep(kernel, kind, np.asarray(tel))
                    if rec is not None and rec.iterations > 0:
                        timeline.TIMELINE.retag_kernel(
                            batch_id, rec.iterations * sweep_bytes)
                        workload.WORKLOAD.note_depth(comp, rec.iterations)
                dev.copy_to_host_async()
                host = np.asarray(dev)
                a["nbytes"] = int(host.nbytes)
            return host
        except Exception:
            if on_error is not None:
                try:
                    on_error()
                except Exception:
                    _log.exception("readback error cleanup failed")
            raise

    return _readback_pool().submit(ctx.run, wait_and_fetch)


def _ids_for(ids: np.ndarray, idx: np.ndarray, ph, mask) -> tuple:
    """Materialize an allowed-id list, dropping the phantom column's
    reserved id (part of every type's universe, never emitted).

    Defense in depth: ALL internal placeholder ids carry a NUL prefix
    (phantom + spare rows) and must never reach a client of an
    authorization proxy.  A non-phantom placeholder surviving to this
    point indicates an id-view/bitmap inconsistency — suppress it (fail
    closed, via the C-speed mask) and report it: returns
    (id list, suppressed count, sample names) for the caller to count
    under its lock and log with its capture fingerprint; tests assert
    the counter stays zero."""
    if ph is not None:
        idx = idx[idx != ph]
    bad_n, bad_sample = 0, []
    sel = mask[idx]
    if sel.any():
        bad_n = int(sel.sum())
        bad_sample = ids[idx[sel][:4]].tolist()
        idx = idx[~sel]
    return ids[idx].tolist(), bad_n, bad_sample





def _rel_from_key(key: tuple) -> Relationship:
    """Reconstruct the identity fields of a relationship from its key
    (sufficient for edge-endpoint computation)."""
    return Relationship(resource=ObjectRef(key[0], key[1]), relation=key[2],
                        subject=SubjectRef(key[3], key[4], key[5]))


def _unify_check_buckets(q_arr, gather_idx, gather_col, dead) -> tuple:
    """Bucket a check batch's gather arrays so their jit key lands on a
    shape the prewarm ladder compiled.  The check kernels jit-retrace
    per (lanes, gather) shape pair; an independent gather ladder put
    small real batches (gather <= lanes) on sub-diagonal keys prewarm
    never compiled — each one a multi-second lazy XLA compile on the
    hot path.  The gather is floored at the lane width (padded slots
    re-read (row 0, col 0) and are discarded by the caller — free),
    putting every gather <= lanes batch on the prewarmed diagonal.
    The query lanes are NEVER padded: q_arr's length keys the donated
    arena pool (arena_key) and the sweep cost, so inflating it to a
    large gather bucket would multiply every such batch's kernel work
    (a 4096-request single-subject postfilter check would sweep 4096
    lanes instead of 32).  gather > lanes batches therefore keep
    supra-diagonal keys — prewarm walks those pairs up the ladder, and
    beyond-ladder shapes pay one attributed compile on first use
    (shape_args in timeline.time_first_call).  `dead` is unused but
    kept so call sites document the pad value the lanes already carry."""
    g = max(bucket(len(gather_idx), _MIN_BATCH_BUCKET), len(q_arr))
    gi = np.zeros(g, np.int32)
    gc = np.zeros(g, np.int32)
    gi[: len(gather_idx)] = gather_idx
    gc[: len(gather_col)] = gather_col
    return q_arr, gi, gc


class _PrewarmMixin:
    """Compile-prewarm of the common pow-2 bucket ladder, shared by the
    segment and ELL graphs (warm_start(prewarm=True))."""

    def prewarm(self, lanes: Iterable[int] = (32, 64, 128, 256),
                slot_ranges: Iterable[tuple] = (),
                pipelined: bool = True) -> int:
        """Compile the common pow-2 bucket ladder NOW: XLA compiles
        lazily inside the first execution of each (entry point, bucket,
        static slot range) key, so without prewarm every first request
        of a new bucket absorbs a multi-second stall.  The dummy batches
        carry only dead-index columns — every evaluate converges in one
        sweep, so the cost here is compile, not execution.  Each warmed
        call is recorded as a `compile` event on the rebuild track
        (near-zero slices for keys that were already compiled)."""
        pipelined = (pipelined
                     and getattr(self, "run_checks3_device", None) is not None)
        if pipelined:
            lookup = (getattr(self, "run_lookup_packed_T_device", None)
                      or self.run_lookup_T_device)
        else:
            lookup = (getattr(self, "run_lookup_packed", None)
                      or self.run_lookup)
        dead = self.prog.dead_index
        snap = self.snapshot()
        warmed = 0
        buckets = sorted({self.batch_bucket(b) for b in lanes})
        g_max = buckets[-1] if buckets else 0
        for b in buckets:
            q = np.full(b, dead, np.int32)
            # checks jit-key per (lanes, gather) shape pair.  Real
            # batches sit on the diagonal (gather floored at the lane
            # width, _unify_check_buckets) or ABOVE it (more gather
            # slots than distinct subjects — the many-requests-per-
            # subject postfilter shape), so walk gather from b up the
            # ladder; beyond-ladder gathers pay one attributed compile
            # on first use.
            g = b
            while g <= g_max:
                gi = np.zeros(g, np.int32)
                gc = np.zeros(g, np.int32)
                t0 = timeline.now()
                if pipelined:
                    dev, _, _ = self.run_checks3_device(q, gi, gc, snap=snap)
                    np.asarray(dev)
                else:
                    self.run_checks3(q, gi, gc, snap=snap)
                timeline.record("compile", "rebuild", t0, bucket=b,
                                prewarm="checks" if g == b
                                else f"checks:g{g}")
                warmed += 1
                g *= 2
            for (off, length) in slot_ranges:
                t0 = timeline.now()
                if pipelined:
                    dev, _, _ = lookup(off, length, q, snap=snap)
                    np.asarray(dev)
                else:
                    lookup(off, length, q, snap=snap)
                timeline.record("compile", "rebuild", t0, bucket=b,
                                prewarm=f"lookup:{off}")
                warmed += 1
        warmed += self.prewarm_flush()
        return warmed

    # delta-flush scatter ladder (pad_scatter buckets dirty-row counts
    # at a floor of 16; drains bigger than 512 rows are rare enough to
    # eat their one compile when they first happen)
    _FLUSH_PREWARM_BUCKETS = (16, 32, 64, 128, 256, 512)

    def prewarm_flush(self) -> int:
        """Compile the delta-flush scatter ladder NOW: flush() runs
        `.at[rows].set(vals)` with pad_scatter-bucketed row counts, and
        each novel (table, bucket) shape is a lazy XLA scatter compile
        (~0.4s on CPU) that would otherwise land under the endpoint
        lock on the first drain of that size — a request-visible stall
        the churn soak flags.  Idempotent: every scatter rewrites row 0
        with its current value."""
        warmed = 0
        for b in self._FLUSH_PREWARM_BUCKETS:
            t0 = timeline.now()
            if self._prewarm_flush_bucket(b):
                warmed += 1
                timeline.record("compile", "rebuild", t0, bucket=b,
                                prewarm="flush")
        return warmed

    def _prewarm_flush_bucket(self, b: int) -> bool:  # per-graph
        return False


class _SegmentGraph(_PrewarmMixin):
    """Flat padded edge arrays + gather/segment_sum kernel (ops/spmv.py)."""

    def __init__(self, prog: GraphProgram, edge_endpoints,
                 num_iters: Optional[int] = None):
        self.prog = prog
        self.num_iters = num_iters
        self._edge_endpoints = edge_endpoints
        # the segment kernel has no MAYBE plane: it can skip the oracle
        # for caveat-affected pairs only when every caveat resolved at
        # compile time (no undecidable edges)
        self.tri_state_capable = (prog.caveats_device_ok
                                  and not len(prog.cav_src))
        # context-decided caveats delta incrementally (they are ordinary
        # definite edges here); an undecidable caveat arrives through
        # add_cav_rel, which reports failure and forces a rebuild — the
        # rebuilt segment graph STAYS plane-less, so correctness comes
        # from tri_state_capable flipping False and routing caveat-
        # affected pairs to the host oracle
        self.supports_cav_deltas = True
        capacity = bucket(max(len(prog.edge_src) * 2, _MIN_EDGE_BUCKET))
        src, dst = pad_edges(prog, capacity)
        self.edge_src = jnp.asarray(src)
        self.edge_dst = jnp.asarray(dst)
        self.sorted_edges = True
        e = len(prog.edge_src)
        self.free: list[int] = list(range(e, capacity))
        # tuple key -> positions occupied by that tuple's edges
        self.positions: dict[tuple, list] = {}
        self._kernels: dict[bool, KernelCache] = {}
        # HBM-ledger generation for lazily created kernel caches (their
        # donated state arenas register under it; _register_graph_buffers
        # restamps on rebuild)
        self.devtel_generation = 0
        self._updates: dict[int, tuple] = {}  # pos -> (src, dst), batched
        # index tuple keys -> edge positions (edges were emitted in tuple
        # order then sorted; recover positions by pair matching)
        self._pos_by_pair: dict[tuple, list] = {}
        for i, (s, dd) in enumerate(zip(prog.edge_src, prog.edge_dst)):
            self._pos_by_pair.setdefault((int(s), int(dd)), []).append(i)

    def index_tuples(self, tuples: list) -> None:
        for rel in tuples:
            pairs = self._edge_endpoints(self.prog, rel)
            if not pairs:
                continue
            positions = []
            for pair in pairs:
                stack = self._pos_by_pair.get(pair)
                if stack:
                    positions.append(stack.pop())
            self.positions[rel.key()] = positions
        self._pos_by_pair = {}

    def _kernel(self) -> KernelCache:
        key = self.sorted_edges
        k = self._kernels.get(key)
        if k is None:
            k = KernelCache(self.prog, num_iters=self.num_iters,
                            indices_sorted=key)
            k.devtel_generation = self.devtel_generation
            self._kernels[key] = k
        return k

    # -- delta application (host side; device flush batched) ----------------

    def remove_key(self, key: tuple) -> bool:
        for pos in self.positions.pop(key, ()):
            self._updates[pos] = (self.prog.dead_index, self.prog.dead_index)
            self.free.append(pos)
        return True

    def add_rel(self, rel: Relationship) -> bool:
        key = rel.key()
        if key in self.positions:
            return True  # edges already present (re-touch)
        pairs = self._edge_endpoints(self.prog, rel)
        if pairs is None:
            return False
        positions = []
        for (s, dd) in pairs:
            if not self.free:
                return False
            pos = self.free.pop()
            self._updates[pos] = (s, dd)
            positions.append(pos)
        self.positions[key] = positions
        return True

    def flush(self) -> bool:
        """Push batched host edits to the device arrays.  A position freed
        and re-allocated within one drain appears once (dict is last-write-
        wins, matching XLA scatter's undefined duplicate order)."""
        if not self._updates:
            return False
        pos_np = np.asarray(list(self._updates.keys()), np.int32)
        sd = np.asarray(list(self._updates.values()), np.int32)  # [M, 2]
        pos_np, sd = pad_scatter(pos_np, sd)
        pos = jnp.asarray(pos_np)
        self.edge_src = self.edge_src.at[pos].set(jnp.asarray(sd[:, 0]))
        self.edge_dst = self.edge_dst.at[pos].set(jnp.asarray(sd[:, 1]))
        self.sorted_edges = False
        self._updates = {}
        return True

    def _prewarm_flush_bucket(self, b: int) -> bool:
        """Idempotent `.at[pos].set` on both edge arrays at dirty-edge
        bucket `b` (position 0 rewritten with its current value), so
        flush()'s scatter shapes are compiled before churn arrives.
        Does NOT clear sorted_edges — nothing changed."""
        if not len(self.edge_src):
            return False
        s0 = int(self.edge_src[0])
        d0 = int(self.edge_dst[0])
        pos = jnp.asarray(np.zeros(b, np.int32))
        self.edge_src = self.edge_src.at[pos].set(
            jnp.asarray(np.full(b, s0, np.int32))).block_until_ready()
        self.edge_dst = self.edge_dst.at[pos].set(
            jnp.asarray(np.full(b, d0, np.int32))).block_until_ready()
        return True

    # -- queries ------------------------------------------------------------

    def batch_bucket(self, n: int) -> int:
        return bucket(max(n, 1), _MIN_BATCH_BUCKET)

    def snapshot(self) -> tuple:
        """Immutable query view (kernel choice + edge arrays) captured
        under the endpoint lock; kernel execution then proceeds OUTSIDE
        the lock on a consistent graph (flush swaps whole arrays, never
        mutates them)."""
        return (self._kernel(), self.edge_src, self.edge_dst)

    def run_checks(self, q_arr, gather_idx, gather_col,
                   snap=None) -> np.ndarray:
        kern, src, dst = snap if snap is not None else self.snapshot()
        # unify lanes and gather into ONE bucket so every check lands
        # on a diagonal jit key the prewarm ladder already compiled —
        # see _EllGraph.run_checks3
        q_arr, gi, gc = _unify_check_buckets(
            q_arr, gather_idx, gather_col, self.prog.dead_index)
        return kern.checks(q_arr, gi, gc, src, dst)

    def run_checks3(self, q_arr, gather_idx, gather_col,
                    snap=None) -> np.ndarray:
        return np.where(
            self.run_checks(q_arr, gather_idx, gather_col, snap), 2, 0)

    def run_lookup(self, offset: int, length: int, q_arr,
                   snap=None) -> np.ndarray:
        kern, src, dst = snap if snap is not None else self.snapshot()
        return kern.lookup(offset, length, q_arr, src, dst)

    # -- device-resident pipeline (dispatch-only; caller owns readback) ------

    def run_checks3_device(self, q_arr, gather_idx, gather_col, snap=None):
        kern, src, dst = snap if snap is not None else self.snapshot()
        # same bucket unification as run_checks (prewarm-diagonal keys)
        q_arr, gi, gc = _unify_check_buckets(
            q_arr, gather_idx, gather_col, self.prog.dead_index)
        dev, tel = kern.checks3_device(q_arr, gi, gc, src, dst)
        return dev, tel, kern

    def run_lookup_T_device(self, offset: int, length: int, q_arr,
                            snap=None):
        kern, src, dst = snap if snap is not None else self.snapshot()
        dev, tel = kern.lookup_T_device(offset, length, q_arr, src, dst)
        return dev, tel, kern

    # no MAYBE plane: removals are vacuous, insertions force a rebuild
    def remove_cav_key(self, key: tuple) -> bool:
        return True

    def add_cav_rel(self, rel: Relationship) -> bool:
        return False


class _EllGraph(_PrewarmMixin):
    """Bit-packed fixed-fanin tables + gather-only kernel (ops/ell.py).

    Delta edits are positionless: an edge (src -> dst) lives somewhere in
    dst's root row or its OR-tree, and because every tree node is a
    monotone OR gate, *any* dead slot in the tree can absorb a new child.
    Insert/remove walk the tree host-side (O(row fanin), only on writes)
    and batch row-wise device updates.
    """

    def __init__(self, prog: GraphProgram, edge_endpoints,
                 num_iters: Optional[int] = None):
        self.prog = prog
        self._edge_endpoints = edge_endpoints
        t = build_tables(prog)
        # tri-state device path (VERDICT r3 item 5): undecidable caveated
        # edges live in a separate MAYBE-plane gather table; queries on
        # caveat-affected pairs stay on the kernel instead of dropping to
        # the recursive host oracle
        self.has_cav = bool(len(prog.cav_src)) and prog.caveats_device_ok
        self.tri_state_capable = prog.caveats_device_ok
        # caveated tuples delta incrementally: decided ones through the
        # definite tables, undecidable ones through the cav (MAYBE) table
        self.supports_cav_deltas = True
        tree_depth = t.tree_depth
        a_shared = t.idx_aux.shape[0]
        if self.has_cav:
            from .ell import K_AUX, build_cav_tables
            ct = build_cav_tables(prog, a_shared)
            if ct.n_aux_cav:
                # caveat OR-tree nodes get dead rows in the shared aux
                # table so the one-step concat covers every state row
                t.idx_aux = np.vstack([
                    t.idx_aux,
                    np.full((ct.n_aux_cav, K_AUX), prog.dead_index,
                            np.int32)])
            self.host_cav = ct.idx_cav
            self.dev_cav = jnp.asarray(ct.idx_cav)
            tree_depth = max(tree_depth, ct.tree_depth)
        else:
            self.host_cav = None
            self.dev_cav = None
        self.host_main = t.idx_main
        self.host_aux = t.idx_aux
        self._init_aux_growth(t)
        self.dev_main = jnp.asarray(t.idx_main)
        self.dev_aux = jnp.asarray(t.idx_aux)
        self.kernel = EllKernelCache(prog, n_aux_rows=t.idx_aux.shape[0],
                                     tree_depth=tree_depth,
                                     num_iters=num_iters,
                                     planes=self.has_cav,
                                     shared_tree_depth=t.tree_depth,
                                     aux_slices=t.aux_slices,
                                     spare_rows=t.spare_rows)
        self._dirty_main: set = set()
        self._dirty_aux: set = set()
        self._dirty_cav: set = set()
        # growths that flipped a build-time aux-free stage annotation
        # (surfaced as the endpoint's stage_aux_flips stat)
        self.stage_aux_flips = 0
        # first cav-aux row index: values >= this in the cav table are
        # OR-tree nodes whose children live in the cav table itself
        self._cav_aux_base = prog.state_size + a_shared

    def _init_aux_growth(self, t) -> None:
        """Delta-growth state over host_aux (built from tables `t`)."""
        # spare rows are taken in order, so the staged step refreshes a
        # prefix of the pool (EllKernelCache.note_spare_row)
        self._spare_aux = collections.deque(t.spare_rows)
        # columns a delta insert may fill: a built row's slice width
        self._aux_width = aux_row_widths(self.host_aux.shape[0],
                                         t.aux_slices)
        self._spare_depth: dict = {}  # spare row -> spare levels it tops
        self._grow_extra: dict = {}  # root row -> levels grown past build

    def index_tuples(self, tuples: list) -> None:
        pass  # positionless — nothing to index

    # -- tree walking --------------------------------------------------------

    def _walk(self, root_row: int, want: int) -> Optional[tuple]:
        """Find `want` (a state index, or the dead index for a free slot) in
        root_row's row or its aux subtree; returns (table, row, col)."""
        n = self.prog.state_size
        stack = [("m", root_row)]
        while stack:
            table, row = stack.pop()
            vals = (self.host_main[row] if table == "m"
                    else self.host_aux[row, : self._aux_width[row]])
            for col, v in enumerate(vals):
                v = int(v)
                if v == want:
                    return (table, row, col)
                if v >= n:  # aux child: descend
                    stack.append(("a", v - n))
        return None

    def _set(self, loc: tuple, value: int) -> None:
        table, row, col = loc
        if table == "m":
            self.host_main[row, col] = value
            self._dirty_main.add(row)
        else:
            self.host_aux[row, col] = value
            self._dirty_aux.add(row)

    # -- delta application ---------------------------------------------------

    def _remove_pairs(self, pairs: list) -> bool:
        for (s, d) in pairs:
            loc = self._walk(d, s)
            if loc is not None:
                self._set(loc, self.prog.dead_index)
        return True

    def remove_key(self, key: tuple) -> bool:
        pairs = self._edge_endpoints(self.prog, _rel_from_key(key))
        if pairs is None:
            # endpoints unresolvable means the ids were never compiled; the
            # tuple can't be in the tables — nothing to remove
            return True
        return self._remove_pairs(pairs)

    # Repeated growth on one destination nests OR-tree levels: the staged
    # step re-jits with one more spare pass, the Jacobi step (passes =
    # shared_tree_depth + 1) pays an extra outer iteration per level past
    # its budget for queries touching that hub.  Cap it: past this many
    # extra levels on one root, fall back to a rebuild (which recompiles
    # with the true tree height).
    _GROW_EXTRA_MAX = 3

    def _grow(self, root_row: int, src: int) -> bool:
        """Full main row (no dead slot anywhere in its tree): move the
        row's direct entries into a spare aux node, append `src` there,
        and point the row at the node — one extra OR-tree level for this
        destination, no rebuild.  Monotone OR gates make this exactly
        equivalent; levels past _GROW_EXTRA_MAX force a rebuild (see
        above)."""
        if not self._spare_aux:
            return False
        grown = self._grow_extra.get(root_row, 0)
        if grown >= self._GROW_EXTRA_MAX:
            return False  # budget exhausted for this hub: rebuild
        row = self.host_main[root_row].copy()
        if len(row) + 1 > self.host_aux.shape[1]:
            # K_MAIN tuned >= K_AUX: the row's children + the new source
            # don't fit one aux node — fall back to the rebuild path
            return False
        j = self._spare_aux.popleft()
        n = self.prog.state_size
        depth = 1 + max((self._spare_depth.get(int(v) - n, 0)
                         for v in row if v >= n), default=0)
        self._spare_depth[j] = depth
        self.host_aux[j, : len(row)] = row
        self.host_aux[j, len(row)] = src
        self._dirty_aux.add(j)
        self.host_main[root_row, 0] = n + j
        self.host_main[root_row, 1:] = self.prog.dead_index
        self._dirty_main.add(root_row)
        self._grow_extra[root_row] = grown + 1
        # the row now reads an aux node: if its stage was annotated
        # aux-free at build, flip the flag (and count it) instead of
        # silently paying an extra sweep per query on this hub
        note = getattr(self.kernel, "note_main_aux_ref", None)
        if note is not None and note(root_row):
            self.stage_aux_flips += 1
        # the staged step must now refresh row j, `depth` passes deep
        note = getattr(self.kernel, "note_spare_row", None)
        if note is not None:
            note(j, depth)
        return True

    def add_rel(self, rel: Relationship) -> bool:
        pairs = self._edge_endpoints(self.prog, rel)
        if pairs is None:
            return False
        dead = self.prog.dead_index
        for (s, d) in pairs:
            if self._walk(d, s) is not None:
                continue  # edge already present (re-touch)
            loc = self._walk(d, dead)
            if loc is None:
                if not self._grow(d, s):
                    return False  # spare pool dry: rebuild grows a level
                continue
            self._set(loc, s)
        return True

    # -- caveat (MAYBE plane) table deltas -----------------------------------
    # Same positionless tree-walk discipline as the definite tables, over
    # the cav gather table; callers route a tuple's edges here when its
    # caveat is undecidable.  Only meaningful when planes were compiled
    # (has_cav); the endpoint rebuilds otherwise.

    def _walk_cav(self, root_row: int, want: int) -> Optional[tuple]:
        if self.host_cav is None:
            return None
        stack = [root_row]
        while stack:
            row = stack.pop()
            for col, v in enumerate(self.host_cav[row]):
                v = int(v)
                if v == want:
                    return (row, col)
                if v >= self._cav_aux_base:  # cav OR-tree node: descend
                    stack.append(v)
        return None

    def _set_cav(self, loc: tuple, value: int) -> None:
        row, col = loc
        self.host_cav[row, col] = value
        self._dirty_cav.add(row)

    def remove_cav_key(self, key: tuple) -> bool:
        """Remove a tuple's MAYBE-plane edges (no-op if absent)."""
        if self.host_cav is None:
            return True
        pairs = self._edge_endpoints(self.prog, _rel_from_key(key))
        if pairs is None:
            return True  # ids never compiled: cannot be in the table
        for (s, d) in pairs:
            loc = self._walk_cav(d, s)
            if loc is not None:
                self._set_cav(loc, self.prog.dead_index)
        return True

    def add_cav_rel(self, rel: Relationship) -> bool:
        """Insert a tuple's edges into the MAYBE plane; False forces a
        rebuild (no planes compiled, unknown ids, or a full row/tree)."""
        if self.host_cav is None:
            return False
        pairs = self._edge_endpoints(self.prog, rel)
        if pairs is None:
            return False
        dead = self.prog.dead_index
        for (s, d) in pairs:
            if self._walk_cav(d, s) is not None:
                continue  # already present (re-touch)
            loc = self._walk_cav(d, dead)
            if loc is None:
                return False  # row and tree full: rebuild grows a level
            self._set_cav(loc, s)
        return True

    def flush(self) -> bool:
        changed = False
        if self._dirty_main:
            rows = np.asarray(sorted(self._dirty_main), np.int32)
            rows, vals = pad_scatter(rows, self.host_main[rows])
            self.dev_main = self.dev_main.at[jnp.asarray(rows)].set(
                jnp.asarray(vals))
            self._dirty_main = set()
            changed = True
        if self._dirty_aux:
            rows = np.asarray(sorted(self._dirty_aux), np.int32)
            rows, vals = pad_scatter(rows, self.host_aux[rows])
            self.dev_aux = self.dev_aux.at[jnp.asarray(rows)].set(
                jnp.asarray(vals))
            self._dirty_aux = set()
            changed = True
        if self._dirty_cav:
            rows = np.asarray(sorted(self._dirty_cav), np.int32)
            rows, vals = pad_scatter(rows, self.host_cav[rows])
            self.dev_cav = self.dev_cav.at[jnp.asarray(rows)].set(
                jnp.asarray(vals))
            self._dirty_cav = set()
            changed = True
        return changed

    def _prewarm_flush_bucket(self, b: int) -> bool:
        """One idempotent `.at[rows].set` per device table at dirty-row
        bucket `b` (row 0 rewritten with its current host values), so
        flush()'s scatter shapes are compiled before churn arrives."""
        rows = np.zeros(b, np.int32)
        jrows = jnp.asarray(rows)
        done = False
        if len(self.host_main):
            self.dev_main = self.dev_main.at[jrows].set(
                jnp.asarray(self.host_main[rows])).block_until_ready()
            done = True
        if len(self.host_aux):
            self.dev_aux = self.dev_aux.at[jrows].set(
                jnp.asarray(self.host_aux[rows])).block_until_ready()
            done = True
        if self.host_cav is not None and len(self.host_cav):
            self.dev_cav = self.dev_cav.at[jrows].set(
                jnp.asarray(self.host_cav[rows])).block_until_ready()
            done = True
        return done

    # -- queries ------------------------------------------------------------

    def batch_bucket(self, n: int) -> int:
        # SPICEDB_TPU_MIN_BATCH_WORDS floors the packed word width — an
        # experiment knob, default off.  Measured on v5e
        # (scripts/probe_wide_batch.py): on the production multitenant-1m
        # graph the iteration cost is bandwidth-proportional in W, so
        # widening is a wash (uniform-random gathers DO scalarize at W=8
        # per probe_gather_layout.py, but real graphs' index locality
        # avoids that cliff) — keep W at demand size.
        return batch_words(n, _min_batch_words()) * 32

    def snapshot(self) -> tuple:
        """Immutable query view of the device tables, captured under the
        endpoint lock so kernel execution can proceed OUTSIDE it (flush
        swaps whole arrays via .at[].set, never mutates in place)."""
        return (self.dev_main, self.dev_aux, self.dev_cav)

    def run_checks(self, q_arr, gather_idx, gather_col,
                   snap=None) -> np.ndarray:
        out = self.run_checks3(q_arr, gather_idx, gather_col, snap)
        return out == 2

    def run_checks3(self, q_arr, gather_idx, gather_col,
                    snap=None) -> np.ndarray:
        """Tri-state check values {0: NO, 1: CONDITIONAL, 2: HAS}."""
        main, aux, cav = snap if snap is not None else self.snapshot()
        # lanes and gather unified into ONE bucket: the check jit
        # retraces per (lanes, gather) SHAPE pair, so independent
        # ladders would put small fused batches on off-diagonal keys
        # the prewarm ladder never compiled — a multi-second
        # first-request stall.  Padding the smaller side up (dead query
        # lanes converge in one sweep; gather duplicates of slot 0 are
        # discarded) keeps every batch on the prewarmed diagonal.
        q_arr, gi, gc = _unify_check_buckets(
            q_arr, gather_idx, gather_col, self.prog.dead_index)
        n_words = max(1, len(q_arr) // 32)
        out = self.kernel.checks(q_arr, n_words, gi, gc, main, aux, cav)
        if not self.has_cav:
            return np.where(out, 2, 0)
        return out

    def run_lookup(self, offset: int, length: int, q_arr,
                   snap=None) -> np.ndarray:
        main, aux, cav = snap if snap is not None else self.snapshot()
        n_words = max(1, len(q_arr) // 32)
        return self.kernel.lookup(offset, length, q_arr, n_words,
                                  main, aux, cav)

    def run_lookup_packed(self, offset: int, length: int, q_arr,
                          snap=None) -> np.ndarray:
        main, aux, cav = snap if snap is not None else self.snapshot()
        n_words = max(1, len(q_arr) // 32)
        return self.kernel.lookup_packed(offset, length, q_arr, n_words,
                                         main, aux, cav)

    # -- device-resident pipeline (dispatch-only; caller owns readback) ------

    def run_checks3_device(self, q_arr, gather_idx, gather_col, snap=None):
        main, aux, cav = snap if snap is not None else self.snapshot()
        # same bucket unification as run_checks3 (prewarm-diagonal keys)
        q_arr, gi, gc = _unify_check_buckets(
            q_arr, gather_idx, gather_col, self.prog.dead_index)
        n_words = max(1, len(q_arr) // 32)
        dev, tel = self.kernel.checks_device(q_arr, n_words, gi, gc,
                                             main, aux, cav)
        return dev, tel, self.kernel

    def run_lookup_packed_T_device(self, offset: int, length: int, q_arr,
                                   snap=None):
        main, aux, cav = snap if snap is not None else self.snapshot()
        n_words = max(1, len(q_arr) // 32)
        dev, tel = self.kernel.lookup_packed_T_device(
            offset, length, q_arr, n_words, main, aux, cav)
        return dev, tel, self.kernel

class _ShardedEllGraph(_EllGraph):
    """Multi-chip ELL graph: same positionless host tables and tree-walk
    delta edits as _EllGraph, but the device tables are row-sharded over a
    2D (data x graph) mesh and queries run through
    parallel.sharding.ShardedEllKernel (word-sharded batch x row-sharded
    one-step closure with per-iteration all_gather over ICI).  This puts
    the sharded kernels behind the same endpoint drain/lock machinery as
    the single-chip path (SURVEY.md §7 step 7); the reference counterpart
    is SpiceDB's internal dispatch distribution
    (reference pkg/spicedb/spicedb.go:31-47)."""

    def __init__(self, prog: GraphProgram, edge_endpoints, mesh,
                 num_iters: Optional[int] = None):
        from ..parallel.sharding import ShardedEllKernel
        from .ell import build_tables as _build

        self.prog = prog
        self._edge_endpoints = edge_endpoints
        t = _build(prog)
        self.kernel = ShardedEllKernel(prog, mesh, num_iters=num_iters,
                                       tables=t)
        # AFTER kernel construction: the kernel extends t.idx_aux with
        # dead rows for caveat OR-tree nodes, and the host tables must
        # match that row space for tree-walk delta edits
        self.host_main = t.idx_main
        self.host_aux = t.idx_aux
        # the sharded kernel carries the same MAYBE plane as the
        # single-chip path (trailing plane axis); only unsupported caveat
        # shapes (wildcards etc.) fall back to the host oracle
        self.has_cav = self.kernel.planes
        self.tri_state_capable = prog.caveats_device_ok
        # caveated deltas are incremental here too: the kernel keeps a
        # compile-row-space host mirror of the cav table; flush remaps
        # rows/values into the padded device space
        self.supports_cav_deltas = True
        self.host_cav = self.kernel.host_cav_compile
        self._cav_aux_base = prog.state_size + self.kernel.n_aux_shared
        self._init_aux_growth(t)
        self._dirty_main: set = set()
        self._dirty_aux: set = set()
        self._dirty_cav: set = set()
        self.stage_aux_flips = 0  # sharded kernel has no staged step

    def flush(self) -> bool:
        changed = False
        if self._dirty_main:
            rows = np.asarray(sorted(self._dirty_main), np.int32)
            self.kernel.update_main_rows(rows, self.host_main[rows])
            self._dirty_main = set()
            changed = True
        if self._dirty_aux:
            rows = np.asarray(sorted(self._dirty_aux), np.int32)
            self.kernel.update_aux_rows(rows, self.host_aux[rows])
            self._dirty_aux = set()
            changed = True
        if self._dirty_cav:
            rows = np.asarray(sorted(self._dirty_cav), np.int32)
            self.kernel.update_cav_rows(rows, self.host_cav[rows])
            self._dirty_cav = set()
            changed = True
        return changed

    def _prewarm_flush_bucket(self, b: int) -> bool:
        """Sharded variant of the delta-flush scatter prewarm: the
        device tables live on the kernel, so warm flush()'s scatter
        shapes through the same update_*_rows entry points it uses
        (row 0 rewritten with its current host values — idempotent)."""
        rows = np.zeros(b, np.int32)
        done = False
        if len(self.host_main):
            self.kernel.update_main_rows(rows, self.host_main[rows])
            done = True
        if len(self.host_aux):
            self.kernel.update_aux_rows(rows, self.host_aux[rows])
            done = True
        if self.host_cav is not None and len(self.host_cav):
            self.kernel.update_cav_rows(rows, self.host_cav[rows])
            done = True
        if done:
            self.kernel.idx_main.block_until_ready()
            self.kernel.idx_aux.block_until_ready()
        return done

    def batch_bucket(self, n: int) -> int:
        # honor the SPICEDB_TPU_MIN_BATCH_WORDS floor here too (the kernel
        # then rounds up to whole words per data-axis shard)
        return self.kernel.padded_batch_words(
            max(n, _min_batch_words() * 32)) * 32

    def snapshot(self) -> tuple:
        return self.kernel.snapshot_tables()

    def run_checks(self, q_arr, gather_idx, gather_col,
                   snap=None) -> np.ndarray:
        out = self.kernel.checks(np.asarray(q_arr, np.int32),
                                 np.asarray(gather_idx, np.int32),
                                 np.asarray(gather_col, np.int64),
                                 tables=snap)
        return (out == 2) if self.kernel.planes else out

    def run_checks3(self, q_arr, gather_idx, gather_col,
                    snap=None) -> np.ndarray:
        out = self.kernel.checks(np.asarray(q_arr, np.int32),
                                 np.asarray(gather_idx, np.int32),
                                 np.asarray(gather_col, np.int64),
                                 tables=snap)
        if self.kernel.planes:
            return out
        return np.where(out, 2, 0)

    def run_lookup(self, offset: int, length: int, q_arr,
                   snap=None) -> np.ndarray:
        return self.kernel.lookup(offset, length,
                                  np.asarray(q_arr, np.int32), tables=snap)

    def run_lookup_packed(self, offset: int, length: int, q_arr,
                          snap=None) -> np.ndarray:
        return self.kernel.lookup_packed(
            offset, length, np.asarray(q_arr, np.int32), tables=snap)

    # -- device-resident pipeline (dispatch-only; caller owns readback) ------
    # Same contract as _EllGraph's entries: the sharded kernel donates
    # per-shard state arenas and word-transposes on device, so the
    # endpoint's async readback/overlap machinery (and pipelined
    # prewarm) run unchanged on the mesh instead of degrading to the
    # blocking serial path.

    def run_checks3_device(self, q_arr, gather_idx, gather_col, snap=None):
        tables = snap if snap is not None else self.snapshot()
        # same bucket unification as run_checks3 (prewarm-diagonal keys)
        q_arr, gi, gc = _unify_check_buckets(
            q_arr, gather_idx, gather_col, self.prog.dead_index)
        n_words = max(1, len(q_arr) // 32)
        dev, tel = self.kernel.checks_device(q_arr, n_words, gi, gc,
                                             *tables)
        return dev, tel, self.kernel

    def run_lookup_packed_T_device(self, offset: int, length: int, q_arr,
                                   snap=None):
        tables = snap if snap is not None else self.snapshot()
        n_words = max(1, len(q_arr) // 32)
        dev, tel = self.kernel.lookup_packed_T_device(
            offset, length, q_arr, n_words, *tables)
        return dev, tel, self.kernel


_GRAPH_KINDS = {"ell": _EllGraph, "segment": _SegmentGraph}


class JaxEndpoint(PermissionsEndpoint):
    def __init__(self, schema: sch.Schema, store: Optional[TupleStore] = None,
                 num_iters: Optional[int] = None, kernel: Optional[str] = None,
                 mesh=None):
        self.schema = schema
        self.store = store if store is not None else TupleStore()
        # workload attribution resolves footprint closures (the Leopard
        # nesting detector) against the serving schema
        workload.WORKLOAD.note_schema(schema)
        # oracle fallback for query endpoints outside the compiled universe
        self._oracle = Evaluator(schema, self.store)
        self._num_iters = num_iters
        kind = kernel or os.environ.get("SPICEDB_TPU_KERNEL", "ell")
        if kind not in _GRAPH_KINDS:
            raise ValueError(f"unknown kernel {kind!r}; "
                             f"expected one of {sorted(_GRAPH_KINDS)}")
        if mesh is not None and kind != "ell":
            raise ValueError("mesh sharding requires the ell kernel")
        self.mesh = mesh
        self.kernel_kind = kind
        self._graph_cls = _GRAPH_KINDS[kind]
        self._lock = threading.RLock()
        self._graph = None
        # store revision the device graph reflects (checked_at source):
        # rebuilds capture it atomically with their snapshot; applied
        # delta batches advance it to their own revision
        self._graph_revision = 0
        # listener callbacks run while the STORE lock is held; they must
        # never take self._lock (ABBA deadlock with queries that hold
        # self._lock and read the store), so delta intake is a lock-free
        # deque append plus an invalidation flag.
        self._pending: collections.deque = collections.deque()
        self._graph_invalid = False
        self._expiry_heap: list = []  # (expires_at, rel key tuple)
        # current expiration per tuple key; heap entries not matching this
        # map are stale and skipped (lazy deletion)
        self._expiry_meta: dict = {}
        # caveat residuals (SURVEY.md hard part (c)): caveated tuples never
        # enter the device graph; queries on (type, permission) pairs whose
        # closure could traverse one are host-evaluated (tri-state oracle)
        self._caveated_pairs: set = set()
        self._caveat_affected: set = set()
        self._caveated_keys: set = set()
        # explain_checks pre-seeded: InstrumentedEndpoint registers its
        # scrape-time gauges from the keys present at construction
        self.stats = {"rebuilds": 0, "delta_batches": 0, "kernel_calls": 0,
                      "oracle_residual_checks": 0, "spare_assignments": 0,
                      "spare_reclaims": 0, "explain_checks": 0,
                      "bg_rebuilds": 0, "preemptive_rebuilds": 0,
                      "rebuild_failures": 0, "stale_pair_marks": 0,
                      "stale_routed": 0, "leopard_checks": 0,
                      "leopard_lookups": 0, "leopard_recloses": 0}
        # off-loop rebuild state (AsyncRebuild gate; docs/performance.md
        # "Overload & rebuild behavior").  While a background rebuild is
        # in flight the OLD generation keeps serving: deltas it can
        # absorb apply normally (full consistency), deltas it cannot
        # mark their affected (type, permission) closure STALE and those
        # pairs route to the host oracle until the swap clears them —
        # reads never block on a rebuild and never observe a revision
        # the answer doesn't reflect.
        self._stale_pairs: set = set()
        self._stale_closure_cache: dict = {}   # (type, rel) -> pair set
        self._bg_inflight = False
        self._bg_future = None
        self._bg_pending: Optional[collections.deque] = None
        self._bg_epoch = 0
        self._bg_not_before = 0.0
        # generation epoch: bumped at every install; a background
        # candidate built against epoch N abandons itself if a sync
        # rebuild (force_rebuild, bulk-load reset) installed N+1 first
        self._gen_epoch = 0
        # monotone counter over rebuild lifecycle events (start +
        # install), exposed to wrappers that need a cheap "did a rebuild
        # overlap this operation" token
        self._rebuild_epoch = 0
        # initial spare-pool sizes of the live generation, for the
        # low-watermark preemptive rebuild (_spare_pressure)
        self._spare_initial: dict = {}
        self._spare_aux_initial = 0
        # compile the pow-2 bucket ladder on background CANDIDATES
        # before the swap (the server flips this on with
        # --prewarm-compiles) so a fresh generation's first requests
        # recompile nothing
        self.prewarm_rebuilds = False
        self._spare_pool: dict = {}
        # (type, id) -> live tuple keys, for spare-ASSIGNED ids only: when
        # the set empties the row is renamed back to a placeholder and
        # returned to the pool, so unique-name create/delete churn (the
        # normal kubernetes pod lifecycle) never exhausts the pool
        self._assigned_refs: dict = {}
        self._spare_seq = 0
        # HBM-ledger graph generation: bumped per rebuild; the outgoing
        # generation's buffers are retired wholesale (utils/devtel.py)
        self._devtel_gen = 0
        # Leopard materialized group index (ops/leopard.py, LeopardIndex
        # gate): the gate is evaluated ONCE, at construction — like a
        # configured mesh — so differential harnesses can hold an
        # index-on and an index-off endpoint in the same process.  The
        # index itself is a per-generation artifact (built with the
        # candidate off-lock, swapped in _install_candidate).
        self._leopard_wanted = _leopard_on()
        self._leopard = None
        # in-flight background re-close futures (delete-quarantine
        # recovery); wait_rebuilds drains them for test quiescence
        self._leo_futures: list = []
        self.store.add_delta_listener(self._on_delta)
        self.store.add_reset_listener(self._on_reset)

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_bootstrap(cls, bootstrap: Optional[Bootstrap] = None,
                       **kwargs) -> "JaxEndpoint":
        if bootstrap is None or not bootstrap.schema_text:
            schema_text = DEFAULT_BOOTSTRAP_SCHEMA
            rel_text = bootstrap.relationships_text if bootstrap else ""
        else:
            schema_text = bootstrap.schema_text
            rel_text = bootstrap.relationships_text
        from ..spicedb.endpoints import (
            apply_bootstrap_once,
            merge_internal_definitions,
        )
        ep = cls(merge_internal_definitions(sch.parse_schema(schema_text)),
                 **kwargs)
        # bootstrap-once: a store recovered from a data dir (revision > 0)
        # already contains its bootstrap + all post-bootstrap writes
        apply_bootstrap_once(ep.store, rel_text)
        return ep

    # compile-prewarm ladder: the pow-2 lane buckets the dispatcher's
    # fused batches actually land in — from the _MIN_BATCH_BUCKET floor
    # (a single-query batch pads to 8 lanes) through the default
    # 256-concurrent headline shape (the segment kernel jit-keys per
    # lane bucket, so the small buckets are real first-request stalls)
    _PREWARM_LANES = (8, 16, 32, 64, 128, 256)
    _PREWARM_SLOT_CAP = 16

    def warm_start(self, prewarm: bool = False) -> None:
        """Build the device graph from the current store NOW instead of
        lazily on the first query — the warm-graph-start step of crash
        recovery (spicedb/persist): a recovered 1M-tuple store pays its
        compile before the server starts accepting traffic.

        `prewarm=True` additionally compiles the common pow-2 bucket
        ladder of kernel entry points (checks + every compiled
        (type, permission) lookup slot range, capped) so
        first-request-per-bucket jit stalls move to startup; each warmed
        compile records a `compile` timeline event on the rebuild
        track."""
        with timeline.span("warm_start", "rebuild"), self._lock:
            self._apply_pending()
            graph = self._graph
        if not prewarm or graph is None:
            return
        if getattr(graph, "prewarm", None) is None:
            return
        slot_ranges = self._prewarm_slot_ranges(graph)
        t0 = timeline.now()
        # same helper the off-loop rebuild uses on its candidate
        # generations (_bg_rebuild_run), so startup and post-swap
        # prewarm coverage can never silently diverge
        warmed = self._prewarm_graph(graph)
        _log.info("prewarmed %d kernel entry points (%d buckets x %d "
                  "lookup slots + checks) in %.1fs",
                  warmed, len(self._PREWARM_LANES), len(slot_ranges),
                  timeline.now() - t0)

    # -- delta intake -------------------------------------------------------

    def _on_delta(self, update: WatchUpdate) -> None:
        # called under the store lock — must not acquire self._lock.
        # The background intake MUST be appended BEFORE self._pending:
        # in the reverse order this thread can be preempted after the
        # _pending append, the foreground drains it onto the OLD
        # generation, the rebuild replays (without this delta) and
        # swaps — and the delta is lost from the new generation.  With
        # bg-first the delta is either in the intake before the swap's
        # drain (replayed onto the candidate) or appended after the
        # swap nulled the attribute, in which case _pending still holds
        # it for the new generation's next drain (re-application of a
        # delta the candidate also replayed is idempotent by design).
        bg = self._bg_pending
        if bg is not None:
            bg.append(update)
        self._pending.append(update)

    def _on_reset(self) -> None:
        """bulk_load/delete_all invalidate the device graph wholesale
        (called under the store lock — must not acquire self._lock)."""
        self._graph_invalid = True

    # -- graph maintenance --------------------------------------------------

    def _edge_endpoints(self, prog: GraphProgram, rel: Relationship) -> Optional[list]:
        """(src, dst) pairs this tuple contributes, or None if an id is
        outside the compiled universe (forces rebuild)."""
        rt = rel.resource.type
        d = self.schema.definitions.get(rt)
        if d is None or rel.relation not in d.relations:
            return []
        dst = prog.state_index(rt, rel.relation, rel.resource.id)
        if dst is None:
            return None
        out = []
        st, sid, srel = rel.subject.type, rel.subject.id, rel.subject.relation
        if sid == WILDCARD:
            # wildcard masks are baked into the compiled program; changing
            # them requires a rebuild
            return None
        src = prog.subject_index(st, sid, srel)
        if src is None:
            return None
        out.append((src, dst))
        # arrow edges (specs recorded by the graph compiler)
        for (perm, k, target, slot) in prog.arrow_specs.get((rt, rel.relation), ()):
            if srel:
                continue
            target_def = self.schema.definitions.get(st)
            if target_def is None or not target_def.has_relation_or_permission(target):
                continue
            asrc = prog.state_index(st, target, sid)
            adst = prog.state_index(rt, slot, rel.resource.id)
            if asrc is None or adst is None:
                return None
            out.append((asrc, adst))
        return out

    def _make_graph(self, prog: GraphProgram):
        if self.mesh is not None:
            return _ShardedEllGraph(prog, self._edge_endpoints, self.mesh,
                                    num_iters=self._num_iters)
        return self._graph_cls(prog, self._edge_endpoints,
                               num_iters=self._num_iters)

    def _rebuild(self) -> None:
        """Synchronous rebuild under the endpoint lock: first build,
        wholesale store resets (bulk_load/delete_all), force_rebuild,
        and the AsyncRebuild-gate-off killswitch path.  Queued deltas
        are subsumed by the snapshot (re-application of a delta already
        inside it is idempotent)."""
        t_rebuild = timeline.now()
        self._drain_pending()
        self._graph_invalid = False
        st = self._build_candidate()
        self._install_candidate(st, t_rebuild, mode="sync")

    def _build_candidate(self) -> "_GenState":
        """Build a complete candidate generation from the current store
        snapshot WITHOUT mutating endpoint state — callable from the
        background rebuild executor while the live generation keeps
        serving.  The snapshot reads and the revision capture hold the
        STORE lock together so checked_at can never name a revision
        other than the one the graph reflects."""
        # kill-matrix site: a rebuild executor crashing here must leave
        # the old generation serving (tests/test_faultmatrix.py)
        fail_point("rebuildExecutor")
        st = _GenState()
        # phantom-subject columns (one reserved column per type so
        # first-contact subjects still hit the kernel) + the spare object
        # pool for rebuild-free object creation.  Pool size amortizes the
        # rebuild: sized from the larger of the previous program's
        # universe (covers subject-only types) and the store's current
        # per-type resource counts (covers the first rebuild after a
        # bulk_load, where no previous program exists).  The live-
        # generation reads are taken under the endpoint lock (cheap);
        # the compile below runs with no endpoint lock at all.
        with self._lock:
            prev_counts = (self._graph.prog.num_objects
                           if self._graph is not None else {})
            # num_objects includes the previous generation's synthetic
            # rows (1 phantom + the unassigned spare placeholders);
            # subtract them so pool sizing tracks the REAL universe
            # instead of compounding by ~1/64 at every rebuild (assigned
            # spares are real objects now and correctly stay counted)
            prev_synthetic = ({t: 1 + len(pool)
                               for t, pool in self._spare_pool.items()}
                              if self._graph is not None else {})
        extra = {}
        for t in self.schema.definitions:
            n_t = max(prev_counts.get(t, 0) - prev_synthetic.get(t, 0),
                      len(self.store.object_ids_of_type(t)))
            n_spare = max(_SPARE_FLOOR, n_t // _SPARE_DIVISOR)
            spares = [f"{_SPARE_PREFIX}{k}" for k in range(n_spare)]
            extra[t] = {PHANTOM_ID, *spares}
            st._spare_pool[t] = spares
        with self.store.lock:
            st._graph_revision = self.store.revision
            st._caveated_pairs = self.store.caveated_relation_pairs()
            st._caveat_affected = (
                caveat_affected_pairs(self.schema, st._caveated_pairs)
                if st._caveated_pairs else set())
            st._caveated_keys = (self.store.caveated_keys()
                                 if st._caveated_pairs else set())
            view = self.store.columnar_view() \
                if self._graph_cls is _EllGraph or self.mesh is not None \
                else None
            tuples = None if view is not None else self.store.read(None)
        # the (long) compile runs outside the store lock: writes landing
        # now queue deltas that re-apply idempotently on the new graph
        if view is not None:
            # vectorized compile straight off the store's columnar base —
            # no per-tuple object materialization (the ELL graph is
            # positionless, so nothing needs the tuple list)
            snap, rows, overlay = view
            prog = compile_graph_columnar(self.schema, snap, rows, overlay,
                                          extra_subject_ids=extra)
            graph = self._make_graph(prog)
            self._reset_expiry_columnar(st, snap, rows, overlay)
        else:
            prog = compile_graph(self.schema, tuples, extra_subject_ids=extra)
            graph = self._make_graph(prog)
            graph.index_tuples(tuples)
            self._reset_expiry(st, tuples)
        st._graph = graph
        if self._leopard_wanted:
            # Leopard closure materialization rides the candidate build:
            # off-lock like the compile, consistent with the captured
            # snapshot (the closure is seeded from the compiled edge
            # arrays, so it reflects exactly st._graph_revision).  Hot
            # pairs the runtime detector flagged are materialized first
            # so the byte budget goes to measured wins.
            from .leopard import LeopardIndex
            cand = tuple((c["resource_type"], c["permission"])
                         for c in workload.WORKLOAD.leopard_candidates())
            st._leopard = LeopardIndex.build(
                self.schema, graph.prog,
                caveat_affected=frozenset(st._caveat_affected),
                mesh=self.mesh, candidate_order=cand)
        return st

    def _install_candidate(self, st: "_GenState", t_start: float,
                           mode: str = "sync") -> None:
        """Atomically swap a candidate generation in (MUST hold
        self._lock): the short-lock tail of both the sync and the
        off-loop rebuild paths."""
        _evict_id_views(self._graph)
        self._graph = st._graph
        self._graph_revision = st._graph_revision
        self._spare_pool = st._spare_pool
        self._assigned_refs = st._assigned_refs
        self._spare_seq = st._spare_seq
        self._caveated_pairs = st._caveated_pairs
        self._caveat_affected = st._caveat_affected
        self._caveated_keys = st._caveated_keys
        self._expiry_heap = st._expiry_heap
        self._expiry_meta = st._expiry_meta
        # the candidate's unresolved stale pairs (replay kept failing)
        # carry over — they keep routing to the oracle and re-arm the
        # follow-up rebuild; a clean candidate clears the set
        self._stale_pairs = set(st._stale_pairs)
        self._spare_initial = {t: len(p) for t, p in st._spare_pool.items()}
        self._spare_aux_initial = len(getattr(st._graph, "_spare_aux", ()))
        self._gen_epoch += 1
        self._rebuild_epoch += 1
        self.stats["rebuilds"] += 1
        if mode != "sync":
            # bg_rebuilds counts every off-loop INSTALL (preemptive
            # included); preemptive_rebuilds is the subset kicked by the
            # spare low-watermark.  Both count at install, same as the
            # authz_rebuilds_total{mode=} metric — an abandoned
            # candidate (epoch race, store reset) counts nowhere, so
            # the soak verdict and the Prometheus counter reconcile.
            self.stats["bg_rebuilds"] += 1
        if mode == "preemptive":
            self.stats["preemptive_rebuilds"] += 1
        devtel.REBUILDS.note_rebuild(mode)
        # HBM ledger: the new generation registers, the outgoing one
        # retires wholesale — a leaked old-generation buffer shows up as
        # a non-returning total within one scrape.  The delta is logged
        # per rebuild/warm-start so leak forensics need no scrape at all.
        old_gen = self._devtel_gen
        self._devtel_gen = devtel.next_generation()
        added = _register_graph_buffers(st._graph, self._devtel_gen)
        # the Leopard closure planes are generation artifacts like the
        # graph tables: register under the incoming generation so the
        # wholesale retire below reclaims the outgoing index too
        self._leopard = st._leopard
        if st._leopard is not None:
            added += st._leopard.register_ledger(self._devtel_gen)
            workload.WORKLOAD.note_leopard_status(st._leopard.status_map())
        freed = devtel.LEDGER.retire_generation(old_gen) if old_gen else 0
        # timeline: the rebuild span covers build start -> swap.  Off-
        # loop modes tag background=True so stall attribution can tell
        # "a rebuild ran" from "a rebuild stalled requests" — with the
        # old generation serving throughout, this span is no longer a
        # request stall.
        timeline.record("rebuild", "rebuild", t_start, nbytes=added,
                        generation=self._devtel_gen, mode=mode,
                        background=mode != "sync")
        _log.info("device graph rebuild (%s): generation %d registered "
                  "%d bytes%s; ledger total %d bytes (peak %d)",
                  mode, self._devtel_gen, added,
                  f", generation {old_gen} retired {freed} bytes"
                  if old_gen else "",
                  devtel.LEDGER.total(), devtel.LEDGER.peak)

    def _reset_expiry_columnar(self, st, snap, rows, overlay) -> None:
        st._expiry_heap = []
        st._expiry_meta = {}
        exp = snap.expiry[rows]
        for i in np.nonzero(~np.isnan(exp))[0]:
            key = snap.key_of(int(rows[i]))
            st._expiry_meta[key] = float(exp[i])
            heapq.heappush(st._expiry_heap, (float(exp[i]), key))
        for rel in overlay:
            if rel.expires_at is not None:
                st._expiry_meta[rel.key()] = rel.expires_at
                heapq.heappush(st._expiry_heap, (rel.expires_at, rel.key()))

    def _reset_expiry(self, st, tuples: list) -> None:
        st._expiry_heap = []
        st._expiry_meta = {}
        for rel in tuples:
            if rel.expires_at is not None:
                st._expiry_meta[rel.key()] = rel.expires_at
                heapq.heappush(st._expiry_heap, (rel.expires_at, rel.key()))

    def _set_expiry(self, st, key: tuple, expires_at) -> None:
        if expires_at is None:
            st._expiry_meta.pop(key, None)
        else:
            st._expiry_meta[key] = expires_at
            heapq.heappush(st._expiry_heap, (expires_at, key))

    def _caveat_decidability(self, rel: Relationship):
        """Mirror of the compiler's caveat resolution (_emit_tuple_edges):
        True = definite edges, False = no edges, None = MAYBE-plane edges,
        "unsupported" = no device lowering (wildcard / unknown caveat /
        evaluation error) — rebuild-only."""
        c = self.schema.caveats.get(rel.caveat.name)
        if c is None or rel.subject.id == WILDCARD:
            return "unsupported"
        try:
            return c.evaluate(rel.caveat.context())
        except Exception:
            return "unsupported"

    def _assign_spare(self, st, graph, type_name: str, new_id: str) -> bool:
        """Claim a spare row for a brand-new object id by renaming it in
        the program's id maps (slot layout, row count, and device tables
        are untouched — the row exists, dead, in every slot of the type).
        Runs under self._lock (st is the live endpoint or a candidate
        generation being replayed at swap time); the graph's cached
        numpy id view is patched copy-on-write (see _rename_row — never
        invalidated, and never mutated in place across a drain-epoch
        boundary)."""
        pool = st._spare_pool.get(type_name)
        if not pool:
            return False
        self._rename_row(graph, type_name, pool.pop(), new_id)
        st._assigned_refs[(type_name, new_id)] = set()
        if st is self:
            # candidate-replay applications re-apply deltas the live
            # generation already counted — counting both would double
            # every churn stat across a background rebuild window
            self.stats["spare_assignments"] += 1
        return True

    def _rename_row(self, graph, type_name: str, old_id: str,
                    new_id: str) -> bool:
        """Rename one object row in the program's id maps (the single
        place the rename discipline lives — assignment and reclaim both
        use it) and patch the graph's cached numpy id view copy-on-write.

        COW, not in-place: lookups capture the cached (arr, mask) pair
        under the lock and fancy-index it OUTSIDE the lock against their
        own snapshot — mutating a pair a released lock hold may have
        captured would corrupt those in-flight results (a reclaim rename
        would suppress ids that were legitimately live at the captured
        revision).  _object_ids_np marks an entry PUBLISHED when it
        hands it to a caller; only published entries are copied before
        patching (the fresh copy is private until the next capture, so
        write-heavy/lookup-idle churn patches in place and never pays
        the O(universe) copy).  This replaces dropping the entry
        wholesale, which made every post-churn lookup rebuild an
        O(universe) object array + NUL-mask scan under the lock
        (~tens of ms on the 1M graph)."""
        prog = graph.prog
        local = prog.object_index[type_name].pop(old_id, None)
        if local is None:
            return False
        prog.object_index[type_name][new_id] = local
        prog.object_ids[type_name][local] = new_id
        cache = getattr(graph, "_ids_np_cache", None)
        if cache is not None:
            entry = cache.get(type_name)
            if entry is not None:
                arr, mask = entry
                published = graph._ids_np_published
                if type_name in published:
                    arr = arr.copy()
                    mask = mask.copy()
                    cache[type_name] = (arr, mask)
                    published.discard(type_name)
                arr[local] = new_id
                mask[local] = "\x00" in new_id
        return True

    def _note_key_applied(self, st, key: tuple) -> None:
        """Record a live tuple against any spare-assigned ids it names."""
        for side in ((key[0], key[1]), (key[3], key[4])):
            refs = st._assigned_refs.get(side)
            if refs is not None:
                refs.add(key)

    def _note_key_removed(self, st, graph, key: tuple) -> None:
        """Drop a tuple from its ids' ref sets; an emptied set reclaims
        the spare row (rename back to a fresh placeholder + repool)."""
        for side in ((key[0], key[1]), (key[3], key[4])):
            refs = st._assigned_refs.get(side)
            if refs is None:
                continue
            refs.discard(key)
            if not refs:
                self._reclaim_spare(st, graph, side)

    def _reclaim_spare(self, st, graph, side: tuple) -> None:
        t, old_id = side
        st._assigned_refs.pop(side, None)
        st._spare_seq += 1
        placeholder = f"{_SPARE_PREFIX}r{st._spare_seq}"
        if not self._rename_row(graph, t, old_id, placeholder):
            return
        st._spare_pool.setdefault(t, []).append(placeholder)
        if st is self:  # not candidate replay (see _assign_spare)
            self.stats["spare_reclaims"] += 1

    def _leo_insert(self, st, graph, rel, key) -> None:
        """Mirror a definite tuple the device graph just absorbed into
        the generation's Leopard closure (ops/leopard.py); no-op when no
        index was built for this generation."""
        lp = st._leopard
        if lp is not None:
            lp.apply_insert(key, self._edge_endpoints(graph.prog, rel))

    def _leo_remove(self, st, graph, key) -> None:
        """Mirror a removal into the Leopard closure BEFORE spare-row
        reclaim renames the ids away (_note_key_removed): the closure's
        local rows are keyed by the compiled state index, which the
        rename re-purposes."""
        lp = st._leopard
        if lp is not None:
            lp.apply_remove(key, self._edge_endpoints(
                graph.prog, _rel_from_key(key)))

    def _ensure_ids_for(self, st, graph, rel: Relationship) -> bool:
        """Make every id a TOUCHed tuple names indexable, assigning spare
        rows to new ones; False (pool dry / unknown type combination)
        forces a rebuild."""
        prog = graph.prog
        rt, rid = rel.resource.type, rel.resource.id
        d = self.schema.definitions.get(rt)
        if d is None or rel.relation not in d.relations:
            # edgeless tuple (unmodeled relation/type): _edge_endpoints
            # will report no edges — never spend spare rows on it
            return True
        if rt in prog.object_index and rid not in prog.object_index[rt]:
            if not self._assign_spare(st, graph, rt, rid):
                return False
        stype, sid = rel.subject.type, rel.subject.id
        if (stype in prog.object_index and sid != WILDCARD
                and sid not in prog.object_index[stype]):
            if not self._assign_spare(st, graph, stype, sid):
                return False
        return True

    def _drain_pending(self) -> list:
        """Atomically take all queued delta batches."""
        out = []
        while True:
            try:
                out.append(self._pending.popleft())
            except IndexError:
                return out

    def _stale_closure(self, resource_type: str, relation: str) -> set:
        """(type, permission) pairs whose answers could depend on tuples
        of (resource_type, relation) — the reachability closure used for
        caveat routing, reused to quarantine pairs the live graph can no
        longer answer (an unapplicable delta).  Memoized per schema
        (static)."""
        key = (resource_type, relation)
        out = self._stale_closure_cache.get(key)
        if out is None:
            out = set(caveat_affected_pairs(self.schema, {key}))
            self._stale_closure_cache[key] = out
        return out

    def _apply_batches(self, st, batches: list) -> tuple:
        """Apply drained delta batches + due expirations to one
        generation's graph (under self._lock).  `st` is the live
        endpoint or a background candidate mid-replay.

        An update the graph cannot absorb (wildcard change, new id with
        the spare pool dry, unsupported caveat shape, grown hub budget
        exhausted) no longer aborts the drain: its affected
        (type, permission) closure is collected into the returned stale
        set — the caller routes those pairs to the host oracle and
        schedules an off-loop rebuild — and application continues, so
        one hard delta cannot stall every other write.  Returns
        (stale pairs, applied revision); the caller flushes."""
        graph = st._graph
        stale: set = set()
        applied_revision = st._graph_revision
        cav_deltas = getattr(graph, "supports_cav_deltas", False)
        for batch in batches:
            applied_revision = max(applied_revision, batch.revision)
            for u in batch.updates:
                key = u.rel.key()
                rt, relation = u.rel.resource.type, u.rel.relation
                if u.op == UpdateOp.DELETE:
                    if u.rel.subject.id == WILDCARD:
                        # wildcard contributions are baked into the
                        # compiled program's masks; only a rebuild
                        # removes them
                        stale |= self._stale_closure(rt, relation)
                        continue
                    self._set_expiry(st, key, None)
                    if key in st._caveated_keys:
                        # caveated tuples can occupy the definite tables
                        # (context decided True) or the MAYBE plane
                        # (undecidable): clear both placements
                        if not (cav_deltas and graph.remove_key(key)
                                and graph.remove_cav_key(key)):
                            stale |= self._stale_closure(rt, relation)
                            continue
                        st._caveated_keys.discard(key)
                        self._leo_remove(st, graph, key)
                        self._note_key_removed(st, graph, key)
                        continue
                    if not graph.remove_key(key):
                        stale |= self._stale_closure(rt, relation)
                        continue
                    self._leo_remove(st, graph, key)
                    self._note_key_removed(st, graph, key)
                elif u.rel.caveat is not None:  # TOUCH, caveated
                    self._set_expiry(st, key, u.rel.expires_at)
                    if not self._ensure_ids_for(st, graph, u.rel):
                        stale |= self._stale_closure(rt, relation)
                        continue
                    value = self._caveat_decidability(u.rel)
                    if value == "unsupported" or not cav_deltas:
                        stale |= self._stale_closure(rt, relation)
                        continue
                    # a re-touch may change the caveat's decidability
                    # (context edits): clear any previous placement, then
                    # insert per the new value
                    if not (graph.remove_key(key)
                            and graph.remove_cav_key(key)):
                        stale |= self._stale_closure(rt, relation)
                        continue
                    st._caveated_keys.add(key)
                    st._caveated_pairs.add((rt, relation))
                    if value is True:
                        if not graph.add_rel(u.rel):
                            stale |= self._stale_closure(rt, relation)
                            continue
                    elif value is None:
                        # MAYBE: needs compiled bitplanes (add_cav_rel
                        # fails when the graph has none -> the rebuild
                        # turns them on)
                        if not graph.add_cav_rel(u.rel):
                            stale |= self._stale_closure(rt, relation)
                            continue
                    # value False: no edges at all
                    if st._leopard is not None:
                        # a caveated tuple now lives on a fragment
                        # relation: a closure bit cannot represent
                        # CONDITIONAL, so the fragment retires for the
                        # generation (the rebuild skips it via
                        # caveat_affected)
                        st._leopard.retire_relation((rt, relation))
                    self._note_key_applied(st, key)
                else:  # TOUCH, definite
                    self._set_expiry(st, key, u.rel.expires_at)
                    if not self._ensure_ids_for(st, graph, u.rel):
                        stale |= self._stale_closure(rt, relation)
                        continue
                    if key in st._caveated_keys:
                        # previously-caveated tuple replaced by a
                        # definite one: undo its old plane placement
                        if not (cav_deltas and graph.remove_cav_key(key)):
                            stale |= self._stale_closure(rt, relation)
                            continue
                        st._caveated_keys.discard(key)
                    if not graph.add_rel(u.rel):
                        stale |= self._stale_closure(rt, relation)
                        continue
                    self._leo_insert(st, graph, u.rel, key)
                    self._note_key_applied(st, key)
        # expire lazily AFTER batch processing so expirations registered by
        # the batches just drained take effect this query; heap entries whose
        # expiry no longer matches the current metadata are stale (tuple
        # deleted/re-touched) and skipped.  The STORE clock is the single
        # time source: reads filter expired tuples with it, so the device
        # graph must agree or kernel/oracle results diverge at the expiry
        # instant.
        now = self.store.now()
        while st._expiry_heap and st._expiry_heap[0][0] <= now:
            exp, key = heapq.heappop(st._expiry_heap)
            if st._expiry_meta.get(key) != exp:
                continue
            del st._expiry_meta[key]
            if key[4] == WILDCARD:
                stale |= self._stale_closure(key[0], key[2])
                continue
            if key in st._caveated_keys:
                # may occupy the definite tables (decided True) or the
                # MAYBE plane — clear both placements
                if not (cav_deltas and graph.remove_key(key)
                        and graph.remove_cav_key(key)):
                    stale |= self._stale_closure(key[0], key[2])
                    continue
                st._caveated_keys.discard(key)
                self._leo_remove(st, graph, key)
                self._note_key_removed(st, graph, key)
                continue
            if not graph.remove_key(key):
                stale |= self._stale_closure(key[0], key[2])
                continue
            self._leo_remove(st, graph, key)
            self._note_key_removed(st, graph, key)
        if stale and st is self:  # not candidate replay (_assign_spare)
            self.stats["stale_pair_marks"] += len(stale)
        return stale, applied_revision

    def _apply_pending(self) -> None:
        """Drain store deltas into the device graph (under self._lock)."""
        if self._graph_invalid:
            self._graph_invalid = False
            dead = self._graph
            self._graph = None
            _evict_id_views(dead)
        graph = self._graph
        if graph is None:
            self._rebuild()
            return
        # re-arm a needed rebuild (pairs still quarantined after a
        # crashed/abandoned background attempt) — rate-limited
        if (self._stale_pairs and not self._bg_inflight
                and self._async_rebuild_on()
                and time.monotonic() >= self._bg_not_before):
            self._kick_background_rebuild("background")
        batches = self._drain_pending()
        if not batches and not (self._expiry_heap
                                and self._expiry_heap[0][0]
                                <= self.store.now()):
            return

        # timeline "compact": incremental delta application + device
        # row flush under the endpoint lock (the rebuild-free churn
        # absorption path); a rebuild taken below records its own span
        t_compact = timeline.now()
        stale, applied_revision = self._apply_batches(self, batches)
        if stale and not self._async_rebuild_on():
            # killswitch path (AsyncRebuild off): reproduce the pre-PR
            # synchronous rebuild-under-lock — the snapshot subsumes
            # every drained delta, stale routing never engages
            self._rebuild()
            return
        self._graph_revision = applied_revision
        if stale:
            # quarantine: affected pairs route to the host oracle (full
            # consistency preserved) while the replacement generation
            # builds off-loop and the old one keeps serving everything
            # else
            self._stale_pairs |= stale
            self._kick_background_rebuild("background")
        flips = getattr(graph, "stage_aux_flips", 0)
        if flips:
            self.stats["stage_aux_flips"] = (
                self.stats.get("stage_aux_flips", 0) + flips)
            graph.stage_aux_flips = 0
        if graph.flush():
            self.stats["delta_batches"] += 1
        timeline.record("compact", "rebuild", t_compact,
                        batches=len(batches))
        if not stale and self._async_rebuild_on() and self._spare_pressure():
            # low-watermark preemption: rebuild in the background BEFORE
            # new-object churn drains the spare pool dry, so the pool
            # refresh is never a request-visible event
            self._kick_background_rebuild("preemptive")
        self._kick_leopard_recloses()

    def _kick_leopard_recloses(self) -> None:
        """Submit background re-closes for delete-quarantined Leopard
        fragments (under self._lock).  Quarantined fragments already
        route to the iterative kernel — which the delta path kept
        correct — so the re-close is pure capacity recovery and shares
        the rebuild executor."""
        lp = self._leopard
        if lp is None:
            return
        self._leo_futures = [f for f in self._leo_futures if not f.done()]
        if self._leo_futures:
            return  # one re-close wave at a time
        pending = lp.reclose_pending()
        if not pending:
            return
        for frag in pending:
            try:
                fut = _rebuild_pool().submit(lp.reclose, frag)
            except BaseException:
                break  # executor shut down at teardown: fragments stay
                       # quarantined (kernel fallback remains correct)
            self._leo_futures.append(fut)
            self.stats["leopard_recloses"] += 1

    def _current_graph(self):
        self._apply_pending()
        return self._graph

    def _drain_before_pack(self) -> None:
        """Drain store deltas ahead of a call's `pack` stage, so a
        compaction or rebuild the drain runs (stages of their own) is
        never counted as packing; the drain `pack` repeats under its
        own lock hold is a no-op unless a write landed in between."""
        with self._lock:
            self._apply_pending()

    # -- off-loop rebuild machinery ------------------------------------------

    _SPARE_LOW_FRACTION = 0.25
    _BG_RETRY_BACKOFF_S = 1.0
    _BG_REPLAY_ATTEMPTS = 3

    def _async_rebuild_on(self) -> bool:
        """AsyncRebuild gate accessor; unknown-gate errors fail CLOSED
        (sync rebuilds) — the conservative default for a stripped gate
        registry."""
        try:
            from ..utils.features import GATES
            return GATES.enabled("AsyncRebuild")
        except Exception:
            return False

    def _spare_pressure(self) -> bool:
        """True when the live generation's spare capacity (object pool
        per type, or the ELL spare-aux grow pool) has dropped below the
        low watermark — the signal to rebuild preemptively while churn
        can still be absorbed in place."""
        for t, init in self._spare_initial.items():
            if init >= 8 and (len(self._spare_pool.get(t, ()))
                              < init * self._SPARE_LOW_FRACTION):
                return True
        if self._spare_aux_initial >= 8:
            free_aux = len(getattr(self._graph, "_spare_aux", ()))
            if free_aux < self._spare_aux_initial * self._SPARE_LOW_FRACTION:
                return True
        return False

    def _kick_background_rebuild(self, mode: str) -> None:
        """Submit one off-loop rebuild (under self._lock); no-op while
        one is already in flight or inside the failure backoff."""
        if self._bg_inflight:
            return
        if time.monotonic() < self._bg_not_before:
            return
        self._bg_inflight = True
        self._rebuild_epoch += 1
        self._bg_epoch = self._gen_epoch
        # open the candidate's delta intake BEFORE the snapshot is
        # taken: every delta committed from this instant is either
        # inside the snapshot (idempotent replay) or replayed at swap
        self._bg_pending = collections.deque()
        devtel.REBUILDS.note_inflight(+1)
        try:
            self._bg_future = _rebuild_pool().submit(self._bg_rebuild_run,
                                                     mode)
        except BaseException:
            # a failed submit (e.g. executor shut down at teardown) must
            # not leave _bg_inflight latched True — that would disable
            # background rebuilds for the life of the process and pin
            # stale pairs on the oracle forever
            self._bg_pending = None
            self._bg_inflight = False
            self._bg_future = None
            devtel.REBUILDS.note_inflight(-1)
            self._bg_not_before = (time.monotonic()
                                   + self._BG_RETRY_BACKOFF_S)
            _log.exception("background rebuild submit failed; will re-arm")

    def _drain_bg_pending(self) -> list:
        out = []
        bg = self._bg_pending
        if bg is not None:
            while True:
                try:
                    out.append(bg.popleft())
                except IndexError:
                    break
        return out

    def _bg_rebuild_run(self, mode: str) -> None:
        """Executor body of one off-loop rebuild: build a candidate
        generation against a store snapshot (no endpoint lock), then
        under a short lock replay the deltas that accumulated during
        the build and swap atomically.  A replay that itself hits
        unapplicable deltas retries from a fresh snapshot; the final
        attempt installs anyway with the residue quarantined (strictly
        better than the old generation) and re-arms.  Any crash leaves
        the old generation serving."""
        t0 = timeline.now()
        try:
            for attempt in range(self._BG_REPLAY_ATTEMPTS):
                st = self._build_candidate()
                if self.prewarm_rebuilds:
                    try:
                        self._prewarm_graph(st._graph)
                    except Exception:
                        # the generation installs anyway and compiles
                        # on first use, as it would without prewarm
                        _log.exception("prewarm of a rebuilt generation "
                                       "failed")
                with self._lock:
                    if self._gen_epoch != self._bg_epoch:
                        # a sync rebuild (force_rebuild / store reset)
                        # installed a newer generation mid-build: this
                        # candidate is stale wholesale — abandon it
                        return
                    if self._graph_invalid:
                        # bulk_load/delete_all during the build: the
                        # snapshot predates the reset.  The flag stays
                        # set — wholesale resets are the foreground's
                        # job (next query drops the graph and rebuilds
                        # synchronously); this candidate is abandoned.
                        return
                    batches = self._drain_bg_pending()
                    stale, rev = self._apply_batches(st, batches)
                    st._graph_revision = max(st._graph_revision, rev)
                    if stale and attempt < self._BG_REPLAY_ATTEMPTS - 1:
                        continue  # fresh snapshot subsumes the misfits
                    st._stale_pairs |= stale
                    st._graph.flush()
                    self._install_candidate(st, t0, mode=mode)
                    if stale:
                        # residue carried over: back off, then the next
                        # query's _apply_pending re-arms a follow-up
                        self._bg_not_before = (time.monotonic()
                                               + self._BG_RETRY_BACKOFF_S)
                    return
            # unreachable: every loop path returns (epoch mismatch and
            # store resets abandon; the final attempt always installs
            # with residue quarantined)
        except BaseException:
            _log.exception("background device-graph rebuild (%s) failed; "
                           "the previous generation keeps serving "
                           "(stale pairs stay oracle-routed)", mode)
            with self._lock:
                self.stats["rebuild_failures"] += 1
                self._bg_not_before = (time.monotonic()
                                       + self._BG_RETRY_BACKOFF_S)
        finally:
            with self._lock:
                self._bg_pending = None
                self._bg_inflight = False
                self._bg_future = None
            devtel.REBUILDS.note_inflight(-1)

    def _prewarm_graph(self, graph) -> int:
        """Compile the pow-2 bucket ladder on a graph — the warm-start
        path AND candidate generations BEFORE they are swapped in
        (off-lock, graph not yet visible), so first requests recompile
        nothing.  Returns the number of entry points warmed (0 when
        the graph has no prewarm); a compile that fails raises, so a
        program the device refuses stops warm start instead of
        surfacing at the first request."""
        fn = getattr(graph, "prewarm", None)
        if fn is None:
            return 0
        return fn(lanes=self._PREWARM_LANES,
                  slot_ranges=self._prewarm_slot_ranges(graph),
                  pipelined=_pipeline_on())

    def _prewarm_slot_ranges(self, graph) -> list:
        slot_ranges = []
        for t, d in self.schema.definitions.items():
            for p in d.permissions:
                rng = graph.prog.slot_range(t, p)
                if rng is not None:
                    slot_ranges.append(rng)
            if len(slot_ranges) >= self._PREWARM_SLOT_CAP:
                break
        return slot_ranges[: self._PREWARM_SLOT_CAP]

    @property
    def rebuild_inflight(self) -> bool:
        return self._bg_inflight

    @property
    def rebuild_epoch(self) -> int:
        """Monotone counter over rebuild starts + installs: wrappers use
        an unchanged value as proof no rebuild overlapped an operation."""
        return self._rebuild_epoch

    def wait_rebuilds(self, timeout: float = 30.0) -> bool:
        """Quiesce background rebuild work: block until no rebuild is in
        flight and no pairs remain quarantined (kicking a follow-up
        rebuild if residue needs one).  Test/ops helper — the serving
        paths never call this.  Returns True when quiescent."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                fut = self._bg_future
                if fut is None:
                    # leopard re-closes ride the same quiescence contract
                    leo = [f for f in self._leo_futures if not f.done()]
                    if leo:
                        fut = leo[0]
                    elif (self._leopard is not None
                            and self._leopard.reclose_pending()):
                        self._kick_leopard_recloses()
                        leo = [f for f in self._leo_futures
                               if not f.done()]
                        fut = leo[0] if leo else None
                    elif (not self._stale_pairs
                            or not self._async_rebuild_on()):
                        return True
                    else:
                        self._bg_not_before = 0.0
                        self._kick_background_rebuild("background")
                        fut = self._bg_future
            if fut is not None:
                try:
                    fut.result(timeout=max(0.01,
                                           deadline - time.monotonic()))
                except Exception:
                    pass
        with self._lock:
            return not self._bg_inflight and not self._stale_pairs

    # -- query encoding -----------------------------------------------------

    def _encode_subjects(self, graph, subjects: list) -> tuple:
        """Dedupe subjects into query columns; returns (q_idx array,
        col_of_subject dict, unknown set).  Subjects outside the compiled
        id universe share their type's phantom column (zero tuples ⇒ only
        wildcard terms can grant, and those key on the type); `unknown` is
        left only for subjects whose (type, relation) has no slot at all —
        schema errors the oracle reproduces exactly."""
        cols: dict = {}
        q: list[int] = []
        unknown: set = set()
        phantom_cols: dict = {}  # (type, relation) -> column
        for s in subjects:
            if s in cols or s in unknown:
                continue
            idx = graph.prog.subject_index(s.type, s.id, s.relation)
            if idx is None:
                pk = (s.type, s.relation)
                col = phantom_cols.get(pk)
                if col is not None:
                    cols[s] = col
                    continue
                pidx = graph.prog.subject_index(s.type, PHANTOM_ID, s.relation)
                if pidx is None:
                    unknown.add(s)
                    continue
                phantom_cols[pk] = cols[s] = len(q)
                q.append(pidx)
                continue
            cols[s] = len(q)
            q.append(idx)
        b = graph.batch_bucket(len(q))
        q_arr = np.full(b, graph.prog.dead_index, np.int32)
        q_arr[: len(q)] = q
        return q_arr, cols, unknown

    # -- verbs --------------------------------------------------------------

    _TRISTATE = {0: Permissionship.NO_PERMISSION,
                 1: Permissionship.CONDITIONAL_PERMISSION,
                 2: Permissionship.HAS_PERMISSION}

    def _check_batch_sync(self, reqs: list) -> list:
        """One-shot fused check: capture (drain + encode + dispatch)
        immediately followed by finish (readback + assembly).  The
        two-phase pair below is the dispatcher's pipelining surface."""
        return self._check_batch_finish(self._check_batch_capture(reqs))

    def _leo_check_fill(self, leo_rows: list, results: list,
                        rev: int) -> None:
        """Answer closure-plane check rows: one word-gather per distinct
        plane (fragment closures never carry CONDITIONAL, so the bit maps
        exactly to {NO, HAS}_PERMISSION)."""
        by_plane: dict = {}
        for (i, view, row, col) in leo_rows:
            by_plane.setdefault(id(view[0]), (view[0], []))[1].append(
                (i, row, col))
        for plane, items in by_plane.values():
            rows = np.asarray([r for (_i, r, _c) in items], np.int32)
            cls = np.asarray([c for (_i, _r, c) in items], np.int64)
            words = np.asarray(plane[jnp.asarray(rows),
                                     jnp.asarray(cls // 32)])
            bits = (words >> (cls % 32).astype(np.uint32)) & np.uint32(1)
            for (it, bit) in zip(items, bits):
                results[it[0]] = (int(bit) * 2, rev)

    def _check_batch_capture(self, reqs: list) -> dict:
        """Phase 1 of a fused check batch: the delta drain, the `pack`
        stage (query encoding under the lock, the per-row loop and the
        Leopard fill), then `launch` (pipelined: the async dispatch; the readback waiter
        marks `ready` and `transfer`) or the blocking `ready` call of the
        serial path."""
        bid = timeline.next_batch()
        self._drain_before_pack()
        with tracing.span("pack", fused="check", batch_id=bid,
                          rows=len(reqs)) as pack:
            ctx, kernel_call = self._check_pack(reqs, bid, pack)
        if kernel_call is not None:
            self._check_dispatch(ctx, *kernel_call)
        return ctx

    def _check_pack(self, reqs: list, bid: int, pack: dict) -> tuple:
        """The `pack` stage of a fused check: -> (ctx, kernel call args
        or None when no row needs the kernel)."""
        with self._lock:
            # checked_at = the revision the drained graph actually
            # reflects (tracked through rebuilds and applied deltas) —
            # reading store.revision here instead would race loop-thread
            # writes landing between the read and the drain, attributing
            # results to a revision the kernel never evaluated
            graph = self._current_graph()
            rev = self._graph_revision
            q_arr, cols, unknown = self._encode_subjects(
                graph, [r.subject for r in reqs])
            pack["bucket"] = len(q_arr)
            pack["nbytes"] = int(q_arr.nbytes)
            gather_idx: list[int] = []
            gather_col: list[int] = []
            kernel_rows: list[int] = []  # positions in reqs served by kernel
            # per-row (tri-state value, checked_at): oracle fallbacks
            # evaluate the LIVE store, so they carry its revision rather
            # than claiming the graph snapshot's
            results: list[Optional[tuple]] = [None] * len(reqs)
            oracle_rows: list[int] = []  # positions needing host evaluation
            tri = getattr(graph, "tri_state_capable", False)
            # Leopard closure-plane consult (ops/leopard.py): rows whose
            # (type, permission) has a live flattened fragment answer
            # with one bit-gather instead of the fixpoint sweep.  Views
            # are immutable snapshots, so the gather below runs outside
            # the lock like the kernel dispatch.
            leo = self._leopard
            leo_rows: list = []  # (i, view, row, col)

            for i, r in enumerate(reqs):
                if (self._stale_pairs and (r.resource.type, r.permission)
                        in self._stale_pairs):
                    # quarantined pair: a delta affecting it could not be
                    # absorbed by this generation (off-loop rebuild in
                    # flight) — the host oracle reads the live store and
                    # stays exact
                    oracle_rows.append(i)
                    self.stats["stale_routed"] += 1
                    continue
                if (not tri and (r.resource.type, r.permission)
                        in self._caveat_affected):
                    # caveat residual with no device plane: host tri-state
                    # evaluation (pre-round-4 behavior; only the sharded /
                    # segment kernels and unsupported caveat shapes land
                    # here now)
                    oracle_rows.append(i)
                    self.stats["oracle_residual_checks"] += 1
                    continue
                if r.subject in unknown:
                    # no slot for (type, relation) at all: oracle reproduces
                    # the schema error/edge semantics
                    oracle_rows.append(i)
                    continue
                state_idx = graph.prog.state_index(
                    r.resource.type, r.permission, r.resource.id)
                if state_idx is None:
                    d = self.schema.definitions.get(r.resource.type)
                    if d is None or not d.has_relation_or_permission(r.permission):
                        # surface schema errors like the oracle does
                        oracle_rows.append(i)
                    else:
                        # unknown object: not in the compiled universe, so
                        # it has no tuples and the kernel would gather all
                        # zeros — the short-circuit is the kernel path's
                        # answer (source stays "kernel" below)
                        results[i] = (0, rev)
                    continue
                if leo is not None:
                    hit = leo.check_coords(
                        r.resource.type, r.permission,
                        int(q_arr[cols[r.subject]]), state_idx)
                    if hit is not None:
                        leo_rows.append((i,) + hit)
                        continue
                gather_idx.append(state_idx)
                gather_col.append(cols[r.subject])
                kernel_rows.append(i)
            if leo_rows:
                self.stats["leopard_checks"] += len(leo_rows)
            if kernel_rows:
                snap = graph.snapshot()
                self.stats["kernel_calls"] += 1
                # batch occupancy, recorded only when a kernel actually
                # dispatches (an all-oracle batch is not a device batch):
                # distinct query columns vs the padded pow-2 bucket the
                # jit cache keys on (utils/devtel.py)
                used = len(set(cols.values()))
                devtel.OCCUPANCY.record("check", used, len(q_arr) - used)
                devtel.LEDGER.note_scratch(
                    int(q_arr.nbytes) + 8 * len(gather_idx))
        ctx = {"reqs": reqs, "results": results, "kernel_rows": kernel_rows,
               "oracle_rows": oracle_rows, "rev": rev, "batch_id": bid}
        if leo_rows:
            # one AND+popcount instead of N sweep iterations: the
            # measured depth on indexed pairs is 1 by construction —
            # recorded through note_batch so /debug/workload shows the
            # collapse the index buys
            with tracing.span("kernel.leopard", rows=len(leo_rows)):
                self._leo_check_fill(leo_rows, results, rev)
            workload.WORKLOAD.note_batch(
                workload.comp_rows([reqs[i] for (i, _v, _r, _c)
                                    in leo_rows]), "check", 1, None)
            leo.note_hits("check", len(leo_rows))
        if oracle_rows:
            workload.WORKLOAD.note_oracle(
                workload.comp_rows([reqs[i] for i in oracle_rows]))
        if not kernel_rows:
            return ctx, None
        # (type, permission) composition of the kernel-served rows:
        # attributes the call's device window in the workload
        # cost-attribution plane (utils/workload.py)
        comp = workload.comp_rows([reqs[i] for i in kernel_rows])
        occ = used / len(q_arr) if len(q_arr) else None
        return ctx, (graph, snap, q_arr, gather_idx, gather_col, comp, occ)

    def _check_dispatch(self, ctx: dict, graph, snap, q_arr, gather_idx,
                        gather_col, comp, occ) -> None:
        """Run the fused check's kernel outside the lock: the snapshot is
        immutable, so concurrent drains/queries proceed instead of
        queueing behind a hundreds-of-ms kernel hold."""
        bid = ctx["batch_id"]
        rows = len(ctx["kernel_rows"])
        pipe = (getattr(graph, "run_checks3_device", None)
                if _pipeline_on() else None)
        if pipe is not None:
            workload.WORKLOAD.note_batch(comp, "check", occupancy=occ)
            # hotpath: begin pipelined check dispatch (device does the
            # word/bit split and the readback is async — reintroducing
            # host numpy staging here is the regression M003 guards)
            with tracing.span("launch", fused="check", batch_id=bid,
                              rows=rows, bucket=len(q_arr)):
                dev, tel, kern = pipe(q_arr, gather_idx, gather_col,
                                      snap=snap)
            key = kern.arena_key(len(q_arr))
            ctx["readback"] = _start_readback(
                dev, bid, bucket=len(q_arr),
                sweep_bytes=_sweep_bytes(graph, len(q_arr)),
                kind="check",
                on_error=lambda: kern.discard_arena(key),
                tel=tel, comp=comp,
                kernel=getattr(kern, "kernel_name", "ell"))
            # hotpath: end
            return
        # serial path: one blocking call launches, runs and reads back
        ready = tracing.span("ready", fused="check", batch_id=bid,
                             rows=rows, bucket=len(q_arr),
                             nbytes=_sweep_bytes(graph, len(q_arr)))
        with ready as a:
            workload.take_last_sweep()  # drop any stale record
            ctx["out"] = graph.run_checks3(q_arr, gather_idx, gather_col,
                                           snap=snap)
            # the sweep record is available synchronously (same thread)
            # — upgrade the span's byte tag to measured iterations x
            # one-sweep bytes
            rec = workload.take_last_sweep()
            if rec is not None and rec.iterations > 0:
                a["nbytes"] *= rec.iterations
                a["measured"] = True
        workload.note_device_time(comp, "ready", ready.seconds)
        workload.WORKLOAD.note_batch(
            comp, "check", rec.iterations if rec is not None else None, occ)

    def _check_batch_finish(self, ctx: dict) -> list:
        """Phase 2 of a fused check batch: `wait` on the async readback
        (pipelined) or take the already-host result (serial), then
        `assemble` CheckResults.  Oracle fallbacks evaluate the LIVE
        store here, outside the endpoint lock, and carry its revision
        rather than claiming the graph snapshot's."""
        results = ctx["results"]
        fut = ctx.get("readback")
        if fut is not None:
            with tracing.span("wait", fused="check",
                              batch_id=ctx["batch_id"]):
                out = fut.result()
        else:
            out = ctx.get("out")
        with tracing.span("assemble", fused="check",
                          batch_id=ctx["batch_id"], rows=len(results)):
            if out is not None:
                rev = ctx["rev"]
                for j, row in enumerate(ctx["kernel_rows"]):
                    results[row] = (int(out[j]), rev)
            oracle_rows = ctx["oracle_rows"]
            if oracle_rows:
                with tracing.span("kernel.oracle", rows=len(oracle_rows)):
                    for i in oracle_rows:
                        r = ctx["reqs"][i]
                        results[i] = (self._oracle.check3(
                            r.resource, r.permission, r.subject),
                            self.store.revision)
            oracle_set = set(oracle_rows)
            return [CheckResult(permissionship=self._TRISTATE[v],
                                checked_at=at,
                                source="oracle" if i in oracle_set
                                else "kernel")
                    for i, (v, at) in enumerate(results)]

    def _report_suppressed(self, n: int, sample: list, context,
                           retry: bool = False) -> None:
        """Count (under the lock — callers run lock-free) and log a
        placeholder suppression with the caller's capture fingerprint.

        `retry=True` marks a suppression observed during the self-heal
        re-capture of an event already counted: it lands in a separate
        `placeholder_suppressed_retry` counter (and logs at debug, not
        warning) so one inconsistency is never double-counted and the
        forensic log is not re-emitted for the same event."""
        stat = ("placeholder_suppressed_retry" if retry
                else "placeholder_suppressed")
        with self._lock:
            self.stats[stat] = self.stats.get(stat, 0) + n
        log = _log.debug if retry else _log.warning
        log("suppressed %d internal placeholder ids from lookup "
            "result (id-view/bitmap inconsistency%s): %r capture=%r",
            n, ", retry" if retry else "", sample, context)

    async def _off_loop(self, fn, *args):
        """Run a device-touching sync path in the executor: a fused
        1M-graph batch holds the kernel + transfer + unpack for hundreds
        of ms, and running it ON the event loop would freeze every
        concurrent request, watch frame, and health probe for that long.
        self._lock is a threading.RLock, so executor threads serialize
        against the delta-drain machinery exactly like loop-thread
        callers did.  The caller's context is copied across the thread
        hop so the active request trace (utils/tracing.py) — including a
        dispatch-fanned-out batch trace — still resolves in the executor
        and the dispatch-stage spans land in the right request(s)."""
        loop = asyncio.get_running_loop()
        ctx = contextvars.copy_context()
        return await loop.run_in_executor(None, lambda: ctx.run(fn, *args))

    async def check_permission(self, req: CheckRequest) -> CheckResult:
        return (await self._off_loop(self._check_batch_sync, [req]))[0]

    async def check_bulk_permissions(self, reqs: list) -> list:
        if not reqs:
            return []
        return await self._off_loop(self._check_batch_sync, reqs)

    async def check_bulk_permissions_start(self, reqs: list) -> dict:
        """Two-phase fused check, phase 1 (encode + kernel dispatch +
        async readback).  Pair with check_bulk_permissions_finish; the
        dispatcher uses the pair to pipeline fused check batches."""
        return await self._off_loop(self._check_batch_capture, reqs)

    async def check_bulk_permissions_finish(self, ctx: dict) -> list:
        """Two-phase fused check, phase 2 (blocking readback + oracle
        fallbacks + result assembly)."""
        return await self._off_loop(self._check_batch_finish, ctx)

    def _lookup_sync(self, resource_type: str, permission: str,
                     subject: SubjectRef) -> list:
        """One retry on placeholder suppression: a suppressed result was
        built from an id view detected inconsistent with the bitmap, so
        re-capturing against the current graph returns the correct,
        complete answer instead of a truncated one (the counter and log
        still record the event).  If the re-capture is ALSO inconsistent,
        fall back to the host oracle: complete, fail-safe results beat a
        silently truncated list with no failure signal to the caller."""
        out, bad_n = self._lookup_once(resource_type, permission, subject)
        if bad_n:
            self._purge_ids_view(resource_type)
            out, bad_n = self._lookup_once(resource_type, permission, subject,
                                           retry=True)
            if bad_n:
                with self._lock:
                    self.stats["suppression_oracle_fallbacks"] = (
                        self.stats.get("suppression_oracle_fallbacks", 0) + 1)
                out = AnnotatedIds(
                    self._oracle.lookup_resources(resource_type, permission,
                                                  subject),
                    source="oracle")
        return out

    def _purge_ids_view(self, resource_type: str) -> None:
        """Drop the current graph's cached id view for a type so the
        retry rebuilds it fresh from prog.object_ids: with copy-on-write
        patching a diverged (arr, mask) entry would otherwise persist
        for the graph generation's lifetime and defeat the retry."""
        with self._lock:
            graph = self._graph
            if graph is None:
                return
            cache = getattr(graph, "_ids_np_cache", None)
            if cache is not None:
                if cache.pop(resource_type, None) is not None:
                    devtel.LEDGER.unregister(
                        "id_view", generation=getattr(graph, "_devtel_gen", 0),
                        name=f"ids:{resource_type}")
                graph._ids_np_published.discard(resource_type)

    def _lookup_once(self, resource_type: str, permission: str,
                     subject: SubjectRef, retry: bool = False) -> tuple:
        self.schema.definition(resource_type)  # raises like the oracle
        oracle = False
        leo_hit = None  # (fragment view, closure column) when indexed
        bid = timeline.next_batch()
        self._drain_before_pack()
        with tracing.span("pack", fused="lookup", batch_id=bid,
                          rows=1) as pack, self._lock:
            graph = self._current_graph()
            if (resource_type, permission) in self._stale_pairs:
                # quarantined pair (off-loop rebuild in flight): the
                # host oracle reads the live store and stays exact
                oracle = True
                self.stats["stale_routed"] += 1
            elif ((resource_type, permission) in self._caveat_affected
                    and not getattr(graph, "tri_state_capable", False)):
                # caveat residual with no device plane: the oracle already
                # skips CONDITIONAL results (reference lookups.go:85-88);
                # plane-capable kernels return the DEFINITE plane, which
                # skips them by construction
                oracle = True
            elif (rng := graph.prog.slot_range(resource_type,
                                               permission)) is None:
                oracle = True
            else:
                q_arr, cols, unknown = self._encode_subjects(graph, [subject])
                pack["bucket"] = len(q_arr)
                pack["nbytes"] = int(q_arr.nbytes)
                if subject in unknown:
                    oracle = True
                else:
                    devtel.OCCUPANCY.record("lookup", 1, len(q_arr) - 1)
                    col = cols[subject]
                    snap = graph.snapshot()
                    # id view + phantom index captured under the lock:
                    # spare-row assignment renames ids in place, so the
                    # cache read must serialize with it (the captured
                    # array is consistent with `snap` — rows renamed
                    # later are dead in this snapshot)
                    ids, mask = _object_ids_np(graph, resource_type)
                    ph = graph.prog.object_index[resource_type].get(
                        PHANTOM_ID)
                    _forensic = (id(graph), self._graph_revision,
                                 self.stats.get("spare_assignments"),
                                 id(ids), threading.get_ident())
                    # Leopard consult: a live fragment for this pair with
                    # a closure column for this subject answers from the
                    # plane (the view is immutable, read outside the lock)
                    lp = self._leopard
                    if lp is not None:
                        frag = lp.lookup_frag(resource_type, permission)
                        if frag is not None:
                            lcol = int(frag.col_of[int(q_arr[col])])
                            if lcol >= 0:
                                leo_hit = (frag.view, lcol)
                                self.stats["leopard_lookups"] += 1
                    if leo_hit is None:
                        self.stats["kernel_calls"] += 1
        if oracle:
            # host evaluation outside the lock (reads the live store)
            workload.WORKLOAD.note_oracle([(resource_type, permission, 1)])
            with tracing.span("kernel.oracle", rows=1):
                return AnnotatedIds(
                    self._oracle.lookup_resources(resource_type, permission,
                                                  subject),
                    source="oracle"), 0
        comp = [(resource_type, permission, 1)]
        if leo_hit is not None:
            # closure-plane lookup: one word-column slice of the
            # fragment plane replaces the fixpoint kernel (depth 1)
            (plane, plane_rows), lcol = leo_hit
            with tracing.span("kernel.leopard", rows=1):
                wordcol = np.asarray(plane[:plane_rows, lcol // 32])
                idx = np.nonzero((wordcol >> np.uint32(lcol % 32))
                                 & np.uint32(1))[0]
            workload.WORKLOAD.note_batch(
                comp, "lookup", 1, 1 / len(q_arr) if len(q_arr) else None)
            self._leopard.note_hits("lookup", 1)
        else:
            # kernel outside the lock (immutable snapshot); the serial
            # call launches, runs and reads back in one blocking `ready`
            ready = tracing.span("ready", fused="lookup", batch_id=bid,
                                 bucket=len(q_arr),
                                 nbytes=_sweep_bytes(graph, len(q_arr)))
            with ready as a:
                workload.take_last_sweep()  # drop any stale record
                if hasattr(graph, "run_lookup_packed"):
                    packed = graph.run_lookup_packed(rng[0], rng[1], q_arr,
                                                     snap=snap)
                else:
                    bitmap = graph.run_lookup(rng[0], rng[1], q_arr,
                                              snap=snap)
                rec = workload.take_last_sweep()
                if rec is not None and rec.iterations > 0:
                    a["nbytes"] *= rec.iterations
                    a["measured"] = True
            workload.note_device_time(comp, "ready", ready.seconds)
            workload.WORKLOAD.note_batch(
                comp, "lookup",
                rec.iterations if rec is not None else None,
                1 / len(q_arr) if len(q_arr) else None)
        with tracing.span("extract", fused="lookup", batch_id=bid, rows=1):
            if leo_hit is None:
                if hasattr(graph, "run_lookup_packed"):
                    idx = _word_col_indices(
                        np.ascontiguousarray(packed[:, col // 32]), col % 32)
                else:
                    idx = np.nonzero(bitmap[:, col])[0]
            out, bad_n, bad_sample = _ids_for(ids, idx, ph, mask)
        if bad_n:
            self._report_suppressed(bad_n, bad_sample, _forensic, retry=retry)
        return AnnotatedIds(out, source="kernel"), bad_n

    async def lookup_resources(self, resource_type: str, permission: str,
                               subject: SubjectRef) -> list:
        return await self._off_loop(self._lookup_sync, resource_type,
                                    permission, subject)

    async def lookup_resources_stream(self, resource_type: str,
                                      permission: str, subject: SubjectRef):
        """Chunked id stream: the kernel runs off-loop (the event loop stays
        responsive during device execution) and the id list yields in chunks
        so consumers' per-id extraction interleaves with other work — the
        device analog of draining the reference's LR server-stream
        (lookups.go:74-135)."""
        ids = await self._off_loop(self._lookup_sync, resource_type,
                                   permission, subject)
        chunk = 4096
        for i in range(0, len(ids), chunk):
            for rid in ids[i: i + chunk]:
                yield rid
            await asyncio.sleep(0)

    def _lookup_batch_sync(self, resource_type: str, permission: str,
                           subjects: list) -> list:
        """One retry on placeholder suppression, then host-oracle
        fallback on a second inconsistency — see _lookup_sync.  (The
        tail lives in _lookup_batch_finish_sync so the sync and the
        two-phase dispatcher paths can never drift.)"""
        return self._lookup_batch_finish_sync(
            self._lookup_batch_capture(resource_type, permission, subjects))

    def _lookup_batch_once(self, resource_type: str, permission: str,
                           subjects: list, retry: bool = False) -> tuple:
        ctx = self._lookup_batch_capture(resource_type, permission, subjects)
        return self._lookup_batch_extract(ctx, retry=retry)

    def _lookup_batch_capture(self, resource_type: str, permission: str,
                              subjects: list) -> dict:
        """Phase 1 of a fused batch lookup: capture a consistent
        (snapshot, id view) pair under the lock, DISPATCH the kernel, and
        start the device->host copy asynchronously.  Returns a context
        for _lookup_batch_extract; does not block on device work (jax
        dispatch is async), so a pipelining caller can capture batch N+1
        while batch N's transfer is still streaming — the device runs
        N+1's kernel during N's D2H instead of idling (the dispatcher's
        double-buffer drain, spicedb/dispatch.py)."""
        self.schema.definition(resource_type)
        bid = timeline.next_batch()
        self._drain_before_pack()
        with tracing.span("pack", fused="lookup", batch_id=bid,
                          rows=len(subjects)) as pack:
            ctx, kernel_call = self._lookup_pack(
                resource_type, permission, subjects, bid, pack)
        if kernel_call is not None:
            self._lookup_dispatch(ctx, *kernel_call)
        return ctx

    def _lookup_pack(self, resource_type: str, permission: str,
                     subjects: list, bid: int, pack: dict) -> tuple:
        """The `pack` stage of a fused lookup: -> (ctx, kernel call args
        or None when the oracle or the Leopard plane answers)."""
        all_oracle = False
        leo = None  # (fragment view, {query col -> closure col}) if indexed
        with self._lock:
            graph = self._current_graph()
            if (resource_type, permission) in self._stale_pairs:
                # quarantined pair (off-loop rebuild in flight): exact
                # answers come from the host oracle until the swap
                all_oracle = True
                self.stats["stale_routed"] += 1
            elif ((resource_type, permission) in self._caveat_affected
                    and not getattr(graph, "tri_state_capable", False)):
                all_oracle = True
            elif (rng := graph.prog.slot_range(resource_type,
                                               permission)) is None:
                all_oracle = True
            else:
                q_arr, cols, unknown = self._encode_subjects(graph, subjects)
                pack["bucket"] = len(q_arr)
                pack["nbytes"] = int(q_arr.nbytes)
                used = len(set(cols.values()))
                devtel.OCCUPANCY.record("lookup", used, len(q_arr) - used)
                snap = graph.snapshot()
                # captured under the lock — see _lookup_sync
                ids, mask = _object_ids_np(graph, resource_type)
                ph = graph.prog.object_index[resource_type].get(PHANTOM_ID)
                _forensic = (id(graph), self._graph_revision,
                             self.stats.get("spare_assignments"),
                             id(ids), threading.get_ident())
                # Leopard consult (mirrors _lookup_once): a live fragment
                # with a closure column for EVERY known subject answers
                # the whole batch from the plane — unknown subjects route
                # to the oracle per-subject at extract time either way
                lp = self._leopard
                if lp is not None:
                    frag = lp.lookup_frag(resource_type, permission)
                    if frag is not None:
                        lcols: Optional[dict] = {}
                        for s, col in cols.items():
                            lcol = int(frag.col_of[int(q_arr[col])])
                            if lcol < 0:
                                lcols = None
                                break
                            lcols[col] = lcol
                        if lcols is not None:
                            leo = (frag.view, lcols)
                            self.stats["leopard_lookups"] += len(lcols)
                if leo is None:
                    self.stats["kernel_calls"] += 1
                    devtel.LEDGER.note_scratch(
                        int(q_arr.nbytes)
                        + rng[1] * max(1, len(q_arr) // 32) * 4)
        ctx = {"rt": resource_type, "perm": permission, "subjects": subjects,
               "batch_id": bid}
        if all_oracle:
            workload.WORKLOAD.note_oracle(
                [(resource_type, permission, len(subjects))])
            ctx["all_oracle"] = True
            return ctx, None
        comp = [(resource_type, permission, len(subjects))]
        occ = used / len(q_arr) if len(q_arr) else None
        ctx.update(cols=cols, unknown=unknown, ids=ids, mask=mask, ph=ph,
                   forensic=_forensic)
        if leo is not None:
            # closure-plane batch: word-column slices of the fragment
            # plane replace the fixpoint kernel (measured depth 1)
            ctx["leopard"] = leo
            workload.WORKLOAD.note_batch(comp, "lookup", 1, occ)
            self._leopard.note_hits("lookup", len(leo[1]))
            return ctx, None
        return ctx, (graph, snap, rng, q_arr, comp, occ)

    def _lookup_dispatch(self, ctx: dict, graph, snap, rng, q_arr, comp,
                         occ) -> None:
        """Run the fused lookup's kernel outside the lock (immutable
        snapshot)."""
        bid = ctx["batch_id"]
        pipe = None
        if _pipeline_on():
            pipe = (getattr(graph, "run_lookup_packed_T_device", None)
                    or getattr(graph, "run_lookup_T_device", None))
        if pipe is not None:
            workload.WORKLOAD.note_batch(comp, "lookup", occupancy=occ)
            # hotpath: begin pipelined lookup dispatch — bitplane pack,
            # word transpose, and final-slice all fused in-jit; the
            # device array reads back asynchronously (reintroducing the
            # host `.T`/ascontiguousarray copy here is the regression
            # M003 guards)
            with tracing.span("launch", fused="lookup", batch_id=bid,
                              rows=len(ctx["subjects"]), bucket=len(q_arr)):
                dev, tel, kern = pipe(rng[0], rng[1], q_arr, snap=snap)
            key = kern.arena_key(len(q_arr))
            ctx["readback"] = _start_readback(
                dev, bid, bucket=len(q_arr),
                sweep_bytes=_sweep_bytes(graph, len(q_arr)),
                kind="lookup",
                on_error=lambda: kern.discard_arena(key),
                tel=tel, comp=comp,
                kernel=getattr(kern, "kernel_name", "ell"))
            # hotpath: end
            return
        # serial path: the blocking call launches and runs the kernel;
        # a packed result's D2H starts here and lands in extraction
        ready = tracing.span("ready", fused="lookup", batch_id=bid,
                             rows=len(ctx["subjects"]), bucket=len(q_arr),
                             nbytes=_sweep_bytes(graph, len(q_arr)))
        with ready as a:
            workload.take_last_sweep()  # drop any stale record
            if hasattr(graph, "run_lookup_packed"):
                # packed fast path: per-column shift/AND/nonzero over
                # one uint32 word column — never materializes the 32x
                # larger bool bitmap or its [B, L] transpose.
                # Transposed on device so the transfer lands
                # contiguous per word column.
                packed_T = graph.run_lookup_packed(rng[0], rng[1], q_arr,
                                                   snap=snap).T
                if isinstance(packed_T, jax.Array):
                    packed_T.copy_to_host_async()
                ctx["packed_T"] = packed_T
            else:
                ctx["bitmap"] = graph.run_lookup(rng[0], rng[1], q_arr,
                                                 snap=snap)
            rec = workload.take_last_sweep()
            if rec is not None and rec.iterations > 0:
                a["nbytes"] *= rec.iterations
                a["measured"] = True
        workload.note_device_time(comp, "ready", ready.seconds)
        workload.WORKLOAD.note_batch(
            comp, "lookup", rec.iterations if rec is not None else None, occ)

    def _lookup_batch_extract(self, ctx: dict, retry: bool = False) -> tuple:
        """Phase 2: block on the transfer and materialize per-subject id
        lists; returns (results, suppressed_count).  `retry` marks the
        self-heal re-capture so its suppressions are counted separately
        (never double-counted against the first detection)."""
        bid = ctx.get("batch_id")
        if ctx.get("all_oracle"):
            # host evaluation outside the lock (reads the live store)
            with tracing.span("kernel.oracle", rows=len(ctx["subjects"])):
                return [AnnotatedIds(
                            self._oracle.lookup_resources(
                                ctx["rt"], ctx["perm"], s),
                            source="oracle")
                        for s in ctx["subjects"]], 0
        if "leopard" in ctx:
            # closure-plane batch: read each needed word column of the
            # fragment plane once (columns are shared across subjects)
            (plane, plane_rows), lcols = ctx["leopard"]
            with tracing.span("kernel.leopard", rows=len(ctx["subjects"])):
                word_cols = {}
                for lcol in lcols.values():
                    w = lcol // 32
                    if w not in word_cols:
                        word_cols[w] = np.asarray(plane[:plane_rows, w])

            def col_indices(col):
                lcol = lcols[col]
                return np.nonzero((word_cols[lcol // 32]
                                   >> np.uint32(lcol % 32))
                                  & np.uint32(1))[0]
        elif "readback" in ctx:
            # pipelined path: the device already transposed; block on the
            # waiter future (the waiter thread marked `ready` and
            # `transfer`; this span is the finishing thread's residual
            # wait)
            with tracing.span("wait", fused="lookup", batch_id=bid):
                arr = ctx["readback"].result()
            if arr.dtype == np.uint32:
                packed_T = arr          # [W, L]: word rows, bit-packed

                def col_indices(col):
                    return _word_col_indices(packed_T[col // 32], col % 32)
            else:
                bitmap_T = arr          # [B, L] bool: row per query column

                def col_indices(col):
                    return np.nonzero(bitmap_T[col])[0]
        elif "packed_T" in ctx:
            # the device->host sync point: this blocks until the async
            # D2H started at capture time lands.  When the pending result
            # is already a host array (the packed kernels sync at
            # capture) the block is the word-transpose copy, not a device
            # transfer, and is marked as such
            pending = ctx["packed_T"]
            stage = ("transfer" if isinstance(pending, jax.Array)
                     else "transpose")
            with tracing.span(stage, fused="lookup", batch_id=bid) as a:
                packed_T = np.ascontiguousarray(pending)  # [W, L]
                a["bucket"] = int(packed_T.shape[0]) * 32
                a["nbytes"] = int(packed_T.nbytes)

            def col_indices(col):
                return _word_col_indices(packed_T[col // 32], col % 32)
        else:
            bitmap = ctx["bitmap"]

            def col_indices(col):
                return np.nonzero(bitmap[:, col])[0]

        ids, mask, ph = ctx["ids"], ctx["mask"], ctx["ph"]
        cols, unknown = ctx["cols"], ctx["unknown"]
        per_col_ids: dict = {}  # column -> id list (columns are shared)
        out = []
        total_bad = 0
        with tracing.span("extract", fused="lookup", batch_id=bid,
                          rows=len(ctx["subjects"])):
            for s in ctx["subjects"]:
                if s in unknown:
                    out.append(AnnotatedIds(self._oracle.lookup_resources(
                        ctx["rt"], ctx["perm"], s), source="oracle"))
                    continue
                col = cols[s]
                lst = per_col_ids.get(col)
                if lst is None:
                    lst, bad_n, bad_sample = _ids_for(
                        ids, col_indices(col), ph, mask)
                    if bad_n:
                        total_bad += bad_n
                        self._report_suppressed(bad_n, bad_sample,
                                                ctx["forensic"], retry=retry)
                    per_col_ids[col] = lst = AnnotatedIds(lst,
                                                          source="kernel")
                out.append(lst)
        return out, total_bad

    def _lookup_batch_finish_sync(self, ctx: dict) -> list:
        """Extraction + the suppression tail (purge -> recapture ->
        oracle fallback) for a context from _lookup_batch_capture."""
        out, bad_n = self._lookup_batch_extract(ctx)
        if bad_n:
            self._purge_ids_view(ctx["rt"])
            out, bad_n = self._lookup_batch_once(ctx["rt"], ctx["perm"],
                                                 ctx["subjects"], retry=True)
            if bad_n:
                with self._lock:
                    self.stats["suppression_oracle_fallbacks"] = (
                        self.stats.get("suppression_oracle_fallbacks", 0) + 1)
                out = [AnnotatedIds(
                           self._oracle.lookup_resources(
                               ctx["rt"], ctx["perm"], s),
                           source="oracle")
                       for s in ctx["subjects"]]
        return out

    async def lookup_resources_batch_start(self, resource_type: str,
                                           permission: str,
                                           subjects: list) -> dict:
        """Two-phase fused lookup, phase 1 (kernel dispatch + async D2H).
        Pair with lookup_resources_batch_finish; the dispatcher uses the
        pair to double-buffer fused batches."""
        return await self._off_loop(self._lookup_batch_capture,
                                    resource_type, permission, subjects)

    async def lookup_resources_batch_finish(self, ctx: dict) -> list:
        """Two-phase fused lookup, phase 2 (blocking transfer +
        extraction + self-heal tail)."""
        return await self._off_loop(self._lookup_batch_finish_sync, ctx)

    async def lookup_resources_batch(self, resource_type: str, permission: str,
                                     subjects: list) -> list:
        if not subjects:
            return []
        return await self._off_loop(self._lookup_batch_sync, resource_type,
                                    permission, subjects)

    async def read_relationships(self, flt: RelationshipFilter) -> list:
        return self.store.read(flt)

    async def write_relationships(self, updates: Iterable[RelationshipUpdate],
                                  preconditions: Iterable[Precondition] = ()) -> int:
        # commits journal synchronously (WAL append + fsync) before
        # visibility — a disk barrier that must never park the event
        # loop (analyzer A001 class).  _off_loop carries the request
        # context across the hop like every other store-touching verb;
        # the store lock keeps commit semantics identical.
        ups = self._validate_updates(updates)
        return await self._off_loop(self.store.write, ups,
                                    list(preconditions))

    async def delete_relationships(self, flt: RelationshipFilter,
                                   preconditions: Iterable[Precondition] = ()) -> int:
        rev, _ = await self._off_loop(self.store.delete_by_filter, flt,
                                      list(preconditions))
        return rev

    def watch(self, object_types: Optional[Iterable[str]] = None) -> Watcher:
        return self.store.subscribe(object_types)

    # -- decision explain ----------------------------------------------------

    def explain_check(self, resource: ObjectRef, permission: str,
                      subject: SubjectRef):
        """Per-check evaluation witness (authz/explain.py Witness).

        One targeted re-check through the real kernel path pins the
        decision; the witness path comes from the host replay of the
        staged SpMV iterate over the compiled program (allowed rows:
        which relation hop / fixpoint iteration admitted the subject —
        no device work beyond the re-check).  Incremental deltas applied
        since the last compile live in the device tables, not the
        program's edge arrays, so a replay that disagrees with the
        kernel — and every denial/conditional — is explained by the
        (always-current) host oracle instead.
        """
        from ..authz.explain import device_witness, oracle_witness

        req = CheckRequest(resource=resource, permission=permission,
                           subject=subject)
        result = self._check_batch_sync([req])[0]
        decision = {
            Permissionship.HAS_PERMISSION: "allowed",
            Permissionship.CONDITIONAL_PERMISSION: "conditional",
            Permissionship.NO_PERMISSION: "denied",
        }[result.permissionship]
        with self._lock:
            self.stats["explain_checks"] += 1
        prog = sidx = tidx = None
        if decision == "allowed":
            with self._lock:
                graph = self._graph
                if graph is not None:
                    prog = graph.prog
                    sidx = prog.subject_index(subject.type, subject.id,
                                              subject.relation)
                    tidx = prog.state_index(resource.type, permission,
                                            resource.id)
        if prog is not None and sidx is not None and tidx is not None:
            # prog arrays are immutable after compile: replay runs
            # outside the lock
            w = device_witness(prog, sidx, tidx)
            if w.decision == decision:
                w.backend = "jax"
                return w
            # replay disagreed (post-compile deltas / caveat planes):
            # the oracle reads the live store and stays authoritative
        w = oracle_witness(self.schema, self.store, resource, permission,
                           subject)
        w.backend = "jax"
        if w.decision != decision:
            w.note = (f"kernel decision {decision!r} diverges from oracle "
                      f"witness {w.decision!r}")
            w.decision = decision
        return w

    # -- maintenance hooks --------------------------------------------------

    def force_rebuild(self) -> None:
        with self._lock:
            self._rebuild()
