"""Multi-chip sharded reachability: 2D (data x graph) mesh over ICI/DCN.

Replaces the reference's single-process graph-walk distribution (SpiceDB
internal dispatch, reference pkg/spicedb/spicedb.go:31-47) with a
`shard_map` program over a `jax.sharding.Mesh`:

- `data` axis  — query batch sharded (each chip owns B/n_data query
  columns): pure data parallelism for concurrent list requests, zero
  communication.
- `graph` axis — edge set sharded (each chip owns E/n_graph edges of the
  tuple graph): each chip computes a partial one-step closure over the full
  state vector, combined with a boolean all-reduce (`lax.pmax`) per
  iteration.  This is what lets tuple counts exceed single-chip HBM.

The per-iteration body is ops/spmv.make_step with the all-reduce injected
via its `combine` hook, so single-chip and sharded kernels cannot drift.
Convergence (while_loop) uses a globally all-reduced changed flag so every
shard agrees on the trip count.  On a v5e-8 both axes map onto ICI, and
`jax.distributed` extends the same program across hosts over DCN
(SURVEY.md §5 communication-backend note).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.graph_compile import GraphProgram
from ..ops.spmv import (MAX_ITERATIONS, bucket, make_evaluate,
                        pad_edges, pad_scatter)
from ..utils import devtel, workload
from ..utils.failpoints import fail_point


def make_mesh(devices=None, data: Optional[int] = None,
              graph: Optional[int] = None) -> Mesh:
    """Build a 2D (data, graph) mesh.  Defaults: square-ish split of all
    local devices with the graph axis at least as large as the data axis."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if data is None or graph is None:
        # smallest factor pair with graph >= data: the graph axis is the
        # HBM-capacity axis and must get the larger share
        graph = n
        g = 1
        while g * g <= n:
            if n % g == 0:
                graph = n // g  # g = data candidate, n//g = graph >= g
            g += 1
        data = n // graph
    if data * graph != n:
        raise ValueError(f"mesh {data}x{graph} != {n} devices")
    arr = np.asarray(devices).reshape(data, graph)
    return Mesh(arr, axis_names=("data", "graph"))


def replicate(mesh: Mesh, a) -> jax.Array:
    """A host array placed on every device of `mesh`: operands a sharded
    program reads whole (gather indices, scatter rows) go there directly
    instead of landing on the default device first."""
    return jax.device_put(np.asarray(a), NamedSharding(mesh, P()))


def make_sharded_evaluate(prog: GraphProgram, mesh: Mesh, num_iters: int):
    """Build fn(q_idx, edge_src, edge_dst) -> x_final [N, B] where q_idx is
    sharded over `data` and the edge arrays over `graph`.  The state vector
    is replicated along `graph`."""
    shard_fn = make_evaluate(
        prog, num_iters, use_while=True, indices_sorted=False,
        combine=lambda y: jax.lax.pmax(y, "graph"),
        changed_reduce=lambda c: jax.lax.pmax(
            c.astype(jnp.int32), ("data", "graph")) > 0,
    )
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P("data"), P("graph"), P("graph")),
        out_specs=P(None, "data"),
        check_vma=False,  # x is replicated along `graph` by construction
    )


class ShardedKernel:
    """Sharded check/lookup entry points (multi-chip counterpart of
    ops.spmv.KernelCache)."""

    def __init__(self, prog: GraphProgram, mesh: Mesh,
                 num_iters: Optional[int] = None):
        self.prog = prog
        self.mesh = mesh
        self.num_iters = num_iters or MAX_ITERATIONS
        evaluate = make_sharded_evaluate(prog, mesh, self.num_iters)

        def run_checks(q_idx, gather_idx, gather_col, edge_src, edge_dst):
            x = evaluate(q_idx, edge_src, edge_dst)
            return x[gather_idx, gather_col] > 0

        def run_lookup(slot_offset, slot_length, q_idx, edge_src, edge_dst):
            x = evaluate(q_idx, edge_src, edge_dst)
            return jax.lax.dynamic_slice_in_dim(
                x, slot_offset, slot_length, axis=0) > 0

        self._checks = jax.jit(run_checks)
        self._lookup = jax.jit(run_lookup, static_argnums=(0, 1))

    # -- shape discipline ---------------------------------------------------

    def _pad_batch(self, q_idx: np.ndarray) -> np.ndarray:
        n_data = self.mesh.shape["data"]
        b = bucket(max(len(q_idx), 1), max(8, n_data))
        if b % n_data:
            b += n_data - (b % n_data)
        out = np.full(b, self.prog.dead_index, np.int32)
        out[: len(q_idx)] = q_idx
        return out

    def pad_edges_for_mesh(self, capacity: Optional[int] = None) -> tuple:
        n_graph = self.mesh.shape["graph"]
        e = max(len(self.prog.edge_src), 1)
        cap = capacity if capacity is not None else bucket(e)
        if cap % n_graph:
            cap += n_graph - (cap % n_graph)
        return pad_edges(self.prog, cap)

    def device_edges(self, capacity: Optional[int] = None) -> tuple:
        src, dst = self.pad_edges_for_mesh(capacity)
        spec = NamedSharding(self.mesh, P("graph"))
        return (jax.device_put(src, spec), jax.device_put(dst, spec))

    # -- host-facing --------------------------------------------------------

    def lookup(self, slot_offset: int, slot_length: int, q_idx: np.ndarray,
               edge_src, edge_dst) -> np.ndarray:
        q = self._pad_batch(np.asarray(q_idx, np.int32))
        q = jax.device_put(q, NamedSharding(self.mesh, P("data")))
        return np.asarray(self._lookup(slot_offset, slot_length, q,
                                       edge_src, edge_dst))[:, : len(q_idx)]

    def checks(self, q_idx: np.ndarray, gather_idx: np.ndarray,
               gather_col: np.ndarray, edge_src, edge_dst) -> np.ndarray:
        q = self._pad_batch(np.asarray(q_idx, np.int32))
        q = jax.device_put(q, NamedSharding(self.mesh, P("data")))
        g = bucket(max(len(gather_idx), 1), 8)
        gi = np.zeros(g, np.int32)
        gc = np.zeros(g, np.int32)
        gi[: len(gather_idx)] = gather_idx
        gc[: len(gather_col)] = gather_col
        out = np.asarray(self._checks(q, replicate(self.mesh, gi),
                                      replicate(self.mesh, gc),
                                      edge_src, edge_dst))
        return out[: len(gather_idx)]


# -- packed (ELL) sharded kernel ---------------------------------------------

def padded_batch_words_for(n_data: int, batch: int) -> int:
    """uint32 word count for a `batch`-column query under a data axis of
    size n_data: the SINGLE source of the padding formula, used by
    ShardedEllKernel.padded_batch_words and comm_model."""
    from ..ops.ell import batch_words

    w = batch_words(batch, minimum=n_data)
    if w % n_data:
        w += n_data - (w % n_data)
    return w


def comm_model(state_size: int, n_aux_rows: int, n_data: int, n_graph: int,
               batch: int, planes: bool = False,
               aux_passes: int = 1) -> dict:
    """Per-iteration ICI traffic of the sharded ELL layout — the SINGLE
    source of the communication model consumed by bench.py and
    __graft_entry__.dryrun_multichip, mirroring ShardedEllKernel's padding
    exactly: row blocks are reassembled by a tiled all_gather along the
    `graph` axis each iteration; the `data` (packed word) axis is pure
    throughput parallelism with zero communication.

    With the tri-state plane path active (`planes`), each gathered row
    carries 2 planes, plus the step all_gathers the extra y_cav closure
    (maybe plane only) over the same row count — 3x the definite-path
    traffic."""
    n_pad = _ceil_mult(state_size, n_graph)
    a_pad = _ceil_mult(max(n_aux_rows, 1), n_graph)
    w_local = max(1, padded_batch_words_for(n_data, batch) // n_data)
    # the bottom-up aux refresh all_gathers the aux block aux_passes
    # times per outer iteration (main block still once)
    rows = n_pad + a_pad * max(1, aux_passes)
    factor = 3 if planes else 1
    return {
        "mesh": f"{n_data}x{n_graph} (data x graph)",
        "padded_rows": n_pad + a_pad,
        "aux_passes": max(1, aux_passes),
        "words_per_device": w_local,
        "bitplanes": 2 if planes else 1,
        "all_gather_recv_bytes_per_device_per_iter":
            rows * w_local * 4 * (n_graph - 1) // n_graph * factor,
        "data_axis_comm_bytes": 0,
    }


# v5e ICI: each chip has 4 links usable in a 2D torus at ~186 GB/s
# bidirectional per link (~93 GB/s per direction); an 8-chip v5e slice
# is a 2x4 torus.  Ring all_gather of S bytes over g devices moves
# S*(g-1)/g per device in g-1 hops; per-hop latency ~1 us.
ICI_GBPS_PER_LINK_DIR = 93.0
ICI_HOP_LATENCY_S = 1e-6


def predict_v5e8_checks_per_s(state_size: int, n_aux_rows: int,
                              n_data: int, n_graph: int, batch: int,
                              objects: int,
                              single_chip_iter_s: float,
                              iters: int,
                              planes: bool = False,
                              aux_passes: int = 1,
                              fixed_overhead_s: float = 0.0) -> dict:
    """Analytic v5e-8 projection (VERDICT r4 item 4): compose the
    MEASURED single-chip per-sweep time with the comm model's ICI
    all_gather bytes.

    Per sweep on the (n_data x n_graph) mesh:
      compute  = single_chip_iter_s / n_graph  (rows shard over `graph`;
                 the `data` axis splits words, which scales the same
                 per-row gather cost, so it divides the BATCH not the
                 sweep — words/device = W/n_data)
      comm     = recv_bytes / (links * per-dir GB/s) + (g-1) hops
    The projection assumes compute and the all_gather serialize (the
    kernel needs the full row space before the next sweep) — a
    conservative (non-overlapped) composition.

    Returns the predicted checks/s for `batch` concurrent lookups over
    `objects` objects plus the inputs, so the artifact shows the
    formula's terms."""
    cm = comm_model(state_size, n_aux_rows, n_data, n_graph, batch,
                    planes=planes, aux_passes=aux_passes)
    recv = cm["all_gather_recv_bytes_per_device_per_iter"]
    # a 2x4 torus gives each device 2 usable links along the gathered
    # (graph) ring when n_graph > 2
    links = 2 if n_graph > 2 else 1
    comm_s = (recv / (links * ICI_GBPS_PER_LINK_DIR * 1e9)
              + (n_graph - 1) * ICI_HOP_LATENCY_S)
    # the data axis splits the word axis: each device computes W/n_data
    # words, and per-row gather cost is ~word-width-proportional only
    # above the vector width — conservatively model compute as
    # row-sharded only (words held constant)
    compute_s = single_chip_iter_s / n_graph
    per_iter = compute_s + comm_s
    total = per_iter * max(iters, 1) + fixed_overhead_s
    checks = objects * batch
    # break-even batch: fixed overhead amortizes; sweep cost is nearly
    # batch-independent below one word per device
    return {
        **cm,
        "ici_gbps_per_link_dir": ICI_GBPS_PER_LINK_DIR,
        "ici_links_used": links,
        "ici_hop_latency_s": ICI_HOP_LATENCY_S,
        "single_chip_iter_ms_measured": round(single_chip_iter_s * 1e3, 3),
        "iters": iters,
        "predicted_compute_ms_per_iter": round(compute_s * 1e3, 3),
        "predicted_comm_ms_per_iter": round(comm_s * 1e3, 3),
        "predicted_iter_ms": round(per_iter * 1e3, 3),
        "fixed_overhead_ms": round(fixed_overhead_s * 1e3, 3),
        "predicted_batch_s": round(total, 6),
        "predicted_v5e8_checks_per_s": round(checks / max(total, 1e-9), 1),
        "predicted_speedup_vs_single_chip": round(
            (single_chip_iter_s * max(iters, 1) + fixed_overhead_s)
            / max(total, 1e-9), 2),
        "note": ("analytic projection: measured single-chip sweep time "
                 "row-sharded over the graph axis + ring all_gather over "
                 "ICI (serialized, conservative); multi-chip hardware is "
                 "not available in this environment to validate"),
    }


def _ceil_mult(n: int, m: int) -> int:
    return ((max(n, 1) + m - 1) // m) * m


class ShardedEllKernel:
    """Multi-chip variant of the bit-packed fixed-fanin kernel (ops/ell.py).

    Sharding layout over the 2D (data x graph) mesh:

    - `data` axis — the packed WORD axis: each chip owns W/n_data uint32
      words (32 query columns per word).  Pure throughput parallelism for
      concurrent list requests; zero communication.
    - `graph` axis — table ROWS: each chip owns a contiguous block of the
      main/aux gather tables and computes the one-step closure for its
      rows; blocks are reassembled with a tiled `all_gather` over ICI each
      iteration (the packed state is replicated along `graph`, so the
      gather payload is N x W/n_data words).

    Main rows are padded to a multiple of n_graph, which shifts the aux
    block's global offset — aux references in both tables are remapped from
    state_size to the padded offset at construction.  Padding rows read the
    dead index and stay zero.  Wildcards/permission ops run replicated per
    shard on the gathered full state (tiny elementwise work).
    """

    # metric label for authz_sweep_iterations / authz_frontier_decay:
    # the sharded kernel runs the same packed fixed-fanin sweep, so its
    # telemetry shares the single-chip label value space
    kernel_name = "ell"

    def __init__(self, prog: GraphProgram, mesh: Mesh,
                 num_iters: Optional[int] = None, tables=None):
        from ..ops.ell import K_AUX, build_cav_tables, build_tables
        from ..ops.ell import MAX_ITERATIONS as ELL_MAX

        self.prog = prog
        self.mesh = mesh
        t = tables if tables is not None else build_tables(prog)
        n = prog.state_size
        dead = prog.dead_index
        n_graph = mesh.shape["graph"]
        # tri-state plane path: undecidable caveated edges feed a MAYBE
        # plane carried on a trailing size-2 axis (plane swap at exclusion
        # stays device-local; see _apply_perm_expr_packed plane_last)
        self.planes = bool(len(prog.cav_src)) and prog.caveats_device_ok
        a = t.idx_aux.shape[0]
        self.n_aux_shared = a  # cav OR-tree nodes start past this
        tree_depth = t.tree_depth
        cav = None
        self.host_cav_compile = None
        if self.planes:
            cav = build_cav_tables(prog, a)
            # compile-row-space copy for the graph wrapper's incremental
            # tree-walk edits (device copy lives in padded row space)
            self.host_cav_compile = cav.idx_cav
            if cav.n_aux_cav:
                # caveat OR-tree nodes live in the aux block (dead rows in
                # the shared aux table; children in the cav table)
                t.idx_aux = np.vstack([
                    t.idx_aux,
                    np.full((cav.n_aux_cav, K_AUX), dead, np.int32)])
                a += cav.n_aux_cav
            tree_depth = max(tree_depth, cav.tree_depth)
        # in-step bottom-up aux refresh passes (Gauss-Seidel tree
        # collapse, matching the single-chip kernel): SHARED tree height
        # only — cav trees propagate via idx_cav per outer iteration —
        # +1 spare pass for incrementally grown levels
        self.aux_passes = t.tree_depth + 1
        self.n_pad = _ceil_mult(n, n_graph)
        self.a_pad = _ceil_mult(max(a, 1), n_graph)
        main = np.full((self.n_pad, t.idx_main.shape[1]), dead, np.int32)
        main[:n] = t.idx_main
        aux = np.full((self.a_pad, t.idx_aux.shape[1]), dead, np.int32)
        aux[:a] = t.idx_aux
        if self.n_pad != n:
            # remap aux references past the padded main block
            main[main >= n] += self.n_pad - n
            aux[aux >= n] += self.n_pad - n
        base = num_iters or ELL_MAX
        self.num_iters = base * (1 + tree_depth)
        self._row_spec = NamedSharding(mesh, P("graph", None))
        self.idx_main = jax.device_put(main, self._row_spec)
        self.idx_aux = jax.device_put(aux, self._row_spec)
        self.idx_cav = None
        if self.planes:
            # reindex the cav table from compile row space ([0,n) main +
            # [n, n+a) aux) to the padded device row space, values incl.
            cav_dev = np.full(
                (self.n_pad + self.a_pad, cav.idx_cav.shape[1]), dead,
                np.int32)
            cav_dev[:n] = cav.idx_cav[:n]
            cav_dev[self.n_pad: self.n_pad + (cav.idx_cav.shape[0] - n)] = \
                cav.idx_cav[n:]
            if self.n_pad != n:
                cav_dev[cav_dev >= n] += self.n_pad - n
            self.idx_cav = jax.device_put(cav_dev, self._row_spec)
        self._jits: dict = {}
        # pipelined dispatch state (mirrors ops/ell.EllKernelCache): the
        # sweep state is a (n_pad + a_pad) x local-words arena, word-
        # sharded along `data` and replicated along `graph` — exactly the
        # layout the shard program carries — so donation aliases the
        # previous call's per-device buffers in place
        self._state_spec = NamedSharding(
            mesh, P(None, "data", None) if self.planes else P(None, "data"))
        self._q_spec = NamedSharding(mesh, P("data"))
        self._arenas: dict = {}
        self._arena_lock = threading.Lock()
        # Collective executions (the shard_map programs and sharded
        # scatters) must not interleave across host threads: two
        # concurrent launches can pair device A's all_gather with
        # device B's from the OTHER program and deadlock the per-device
        # rendezvous (observed as both callers parked forever in the
        # D2H readback).  Every device execution takes this lock and
        # drains the program before releasing.
        self._dispatch_lock = threading.Lock()
        self.devtel_generation = 0

    def _run_collective(self, fn, *args):
        """Execute one sharded program under the dispatch lock and block
        until every per-device buffer is done before the next program
        may launch."""
        with self._dispatch_lock:
            return jax.block_until_ready(fn(*args))

    def update_cav_rows(self, rows: np.ndarray, vals: np.ndarray) -> None:
        """Incremental MAYBE-plane table edits.  Host tables are in compile
        row space; the target rows and the gathered values shift by the
        SAME aux-block offset (remap_values), since cav-table rows span
        main+aux exactly like the values they hold."""
        self.idx_cav = self._scatter_rows(
            self.idx_cav, self.remap_values(rows), self.remap_values(vals))

    # -- incremental row updates ---------------------------------------------

    def remap_values(self, vals: np.ndarray) -> np.ndarray:
        """Shift aux references for the padded main block (host tables are
        unpadded; device tables pad main rows to a multiple of n_graph)."""
        n = self.prog.state_size
        if self.n_pad != n:
            vals = vals.copy()
            vals[vals >= n] += self.n_pad - n
        return vals

    def _scatter_rows(self, arr, rows: np.ndarray, vals: np.ndarray):
        rows, vals = pad_scatter(np.asarray(rows), np.asarray(vals))

        def scatter(a, r, v):
            out = a.at[r].set(v)
            # keep the row sharding stable regardless of what the
            # scatter's output sharding propagation decided
            return jax.device_put(out, self._row_spec)

        return self._run_collective(scatter, arr,
                                    replicate(self.mesh, rows),
                                    replicate(self.mesh, vals))

    def update_main_rows(self, rows: np.ndarray, vals: np.ndarray) -> None:
        self.idx_main = self._scatter_rows(self.idx_main, rows,
                                           self.remap_values(vals))

    def update_aux_rows(self, rows: np.ndarray, vals: np.ndarray) -> None:
        self.idx_aux = self._scatter_rows(self.idx_aux, rows,
                                          self.remap_values(vals))

    # -- the sharded program -------------------------------------------------

    def _evaluate_shard_fn(self, arena: bool = False,
                           introspect: bool = False):
        """Build the shard_map'd sweep program.

        Default flavor: fn(q, idx_main, idx_aux[, idx_cav]) -> x_main
        [n_pad, W(, 2)] — the main block only, for the blocking entries.

        `arena=True` (the pipelined dispatch flavor, mirroring
        ops/ell.make_ell_evaluate): the signature grows a LEADING
        donated `state` operand [n_pad + a_pad, W(, 2)] whose buffer
        seeds the zero-init in place (the jit's donate_argnums aliases
        it to the returned full main+aux state), and the return value is
        that full state so the caller can repool it.

        `introspect=True` (arena flavor only; KernelIntrospect resolved
        at jit-build time, see ops/ell._pipe_fns): the return becomes
        (state, tel) — tel the int32 [1 + num_iters] sweep trace
        (tel[0] executed iterations, tel[1:] per-iteration global
        frontier popcount)."""
        from ..ops.ell import _apply_perm_expr_packed

        prog = self.prog
        n_pad = self.n_pad
        a_pad = self.a_pad
        dead = prog.dead_index
        planes = self.planes
        perm_ops = tuple(prog.perm_ops)
        wc_masks = []
        for term in prog.wildcard_terms:
            shape = (n_pad, 1, 1) if planes else (n_pad, 1)
            m = np.zeros(shape, np.uint32)
            m[np.asarray(term.mask_indices, np.int64)] = np.uint32(0xFFFFFFFF)
            wc_masks.append((term, jnp.asarray(m)))
        num_iters = self.num_iters
        aux_passes = self.aux_passes

        def sweep(x0_main, x0_aux, main_local, aux_local, cav_local):
            def step(x_main, x_aux):
                x = jnp.concatenate([x_main, x_aux], axis=0)
                # bottom-up aux refresh first (Gauss-Seidel tree collapse,
                # same as the single-chip step): each pass gathers the
                # local aux rows and reassembles the full aux block over
                # ICI — the aux table is tiny next to the main block, so
                # the extra all_gathers cost far less than the outer
                # iterations they remove
                aux_cur = x_aux
                for _ in range(max(1, aux_passes)):
                    base = jnp.concatenate([x_main, aux_cur], axis=0)
                    y_aux_l = base[aux_local[:, 0]]
                    for k in range(1, aux_local.shape[1]):
                        y_aux_l = y_aux_l | base[aux_local[:, k]]
                    aux_cur = jax.lax.all_gather(y_aux_l, "graph", axis=0,
                                                 tiled=True)
                xm = jnp.concatenate([x_main, aux_cur], axis=0)
                y_main_l = xm[main_local[:, 0]]
                for k in range(1, main_local.shape[1]):
                    y_main_l = y_main_l | xm[main_local[:, k]]
                # reassemble row blocks across the graph axis (tiled ICI
                # all-gather; payload is rows x local words [x planes])
                y_main = jax.lax.all_gather(y_main_l, "graph", axis=0,
                                            tiled=True)
                y_aux = aux_cur
                if cav_local is not None:
                    # undecidable caveated edges: closure feeds the MAYBE
                    # plane only — slice the plane BEFORE the all_gather
                    # so only maybe-plane words cross ICI
                    y_cav_l = x[cav_local[:, 0], :, 1]
                    for k in range(1, cav_local.shape[1]):
                        y_cav_l = y_cav_l | x[cav_local[:, k], :, 1]
                    y_cav = jax.lax.all_gather(y_cav_l, "graph", axis=0,
                                               tiled=True)
                    y_main = jnp.stack(
                        [y_main[..., 0],
                         y_main[..., 1] | y_cav[:n_pad]], axis=-1)
                    y_aux = jnp.stack(
                        [y_aux[..., 0],
                         y_aux[..., 1] | y_cav[n_pad:]], axis=-1)
                for term, mask in wc_masks:
                    live = jax.lax.dynamic_slice_in_dim(
                        y_main | x0_main, term.self_offset, term.self_length,
                        axis=0)
                    any_live = jax.lax.reduce(
                        live, np.uint32(0), jax.lax.bitwise_or, (0,))[None]
                    y_main = y_main | (mask & any_live)
                x1 = y_main | x0_main
                for op in perm_ops:
                    vec = _apply_perm_expr_packed(op.expr, x1,
                                                  plane_last=planes)
                    seed = jax.lax.dynamic_slice_in_dim(
                        x0_main, op.offset, op.length, axis=0)
                    x1 = jax.lax.dynamic_update_slice_in_dim(
                        x1, vec | seed, op.offset, axis=0)
                x1 = x1.at[dead].set(np.uint32(0))
                return x1, y_aux

            if introspect:
                def cond(state):
                    _, _, changed, i, _ = state
                    return jnp.logical_and(changed, i < num_iters)

                def body(state):
                    x_main, x_aux, _, i, trace = state
                    x1_main, x1_aux = step(x_main, x_aux)
                    changed = (jnp.any(x1_main != x_main)
                               | jnp.any(x1_aux != x_aux))
                    changed = jax.lax.pmax(changed.astype(jnp.int32),
                                           ("data", "graph")) > 0
                    delta = (jnp.sum(jax.lax.population_count(
                                 x1_main ^ x_main))
                             + jnp.sum(jax.lax.population_count(
                                 x1_aux ^ x_aux))).astype(jnp.int32)
                    # the local popcount covers this shard's WORDS only:
                    # psum over `data` yields the global frontier delta.
                    # The state is replicated along `graph` — reducing
                    # over it too would multiply the count by n_graph.
                    delta = jax.lax.psum(delta, "data")
                    return (x1_main, x1_aux, changed, i + 1,
                            trace.at[i].set(delta))

                x_main, x_aux, _, i, trace = jax.lax.while_loop(
                    cond, body,
                    (x0_main, x0_aux, jnp.bool_(True), jnp.int32(0),
                     jnp.zeros((num_iters,), jnp.int32)))
                return x_main, x_aux, jnp.concatenate([i[None], trace])

            def cond(state):
                _, _, changed, i = state
                return jnp.logical_and(changed, i < num_iters)

            def body(state):
                x_main, x_aux, _, i = state
                x1_main, x1_aux = step(x_main, x_aux)
                changed = jnp.any(x1_main != x_main) | jnp.any(x1_aux != x_aux)
                changed = jax.lax.pmax(changed.astype(jnp.int32),
                                       ("data", "graph")) > 0
                return (x1_main, x1_aux, changed, i + 1)

            x_main, x_aux, _, _ = jax.lax.while_loop(
                cond, body, (x0_main, x0_aux, jnp.bool_(True), jnp.int32(0)))
            return x_main, x_aux

        def seed_main(x0_main, q_local):
            # planes: trailing size-2 axis (0=definite, 1=maybe); the
            # query subject seeds BOTH planes (broadcast add)
            cols = jnp.arange(q_local.shape[0])
            word = cols // 32
            bit = (cols % 32).astype(jnp.uint32)
            if planes:
                x0_main = x0_main.at[q_local, word, :].add(
                    jnp.uint32(1) << bit[:, None])
            else:
                x0_main = x0_main.at[q_local, word].add(jnp.uint32(1) << bit)
            return x0_main.at[dead].set(np.uint32(0))

        if arena:
            def shard_fn(state_local, q_local, main_local, aux_local,
                         cav_local=None):
                # zero-init THROUGH the donated buffer (the sharded
                # counterpart of ops/ell.init_packed_state `like=`): the
                # bitplane pack seeds per-device buffers XLA aliases to
                # the previous call's donated output
                x0_main = seed_main(jnp.zeros_like(state_local[:n_pad]),
                                    q_local)
                x0_aux = jnp.zeros_like(state_local[n_pad:])
                res = sweep(x0_main, x0_aux, main_local, aux_local,
                            cav_local)
                if introspect:
                    x_main, x_aux, tel = res
                    return jnp.concatenate([x_main, x_aux], axis=0), tel
                x_main, x_aux = res
                return jnp.concatenate([x_main, x_aux], axis=0)
        else:
            def shard_fn(q_local, main_local, aux_local, cav_local=None):
                wl = q_local.shape[0] // 32
                shape = (n_pad, wl, 2) if planes else (n_pad, wl)
                x0_main = seed_main(jnp.zeros(shape, jnp.uint32), q_local)
                x0_aux = jnp.zeros((a_pad,) + shape[1:], jnp.uint32)
                x_main, _ = sweep(x0_main, x0_aux, main_local, aux_local,
                                  cav_local)
                return x_main

        row = P("graph", None)
        # the state is replicated along `graph` by design (check_vma off)
        state_sp = P(None, "data", None) if planes else P(None, "data")
        in_specs = (P("data"), row, row) + ((row,) if planes else ())
        if arena:
            in_specs = (state_sp,) + in_specs
            out_specs = (state_sp, P(None)) if introspect else state_sp
        else:
            out_specs = state_sp
        return jax.shard_map(shard_fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _fns(self) -> tuple:
        fns = self._jits.get("serial")
        if fns is None:
            evaluate = self._evaluate_shard_fn()
            if self.planes:
                def run_lookup(slot_offset, slot_length, q, idx_main,
                               idx_aux, idx_cav):
                    x = evaluate(q, idx_main, idx_aux, idx_cav)
                    # DEFINITE plane only: LookupResources skips
                    # conditional results (reference lookups.go:85-88)
                    return jax.lax.dynamic_slice_in_dim(
                        x[..., 0], slot_offset, slot_length, axis=0)

                def run_checks(q, gather_idx, gather_word, gather_bit,
                               idx_main, idx_aux, idx_cav):
                    x = evaluate(q, idx_main, idx_aux, idx_cav)
                    d = (x[gather_idx, gather_word, 0] >> gather_bit) \
                        & jnp.uint32(1)
                    m = (x[gather_idx, gather_word, 1] >> gather_bit) \
                        & jnp.uint32(1)
                    # 2=HAS, 1=CONDITIONAL, 0=NO
                    return d * 2 + (m & (d ^ jnp.uint32(1)))
            else:
                def run_lookup(slot_offset, slot_length, q, idx_main, idx_aux):
                    x = evaluate(q, idx_main, idx_aux)
                    return jax.lax.dynamic_slice_in_dim(
                        x, slot_offset, slot_length, axis=0)

                def run_checks(q, gather_idx, gather_word, gather_bit,
                               idx_main, idx_aux):
                    x = evaluate(q, idx_main, idx_aux)
                    return (x[gather_idx, gather_word] >> gather_bit) \
                        & jnp.uint32(1)

            fns = (jax.jit(run_lookup, static_argnums=(0, 1)),
                   jax.jit(run_checks))
            self._jits["serial"] = fns
        return fns

    # -- pipelined (device-resident) entry points ----------------------------
    # Sharded counterpart of ops/ell.EllKernelCache's pipelined dispatch:
    # the bitplane pack seeds a DONATED per-shard state arena, the word
    # transpose folds into the jit, and the un-materialized device array
    # is returned so the endpoint overlaps the D2H readback with the
    # next batch's dispatch — the mesh path no longer degrades to the
    # blocking serial entries.

    def _pipe_fns(self) -> tuple:
        fns = self._jits.get("pipe")
        if fns is not None:
            return fns
        # introspection resolved at jit-BUILD time (see ops/ell._fns):
        # gate off, the carry and return shapes are byte-identical to
        # the pre-introspection build
        intro = workload.enabled()
        evaluate = self._evaluate_shard_fn(arena=True, introspect=intro)

        if self.planes:
            def run_checks(q, gather_idx, gather_col, state,
                           idx_main, idx_aux, idx_cav):
                # word/bit split of the raw query columns happens HERE:
                # the host uploads plain int32 column ids
                gw = gather_col // 32
                gb = (gather_col % 32).astype(jnp.uint32)
                xe = evaluate(state, q, idx_main, idx_aux, idx_cav)
                x, tel = xe if intro else (xe, None)
                d = (x[gather_idx, gw, 0] >> gb) & jnp.uint32(1)
                m = (x[gather_idx, gw, 1] >> gb) & jnp.uint32(1)
                # 2=HAS, 1=CONDITIONAL (maybe without definite), 0=NO
                out = d * 2 + (m & (d ^ jnp.uint32(1)))
                return (out, x, tel) if intro else (out, x)

            def run_lookup(slot_offset, slot_length, q, state,
                           idx_main, idx_aux, idx_cav):
                xe = evaluate(state, q, idx_main, idx_aux, idx_cav)
                x, tel = xe if intro else (xe, None)
                # DEFINITE plane only (reference lookups.go:85-88);
                # transpose ON DEVICE so the D2H lands [W, L]
                sl = jax.lax.dynamic_slice_in_dim(
                    x[..., 0], slot_offset, slot_length, axis=0)
                return (sl.T, x, tel) if intro else (sl.T, x)
        else:
            def run_checks(q, gather_idx, gather_col, state,
                           idx_main, idx_aux):
                gw = gather_col // 32
                gb = (gather_col % 32).astype(jnp.uint32)
                xe = evaluate(state, q, idx_main, idx_aux)
                x, tel = xe if intro else (xe, None)
                # tri-state encoding ({0, 2}) so every kernel variant
                # hands the endpoint the same value space
                out = ((x[gather_idx, gw] >> gb) & jnp.uint32(1)) * 2
                return (out, x, tel) if intro else (out, x)

            def run_lookup(slot_offset, slot_length, q, state,
                           idx_main, idx_aux):
                xe = evaluate(state, q, idx_main, idx_aux)
                x, tel = xe if intro else (xe, None)
                sl = jax.lax.dynamic_slice_in_dim(
                    x, slot_offset, slot_length, axis=0)
                return (sl.T, x, tel) if intro else (sl.T, x)

        # donate_argnums=3 = the state arena; donation is a no-op on
        # backends without aliasing support (the virtual CPU mesh) and
        # an in-place per-shard update on TPU
        fns = (jax.jit(run_checks, donate_argnums=(3,)),
               jax.jit(run_lookup, static_argnums=(0, 1),
                       donate_argnums=(3,)),
               intro)
        self._jits["pipe"] = fns
        return fns

    def arena_key(self, lanes: int) -> int:
        """Pool key for a batch of `lanes` padded query columns (GLOBAL
        uint32 words — the data axis splits them across shards)."""
        return max(1, lanes // 32)

    def take_arena(self, n_words: int):
        """Pop the bucket's sharded state arena (exclusive: a donated
        buffer must never be shared between two in-flight calls); lazily
        allocated with the sweep's own sharding and HBM-ledger-registered
        on first use under the owning graph generation."""
        # kill-matrix site (tests/test_faultmatrix.py): a failure at the
        # arena pop must fail the dispatching batch fast without
        # corrupting the pool or the ledger
        fail_point("arenaTake")
        with self._arena_lock:
            a = self._arenas.pop(n_words, None)
        if a is not None:
            return a
        rows = self.n_pad + self.a_pad
        shape = (rows, n_words, 2) if self.planes else (rows, n_words)
        # zeros made in place on every shard, not on one device first
        a = jax.jit(lambda: jnp.zeros(shape, jnp.uint32),
                    out_shardings=self._state_spec)()
        devtel.LEDGER.register("state_arena", int(a.nbytes),
                               generation=self.devtel_generation,
                               name=f"arena:{n_words}")
        return a

    def put_arena(self, n_words: int, state) -> None:
        """Return a call's final state as the bucket's next donated
        arena (first writer wins, as in ops/ell.EllKernelCache)."""
        with self._arena_lock:
            self._arenas.setdefault(n_words, state)

    def discard_arena(self, n_words: int) -> None:
        """Drop a bucket's pooled arena — a failed async computation
        poisons its output array, and donating a poisoned arena would
        fail every later call of the bucket."""
        with self._arena_lock:
            a = self._arenas.pop(n_words, None)
        if a is not None:
            devtel.LEDGER.unregister("state_arena",
                                     generation=self.devtel_generation,
                                     name=f"arena:{n_words}")

    def checks_device(self, q_idx: np.ndarray, n_words: int,
                      gather_idx: np.ndarray, gather_col: np.ndarray,
                      idx_main, idx_aux, idx_cav=None):
        """Dispatch-only tri-state checks over the mesh ({0,2}, or
        {0,1,2} with planes): returns (out, tel) — the un-materialized
        device result plus the sweep-trace device array (None when
        KernelIntrospect was off at jit build); the caller owns the
        blocking readback.  `q_idx` must already be padded to a
        data-divisible lane count (the graph's batch_bucket guarantees
        it)."""
        run_checks, _, intro = self._pipe_fns()
        state = self.take_arena(n_words)
        q = jax.device_put(np.asarray(q_idx, np.int32), self._q_spec)
        args = [q, replicate(self.mesh, np.asarray(gather_idx, np.int32)),
                replicate(self.mesh, np.asarray(gather_col, np.int32)),
                state, idx_main, idx_aux]
        if self.planes:
            args.append(idx_cav)
        res = self._run_collective(run_checks, *args)
        out, x, tel = res if intro else (res[0], res[1], None)
        self.put_arena(n_words, x)
        return out, tel

    def lookup_packed_T_device(self, slot_offset: int, slot_length: int,
                               q_idx: np.ndarray, n_words: int,
                               idx_main, idx_aux, idx_cav=None):
        """Dispatch-only packed lookup over the mesh, word-transposed on
        device: returns (out, tel) — out the un-materialized
        [n_words, slot_length] uint32 device array (bit b of word row w
        = query column w*32+b; DEFINITE plane when planes are active),
        tel the sweep trace (None when KernelIntrospect was off)."""
        _, run_lookup, intro = self._pipe_fns()
        state = self.take_arena(n_words)
        q = jax.device_put(np.asarray(q_idx, np.int32), self._q_spec)
        if self.planes:
            res = self._run_collective(run_lookup, slot_offset, slot_length,
                                       q, state, idx_main, idx_aux, idx_cav)
        else:
            res = self._run_collective(run_lookup, slot_offset, slot_length,
                                       q, state, idx_main, idx_aux)
        out, x, tel = res if intro else (res[0], res[1], None)
        self.put_arena(n_words, x)
        return out, tel

    # -- host-facing ---------------------------------------------------------

    def padded_batch_words(self, batch: int) -> int:
        """uint32 word count for a `batch`-column query: a multiple of the
        data-axis size so every chip gets whole words (formula lives in
        padded_batch_words_for; the endpoint's batch_bucket calls this
        too)."""
        return padded_batch_words_for(self.mesh.shape["data"], batch)

    def _pad_q(self, q_idx: np.ndarray) -> np.ndarray:
        w = self.padded_batch_words(len(q_idx))
        out = np.full(w * 32, self.prog.dead_index, np.int32)
        out[: len(q_idx)] = q_idx
        return out

    def snapshot_tables(self) -> tuple:
        """Current device tables as an immutable view: incremental updates
        swap whole arrays (_scatter_rows), so a captured tuple stays
        internally consistent while queries run outside the endpoint
        lock."""
        if self.planes:
            return (self.idx_main, self.idx_aux, self.idx_cav)
        return (self.idx_main, self.idx_aux)

    def _table_args(self, tables=None) -> tuple:
        return tables if tables is not None else self.snapshot_tables()

    def lookup_packed(self, slot_offset: int, slot_length: int,
                      q_idx: np.ndarray, tables=None) -> np.ndarray:
        """Packed uint32 [slot_length, padded_words] allowed words (bit b
        of word w is query column w*32+b; DEFINITE plane under the
        tri-state path).  Columns past len(q_idx) are padding."""
        run_lookup, _ = self._fns()
        q = jax.device_put(self._pad_q(np.asarray(q_idx, np.int32)),
                           NamedSharding(self.mesh, P("data")))
        return np.ascontiguousarray(
            self._run_collective(run_lookup, slot_offset, slot_length, q,
                                 *self._table_args(tables)))

    def lookup(self, slot_offset: int, slot_length: int,
               q_idx: np.ndarray, tables=None) -> np.ndarray:
        """bool [slot_length, B] allowed bitmap over the real batch
        (DEFINITE plane under the tri-state path)."""
        packed = self.lookup_packed(slot_offset, slot_length, q_idx, tables)
        bits = np.unpackbits(packed.view(np.uint8).reshape(slot_length, -1),
                             axis=1, bitorder="little").astype(bool)
        return bits[:, : len(q_idx)]

    def checks(self, q_idx: np.ndarray, gather_idx: np.ndarray,
               gather_col: np.ndarray, tables=None) -> np.ndarray:
        """bool allowed per gather slot — or int {0,1,2} tri-state when
        the plane path is active."""
        run_lookup, run_checks = self._fns()
        q = jax.device_put(self._pad_q(np.asarray(q_idx, np.int32)),
                           NamedSharding(self.mesh, P("data")))
        g = bucket(max(len(gather_idx), 1), 8)
        gi = np.zeros(g, np.int32)
        gcol = np.zeros(g, np.int64)
        gi[: len(gather_idx)] = gather_idx
        gcol[: len(gather_col)] = gather_col
        out = np.asarray(self._run_collective(
            run_checks, q, replicate(self.mesh, gi),
            replicate(self.mesh, (gcol // 32).astype(np.int32)),
            replicate(self.mesh, (gcol % 32).astype(np.uint32)),
            *self._table_args(tables)))
        if self.planes:
            return out[: len(gather_idx)].astype(np.int8)
        return (out[: len(gather_idx)] != 0)
