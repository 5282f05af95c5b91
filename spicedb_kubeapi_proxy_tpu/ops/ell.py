"""Bit-packed ELL (fixed-fanin gather) reachability kernel.

The high-performance variant of ops/spmv.py.  Two ideas:

1. **No scatter.**  TPUs execute XLA scatter (the lowering of
   `jax.ops.segment_sum`) nearly serially; it dominated the segment-path
   kernel.  Here the adjacency is stored destination-major as fixed-width
   gather tables ("ELL" format): row r of `idx_main` lists the state
   indices whose OR is the one-step closure of state r.  One iteration is
   K row-gathers + bitwise ORs — gather only, which XLA lowers to fast
   dynamic-slices along the minor dimension.

   Destinations with more than K1 in-edges ("hubs": a namespace with
   thousands of pods pointing at it, a group with thousands of members)
   are split into an OR-reduction tree of **aux nodes** appended after the
   real state rows: each aux node ORs up to K2 children, levels stacked
   until ≤K1 roots remain.  Aux nodes are stateless OR gates recomputed
   from the state rows; they keep every row's fanin static.  Monotonicity
   of the fixpoint makes this exactly equivalent to the flat edge list
   (reference semantics: SpiceDB's recursive graph walk,
   pkg/authz/check.go:48, bounded like dispatch depth
   pkg/spicedb/spicedb.go:34).

   The aux table is NOT small on real graphs: direct per-object grants
   (a pod with 3..18 viewers) each grow a tree, so on the multitenant
   150k-pod graph the aux table holds 5.5M slots against the main
   table's 2.3M, only 15% of them live.  `build_tables` therefore
   orders the aux rows into slices by (reading stage, tree level, live
   width rounded up to a power of two), and the staged step refreshes
   each slice once per pass of the one stage that reads it, at its live
   width — not every row at full width before every stage.

2. **Bit-packed batch.**  The boolean state for a B-query batch is packed
   into uint32 words: x is [NT, W] with W = B/32.  HBM traffic drops 32x
   vs float32, and the whole userset-rewrite algebra maps onto bitwise
   ops: union=OR, intersection=AND, exclusion=AND-NOT — per-bit exact.

Layout: rows [0, state_size) are the GraphProgram's state (slot ranges
unchanged, so permission-op slices and lookup slices work as before);
rows [state_size, NT) are aux tree nodes: the built slices, then the
spare pool for delta growth.  The program's dead index keeps its
position; padding slots in both tables point at it.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import devtel, timeline, workload
from ..utils.failpoints import fail_point
from .graph_compile import (
    GraphProgram,
    PExclude,
    PIntersect,
    PRead,
    PUnion,
    PZero,
)

# Main-table fanin: rows with more in-edges are tree-split.  Production
# graphs are extremely fanin-skewed (multitenant-1m: 62% of rows fanin 0,
# 38% fanin 1, 0.2% more), so narrow main rows win: each main slot costs
# a full [NT]-row gather per iteration, while tree-split hubs ride the
# tiny aux table.  K=2 keeps one spare slot on the common fanin-1 row for
# incremental inserts (a full row grows an aux node from the spare pool,
# see _EllGraph.add_rel).  Env-tunable for experiments.
K_MAIN = int(os.environ.get("SPICEDB_TPU_K_MAIN", "2"))
# Aux-node fanin: wider is better for hubs (fewer tree levels).
K_AUX = 32
# Caveat (MAYBE-plane) table fanin; caveated tuples are typically sparse,
# hubs tree-split inside the same table
K_CAV = 8

MAX_ITERATIONS = 50  # matches embedded reference dispatch depth cap


def batch_words(batch: int, minimum: int = 1) -> int:
    """Power-of-two uint32 word count covering `batch` query columns."""
    w = max(minimum, 1)
    need = (max(batch, 1) + 31) // 32
    while w < need:
        w *= 2
    return w


@dataclass
class EllTables:
    """Host-side adjacency in fixed-fanin form (device copies are owned by
    the endpoint so it can do row-wise incremental updates)."""
    idx_main: np.ndarray                 # int32 [state_size, K_MAIN]
    idx_aux: np.ndarray                  # int32 [n_aux, K_AUX]
    tree_depth: int                      # max OR-tree levels over all hubs
    # trailing all-dead aux rows reserved for incremental growth: a delta
    # insert hitting a full main row moves the row's children into one of
    # these and gains an OR-tree level instead of forcing a rebuild
    spare_rows: tuple = ()               # aux-table row numbers
    # (stage, level, lo, hi, width): built aux rows [lo, hi) are the
    # level-`level` nodes of trees rooted in compute_stages' stage
    # `stage` (len(stages) for roots in no stage), each with at most
    # `width` live children, all in columns [0, width)
    aux_slices: tuple = ()


def _pow2_ceil(a: np.ndarray) -> np.ndarray:
    """Smallest power of two >= each element (>= 1)."""
    out = np.ones_like(a)
    while (out < a).any():
        out = np.where(out < a, out * 2, out)
    return out


def aux_row_widths(n_rows: int, slices: tuple) -> np.ndarray:
    """Per aux row, the columns the staged step refreshes: the slice
    width for built rows, K_AUX for spare (and any other) rows.  A delta
    insert may only fill a dead slot below its row's width."""
    w = np.full(n_rows, K_AUX, np.int32)
    for _, _, lo, hi, width in slices:
        w[lo:hi] = width
    return w


def _order_aux(prog: GraphProgram, idx_main: np.ndarray,
               idx_aux: np.ndarray, root: np.ndarray, level: np.ndarray,
               live: np.ndarray) -> tuple:
    """Renumber built aux rows so each (stage of the root row, level,
    power-of-two live width) group is one contiguous range; rewrite the
    aux references in both tables to match.  Returns (idx_main, idx_aux,
    slices)."""
    n = prog.state_size
    stages = compute_stages(prog)
    stage = np.full(len(root), len(stages), np.int64)
    for i, (ranges, _, _) in enumerate(stages):
        for lo, hi in ranges:
            stage[(root >= lo) & (root < hi)] = i
    width = _pow2_ceil(live)
    order = np.lexsort((width, level, stage))
    new_of = np.empty(len(order), np.int64)
    new_of[order] = np.arange(len(order))

    def remap(t: np.ndarray) -> np.ndarray:
        is_aux = t >= n
        out = t.copy()
        out[is_aux] = n + new_of[t[is_aux] - n]
        return out

    keys = np.stack([stage[order], level[order], width[order]], axis=1)
    cut = np.nonzero((np.diff(keys, axis=0) != 0).any(axis=1))[0] + 1
    bounds = np.concatenate([[0], cut, [len(order)]])
    slices = tuple(
        (int(keys[lo, 0]), int(keys[lo, 1]), int(lo), int(hi),
         int(keys[lo, 2]))
        for lo, hi in zip(bounds[:-1], bounds[1:]))
    return remap(idx_main), remap(idx_aux[order]), slices


def build_tables(prog: GraphProgram,
                 k_main: Optional[int] = None) -> EllTables:
    """Group the program's (src, dst) edge list destination-major into
    fixed-fanin tables, tree-splitting hubs.

    Vectorized: one stable sort by destination, then per-slot scatter for
    the (overwhelmingly common) small rows; only hub destinations fall to
    a Python loop.  The built aux rows are then ordered into slices
    (`_order_aux`), and the spare pool follows them."""
    km = k_main if k_main is not None else K_MAIN
    n = prog.state_size
    dead = prog.dead_index
    idx_main = np.full((n, km), dead, np.int32)
    aux_rows: list[np.ndarray] = []
    aux_root: list[int] = []     # main row each built node's tree feeds
    aux_level: list[int] = []    # 1 = children are state rows
    aux_live: list[int] = []     # live children
    tree_depth = 0
    e = len(prog.edge_src)
    if e:
        order = np.argsort(prog.edge_dst, kind="stable")
        sdst = prog.edge_dst[order]
        ssrc = prog.edge_src[order]
        starts = np.concatenate(
            [[0], np.nonzero(np.diff(sdst))[0] + 1])
        counts = np.diff(np.concatenate([starts, [e]]))
        gdst = sdst[starts]
        # rank of each edge within its destination group
        rank = np.arange(e) - np.repeat(starts, counts)
        small = counts <= km
        small_edges = np.repeat(small, counts)
        idx_main[sdst[small_edges], rank[small_edges]] = ssrc[small_edges]

        def new_aux(children: np.ndarray, level: int, root: int) -> int:
            row = np.full(K_AUX, dead, np.int32)
            row[: len(children)] = children
            aux_rows.append(row)
            aux_root.append(root)
            aux_level.append(level)
            aux_live.append(len(children))
            return n + len(aux_rows) - 1

        for g in np.nonzero(~small)[0]:
            lo = int(starts[g])
            root = int(gdst[g])
            children = ssrc[lo: lo + int(counts[g])]
            depth = 0
            while len(children) > km:
                children = np.asarray(
                    [new_aux(children[i: i + K_AUX], depth + 1, root)
                     for i in range(0, len(children), K_AUX)], np.int32)
                depth += 1
            idx_main[root, : len(children)] = children
            tree_depth = max(tree_depth, depth)

    slices: tuple = ()
    if aux_rows:
        idx_main, idx_aux, slices = _order_aux(
            prog, idx_main, np.stack(aux_rows).astype(np.int32),
            np.asarray(aux_root, np.int64), np.asarray(aux_level, np.int64),
            np.asarray(aux_live, np.int64))
    else:
        idx_aux = np.full((0, K_AUX), dead, np.int32)
    # spare pool sized to the graph; hub-free graphs keep an empty aux
    # table (no per-iteration aux gather at all) and fall back to the
    # rebuild path on their rare full-row inserts.  The pool scales with
    # BOTH the aux table (hub growth) and the main row count (capped):
    # under sustained write churn full-row inserts land anywhere in the
    # graph, and the pool drying up is what turns churn back into
    # rebuilds — on the 1M-row graph the main-scaled term costs ~0.5MB
    # of aux table for thousands of extra in-place growths
    # (docs/performance.md "Overload & rebuild behavior").
    if aux_rows:
        n_spare = max(64, len(aux_rows) // 4, min(4096, n // 256))
        spare0 = idx_aux.shape[0]
        idx_aux = np.vstack([idx_aux,
                             np.full((n_spare, K_AUX), dead, np.int32)])
        spares = tuple(range(spare0, spare0 + n_spare))
    else:
        spares = ()
    return EllTables(idx_main=idx_main.astype(np.int32),
                     idx_aux=idx_aux.astype(np.int32),
                     tree_depth=tree_depth, spare_rows=spares,
                     aux_slices=slices)


@dataclass
class CavTables:
    """MAYBE-plane adjacency: one [NT, K_CAV] gather table whose OR is the
    one-step closure over UNDECIDABLE caveated edges only.  Caveat hubs
    tree-split into aux rows appended after the shared aux rows (their
    children live in this same table); rows the shared tables own are
    dead-padded here and vice versa."""
    idx_cav: np.ndarray   # int32 [NT, K_CAV]
    n_aux_cav: int
    tree_depth: int


def build_cav_tables(prog: GraphProgram, n_aux_shared: int) -> CavTables:
    """Destination-major fixed-fanin table for the program's caveat edges.
    Python-loop build is fine: caveated tuples are sparse by nature."""
    dead = prog.dead_index
    base = prog.state_size + n_aux_shared
    groups: dict[int, list] = {}
    for s, d in zip(prog.cav_src, prog.cav_dst):
        groups.setdefault(int(d), []).append(int(s))
    aux_rows: list[list] = []
    roots: dict[int, list] = {}
    tree_depth = 0
    for dst, children in groups.items():
        depth = 0
        while len(children) > K_CAV:
            nxt = []
            for i in range(0, len(children), K_CAV):
                aux_rows.append(children[i: i + K_CAV])
                nxt.append(base + len(aux_rows) - 1)
            children = nxt
            depth += 1
        roots[dst] = children
        tree_depth = max(tree_depth, depth)
    nt = base + len(aux_rows)
    idx_cav = np.full((nt, K_CAV), dead, np.int32)
    for dst, children in roots.items():
        idx_cav[dst, : len(children)] = children
    for j, children in enumerate(aux_rows):
        idx_cav[base + j, : len(children)] = children
    return CavTables(idx_cav=idx_cav, n_aux_cav=len(aux_rows),
                     tree_depth=tree_depth)


# -- packed expression program ----------------------------------------------

def _apply_perm_expr_packed(expr, x: jnp.ndarray,
                            half: Optional[int] = None,
                            plane_last: bool = False) -> jnp.ndarray:
    """Evaluate a permission expression over packed state.

    Tri-state (definite/maybe bitplane) modes — maybe ⊇ definite always;
    union/intersection act planewise (Kleene: T∨U=T via the def plane,
    T∧U=U via the maybe plane); exclusion mixes planes:
    def(A−B) = def(A) ∧ ¬maybe(B),  maybe(A−B) = maybe(A) ∧ ¬def(B) —
    i.e. `base & ~swap(sub)` with the subtrahend's planes swapped.

    - `half` set: planes side by side on the WORD axis (single-chip
      layout; words [0, half) definite, [half, 2*half) maybe) — swap is a
      word-halves concat.
    - `plane_last`: planes on a trailing size-2 axis (sharded layout, so
      the swap stays device-local under a word-sharded mesh) — swap is a
      flip of the last axis."""
    if isinstance(expr, PRead):
        return jax.lax.dynamic_slice_in_dim(x, expr.offset, expr.length, axis=0)
    if isinstance(expr, PZero):
        return jnp.zeros((expr.length,) + x.shape[1:], dtype=x.dtype)
    if isinstance(expr, PUnion):
        out = _apply_perm_expr_packed(expr.children[0], x, half, plane_last)
        for c in expr.children[1:]:
            out = out | _apply_perm_expr_packed(c, x, half, plane_last)
        return out
    if isinstance(expr, PIntersect):
        out = _apply_perm_expr_packed(expr.children[0], x, half, plane_last)
        for c in expr.children[1:]:
            out = out & _apply_perm_expr_packed(c, x, half, plane_last)
        return out
    if isinstance(expr, PExclude):
        base = _apply_perm_expr_packed(expr.base, x, half, plane_last)
        sub = _apply_perm_expr_packed(expr.subtract, x, half, plane_last)
        if plane_last:
            sub = sub[..., ::-1]
        elif half is not None:
            sub = jnp.concatenate([sub[:, half:], sub[:, :half]], axis=1)
        return base & ~sub
    raise TypeError(f"unknown perm expr {expr!r}")


def compute_stages(prog: GraphProgram) -> tuple:
    """Type-level Gauss-Seidel stages for the staged step: contiguous
    state-row ranges grouped by type-SCC, topologically ordered by the
    COMPILED edge list (type-of(src) -> type-of(dst)).

    Evaluating ranges in this order lets one sweep propagate a whole
    user->group->tenant->namespace->pod chain: the fixpoint trip count
    drops from the type-graph depth to ~the longest in-SCC chain (+1 to
    confirm).  Correctness never depends on the order — the while_loop
    exits at the true fixpoint under ANY update order (monotone OR), so
    delta-added edges that violate the compiled order (or cycles) just
    cost extra sweeps, exactly like the unstaged step.

    Returns a tuple of stage descriptors (ranges, repeat): `ranges` is a
    tuple of (lo, hi) row ranges (SCC members merge when adjacent), and
    `repeat` is 2 when the SCC has internal edges (e.g. group#member
    nesting) so one nesting hop resolves within the sweep instead of
    costing an extra sweep; deeper nests still converge via the outer
    while_loop."""
    # per-type contiguous range from the slot layout
    starts: dict = {}
    for (t, _slot), off in prog.slot_offsets.items():
        starts[t] = min(starts.get(t, off), off)
    if not starts:
        return ()
    types = sorted(starts, key=lambda t: starts[t])
    bounds = [starts[t] for t in types] + [prog.dead_index]
    rng_of = {t: (bounds[i], bounds[i + 1]) for i, t in enumerate(types)}
    # type dependency edges from the compiled edge list
    b = np.asarray(bounds[:-1], np.int64)
    deps: dict = {t: set() for t in types}
    self_dep: set = set()
    if len(prog.edge_src):
        from .graph_compile import SELF_SLOT
        live = prog.edge_dst != prog.dead_index
        esrc = prog.edge_src[live]
        src_t = np.searchsorted(b, esrc, side="right") - 1
        dst_t = np.searchsorted(b, prog.edge_dst[live], side="right") - 1
        # a same-type edge forces a within-sweep repeat only when its
        # source is a DYNAMIC slot; sources in the type's self range are
        # static query seeds and resolve in the first pass regardless
        self_lo = np.asarray(
            [prog.slot_offsets.get((t, SELF_SLOT), -1) for t in types],
            np.int64)
        self_hi = self_lo + np.asarray(
            [prog.num_objects.get(t, 0) for t in types], np.int64)
        in_self = (esrc >= self_lo[src_t]) & (esrc < self_hi[src_t])
        for s, d, st in set(zip(src_t.tolist(), dst_t.tolist(),
                                in_self.tolist())):
            if not (0 <= s < len(types) and 0 <= d < len(types)):
                continue
            if s == d:
                if not st:
                    self_dep.add(types[d])
            else:
                deps[types[d]].add(types[s])  # d depends on s
    # SCC condensation (iterative Tarjan) + topological order
    index: dict = {}
    low: dict = {}
    on_stack: dict = {}
    stack: list = []
    sccs: list = []
    counter = [0]
    for root in types:
        if root in index:
            continue
        work = [(root, iter(sorted(deps[root])))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack[root] = True
        while work:
            v, it = work[-1]
            advanced = False
            for nxt in it:
                if nxt not in index:
                    index[nxt] = low[nxt] = counter[0]
                    counter[0] += 1
                    stack.append(nxt)
                    on_stack[nxt] = True
                    work.append((nxt, iter(sorted(deps[nxt]))))
                    advanced = True
                    break
                if on_stack.get(nxt):
                    low[v] = min(low[v], index[nxt])
            if advanced:
                continue
            work.pop()
            if work:
                pv = work[-1][0]
                low[pv] = min(low[pv], low[v])
            if low[v] == index[v]:
                comp = []
                while True:
                    u = stack.pop()
                    on_stack[u] = False
                    comp.append(u)
                    if u == v:
                        break
                sccs.append(comp)
    # Tarjan emits SCCs in reverse topological order of the traversal
    # graph; with edges pointing dependent -> prerequisite, that is
    # exactly prerequisites-first — the evaluation order we want
    stages: list = []
    for comp in sccs:
        ranges = sorted(rng_of[t] for t in comp)
        merged = [list(ranges[0])]
        for lo, hi in ranges[1:]:
            if lo == merged[-1][1]:
                merged[-1][1] = hi
            else:
                merged.append([lo, hi])
        rtuple = tuple((lo, hi) for lo, hi in merged if hi > lo)
        if not rtuple:
            continue
        repeat = 2 if (len(comp) > 1 or any(t in self_dep for t in comp)) \
            else 1
        stages.append((rtuple, repeat, True))
    return tuple(stages)


def make_ell_step(prog: GraphProgram, n_aux_rows: int,
                  half: Optional[int] = None, aux_passes: int = 1,
                  stages: Optional[tuple] = None, aux_slices: tuple = (),
                  spare: Optional[tuple] = None):
    """Per-iteration transition over packed state x: [NT, W] uint32 —
    or [NT, 2*half] when the tri-state (definite/maybe bitplane) path is
    active (`half` = words per plane; an idx_cav table feeds the MAYBE
    half with the undecidable caveated edges).

    Without `stages` (the Jacobi step: the plane path, and the A/B
    switch) `aux_passes` (= the OR-tree height + 1) refreshes every aux
    node bottom-up at full width BEFORE the main gather reads them, so a
    hub edge propagates leaf -> tree -> destination in one outer
    iteration instead of one per tree level.

    `stages` (definite path only) extends the same idea across TYPES:
    state-row ranges are updated in type-topological order within one
    sweep, each range's gather reading the ranges already updated this
    sweep, so a full user->group->...->pod chain propagates in ONE sweep
    instead of one per type hop (measured on multitenant-1m: trips 6->2,
    scripts/probe_staged.py).  Before each pass of a stage, the aux
    slices of `aux_slices` (build_tables) rooted in that stage are
    refreshed once each, in level order, at their live width; the aux
    table is as large as the main table on real graphs, so no row is
    refreshed where no stage reads it.  `spare` = (lo, hi, passes) adds
    the in-use prefix of the spare pool (delta growth) at full width,
    `passes` times, before each pass of a stage flagged as reading aux.
    Every aux row a stage reads is thus recomputed earlier in the same
    sweep, so the sweep is a function of the state rows alone and the
    caller's convergence test compares rows [0, state_size) only
    (make_ell_evaluate).  Monotone OR fixpoint semantics are unchanged
    — only the trip count and the gathered indices drop."""
    n = prog.state_size
    dead = prog.dead_index
    perm_ops = tuple(prog.perm_ops)
    wc_terms = tuple(prog.wildcard_terms)
    wc_masks = []
    for term in prog.wildcard_terms:
        m = np.zeros((n + n_aux_rows, 1), np.uint32)
        m[np.asarray(term.mask_indices, np.int64)] = np.uint32(0xFFFFFFFF)
        wc_masks.append(jnp.asarray(m))

    def gather_or(cur, tbl, width):
        y = cur[tbl[:, 0]]
        for k in range(1, width):
            y = y | cur[tbl[:, k]]
        return y

    if stages:
        # perm ops and wildcard masks grouped by the stage whose ranges
        # contain them (slot layout keeps a type's slots contiguous, so
        # containment is exact)
        def _in_stage(ranges, off):
            return any(lo <= off < hi for lo, hi in ranges)

        stage_ops = [[op for op in perm_ops if _in_stage(s[0], op.offset)]
                     for s in stages]
        stage_wc = [[i for i, term in enumerate(wc_terms)
                     if any(_in_stage(s[0], m) for m in term.mask_indices)]
                    for s in stages]
        # level order within a stage: a level-L node reads level L-1
        stage_slices = [sorted((lvl, lo, hi, w)
                               for st, lvl, lo, hi, w in aux_slices
                               if st == i)
                        for i in range(len(stages))]

        def staged_step(x, x0, idx_main, idx_aux, idx_cav=None):
            assert idx_cav is None and half is None, \
                "staged step is definite-plane only"
            cur = x

            def refresh(cur, i, wants_aux):
                # this stage's OR-tree nodes recomputed from the CURRENT
                # values (pure functions of state) and published into the
                # carry, so the stage's gather reads fresh roots
                for _, lo, hi, w in stage_slices[i]:
                    cur = jax.lax.dynamic_update_slice_in_dim(
                        cur, gather_or(cur, idx_aux[lo:hi], w), n + lo,
                        axis=0)
                if spare and wants_aux:
                    lo, hi, passes = spare
                    for _ in range(passes):
                        cur = jax.lax.dynamic_update_slice_in_dim(
                            cur, gather_or(cur, idx_aux[lo:hi],
                                           idx_aux.shape[1]),
                            n + lo, axis=0)
                return cur

            # wildcard liveness: self slots are static seeds (set at
            # init, never rewritten), so reading x here is exact
            lives = [jax.lax.reduce(
                jax.lax.dynamic_slice_in_dim(
                    x, t.self_offset, t.self_length, axis=0),
                np.uint32(0), jax.lax.bitwise_or, (0,))[None, :]
                for t in wc_terms]
            for i, (ranges, repeat, wants_aux) in enumerate(stages):
                for _ in range(repeat):
                    if n_aux_rows:
                        cur = refresh(cur, i, wants_aux)
                    for lo, hi in ranges:
                        y = gather_or(cur, idx_main[lo:hi],
                                      idx_main.shape[1])
                        y = y | jax.lax.dynamic_slice_in_dim(
                            x0, lo, hi - lo, axis=0)
                        for j in stage_wc[i]:
                            y = y | (wc_masks[j][lo:hi] & lives[j])
                        cur = jax.lax.dynamic_update_slice_in_dim(
                            cur, y, lo, axis=0)
                    for op in stage_ops[i]:
                        vec = _apply_perm_expr_packed(op.expr, cur, half)
                        seed = jax.lax.dynamic_slice_in_dim(
                            x0, op.offset, op.length, axis=0)
                        cur = jax.lax.dynamic_update_slice_in_dim(
                            cur, vec | seed, op.offset, axis=0)
            return cur.at[dead].set(np.uint32(0))

        return staged_step

    def step(x, x0, idx_main, idx_aux, idx_cav=None):
        # one-step closure: K gathers + OR per table, concatenated in row
        # order (main rows first, aux rows after) — no scatter anywhere.
        # Fanin widths come from the table shapes (trace-time constants),
        # so one step fn serves any K layout.
        if n_aux_rows:
            # refresh aux OR-tree nodes bottom-up first; each pass fixes
            # one more tree level (pass 1 = nodes whose children are all
            # state rows), then the main gather reads current roots
            xm = x
            for _ in range(max(1, aux_passes)):
                y_aux = gather_or(xm, idx_aux, idx_aux.shape[1])
                xm = jnp.concatenate([x[:n], y_aux], axis=0)
            y_main = gather_or(xm, idx_main, idx_main.shape[1])
            y = jnp.concatenate([y_main, y_aux], axis=0)
        else:
            y = gather_or(x, idx_main, idx_main.shape[1])
        if idx_cav is not None:
            # caveat edges reach the MAYBE plane only: gather their
            # closure and OR it into the maybe half (definite half is
            # untouched — an undecided caveat can never DEFINITELY grant)
            extra = gather_or(x, idx_cav, idx_cav.shape[1])
            y = jnp.concatenate([y[:, :half], y[:, half:] | extra[:, half:]],
                                axis=1)
        for term, mask in zip(wc_terms, wc_masks):
            live = jax.lax.dynamic_slice_in_dim(
                x, term.self_offset, term.self_length, axis=0)
            any_live = jax.lax.reduce(
                live, np.uint32(0), jax.lax.bitwise_or, (0,))[None, :]
            y = y | (mask & any_live)
        x1 = y | x0
        for op in perm_ops:
            vec = _apply_perm_expr_packed(op.expr, x1, half)
            seed = jax.lax.dynamic_slice_in_dim(x0, op.offset, op.length, axis=0)
            x1 = jax.lax.dynamic_update_slice_in_dim(
                x1, vec | seed, op.offset, axis=0)
        # the dead row must stay zero (table padding reads it)
        x1 = x1.at[dead].set(np.uint32(0))
        return x1

    return step


def init_packed_state(prog: GraphProgram, n_aux_rows: int, q_idx,
                      n_words: int, planes: bool = False,
                      like=None) -> jnp.ndarray:
    """Packed one-hot [NT, W] from per-query state indices ([NT, 2W] with
    both planes seeded when the tri-state path is active: the query
    subject itself is definite, hence also maybe).

    Column c of the batch is bit (c % 32) of word (c // 32); columns are
    distinct, so the scatter-add below never carries (each target bit is
    added at most once per (row, word)) — add is exactly OR here.

    `like` (the donated state arena, shape [NT, width]) makes the arena
    an operand of the zero-init: the bitplane PACK — int columns to
    one-hot uint32 bit words — happens on device, seeded into the
    buffer XLA aliases to the previous call's donated output, so the
    sweep state updates in place instead of allocating per call.
    """
    nt = prog.state_size + n_aux_rows
    b = q_idx.shape[0]
    cols = jnp.arange(b)
    word = cols // 32
    bit = (cols % 32).astype(jnp.uint32)
    width = 2 * n_words if planes else n_words
    x0 = (jnp.zeros((nt, width), jnp.uint32) if like is None
          else jnp.zeros_like(like))
    x0 = x0.at[q_idx, word].add(jnp.uint32(1) << bit)
    if planes:
        x0 = x0.at[q_idx, n_words + word].add(jnp.uint32(1) << bit)
    return x0.at[prog.dead_index].set(np.uint32(0))


def _head(x: jnp.ndarray, rows: Optional[int]) -> jnp.ndarray:
    return x if rows is None else x[:rows]


def make_ell_evaluate(prog: GraphProgram, n_aux_rows: int, n_words: int,
                      num_iters: int, use_while: bool = True,
                      planes: bool = False, aux_passes: int = 1,
                      stages: Optional[tuple] = None, arena: bool = False,
                      introspect: bool = False, aux_slices: tuple = (),
                      spare: Optional[tuple] = None):
    """fn(q_idx, idx_main, idx_aux[, idx_cav]) -> packed x_final
    [NT, W] uint32 ([NT, 2W] on the tri-state plane path).

    With `arena=True` the signature becomes
    fn(state, q_idx, idx_main, idx_aux[, idx_cav]): `state` is the
    previous call's x_final, donated (jax.jit donate_argnums) so XLA
    aliases its buffer to this call's state output — the persistent
    sweep state updates in place instead of allocating per call.

    With `introspect=True` (KernelIntrospect gate, resolved at jit-build
    time) the return value becomes (x_final, tel): tel is an int32
    [1 + num_iters] sweep trace — tel[0] the executed iteration count,
    tel[1:1+tel[0]] the per-iteration frontier population (bits that
    changed, via popcount of x1 ^ x).  The trace rides the carry and is
    read back with the result D2H, so it adds no device sync; off, the
    carry is byte-identical to the pre-introspection build."""
    stages = None if planes else stages
    step = make_ell_step(prog, n_aux_rows,
                         half=n_words if planes else None,
                         aux_passes=aux_passes, stages=stages,
                         aux_slices=aux_slices, spare=spare)
    # rows the convergence test compares: a staged sweep is a function
    # of the state rows alone (make_ell_step); the Jacobi step, all rows
    conv = prog.state_size if stages else None

    def fixpoint(x0, idx_main, idx_aux, idx_cav):
        if use_while:
            if introspect:
                def cond(state):
                    x, prev_changed, i, trace = state
                    return jnp.logical_and(prev_changed, i < num_iters)

                def body(state):
                    x, _, i, trace = state
                    x1 = step(x, x0, idx_main, idx_aux, idx_cav)
                    delta = jnp.sum(jax.lax.population_count(
                        _head(x1, conv) ^ _head(x, conv))).astype(jnp.int32)
                    return (x1, delta > jnp.int32(0), i + 1,
                            trace.at[i].set(delta))

                x_final, _, i, trace = jax.lax.while_loop(
                    cond, body, (x0, jnp.bool_(True), jnp.int32(0),
                                 jnp.zeros((num_iters,), jnp.int32)))
                return x_final, jnp.concatenate([i[None], trace])

            def cond(state):
                x, prev_changed, i = state
                return jnp.logical_and(prev_changed, i < num_iters)

            def body(state):
                x, _, i = state
                x1 = step(x, x0, idx_main, idx_aux, idx_cav)
                return (x1, jnp.any(_head(x1, conv) != _head(x, conv)),
                        i + 1)

            x_final, _, _ = jax.lax.while_loop(
                cond, body, (x0, jnp.bool_(True), jnp.int32(0)))
            return x_final

        if introspect:
            def body(x, _):
                x1 = step(x, x0, idx_main, idx_aux, idx_cav)
                delta = jnp.sum(jax.lax.population_count(
                    _head(x1, conv) ^ _head(x, conv))).astype(jnp.int32)
                return x1, delta

            x_final, deltas = jax.lax.scan(body, x0, None, length=num_iters)
            return x_final, jnp.concatenate(
                [jnp.full((1,), num_iters, jnp.int32), deltas])

        def body(x, _):
            return step(x, x0, idx_main, idx_aux, idx_cav), None

        x_final, _ = jax.lax.scan(body, x0, None, length=num_iters)
        return x_final

    if arena:
        def evaluate(state, q_idx, idx_main, idx_aux, idx_cav=None):
            x0 = init_packed_state(prog, n_aux_rows, q_idx, n_words, planes,
                                   like=state)
            return fixpoint(x0, idx_main, idx_aux, idx_cav)
    else:
        def evaluate(q_idx, idx_main, idx_aux, idx_cav=None):
            x0 = init_packed_state(prog, n_aux_rows, q_idx, n_words, planes)
            return fixpoint(x0, idx_main, idx_aux, idx_cav)

    return evaluate


class EllKernelCache:
    """Jitted packed check/lookup entry points for one (program, tables)
    pair.  Jit cache keys on (batch-word bucket, table shapes).

    With `planes=True` the state carries definite/maybe bitplanes and the
    call signatures grow an `idx_cav` table: checks return tri-state
    {0,1,2} (NO / CONDITIONAL / HAS), lookups return the DEFINITE plane
    only (LookupResources skips conditional results, reference
    lookups.go:85-88)."""

    # metric label for authz_sweep_iterations / authz_frontier_decay
    kernel_name = "ell"

    def __init__(self, prog: GraphProgram, n_aux_rows: int, tree_depth: int,
                 num_iters: Optional[int] = None, planes: bool = False,
                 shared_tree_depth: Optional[int] = None,
                 aux_slices: tuple = (), spare_rows: tuple = ()):
        self.prog = prog
        self.n_aux_rows = n_aux_rows
        self.planes = planes
        # the Jacobi step's in-step bottom-up aux refresh (Gauss-Seidel)
        # collapses OR-tree levels into their outer iteration.  Passes
        # follow the SHARED table's tree height only — cav trees
        # propagate through idx_cav one level per outer iteration
        # regardless, so their depth must not inflate the sweep count
        # (callers fold it into tree_depth for the cap).  +1 spare pass:
        # incremental growth can add a level beyond the built height.
        std = shared_tree_depth if shared_tree_depth is not None else tree_depth
        self.aux_passes = std + 1
        # generous cap — while_loop exits at the true fixpoint anyway
        base = num_iters or MAX_ITERATIONS
        self.num_iters = base * (1 + tree_depth)
        # type-topological Gauss-Seidel stages (definite path only; the
        # plane path keeps the Jacobi step).  SPICEDB_TPU_STAGED=0
        # disables for A/B experiments.
        self.stages = (compute_stages(prog)
                       if not planes
                       and os.environ.get("SPICEDB_TPU_STAGED", "1") != "0"
                       else None)
        # build_tables' aux slices; a stage reads aux (wants_aux) exactly
        # when some slice is rooted in it, until delta growth flips it
        # (note_main_aux_ref)
        self.aux_slices = tuple(aux_slices)
        if self.stages:
            rooted = {sl[0] for sl in self.aux_slices}
            self.stages = tuple((ranges, repeat, i in rooted)
                                for i, (ranges, repeat, _)
                                in enumerate(self.stages))
        # spare pool rows are taken in order (_EllGraph._grow); the staged
        # step refreshes the prefix [spare_base, spare_base + spare_prefix)
        # `spare_passes` times, both trace-time constants grown by
        # note_spare_row
        self.spare_base = spare_rows[0] if len(spare_rows) else 0
        self.spare_pool = len(spare_rows)
        self.spare_prefix = 0
        self.spare_passes = 0
        self._jits: dict[int, tuple] = {}
        # donated per-bucket state arenas (device-resident pipeline):
        # the pipelined entry points return their final sweep state, and
        # the next call of the same bucket donates it back so XLA
        # aliases the buffer in place (one persistent [NT, W] allocation
        # per bucket instead of one per call).  Ledger-registered under
        # the owning graph's generation (set by the endpoint's HBM
        # registration) so a rebuild retires them wholesale.
        self._arenas: dict = {}
        self._arena_lock = threading.Lock()
        self.devtel_generation = 0
        # jit-cache accounting: hits/misses/entries per batch bucket,
        # plus recompile-storm detection (utils/devtel.py)
        devtel.KERNELS.track(self)

    def note_main_aux_ref(self, row: int) -> bool:
        """Incremental growth (_EllGraph._grow) pointed main row `row`
        at an OR-tree aux node.  If the stage covering that row was
        annotated aux-free at build time (annotate_stage_refresh), the
        staged step would keep skipping the per-stage aux refresh and
        every query touching the grown hub pays one extra outer sweep —
        silently.  Flip the stage's wants_aux flag and drop the compiled
        entry points so the next call re-jits with the refresh; returns
        True when a flip happened (callers surface it as a stat)."""
        if not self.stages:
            return False
        for i, (ranges, repeat, wants_aux) in enumerate(self.stages):
            if any(lo <= row < hi for lo, hi in ranges):
                if wants_aux:
                    return False
                self.stages = (self.stages[:i]
                               + ((ranges, repeat, True),)
                               + self.stages[i + 1:])
                self._jits = {}
                return True
        return False

    def note_spare_row(self, row: int, depth: int) -> bool:
        """_EllGraph._grow took spare aux row `row` (aux-table number),
        `depth` spare levels above the built tree.  If the row lies past
        the compiled spare prefix, or its chain is deeper than the
        compiled passes, grow them (the prefix to a power of two, so a run
        of inserts re-jits O(log n) times) and drop the compiled entry
        points; returns True when that happened.  The Jacobi step
        refreshes every aux row already."""
        if not self.stages:
            return False
        need = row - self.spare_base + 1
        if need <= self.spare_prefix and depth <= self.spare_passes:
            return False
        prefix = max(1, self.spare_prefix)
        while prefix < need:
            prefix *= 2
        self.spare_prefix = min(prefix, self.spare_pool)
        self.spare_passes = max(self.spare_passes, depth)
        self._jits = {}
        workload.note_spare_rejit()
        return True

    def _spare(self) -> Optional[tuple]:
        if not self.spare_prefix:
            return None
        return (self.spare_base, self.spare_base + self.spare_prefix,
                self.spare_passes)

    def aux_refresh_slots(self) -> dict:
        """Aux-table indices one sweep of the compiled step gathers, by
        kind: `slices` (built OR-tree slices, per stage pass), `spare`
        (the spare-pool prefix) and `table` (the Jacobi step: every row
        at full width, `aux_passes` times)."""
        out = {"slices": 0, "spare": 0, "table": 0}
        if not self.n_aux_rows:
            return out
        if not self.stages:
            out["table"] = self.aux_passes * self.n_aux_rows * K_AUX
            return out
        spare = self._spare()
        for i, (_, repeat, wants_aux) in enumerate(self.stages):
            out["slices"] += repeat * sum(
                (hi - lo) * w for st, _, lo, hi, w in self.aux_slices
                if st == i)
            if spare and wants_aux:
                out["spare"] += repeat * spare[2] * (spare[1] - spare[0]) \
                    * K_AUX
        return out

    def _evaluate(self, n_words: int, **kw):
        """make_ell_evaluate for this cache's program and tables at the
        current stage flags and spare prefix; records the compiled
        step's aux refresh in authz_aux_refresh_slots.

        Callers take `self._jits` BEFORE this reads the config and store
        into that dict: note_main_aux_ref / note_spare_row change the
        config first and then replace the dict, so an entry built from
        the old config by a concurrent caller lands in the dropped dict,
        never in the new one (a step that skips a spare row a table
        reads is wrong, not only slower)."""
        workload.note_aux_refresh(self.aux_refresh_slots())
        return make_ell_evaluate(self.prog, self.n_aux_rows, n_words,
                                 self.num_iters, planes=self.planes,
                                 aux_passes=self.aux_passes,
                                 stages=self.stages,
                                 aux_slices=self.aux_slices,
                                 spare=self._spare(), **kw)

    def _fns(self, n_words: int) -> tuple:
        jits = self._jits  # before reading the step's config: see _evaluate
        fns = jits.get(n_words)
        if fns is not None:
            devtel.KERNELS.note_jit_hit(n_words * 32)
            return fns
        devtel.KERNELS.note_compile(n_words * 32)
        # introspection is resolved at jit-BUILD time: gate off, the
        # functions below are exactly the pre-introspection build (no
        # trace in the carry, scalar return shapes) — the killswitch is
        # byte-identical, not merely quiet
        intro = workload.enabled()
        evaluate = self._evaluate(n_words, introspect=intro)
        if self.planes:
            def run_checks(q_idx, gather_idx, gather_word, gather_bit,
                           idx_main, idx_aux, idx_cav):
                xe = evaluate(q_idx, idx_main, idx_aux, idx_cav)
                x, tel = xe if intro else (xe, None)
                dw = x[gather_idx, gather_word]
                mw = x[gather_idx, n_words + gather_word]
                d = (dw >> gather_bit) & jnp.uint32(1)
                m = (mw >> gather_bit) & jnp.uint32(1)
                # 2=HAS, 1=CONDITIONAL (maybe without definite), 0=NO
                out = d * 2 + (m & (d ^ jnp.uint32(1)))
                return (out, tel) if intro else out

            def run_lookup(slot_offset, slot_length, q_idx,
                           idx_main, idx_aux, idx_cav):
                xe = evaluate(q_idx, idx_main, idx_aux, idx_cav)
                x, tel = xe if intro else (xe, None)
                out = jax.lax.dynamic_slice(
                    x, (slot_offset, 0), (slot_length, n_words))
                return (out, tel) if intro else out
        else:
            def run_checks(q_idx, gather_idx, gather_word, gather_bit,
                           idx_main, idx_aux):
                xe = evaluate(q_idx, idx_main, idx_aux)
                x, tel = xe if intro else (xe, None)
                words = x[gather_idx, gather_word]
                out = (words >> gather_bit) & jnp.uint32(1)
                return (out, tel) if intro else out

            def run_lookup(slot_offset, slot_length, q_idx, idx_main, idx_aux):
                xe = evaluate(q_idx, idx_main, idx_aux)
                x, tel = xe if intro else (xe, None)
                # return PACKED words: device->host transfer is the dominant
                # cost (32x fewer bytes than a bool bitmap); host unpacks
                out = jax.lax.dynamic_slice_in_dim(
                    x, slot_offset, slot_length, axis=0)       # [L, W] uint32
                return (out, tel) if intro else out

        # XLA compiles lazily inside the first execution; the
        # first-call-per-compile-key wrapper records each such window
        # as a `compile` slice on the dispatch timeline (stall cause
        # the flight recorder links p99 spikes to).  run_lookup's
        # static (slot_offset, slot_length) pair IS part of the jit
        # cache key — every new (type, permission) slot range
        # recompiles, so static_args=2 attributes those too.
        # shape_args: the check gather and the grow-able tables retrace
        # the same jit under novel shapes — attribute those compiles
        # too, not just the first call of the bucket
        fns = (timeline.time_first_call(jax.jit(run_checks),
                                        bucket=n_words * 32,
                                        shape_args=True),
               timeline.time_first_call(
                   jax.jit(run_lookup, static_argnums=(0, 1)),
                   bucket=n_words * 32, static_args=2, shape_args=True),
               intro)
        jits[n_words] = fns
        return fns

    # -- pipelined (device-resident) entry points ----------------------------
    # The serial entries above sync at the numpy conversion and hand the
    # host a [L, W] result it must word-transpose; these variants keep
    # the whole per-batch pipeline on device: the bitplane pack seeds a
    # DONATED state arena (in-place iteration state), the word transpose
    # is folded into the jit where XLA fuses it with the final slice, and
    # the un-materialized device array is returned so the caller overlaps
    # the D2H readback with the next batch's dispatch.

    def _pipe_fns(self, n_words: int) -> tuple:
        jits = self._jits
        fns = jits.get(("pipe", n_words))
        if fns is not None:
            devtel.KERNELS.note_jit_hit(n_words * 32)
            return fns
        devtel.KERNELS.note_compile(n_words * 32)
        # introspection resolved at jit-build time (see _fns); when on,
        # the pipelined entries return (out, state, tel) and the sweep
        # trace rides the same async D2H the result does
        intro = workload.enabled()
        evaluate = self._evaluate(n_words, arena=True, introspect=intro)
        if self.planes:
            def run_checks(q_idx, gather_idx, gather_col, state,
                           idx_main, idx_aux, idx_cav):
                # word/bit split of the raw query columns happens HERE:
                # the host uploads plain int32 column ids
                gw = gather_col // 32
                gb = (gather_col % 32).astype(jnp.uint32)
                xe = evaluate(state, q_idx, idx_main, idx_aux, idx_cav)
                x, tel = xe if intro else (xe, None)
                d = (x[gather_idx, gw] >> gb) & jnp.uint32(1)
                m = (x[gather_idx, n_words + gw] >> gb) & jnp.uint32(1)
                # 2=HAS, 1=CONDITIONAL (maybe without definite), 0=NO
                out = d * 2 + (m & (d ^ jnp.uint32(1)))
                return (out, x, tel) if intro else (out, x)

            def run_lookup(slot_offset, slot_length, q_idx, state,
                           idx_main, idx_aux, idx_cav):
                xe = evaluate(state, q_idx, idx_main, idx_aux, idx_cav)
                x, tel = xe if intro else (xe, None)
                sl = jax.lax.dynamic_slice(
                    x, (slot_offset, 0), (slot_length, n_words))
                # transpose ON DEVICE: the D2H lands [W, L] contiguous
                # per word row, so host extraction is row indexing with
                # no 51MB host transpose copy (DEFINITE plane only)
                return (sl.T, x, tel) if intro else (sl.T, x)
        else:
            def run_checks(q_idx, gather_idx, gather_col, state,
                           idx_main, idx_aux):
                gw = gather_col // 32
                gb = (gather_col % 32).astype(jnp.uint32)
                xe = evaluate(state, q_idx, idx_main, idx_aux)
                x, tel = xe if intro else (xe, None)
                # tri-state encoding ({0, 2}) so every kernel variant
                # hands the endpoint the same value space
                out = ((x[gather_idx, gw] >> gb) & jnp.uint32(1)) * 2
                return (out, x, tel) if intro else (out, x)

            def run_lookup(slot_offset, slot_length, q_idx, state,
                           idx_main, idx_aux):
                xe = evaluate(state, q_idx, idx_main, idx_aux)
                x, tel = xe if intro else (xe, None)
                sl = jax.lax.dynamic_slice_in_dim(
                    x, slot_offset, slot_length, axis=0)
                return (sl.T, x, tel) if intro else (sl.T, x)

        # donate_argnums=3 = the state arena (positions count the full
        # signature, statics included); donation is a no-op on backends
        # without aliasing support (CPU) and an in-place update on TPU
        fns = (timeline.time_first_call(
                   jax.jit(run_checks, donate_argnums=(3,)),
                   bucket=n_words * 32, shape_args=True),
               timeline.time_first_call(
                   jax.jit(run_lookup, static_argnums=(0, 1),
                           donate_argnums=(3,)),
                   bucket=n_words * 32, static_args=2, shape_args=True),
               intro)
        jits[("pipe", n_words)] = fns
        return fns

    def arena_key(self, lanes: int) -> int:
        """Pool key for a batch of `lanes` padded query columns."""
        return max(1, lanes // 32)

    def take_arena(self, n_words: int):
        """Pop the bucket's state arena (exclusive: a donated buffer must
        never be shared between two in-flight calls); lazily allocated
        and HBM-ledger-registered on first use.  Donation accounting:
        the registered bytes are constant for the arena's lifetime —
        in-place aliasing neither allocates nor frees."""
        # kill-matrix site (tests/test_faultmatrix.py): a failure at the
        # arena pop must fail the dispatching batch fast without
        # corrupting the pool or the ledger
        fail_point("arenaTake")
        with self._arena_lock:
            a = self._arenas.pop(n_words, None)
        if a is not None:
            return a
        nt = self.prog.state_size + self.n_aux_rows
        width = 2 * n_words if self.planes else n_words
        a = jnp.zeros((nt, width), jnp.uint32)
        devtel.LEDGER.register("state_arena", int(a.nbytes),
                               generation=self.devtel_generation,
                               name=f"arena:{n_words}")
        return a

    def put_arena(self, n_words: int, state) -> None:
        """Return a call's final state as the bucket's next donated
        arena.  If a concurrent call repooled first, this one is simply
        dropped (registration is keyed by bucket name, so the ledger
        keeps counting exactly one arena per bucket)."""
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

    # hotpath: begin device dispatch (per-batch work stays on device —
    # lint M003 flags host numpy materialization / per-item loops here)
    def checks_device(self, q_idx: np.ndarray, n_words: int,
                      gather_idx: np.ndarray, gather_col: np.ndarray,
                      idx_main, idx_aux, idx_cav=None):
        """Dispatch-only tri-state checks ({0,2}, or {0,1,2} with
        planes): returns (out, tel) — the un-materialized device result
        plus the sweep-trace device array (None when KernelIntrospect
        was off at jit build); the caller owns the blocking readback."""
        run_checks, _, intro = self._pipe_fns(n_words)
        state = self.take_arena(n_words)
        args = [jnp.asarray(q_idx), jnp.asarray(gather_idx),
                jnp.asarray(gather_col), state, idx_main, idx_aux]
        if self.planes:
            res = run_checks(*args, idx_cav)
        else:
            res = run_checks(*args)
        out, x, tel = res if intro else (res[0], res[1], None)
        self.put_arena(n_words, x)
        return out, tel

    def lookup_packed_T_device(self, slot_offset: int, slot_length: int,
                               q_idx: np.ndarray, n_words: int,
                               idx_main, idx_aux, idx_cav=None):
        """Dispatch-only packed lookup, word-transposed on device:
        returns (out, tel) — out the un-materialized
        [n_words, slot_length] uint32 device array (bit b of word row w
        = query column w*32+b; DEFINITE plane when planes are active),
        tel the sweep trace (None when KernelIntrospect was off)."""
        _, run_lookup, intro = self._pipe_fns(n_words)
        state = self.take_arena(n_words)
        if self.planes:
            res = run_lookup(slot_offset, slot_length, jnp.asarray(q_idx),
                             state, idx_main, idx_aux, idx_cav)
        else:
            res = run_lookup(slot_offset, slot_length, jnp.asarray(q_idx),
                             state, idx_main, idx_aux)
        out, x, tel = res if intro else (res[0], res[1], None)
        self.put_arena(n_words, x)
        return out, tel
    # hotpath: end

    def iterations(self, q_idx: np.ndarray, n_words: int, idx_main, idx_aux,
                   idx_cav=None) -> int:
        """Executed while_loop trips to the fixpoint for this batch — the
        bench's roofline probe (bytes-per-iteration x iterations =
        modeled HBM traffic).  Jitted separately; same step function."""
        key = ("iters", n_words)
        jits = self._jits
        fn = jits.get(key)
        if fn is not None:
            devtel.KERNELS.note_jit_hit(n_words * 32)
        else:
            devtel.KERNELS.note_compile(n_words * 32)
            evaluate = self._evaluate(n_words, introspect=True)

            def run(q_idx, idx_main, idx_aux, idx_cav=None):
                _, tel = evaluate(q_idx, idx_main, idx_aux, idx_cav)
                return tel[0]

            fn = timeline.time_first_call(jax.jit(run),
                                          bucket=n_words * 32)
            jits[key] = fn
        if self.planes:
            return int(fn(jnp.asarray(q_idx), idx_main, idx_aux, idx_cav))
        return int(fn(jnp.asarray(q_idx), idx_main, idx_aux))

    # -- host-facing ---------------------------------------------------------

    def checks(self, q_idx: np.ndarray, n_words: int, gather_idx: np.ndarray,
               gather_col: np.ndarray, idx_main, idx_aux,
               idx_cav=None) -> np.ndarray:
        """bool allowed per gather slot — or int {0,1,2} tri-state when the
        plane path is active."""
        run_checks, _, intro = self._fns(n_words)
        gcol = np.asarray(gather_col, np.int64)
        args = [jnp.asarray(q_idx), jnp.asarray(gather_idx),
                jnp.asarray(gcol // 32),
                jnp.asarray((gcol % 32).astype(np.uint32)),
                idx_main, idx_aux]
        out = run_checks(*args, idx_cav) if self.planes else run_checks(*args)
        if intro:
            out, tel = out
            workload.note_sweep("ell", "check", np.asarray(tel))
        if self.planes:
            return np.asarray(out).astype(np.int8)
        return np.asarray(out) != 0

    def lookup_packed(self, slot_offset: int, slot_length: int,
                      q_idx: np.ndarray, n_words: int, idx_main, idx_aux,
                      idx_cav=None) -> np.ndarray:
        """Packed uint32 [slot_length, n_words] allowed words (bit b of
        word w is query column w*32+b; DEFINITE plane when planes are
        active).  The packed form is what the device computes and what the
        host should consume: per-column extraction is a shift/AND/nonzero
        over one word column, 32x less memory traffic than a bool bitmap."""
        _, run_lookup, intro = self._fns(n_words)
        if self.planes:
            out = run_lookup(slot_offset, slot_length,
                             jnp.asarray(q_idx), idx_main, idx_aux, idx_cav)
        else:
            out = run_lookup(slot_offset, slot_length,
                             jnp.asarray(q_idx), idx_main, idx_aux)
        if intro:
            out, tel = out
            workload.note_sweep("ell", "lookup", np.asarray(tel))
        return np.ascontiguousarray(out)

    def lookup(self, slot_offset: int, slot_length: int, q_idx: np.ndarray,
               n_words: int, idx_main, idx_aux, idx_cav=None) -> np.ndarray:
        """bool [slot_length, n_words*32] allowed bitmap (columns beyond the
        real batch are padding; DEFINITE plane when planes are active)."""
        packed = self.lookup_packed(slot_offset, slot_length, q_idx, n_words,
                                    idx_main, idx_aux, idx_cav)
        # uint32 little-endian: bit b of word w lands at column w*32 + b
        return np.unpackbits(packed.view(np.uint8).reshape(slot_length, -1),
                             axis=1, bitorder="little").astype(bool)
