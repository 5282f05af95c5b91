#!/usr/bin/env python
"""Differential fuzz smoke: the fixed-seed gate x replication-role matrix.

check.sh mode (default): replays 31 FIXED seeds — 25 mapped onto the
3 gate-combos x 3 replication-roles matrix (every cell covered >= 2x
across the set; kernels alternate ell/segment), plus 2 `sharded2`
cells replaying through a router over TWO partition leaders
(spicedb/sharding, schema-derived co-location-valid map, off/full
gates), plus 2 `mesh` cells replaying on a 2x2 virtual-device mesh
endpoint differentially checked against a single-device endpoint
(parallel/sharding.py, off/full gates), plus 2 `leopard` cells
replaying a nested-groups-biased case on a Leopard-indexed endpoint
differentially checked against a gate-off endpoint (ops/leopard.py,
off/full gates) — asserting ZERO jax://-vs-oracle divergences.  Deterministic: schemas, delta
streams, clocks, and queries all derive from the seed; wall time is the
only thing that varies.  A divergence shrinks to a self-contained repro
artifact (docs/fuzzing.md) and fails the run with its path + seed line.

Cost control (the smoke time box):

- two worker processes (spawned, jax-safe) split the seed set;
- `--xla_backend_optimization_level=0` (tiny graphs need fast COMPILE,
  not fast code) via a re-exec before jax initializes;
- the persistent jax compilation cache every entry point shares
  (utils/device.configure_compile_cache) keyed by HLO, so repeat runs
  (the common check.sh case) skip XLA entirely;
- the smoke case profile (driver.build_case(smoke=True)): bounded
  schema size, short streams, end-state checkpoints.

Other modes:

  --budget-seconds N   open-ended random search (full-depth profile,
                       every checkpoint compared, randomized kernels)
                       starting at --budget-start, until the budget
                       expires; exits nonzero on the first divergence
                       with a shrunken artifact.
  --replay ART.json    re-run a repro artifact's exact cell; exit 1
                       while it still diverges, 0 once fixed.
  --mutation MUT       self-check: inject a deliberate compiler bug
                       (fuzz/mutations.py) and verify the fixed seed
                       set CATCHES it and shrinks it (exit 0 = caught).

Usage: python scripts/fuzz_smoke.py [--time-box 90] [--seeds N]
       [--workers 2] [--budget-seconds N] [--replay path] [--mutation m]
"""

import argparse
import concurrent.futures
import multiprocessing
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("_FUZZ_SMOKE_REEXEC") != "1":
    # compile-speed flags must be in place before jax initializes its
    # backend — re-exec with them; the forced host device count gives
    # the `mesh` cells their 2x2 virtual mesh (a no-op for every other
    # cell)
    env = dict(os.environ, _FUZZ_SMOKE_REEXEC="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_backend_optimization_level=0"
                          + " --xla_force_host_platform_device_count=8"))
    os.execve(sys.executable, [sys.executable] + sys.argv, env)

ARTIFACT_DIR = os.environ.get("FUZZ_ARTIFACT_DIR", "/tmp/authz_fuzz")


def _enable_compile_cache() -> None:
    from spicedb_kubeapi_proxy_tpu.utils.device import (
        configure_compile_cache,
    )
    configure_compile_cache()
    import jax
    # the smoke's graphs are tiny: cache every compile, however fast
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def cell_for(seed: int) -> tuple:
    """The fixed (gates, role, kernel) cell a smoke seed lands in —
    delegated to fuzz.driver.smoke_cell_for so tests and the smoke
    agree on what 'the fixed seed set' means."""
    from spicedb_kubeapi_proxy_tpu.fuzz.driver import smoke_cell_for
    return smoke_cell_for(seed)


def _worker_init() -> None:
    _enable_compile_cache()
    import spicedb_kubeapi_proxy_tpu.fuzz  # noqa: F401  (pay import once)


def _run_cell(seed: int) -> dict:
    from spicedb_kubeapi_proxy_tpu.fuzz import build_case, run_case
    gates, role, kernel = cell_for(seed)
    t0 = time.time()
    kw = {}
    if role == "leopard":
        # leopard cells replay the nested-groups shape at the smoke
        # size cap, so membership-only fragments actually materialize
        from spicedb_kubeapi_proxy_tpu.fuzz.scenarios import (
            NESTED_GROUPS_SMOKE_BIAS)
        kw["schema_bias"] = NESTED_GROUPS_SMOKE_BIAS
    case = build_case(seed, smoke=True, kernel=kernel, **kw)
    divs = run_case(case, gates=gates, role=role, checkpoints="final")
    return {"seed": seed, "gates": gates, "role": role, "kernel": kernel,
            "elapsed": time.time() - t0,
            "divergences": [d.line() for d in divs]}


def _shrink_and_report(seed: int, smoke: bool = True,
                       checkpoints: str = "final") -> int:
    """Slow path after a failure: re-find the divergence in-process,
    shrink it, write the artifact; returns the delta count."""
    from spicedb_kubeapi_proxy_tpu.fuzz import build_case, run_case
    from spicedb_kubeapi_proxy_tpu.fuzz.shrink import (
        delta_count, shrink_case, write_artifact)
    gates, role, kernel = cell_for(seed)
    kw = {}
    if role == "leopard":
        from spicedb_kubeapi_proxy_tpu.fuzz.scenarios import (
            NESTED_GROUPS_SMOKE_BIAS)
        kw["schema_bias"] = NESTED_GROUPS_SMOKE_BIAS
    case = build_case(seed, smoke=smoke, kernel=kernel, **kw)
    divs = run_case(case, gates=gates, role=role, checkpoints=checkpoints,
                    stop_on_first=True)
    if not divs:
        print(f"seed {seed}: divergence did not reproduce in-process")
        return -1
    d = divs[0]
    print(d.line())
    small = shrink_case(case, d)
    n = delta_count(small)
    path = os.path.join(ARTIFACT_DIR, f"fuzz-seed{seed}-{gates}-{role}.json")
    write_artifact(path, small, d)
    print(f"shrunk to {n} deltas -> {path}")
    return n


def run_fixed_set(n_seeds: int, workers: int, time_box: float) -> int:
    t0 = time.time()
    seeds = list(range(n_seeds))
    cells_hit = {}
    failed = []
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=workers, mp_context=ctx,
            initializer=_worker_init) as pool:
        for res in pool.map(_run_cell, seeds):
            gates, role = res["gates"], res["role"]
            cells_hit[(gates, role)] = cells_hit.get((gates, role), 0) + 1
            status = "ok" if not res["divergences"] else "DIVERGED"
            print(f"seed {res['seed']:3d} [{gates:5s}/{role:9s}/"
                  f"{res['kernel']:7s}] {status} in "
                  f"{res['elapsed']:4.1f}s")
            if res["divergences"]:
                failed.append(res)
    elapsed = time.time() - t0
    # matrix-coverage tripwire (a real error path, not an assert: it
    # must survive python -O and scale with --seeds).  The expectation
    # is INDEPENDENT of smoke_cell_for — derived from the documented
    # walk (seeds 0..24 = classic 3x3 matrix, 25..26 = sharded2 cells
    # alternating off/full, 27..28 = mesh cells alternating off/full,
    # >= 29 = leopard cells alternating off/full) — so a regression in
    # the seed->cell map itself trips here instead of validating its
    # own output.
    n_classic = min(n_seeds, 25)
    n_sharded = min(max(0, n_seeds - 25), 2)
    n_mesh = min(max(0, n_seeds - 27), 2)
    n_leopard = max(0, n_seeds - 29)
    classic_hit = {c: v for c, v in cells_hit.items()
                   if c[1] not in ("sharded2", "mesh", "leopard")}
    sharded_hit = {c: v for c, v in cells_hit.items()
                   if c[1] == "sharded2"}
    mesh_hit = {c: v for c, v in cells_hit.items()
                if c[1] == "mesh"}
    leopard_hit = {c: v for c, v in cells_hit.items()
                   if c[1] == "leopard"}
    want_sharded = {k: v for k, v in (
        (("off", "sharded2"), (n_sharded + 1) // 2),
        (("full", "sharded2"), n_sharded // 2)) if v}
    want_mesh = {k: v for k, v in (
        (("off", "mesh"), (n_mesh + 1) // 2),
        (("full", "mesh"), n_mesh // 2)) if v}
    want_leopard = {k: v for k, v in (
        (("off", "leopard"), (n_leopard + 1) // 2),
        (("full", "leopard"), n_leopard // 2)) if v}
    if (len(classic_hit) != min(9, n_classic)
            or sum(classic_hit.values()) != n_classic
            or any(v < max(1, n_classic // 9)
                   for v in classic_hit.values())
            or sharded_hit != want_sharded
            or mesh_hit != want_mesh
            or leopard_hit != want_leopard):
        print(f"fuzz smoke: matrix coverage hole at --seeds {n_seeds}: "
              f"classic {dict(classic_hit)}, sharded {dict(sharded_hit)}, "
              f"mesh {dict(mesh_hit)}, leopard {dict(leopard_hit)} "
              f"(want {min(9, n_classic)} classic cells x >= "
              f"{max(1, n_classic // 9)}, sharded {dict(want_sharded)}, "
              f"mesh {dict(want_mesh)}, leopard {dict(want_leopard)})")
        return 1
    if failed:
        for res in failed:
            for line in res["divergences"]:
                print(line)
            _shrink_and_report(res["seed"])
        print(f"fuzz smoke: {len(failed)}/{n_seeds} seeds DIVERGED "
              f"in {elapsed:.1f}s")
        return 1
    print(f"fuzz smoke: {n_seeds} seeds x 3 gate combos x 3 replication "
          f"roles (+ {n_sharded} sharded2 router cells, + {n_mesh} mesh "
          f"cells, + {n_leopard} leopard cells) AGREE in {elapsed:.1f}s")
    if elapsed > time_box:
        print(f"fuzz smoke: exceeded the {time_box:.0f}s time box")
        return 1
    return 0


def run_budgeted(budget_s: float, start_seed: int, scenario: str = "") -> int:
    """Open-ended search: full-depth cases, every checkpoint compared,
    randomized cells — until the budget expires.  `scenario` steers the
    generators with a fuzz/scenarios.py bias profile."""
    _enable_compile_cache()
    from spicedb_kubeapi_proxy_tpu.fuzz import build_case, run_case
    from spicedb_kubeapi_proxy_tpu.fuzz.scenarios import SCENARIO_BIASES
    from spicedb_kubeapi_proxy_tpu.fuzz.shrink import (
        delta_count, shrink_case, write_artifact)
    from spicedb_kubeapi_proxy_tpu.fuzz.driver import (
        ALL_ROLES, GATE_COMBOS, SMOKE_KERNELS)
    bias_kw = {}
    if scenario:
        sb, db = SCENARIO_BIASES[scenario]
        bias_kw = {"schema_bias": sb, "delta_bias": db}
    t0 = time.time()
    seed = start_seed
    n = 0
    while time.time() - t0 < budget_s:
        gates = tuple(GATE_COMBOS)[seed % 3]
        role = ALL_ROLES[(seed // 3) % len(ALL_ROLES)]
        kernel = SMOKE_KERNELS[(seed // 9) % 2]
        if role == "mesh":
            kernel = "ell"  # the mesh path requires the ell kernel
        case = build_case(seed, kernel=kernel, **bias_kw)
        divs = run_case(case, gates=gates, role=role, checkpoints="every",
                        stop_on_first=True)
        n += 1
        print(f"seed {seed} [{gates}/{role}/{kernel}] "
              f"{'ok' if not divs else 'DIVERGED'} "
              f"({time.time() - t0:.0f}s/{budget_s:.0f}s)")
        if divs:
            d = divs[0]
            print(d.line())
            small = shrink_case(case, d)
            path = os.path.join(
                ARTIFACT_DIR, f"fuzz-seed{seed}-{gates}-{role}.json")
            write_artifact(path, small, d)
            print(f"shrunk to {delta_count(small)} deltas -> {path}")
            return 1
        seed += 1
    print(f"budgeted fuzz: {n} cells agree in {time.time() - t0:.0f}s")
    return 0


def run_replay(path: str) -> int:
    _enable_compile_cache()
    from spicedb_kubeapi_proxy_tpu.fuzz import replay_artifact
    divs = replay_artifact(path)
    if divs:
        for d in divs:
            print(d.line())
        print(f"replay {path}: still diverges")
        return 1
    print(f"replay {path}: agrees (fixed)")
    return 0


def run_mutation_check(name: str, n_seeds: int) -> int:
    """Harness self-check: with a deliberately broken device compiler,
    the fixed seed set must catch a divergence and shrink it small."""
    _enable_compile_cache()
    from spicedb_kubeapi_proxy_tpu.fuzz import build_case, run_case
    from spicedb_kubeapi_proxy_tpu.fuzz.mutations import MUTATIONS
    from spicedb_kubeapi_proxy_tpu.fuzz.shrink import (
        delta_count, shrink_case, write_artifact)
    with MUTATIONS[name]():
        for seed in range(n_seeds):
            gates, role, kernel = cell_for(seed)
            case = build_case(seed, smoke=True, kernel=kernel)
            divs = run_case(case, gates=gates, role=role,
                            checkpoints="final", stop_on_first=True)
            print(f"seed {seed} [{gates}/{role}/{kernel}] "
                  f"{'ok' if not divs else 'CAUGHT'}")
            if not divs:
                continue
            d = divs[0]
            print(d.line())
            small = shrink_case(case, d)
            n = delta_count(small)
            path = os.path.join(ARTIFACT_DIR, f"mutation-{name}.json")
            write_artifact(path, small, d)
            print(f"mutation {name!r}: caught at seed {seed}, shrunk to "
                  f"{n} deltas -> {path}")
            return 0
    print(f"mutation {name!r}: NOT caught by the fixed seed set")
    return 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=31,
                    help="seeds 0..24 walk the classic 3x3 gate x role "
                         "matrix; seeds 25..26 are the appended sharded2 "
                         "(2-partition-leader router) cells; seeds 27..28 "
                         "are the mesh (2x2 virtual-device) cells; seeds "
                         "29+ are the leopard (indexed vs gate-off) cells")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--time-box", type=float, default=90.0,
                    help="hard wall-clock bound for the fixed set "
                         "(generous vs the ~15s warm-cache typical run: "
                         "cold XLA caches + CI contention headroom)")
    ap.add_argument("--budget-seconds", type=float, default=0.0)
    ap.add_argument("--budget-start", type=int, default=1000)
    ap.add_argument("--scenario", default="", choices=(
        "", "caveat-heavy", "wildcard-public", "ephemeral-grants",
        "nested-groups"),
        help="steer the budgeted search with a scenario bias profile")
    ap.add_argument("--replay", default="")
    ap.add_argument("--mutation", default="",
                    help="inject a named mutation (fuzz/mutations.py) "
                         "and require the fixed set to catch it")
    args = ap.parse_args()
    if args.replay:
        return run_replay(args.replay)
    if args.mutation:
        return run_mutation_check(args.mutation, args.seeds)
    if args.budget_seconds > 0:
        return run_budgeted(args.budget_seconds, args.budget_start,
                            scenario=args.scenario)
    return run_fixed_set(args.seeds, args.workers, args.time_box)


if __name__ == "__main__":
    sys.exit(main())
