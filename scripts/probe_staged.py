"""A/B the staged (type-topological Gauss-Seidel) evaluate vs Jacobi on
the real multitenant-1m graph: executed sweeps + amortized wall time.

Run:  PYTHONPATH=/root/repo python scripts/probe_staged.py [reps]
"""

import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np

from spicedb_kubeapi_proxy_tpu.models import workloads as wl
from spicedb_kubeapi_proxy_tpu.ops.ell import make_ell_evaluate
from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef, parse_relationship


def main():
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 5
    print("devices:", jax.devices(), flush=True)
    w = wl.multitenant_1m()
    schema = sch.parse_schema(w.schema_text)
    ep = JaxEndpoint(schema)
    ep.store.bulk_load([parse_relationship(r) for r in w.relationships])
    subjects = [SubjectRef("user", w.subjects[i]) for i in range(256)]
    with ep._lock:
        graph = ep._current_graph()
        q_arr, cols, _ = ep._encode_subjects(graph, subjects)
    prog = graph.prog
    rng = prog.slot_range(w.resource_type, w.permission)
    n_words = max(1, len(q_arr) // 32)
    kern = graph.kernel
    stages = kern.stages
    print(f"stages: {len(stages)} ranges {stages[:8]}", flush=True)

    q = jnp.asarray(q_arr)
    results = {}
    for name, st in (("jacobi", None), ("staged", stages)):
        evaluate = make_ell_evaluate(prog, kern.n_aux_rows, n_words,
                                     kern.num_iters,
                                     aux_passes=kern.aux_passes, stages=st,
                                     aux_slices=kern.aux_slices)

        def run_lookup(q_idx, idx_main, idx_aux):
            x = evaluate(q_idx, idx_main, idx_aux)
            return jax.lax.dynamic_slice_in_dim(x, rng[0], rng[1], axis=0)

        fn = jax.jit(run_lookup)
        out = fn(q, graph.dev_main, graph.dev_aux)
        out.block_until_ready()  # compile
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(q, graph.dev_main, graph.dev_aux).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        results[name] = (best, np.asarray(out))
        print(f"{name:8s} evaluate+slice: {best*1e3:7.1f} ms", flush=True)

        # executed sweeps
        from spicedb_kubeapi_proxy_tpu.ops.ell import (
            init_packed_state,
            make_ell_step,
        )
        step = make_ell_step(prog, kern.n_aux_rows,
                             aux_passes=kern.aux_passes, stages=st,
                             aux_slices=kern.aux_slices)

        def count_iters(q_idx, idx_main, idx_aux):
            x0 = init_packed_state(prog, kern.n_aux_rows, q_idx, n_words)

            def cond(s):
                return jnp.logical_and(s[1], s[2] < kern.num_iters)

            def body(s):
                x1 = step(s[0], x0, idx_main, idx_aux)
                return (x1, jnp.any(x1 != s[0]), s[2] + 1)

            return jax.lax.while_loop(cond, body,
                                      (x0, jnp.bool_(True), jnp.int32(0)))[2]

        it = int(jax.jit(count_iters)(q, graph.dev_main, graph.dev_aux))
        print(f"{name:8s} sweeps to fixpoint: {it}", flush=True)

    assert np.array_equal(results["jacobi"][1], results["staged"][1]), \
        "staged result differs from jacobi!"
    print("results identical; speedup "
          f"{results['jacobi'][0]/results['staged'][0]:.2f}x", flush=True)


if __name__ == "__main__":
    main()
