"""Bisect the ELL fixpoint iteration cost on the REAL multitenant-1m
graph (VERDICT r4 item 3: measure before attacking the roofline gap).

Every variant runs ITERS dependent iterations inside one jitted
fori_loop, so per-call dispatch amortizes away and the per-iteration
cost is honest.

Run:  PYTHONPATH=/root/repo python scripts/probe_step_breakdown.py [W] [ITERS]
"""

import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np

from spicedb_kubeapi_proxy_tpu.models import workloads as wl
from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.types import parse_relationship


def main():
    W = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    ITERS = int(sys.argv[2]) if len(sys.argv) > 2 else 50
    print("devices:", jax.devices(), flush=True)
    w = wl.multitenant_1m()
    schema = sch.parse_schema(w.schema_text)
    ep = JaxEndpoint(schema)
    ep.store.bulk_load([parse_relationship(r) for r in w.relationships])
    with ep._lock:
        graph = ep._current_graph()
    prog = graph.prog
    n = prog.state_size
    a = graph.dev_aux.shape[0]
    dead = prog.dead_index
    host_main = graph.host_main
    fanin = (host_main != dead).sum(axis=1)
    nt = n + a
    print(f"n={n} aux={a} K={host_main.shape[1]} W={W} iters={ITERS}",
          flush=True)

    key = jax.random.PRNGKey(0)
    x_init = jax.random.randint(key, (nt, W), 0, 2**31 - 1, dtype=jnp.int32
                                ).astype(jnp.uint32)
    idx_main = graph.dev_main
    idx_aux = graph.dev_aux
    one = jnp.uint32(1)

    def loop(body):
        @jax.jit
        def run(x):
            return jax.lax.fori_loop(0, ITERS, body, x)
        return run

    # each body perturbs x so iterations stay dependent & non-idempotent
    v = {}

    def body_main2(i, x):
        y = x[idx_main[:, 0]] | x[idx_main[:, 1]]
        return jnp.concatenate([y + one, x[n:]], axis=0) \
            if y.shape[0] != x.shape[0] else y + one

    # main table indexes the FULL nt row space but has n rows
    def body_main2_pad(i, x):
        y = x[idx_main[:, 0]] | x[idx_main[:, 1]]
        return jnp.concatenate([y, x[n:]], axis=0) + one
    v["main2_gather_or"] = body_main2_pad

    def body_main1(i, x):
        y = x[idx_main[:, 0]]
        return jnp.concatenate([y, x[n:]], axis=0) + one
    v["main1_gather"] = body_main1

    idx_local = jnp.arange(n, dtype=jnp.int32)

    def body_local(i, x):
        y = x[idx_local]
        return jnp.concatenate([y, x[n:]], axis=0) + one
    v["local_gather"] = body_local

    idx_dead = jnp.full(n, dead, jnp.int32)

    def body_dead(i, x):
        y = x[idx_dead]
        return jnp.concatenate([y, x[n:]], axis=0) + one
    v["dead_gather"] = body_dead

    active_rows = np.nonzero(fanin > 0)[0].astype(np.int32)
    d_active = jnp.asarray(active_rows)
    d_src0 = jnp.asarray(host_main[active_rows, 0].astype(np.int32))
    d_src1 = jnp.asarray(host_main[active_rows,
                                   1 if host_main.shape[1] > 1 else 0
                                   ].astype(np.int32))
    print(f"active rows: {len(active_rows)} ({len(active_rows)/n*100:.0f}%)",
          flush=True)

    def body_active(i, x):
        y = x[d_src0] | x[d_src1]
        return x.at[d_active].max(y) + one
    v["active_gather_scatter"] = body_active

    def body_elementwise(i, x):
        return jnp.maximum(x + one, x_init)
    v["elementwise_max"] = body_elementwise

    from spicedb_kubeapi_proxy_tpu.ops.ell import make_ell_step
    step = make_ell_step(prog, a, aux_passes=graph.kernel.aux_passes)

    def body_full(i, x):
        return step(x, x_init, idx_main, idx_aux) + one
    v["full_step"] = body_full

    models = {"main2_gather_or": 3 * n * W * 4,
              "main1_gather": 2 * n * W * 4,
              "local_gather": 2 * n * W * 4,
              "dead_gather": 2 * n * W * 4,
              "active_gather_scatter": (4 * len(active_rows) + 2 * nt) * W * 4,
              "elementwise_max": 3 * nt * W * 4,
              "full_step": (3 * n + 4 * nt) * W * 4}

    for name, body in v.items():
        run = loop(body)
        out = run(x_init)
        out.block_until_ready()  # compile
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            run(x_init).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        per = best / ITERS
        gbps = models.get(name, 0) / per / 1e9
        print(f"{name:24s} {per*1e3:8.3f} ms/iter  (~{gbps:6.1f} GB/s model)",
              flush=True)


if __name__ == "__main__":
    main()
