"""A/B probe (real TPU): full packed-word transfer vs device-side
compact extraction (unpack -> per-column nonzero -> flat indices).

The fused 256-subject lookup on multitenant-1m transfers [L=200k, W=8]
uint32 = 6.4 MB (~320 ms at round 5's ~20 MB/s link).  Total set bits
are ~512k -> flat indices = 2 MB.  If the extract-jit + smaller
transfer wins, the endpoint grows a compact lookup path.

Run on the chip:  chiprun -- python scripts/probe_compact_extract.py
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
from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef, parse_relationship


def main():
    print("devices:", jax.devices(), flush=True)
    w = wl.multitenant_1m()
    schema = sch.parse_schema(w.schema_text)
    ep = JaxEndpoint(schema)
    t0 = time.perf_counter()
    ep.store.bulk_load([parse_relationship(r) for r in w.relationships])
    print(f"load {time.perf_counter()-t0:.1f}s", flush=True)

    subjects = [SubjectRef("user", w.subjects[i]) for i in range(256)]
    with ep._lock:
        graph = ep._current_graph()
        q_arr, cols, _ = ep._encode_subjects(graph, subjects)
        snap = graph.snapshot()
    rng = graph.prog.slot_range(w.resource_type, w.permission)
    n_words = max(1, len(q_arr) // 32)
    print(f"slot range {rng}, n_words {n_words}", flush=True)

    t0 = time.perf_counter()
    packed_dev = graph.run_lookup_packed(rng[0], rng[1], q_arr, snap=snap)
    packed_dev = jnp.asarray(packed_dev)
    packed_dev.block_until_ready()
    print(f"first kernel (compile) {time.perf_counter()-t0:.1f}s; "
          f"out {packed_dev.shape} {packed_dev.dtype}", flush=True)

    # -- A: full packed transfer -------------------------------------------
    def fetch_full():
        out = graph.run_lookup_packed(rng[0], rng[1], q_arr, snap=snap)
        return np.ascontiguousarray(out)

    fetch_full()  # warm transfer mode
    for i in range(3):
        t0 = time.perf_counter()
        full = fetch_full()
        ta = time.perf_counter() - t0
        print(f"A full packed fetch: {ta*1e3:.0f} ms "
              f"({full.nbytes/1e6:.1f} MB)", flush=True)

    L, W = full.shape
    C = W * 32

    # ground truth density
    bits = np.unpackbits(full.view(np.uint8), bitorder="little")
    total_set = int(bits.sum())
    print(f"L={L} C={C} total set bits={total_set} "
          f"({total_set/(L*C)*100:.2f}%)", flush=True)

    # -- B: device-side flat extraction ------------------------------------
    main_t, aux_t, cav_t = snap

    def K_bucket(n):
        k = 1 << 16
        while k < n:
            k <<= 1
        return k

    K = K_bucket(int(total_set * 1.25))
    print(f"K bucket = {K}", flush=True)

    @jax.jit
    def extract(sl, K=K):
        # sl [L, W] uint32 -> bools [L, C] -> [C, L] -> flat nonzero
        shifts = jnp.arange(32, dtype=jnp.uint32)
        b = ((sl[:, :, None] >> shifts[None, None, :]) & 1).astype(jnp.bool_)
        b = b.reshape(sl.shape[0], -1)          # [L, C], col = w*32+bit
        counts = b.sum(axis=0, dtype=jnp.int32)  # [C]
        flat = jnp.nonzero(b.T.reshape(-1), size=K,
                           fill_value=sl.shape[0] * b.shape[1])[0]
        return counts, flat.astype(jnp.uint32)

    def fetch_compact():
        sl = graph.run_lookup_packed(rng[0], rng[1], q_arr, snap=snap)
        counts, flat = extract(jnp.asarray(sl))
        return np.asarray(counts), np.asarray(flat)

    t0 = time.perf_counter()
    counts, flat = fetch_compact()
    print(f"B first (compile) {time.perf_counter()-t0:.1f}s", flush=True)
    for i in range(3):
        t0 = time.perf_counter()
        counts, flat = fetch_compact()
        tb = time.perf_counter() - t0
        print(f"B compact fetch: {tb*1e3:.0f} ms "
              f"({(counts.nbytes+flat.nbytes)/1e6:.1f} MB)", flush=True)

    # verify equivalence on a few columns
    total = int(counts.sum())
    assert total == total_set, (total, total_set)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for c in (0, 5, 100, 255):
        got = np.sort(flat[starts[c]:starts[c+1]] % np.uint32(L))
        wcol = np.ascontiguousarray(full[:, c // 32])
        want = np.nonzero((wcol >> np.uint32(c % 32)) & np.uint32(1))[0]
        assert np.array_equal(got, np.sort(want.astype(np.uint32))), c
    print("equivalence ok", flush=True)

    # -- C: pipelining check: dispatch kernel N+1 during N's transfer -------
    t0 = time.perf_counter()
    sl1 = graph.run_lookup_packed(rng[0], rng[1], q_arr, snap=snap)
    c1 = extract(jnp.asarray(sl1))
    sl2 = graph.run_lookup_packed(rng[0], rng[1], q_arr, snap=snap)
    c2 = extract(jnp.asarray(sl2))
    r1 = (np.asarray(c1[0]), np.asarray(c1[1]))
    r2 = (np.asarray(c2[0]), np.asarray(c2[1]))
    tc = time.perf_counter() - t0
    print(f"C two pipelined compact batches: {tc*1e3:.0f} ms total "
          f"({tc/2*1e3:.0f} ms/batch amortized)", flush=True)


if __name__ == "__main__":
    main()
