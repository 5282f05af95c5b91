"""Microbenchmark: how does the ELL gather iteration cost scale with the
packed word width W and the gather count K on this chip?

Hypothesis under test: XLA pads the minor dimension of [NT, W] uint32
arrays to the 128-lane tile, so at W=8 (batch 256) ~15/16 of every
gather's HBM traffic is padding — i.e. widening the batch to W=128
(batch 4096) is nearly free in device time, and the per-iteration cost is
set by physical (padded) bytes, not logical bytes.

Run on the real TPU:  python scripts/probe_gather_layout.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

N = 1_000_000          # state rows (~ the multitenant-1m graph)
ITERS = 16             # scan length per timed call (amortize dispatch)
REPS = 3


def mem_used(dev):
    stats = dev.memory_stats()
    return stats.get("bytes_in_use", 0) if stats else 0


def make_iter_fn(k: int, iters: int):
    def body(x, _):
        idxs = body.idx  # closed over below
        y = x[idxs[:, 0]]
        for j in range(1, k):
            y = y | x[idxs[:, j]]
        return y | body.x0, None

    def run(x0, idx):
        body.idx = idx
        body.x0 = x0
        x, _ = jax.lax.scan(body, x0, None, length=iters)
        return x

    return jax.jit(run)


def main():
    dev = jax.devices()[0]
    print(f"device: {dev} platform={dev.platform}")
    rng = np.random.default_rng(0)
    idx_host = rng.integers(0, N, size=(N, 8), dtype=np.int32)

    base = mem_used(dev)
    results = {}
    for w in (8, 32, 128):
        x0_host = rng.integers(0, 2**32, size=(N, w), dtype=np.uint32)
        before = mem_used(dev)
        x0 = jnp.asarray(x0_host)
        x0.block_until_ready()
        after = mem_used(dev)
        phys = after - before
        logical = x0_host.nbytes
        print(f"W={w:4d}: logical {logical/1e6:8.1f} MB, device alloc "
              f"{phys/1e6:8.1f} MB  (pad factor {phys/max(logical,1):.2f})")
        for k in (2, 4, 8):
            idx = jnp.asarray(idx_host[:, :k])
            fn = make_iter_fn(k, ITERS)
            fn(x0, idx).block_until_ready()  # compile
            times = []
            for _ in range(REPS):
                t0 = time.perf_counter()
                fn(x0, idx).block_until_ready()
                times.append(time.perf_counter() - t0)
            # per-call dispatch rides on every call; ITERS=16 amortizes it
            per_iter = min(times) / ITERS * 1000
            results[(w, k)] = per_iter
            # bytes read per iter if layout is padded to 128 lanes:
            pad_w = max(w, 128)
            padded = k * N * pad_w * 4
            logical_b = k * N * w * 4
            print(f"   K={k}: {per_iter:8.3f} ms/iter   "
                  f"logical {logical_b/per_iter/1e6:7.1f} GB/s   "
                  f"if-padded {padded/per_iter/1e6:7.1f} GB/s")
        del x0

    print("\nscaling (per-iter time relative to W=8,K=8):")
    ref = results[(8, 8)]
    for (w, k), t in sorted(results.items()):
        print(f"  W={w:4d} K={k}: {t/ref:6.2f}x   "
              f"checks/word-bit ratio {(w/8)/(t/ref):6.2f}x")


if __name__ == "__main__":
    main()
