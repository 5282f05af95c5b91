"""Mean outer fixpoint sweeps per fused lookup call over the window (the
program's authz_sweep_iterations histogram, verb lookup)."""
from readers import prom_sum


def read(ctx):
    n = prom_sum(ctx, "authz_sweep_iterations_count", verb="lookup")
    if n <= 0:
        return None
    return prom_sum(ctx, "authz_sweep_iterations_sum", verb="lookup") / n
