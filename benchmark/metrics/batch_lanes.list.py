"""Useful lanes per fused lookup call over the window (the program's
authz_batch_useful_lanes counter, kind lookup)."""
from readers import prom_sum


def read(ctx):
    n = prom_sum(ctx, "authz_batch_useful_lanes_count", kind="lookup")
    if n <= 0:
        return None
    return prom_sum(ctx, "authz_batch_useful_lanes_sum", kind="lookup") / n
