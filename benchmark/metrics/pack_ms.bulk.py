"""Mean ms per fused check call in its `pack` stage: lock taken to kernel
arguments ready, the query encoding, the per-row loop and the Leopard fill
(the program's authz_dispatch_stage_seconds)."""
from readers import prom_sum


def read(ctx):
    n = prom_sum(ctx, "authz_dispatch_stage_seconds_count",
                 stage="pack", kind="check")
    if n <= 0:
        return None
    return prom_sum(ctx, "authz_dispatch_stage_seconds_sum",
                    stage="pack", kind="check") / n * 1e3
