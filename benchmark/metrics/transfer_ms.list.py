"""Mean ms per fused lookup call in its `transfer` stage: device result
ready to host array in hand, the sweep-trace read and the D2H copy (the
program's authz_dispatch_stage_seconds)."""
from readers import prom_sum


def read(ctx):
    n = prom_sum(ctx, "authz_dispatch_stage_seconds_count",
                 stage="transfer", kind="lookup")
    if n <= 0:
        return None
    return prom_sum(ctx, "authz_dispatch_stage_seconds_sum",
                    stage="transfer", kind="lookup") / n * 1e3
