"""Mean ms per fused lookup call in its `extract` stage: host extraction of
each subject's visible ids (the program's authz_dispatch_stage_seconds)."""
from readers import prom_sum


def read(ctx):
    n = prom_sum(ctx, "authz_dispatch_stage_seconds_count",
                 stage="extract", kind="lookup")
    if n <= 0:
        return None
    return prom_sum(ctx, "authz_dispatch_stage_seconds_sum",
                    stage="extract", kind="lookup") / n * 1e3
