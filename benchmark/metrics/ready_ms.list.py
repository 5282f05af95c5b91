"""Mean ms per fused lookup call in its `ready` stage: launch end to device
result ready, so device-stream queueing behind other calls plus the call's
own kernel (the program's authz_dispatch_stage_seconds)."""
from readers import prom_sum


def read(ctx):
    n = prom_sum(ctx, "authz_dispatch_stage_seconds_count",
                 stage="ready", kind="lookup")
    if n <= 0:
        return None
    return prom_sum(ctx, "authz_dispatch_stage_seconds_sum",
                    stage="ready", kind="lookup") / n * 1e3
