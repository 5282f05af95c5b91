"""Mean ms per fused check call in its `assemble` stage: the host assembly
of every row's CheckResult, oracle rows included (the program's
authz_dispatch_stage_seconds)."""
from readers import prom_sum


def read(ctx):
    n = prom_sum(ctx, "authz_dispatch_stage_seconds_count",
                 stage="assemble", kind="check")
    if n <= 0:
        return None
    return prom_sum(ctx, "authz_dispatch_stage_seconds_sum",
                    stage="assemble", kind="check") / n * 1e3
