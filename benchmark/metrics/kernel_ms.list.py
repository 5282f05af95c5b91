"""Device ms per fused lookup call: the trace's device time of each
execution of the jitted lookup entry point (XLA module run_lookup)."""
from readers import module_ms


def read(ctx):
    return module_ms(ctx, "run_lookup")
