"""Device ms per fused check call: the trace's device time of each
execution of the jitted check entry point (XLA module run_checks)."""
from readers import module_ms


def read(ctx):
    return module_ms(ctx, "run_checks")
