"""Share of its roofline a fused lookup reaches: the least time the chip
could take for the call's work (roofline.lookup_bytes over the peak HBM
bandwidth) over the device time per call (kernel_ms.list), in %."""
from readers import module_ms, prom_sum
from roofline import lookup_bytes


def read(ctx):
    ms = module_ms(ctx, "run_lookup")
    n = prom_sum(ctx, "authz_batch_useful_lanes_count", kind="lookup")
    if not ms or n <= 0 or not ctx.footprint or not ctx.peaks:
        return None
    lanes = prom_sum(ctx, "authz_batch_useful_lanes_sum", kind="lookup") / n
    least_s = lookup_bytes(ctx.footprint, lanes) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
