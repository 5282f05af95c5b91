"""Filtered pod list latency, 95th percentile over every list of the
window, from its intended send time (host clock)."""
from readers import latency_ms


def read(ctx):
    return latency_ms(ctx, "list", 95)
