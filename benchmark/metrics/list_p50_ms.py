"""Filtered pod list latency, median over every list of the window, from
its intended send time (host clock)."""
from readers import latency_ms


def read(ctx):
    return latency_ms(ctx, "list", 50)
