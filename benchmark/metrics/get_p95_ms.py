"""Single-pod get (a Check rule) latency, 95th percentile over every get
of the window, from its intended send time (host clock)."""
from readers import latency_ms


def read(ctx):
    return latency_ms(ctx, "get", 95)
