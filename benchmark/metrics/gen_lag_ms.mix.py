"""How late the load generator sent: 99th percentile over the window's
requests of (actual send - intended send), in ms (host clock)."""
from readers import percentile, window


def read(ctx):
    lags = [r.sent - r.intended for r in window(ctx) if r.sent]
    v = percentile(lags, 99)
    return None if v is None else max(0.0, v) * 1e3
