"""Mean ms per get waiting in the dispatcher's queue (phase span)."""
from readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "get", "queue_wait")
