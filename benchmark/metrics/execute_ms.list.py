"""Mean ms per list in the endpoint's execution of its fused lookup:
pack, kernel, readback, extract (phase span)."""
from readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "list", "execute")
