"""Mean ms per namespace relist in the endpoint's execution of its bulk
checks (phase span)."""
from readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "list", "execute")
