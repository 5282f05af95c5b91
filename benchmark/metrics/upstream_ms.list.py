"""Mean ms per list in the upstream kube round trip (phase span)."""
from readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "list", "upstream")
