"""Mean ms per namespace relist in the upstream kube round trip of a
3,000-pod list (phase span)."""
from readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "list", "upstream")
