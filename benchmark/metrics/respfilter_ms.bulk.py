"""Mean ms per list in the response filter: decode, bulk check, keep,
encode (the program's respfilter phase span)."""
from readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "list", "respfilter")
