"""Share of the traced stretch in which no operation ran on the device
(1 - union of device op intervals / stretch), in %, namespace relists."""
from readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
