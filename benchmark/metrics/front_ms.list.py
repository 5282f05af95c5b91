"""Mean ms per list in the HTTP front end: authentication, rule resolution
and rule matching (the program's authn, resolve and match phase spans)."""
from readers import phase_ms


def read(ctx):
    return phase_ms(ctx, "list", "authn", "resolve", "match")
