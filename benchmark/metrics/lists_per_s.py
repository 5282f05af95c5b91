"""Filtered lists completed per second: lists answered inside the window,
whenever sent, over the window's length (host clock)."""


def read(ctx):
    t1 = ctx.t0 + ctx.window_s
    n = sum(1 for r in ctx.records if r.kind == "list" and r.done
            and ctx.t0 <= r.done <= t1)
    return n / ctx.window_s if n else None
