"""Seconds from the harness's start to the window: data, the proxy's
start-up (JAX, bootstrap load, graph build, prewarm, compiles) and the
traffic warm-up."""


def read(ctx):
    return ctx.setup_s
