"""Seconds JAX spent in backend compiles during set-up, a load from the
persistent cache included: the harness's own listener on JAX's
compile-duration events, not the program's counter."""


def read(ctx):
    clock = ctx["compile"]
    if not clock.count.get("setup"):
        return None
    return clock.sec["setup"]
