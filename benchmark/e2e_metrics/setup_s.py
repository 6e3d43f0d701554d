"""Set-up seconds: process start to the start of the measured window
(JAX start, compile-cache load, model build and the warm-up requests)."""


def read(ctx):
    return ctx["setup_s"]
