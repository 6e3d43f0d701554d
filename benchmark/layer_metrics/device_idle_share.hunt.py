"""Idle share of the device in the hunt cells: 1 - busy / traced window,
in %, busy being the union of the device's op intervals in the
profiler's trace of the measured window (trace_reduce.py)."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
