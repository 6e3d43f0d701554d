"""Unique states per second: the unique states of every whole check in
the window, over the window's wall seconds."""


def read(ctx):
    done = [r for r in ctx["records"] if "error" not in r]
    if not done or ctx["window_s"] <= 0:
        return None
    return sum(r["unique"] for r in done) / ctx["window_s"]
