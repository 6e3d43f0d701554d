"""90th percentile of the same hunts' seconds to the first violation
(Python's exclusive quantiles); the window holds 100 hunts or more, so
ten or more lie beyond it."""

import statistics


def read(ctx):
    lat = [r["latency_s"] for r in ctx["records"] if "error" not in r]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=10)[8]
