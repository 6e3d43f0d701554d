"""Median seconds to the first violation over every hunt of the window,
from the spawn to the counterexample's path in hand."""

import statistics


def read(ctx):
    lat = [r["latency_s"] for r in ctx["records"] if "error" not in r]
    return statistics.median(lat) if lat else None
