"""A small thread-safe metrics registry, with histograms.

Every checker carries one (the engine host loop writes, the Explorer's
``GET /.metrics`` endpoint and ``Checker.metrics()`` read).  Deliberately
minimal — flat names, numeric values, one lock — because the write side
sits on the engine host loop: a wave record is a handful of dict stores,
never a device sync.  Metric names are part of the observable surface and
documented in docs/OBSERVABILITY.md; changing one is a breaking change to
anything scraping ``/.metrics``.

Histograms are fixed-boundary (Prometheus classic style: cumulative
``le`` buckets plus ``sum``/``count``) so an observation is one bisect
and one integer increment — cheap enough for the always-on fused-loop
vitals — and the snapshot carries a p50/p95/p99 readback estimated by
linear interpolation inside the owning bucket.  The snapshot shape
(``boundaries``/``counts``/``sum``/``count``/``p50``/``p95``/``p99``)
is what obs/prometheus.py renders as ``_bucket``/``_sum``/``_count``
series.
"""

from __future__ import annotations

import threading
from bisect import bisect_left
from typing import Dict, Optional, Sequence, Union

Number = Union[int, float]

# Shared boundary ladders (seconds / counts).  Latency buckets span the
# range of one fused device call — sub-millisecond on the CPU backend up
# to tens of seconds for a large device quantum.
LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)
# Waves-between-growth-events ladder (powers of two, like the geometry).
COUNT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
# Fractions-of-a-buffer ladder (valid density vs the worst-case U
# buffer, hot-table load factor): log-spaced below 10% — where the
# measured densities actually live (docs/OBSERVABILITY.md "Density
# telemetry") — then coarse to 1.0.
FRACTION_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.2, 0.3, 0.4, 0.5, 0.75, 1.0,
)

# The live-vitals subset of ``Checker.metrics()`` that per-job / per-run
# status surfaces embed (serve ``GET /jobs/{id}``, the Explorer's
# ``/.status``): progress + health, small enough to poll without
# shipping the whole snapshot.  One definition so the two surfaces and
# docs/SERVING.md cannot drift.
VITALS_KEYS = (
    "unique_state_count", "state_count", "max_depth", "waves",
    "uniq_per_sec_ema", "waves_per_sec_ema", "table_load_factor",
    "valid_density_ema", "grows", "overflow_retries",
)


def vitals_view(checker):
    """The :data:`VITALS_KEYS` subset of ``checker.metrics()``, or None
    when it cannot be read (a checker mid-teardown whose device buffers
    are already freed must never break a status snapshot).  The one
    extraction both embedding surfaces share."""
    try:
        m = checker.metrics()
    except Exception:
        return None
    return {k: m[k] for k in VITALS_KEYS if k in m}


class Histogram:
    """Fixed-boundary cumulative histogram with quantile readback.

    ``boundaries`` are the bucket upper bounds (ascending); one implicit
    ``+Inf`` bucket catches the tail.  Not self-locking: the owning
    :class:`MetricsRegistry` serializes access under its lock.
    """

    def __init__(self, boundaries: Sequence[float]):
        b = tuple(float(x) for x in boundaries)
        if not b or any(b[i] >= b[i + 1] for i in range(len(b) - 1)):
            raise ValueError(
                "histogram boundaries must be strictly ascending"
            )
        self.boundaries = b
        self.counts = [0] * (len(b) + 1)  # last = +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, value: Number, count: int = 1) -> None:
        """Fold ``count`` observations of ``value`` (the weighted form
        lets the wave loop record one quantum as waves_per_call equal
        per-wave latencies with a single call)."""
        self.counts[bisect_left(self.boundaries, float(value))] += count
        self.sum += float(value) * count
        self.count += count

    def quantile(self, q: float) -> float:
        """Estimated q-quantile (0..1): find the bucket holding the
        rank, interpolate linearly inside it (Prometheus
        ``histogram_quantile`` semantics; the +Inf bucket reports its
        lower bound)."""
        if self.count == 0:
            return 0.0
        rank = q * self.count
        acc = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if acc + c >= rank:
                lo = self.boundaries[i - 1] if i > 0 else 0.0
                if i >= len(self.boundaries):
                    return lo  # +Inf bucket: report its lower bound
                hi = self.boundaries[i]
                return lo + (hi - lo) * max(0.0, rank - acc) / c
            acc += c
        return self.boundaries[-1]

    def snapshot(self) -> dict:
        return {
            "boundaries": list(self.boundaries),
            "counts": list(self.counts),
            "sum": round(self.sum, 6),
            "count": self.count,
            "p50": round(self.quantile(0.50), 6),
            "p95": round(self.quantile(0.95), 6),
            "p99": round(self.quantile(0.99), 6),
        }

    @classmethod
    def from_snapshot(cls, snap: dict) -> "Histogram":
        """Rehydrate a histogram from its :meth:`snapshot` dict — the
        fleet metrics merge (fleet/service.py) folds per-worker
        snapshots shipped through journal ``fleet_worker_vitals``
        events back into live histograms this way."""
        h = cls(snap["boundaries"])
        counts = list(snap.get("counts") or ())
        if len(counts) != len(h.counts):
            raise ValueError(
                "snapshot counts do not match boundaries "
                f"({len(counts)} buckets for {len(h.counts)} expected)"
            )
        h.counts = [int(c) for c in counts]
        h.sum = float(snap.get("sum", 0.0))
        h.count = int(snap.get("count", 0))
        return h

    def merge(self, snap: dict) -> None:
        """Bucket-wise addition of another histogram's snapshot.  Only
        identical boundary ladders merge — the ladders are module
        constants shared by every writer, so a mismatch means two
        incompatible schema versions, surfaced loudly rather than
        silently misbinned."""
        if tuple(float(b) for b in snap["boundaries"]) != self.boundaries:
            raise ValueError("histogram boundary ladders differ")
        counts = list(snap.get("counts") or ())
        if len(counts) != len(self.counts):
            raise ValueError("histogram bucket counts differ")
        for i, c in enumerate(counts):
            self.counts[i] += int(c)
        self.sum += float(snap.get("sum", 0.0))
        self.count += int(snap.get("count", 0))


def merge_histogram_snapshots(*snaps: Dict[str, dict]) -> Dict[str, dict]:
    """Merge several ``{name: histogram-snapshot}`` maps bucket-wise
    into one (quantiles recomputed from the summed buckets).
    Commutative and associative by construction — bucket addition is —
    which the fleet ``/.metrics`` merge relies on: the merged view must
    not depend on worker enumeration order (pinned in
    tests/test_timeline.py)."""
    merged: Dict[str, Histogram] = {}
    for snap_map in snaps:
        for name in sorted(snap_map or {}):
            snap = snap_map[name]
            if not isinstance(snap, dict) or "boundaries" not in snap:
                continue
            h = merged.get(name)
            if h is None:
                merged[name] = Histogram.from_snapshot(snap)
            else:
                h.merge(snap)
    return {n: h.snapshot() for n, h in merged.items()}


class MetricsRegistry:
    """Flat name -> value store with counter and gauge semantics.

    ``inc`` accumulates (counters: monotone over a run), ``set``
    overwrites (gauges: last-value-wins).  ``snapshot()`` returns a plain
    dict copy safe to serialize while writers keep running.
    """

    def __init__(self, **initial: Number):
        self._lock = threading.Lock()
        self._values: Dict[str, Number] = dict(initial)
        self._hists: Dict[str, Histogram] = {}

    def inc(self, name: str, delta: Number = 1) -> None:
        with self._lock:
            self._values[name] = self._values.get(name, 0) + delta

    def set(self, name: str, value: Number) -> None:
        with self._lock:
            self._values[name] = value

    def update(self, **values: Number) -> None:
        """Set several gauges under one lock acquisition (the per-wave
        hot path writes ~10 values)."""
        with self._lock:
            self._values.update(values)

    def get(self, name: str, default: Optional[Number] = None):
        with self._lock:
            return self._values.get(name, default)

    def observe(
        self,
        name: str,
        value: Number,
        count: int = 1,
        boundaries: Sequence[float] = LATENCY_BUCKETS,
    ) -> None:
        """Record ``value`` into the named histogram, creating it with
        ``boundaries`` on first use (later calls keep the original
        boundaries — one ladder per name for the life of the
        registry)."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(boundaries)
            h.observe(value, count)

    def histogram(self, name: str) -> Optional[Histogram]:
        with self._lock:
            return self._hists.get(name)

    def snapshot(self) -> Dict[str, Number]:
        with self._lock:
            return dict(self._values)

    def snapshot_histograms(self) -> Dict[str, dict]:
        """Plain-dict copies of every histogram (the ``histograms`` key
        of ``Checker.metrics()``; obs/prometheus.py renders them as
        ``_bucket``/``_sum``/``_count`` series)."""
        with self._lock:
            return {n: h.snapshot() for n, h in self._hists.items()}


# Process-global registry for counters that outlive any one checker —
# the compiled-program cache's hit/miss counters in particular
# (parallel/wave_common.cached_program), which are the measured evidence
# behind the serving layer's warm-start story: a second identical job
# reuses the first job's compiled programs, so its hit counter moves and
# its warmup does not (docs/SERVING.md).  Served by the check service's
# aggregated ``GET /.metrics`` (serve/server.py).
GLOBAL = MetricsRegistry()


def global_metrics() -> MetricsRegistry:
    return GLOBAL
