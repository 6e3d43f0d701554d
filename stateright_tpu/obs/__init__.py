"""Observability for the TPU engines: metrics, trace spans, roofline.

Small pieces, composed by the wavefront engines (parallel/wavefront.py,
parallel/sharded.py, tiered/) and surfaced through the runtime journal,
the Explorer's ``GET /.metrics`` endpoint, and the CLI's ``check-tpu``
flags (``--xprof-dir``, ``--tiered --trace``):

- :mod:`.metrics` — a thread-safe name->value registry every checker
  carries; counters, gauges, and fixed-boundary histograms (with
  p50/p95/p99 readback) the host loop updates from the scalars it
  already reads back (no extra device syncs).
- :mod:`.prometheus` — the standard text exposition of any metrics
  dict (``GET /.metrics?format=prometheus`` on the Explorer and the
  checking service) plus a minimal validating parser for CI.
- :mod:`.report` — journal-derived run/service reports (phase
  breakdown, bottleneck_phase, throughput curve, restart timeline, job
  spans) and the cross-round ``BENCH_r*.json`` trajectory with
  regression flagging; backs the ``report`` CLI verb.
- :mod:`.trace` — per-wave phase-timed trace spans: the tiered engine
  runs its waves as separately-dispatched phase programs (step kernel /
  canon+fingerprint / dedup-sort+probe / cold probe / append / host
  readback), and with ``trace=True`` records seconds + modeled bytes
  per phase.
- :mod:`.roofline` — the per-device-peak table and the bytes-touched
  model that reduce a wave's phase records into ``hbm_util_frac``
  (fraction of the device's peak HBM bandwidth the wave achieved).
- :mod:`.timeline` — the unified timeline: host-tail span decomposition
  of the fused loop's per-quantum host work (``host_span`` journal
  events + per-phase histograms), the Chrome trace-event exporter that
  folds run/serve/fleet journals — multi-worker fleets included — onto
  one clock-aligned Perfetto view (``timeline export``), and the JAX
  profiler hooks (``check-tpu --xprof-dir``).

Schema and methodology: docs/OBSERVABILITY.md.
"""

from .metrics import Histogram, MetricsRegistry, merge_histogram_snapshots
from .prometheus import parse_prometheus, render_prometheus
from .report import analyze_journal, bench_trajectory, render_markdown
from .timeline import (
    SpanRecorder,
    build_trace,
    export_timeline,
    host_share_of,
    host_tail_sums,
    validate_trace,
)
from .roofline import (
    DEVICE_PEAKS,
    hbm_util_frac,
    peaks_for_device,
    probe_bytes,
    sort_bytes,
)
from .trace import WaveTracer

__all__ = [
    "DEVICE_PEAKS",
    "Histogram",
    "MetricsRegistry",
    "SpanRecorder",
    "WaveTracer",
    "analyze_journal",
    "bench_trajectory",
    "build_trace",
    "export_timeline",
    "hbm_util_frac",
    "host_share_of",
    "host_tail_sums",
    "merge_histogram_snapshots",
    "parse_prometheus",
    "peaks_for_device",
    "probe_bytes",
    "render_markdown",
    "render_prometheus",
    "sort_bytes",
    "validate_trace",
]
