"""Roofline accounting: per-device peaks and the bytes-touched model.

The wave loop is bandwidth-bound, not compute-bound: every phase is
sorts, gathers, scatters, and block copies over uint32 planes, with a few
integer ALU ops per word.  So the roofline that matters is the HBM one —
``hbm_util_frac`` is the fraction of the device's peak HBM bandwidth the
measured wave achieved, computed as ``modeled bytes touched / (measured
seconds x peak bytes/sec)``.

The byte model is ANALYTIC, derived from the engine's static shapes and
the per-wave counts the host reads back anyway — TPUs expose no
per-kernel DRAM counters through JAX, and the model is what lets the
breakdown say *which phase* to optimize (a sort pass at 40% of peak is
healthy; a probe round at 2% says the gathers dominate).  Modeling
choices, documented here because `hbm_util_frac` inherits them:

- the XLA TPU sort is modeled as a bitonic network: ``k(k+1)/2`` passes
  for ``k = ceil(log2(lanes))``, each pass streaming every key plane
  once in and once out.  This is an upper-bound pass count; real XLA
  sorts fuse stages, so sort bytes (and util) may overestimate by a
  small constant factor;
- random-index gathers/scatters are charged their payload bytes only
  (lanes x word), not the touched-cacheline amplification — on TPU the
  serialization cost of scatter shows up as *time*, which the measured
  denominator already carries;
- phase wall-times come from ``block_until_ready`` around each phase
  dispatch, so they include per-dispatch launch overhead — the tiered
  loop is un-fused, and its utilization reads LOWER than the in-HBM
  engine's fused loop achieves.  The breakdown's
  *relative* shape is the signal; docs/OBSERVABILITY.md discusses the
  bias.

Peaks are public per-chip numbers keyed by JAX ``device_kind``.  The
CPU backend the tests run on has an explicit row flagged ``estimated``,
so a util number from it can never masquerade as a measured-hardware
claim; any other device missing from the table is an error, not a
default.
"""

from __future__ import annotations

import math
from typing import Dict

# Public per-chip peak HBM bandwidth, bytes/sec.  Keys are matched as
# case-insensitive substrings of jax's ``device.device_kind``.
DEVICE_PEAKS: Dict[str, float] = {
    "v6e": 1.64e12,      # Trillium: 1,640 GB/s
    "v5p": 2.765e12,     # 2,765 GB/s
    "v5e": 8.19e11,      # 819 GB/s
    "v5 lite": 8.19e11,  # v5e's device_kind spells it out
    "v4": 1.228e12,      # 1,228 GB/s
    "v3": 9.0e11,        # 900 GB/s
    "v2": 7.0e11,        # 700 GB/s
    # Not a published peak: a conservative host-DRAM figure, so the ratio
    # stays meaningful on the virtual CPU meshes the tests run on.
    "cpu": 2.0e10,       # 20 GB/s, estimated
}


def peaks_for_device(device) -> Dict:
    """Peak table entry for a JAX device: ``{"device_kind", "platform",
    "hbm_bytes_per_sec", "estimated"}``.  ``estimated`` marks the CPU
    row — util fractions derived from it are labeled as such everywhere
    they surface.  Raises ``KeyError`` for a device kind the table does
    not hold."""
    kind = str(getattr(device, "device_kind", "") or "")
    platform = str(getattr(device, "platform", "") or "")
    low = kind.lower()
    for key, peak in DEVICE_PEAKS.items():
        if key in low:
            return {
                "device_kind": kind,
                "platform": platform,
                "hbm_bytes_per_sec": peak,
                "estimated": key == "cpu",
            }
    raise KeyError(
        f"no peak HBM bandwidth for device kind {kind!r} (platform "
        f"{platform!r}); add it to DEVICE_PEAKS with its source"
    )


def hbm_util_frac(bytes_touched: float, seconds: float,
                  peak_bytes_per_sec: float) -> float:
    """Achieved fraction of peak HBM bandwidth; 0.0 for degenerate
    inputs (a wave too fast to time is reported as unknown-low, never
    infinite)."""
    if seconds <= 0 or peak_bytes_per_sec <= 0:
        return 0.0
    return float(bytes_touched) / (seconds * peak_bytes_per_sec)


def sort_passes(lanes: int) -> int:
    """Bitonic-network pass count for a ``lanes``-wide sort."""
    if lanes <= 1:
        return 0
    k = max(1, math.ceil(math.log2(lanes)))
    return k * (k + 1) // 2


def sort_bytes(lanes: int, planes: int, word_bytes: int = 4) -> int:
    """Bytes streamed by sorting ``planes`` co-sorted u32 planes of
    ``lanes`` elements: every pass reads and writes every plane once."""
    return 2 * sort_passes(lanes) * planes * lanes * word_bytes


def probe_bytes(lanes: int, rounds: int, word_bytes: int = 4) -> int:
    """Bytes touched by ``rounds`` claim-plane probe rounds over a
    ``lanes``-wide key buffer (parallel/hashset.py stage 2/3): per round
    each unresolved lane gathers both key planes (2 reads), contends the
    claim plane (1 scatter + 1 gather-back), and winners scatter both key
    words (2 writes) — 6 lane-words a round, charging every lane as
    unresolved (an upper bound; resolved lanes drop out of later
    rounds)."""
    return 6 * max(0, rounds) * lanes * word_bytes


def copy_bytes(lanes: int, width: int, word_bytes: int = 4) -> int:
    """Read+write bytes of moving ``lanes`` rows of ``width`` u32 words
    (gathers and block appends both stream payload in and out)."""
    return 2 * lanes * width * word_bytes
