"""Per-wave phase-timed trace spans for the tiered engine.

The tiered engine (tiered/engine.py) runs each wave as separately
dispatched phase programs; with ``spawn_tpu_tiered(trace=True)`` it
times each phase and hands the tracer one ``(phase -> seconds, phase ->
bytes)`` record per wave.  The tracer:

- enriches the engine's journal ``wave`` event with ``wave_breakdown``
  (seconds per phase), ``bytes`` (modeled bytes touched per phase) and
  ``hbm_util_frac`` for that wave;
- accumulates run totals, reduced by :meth:`WaveTracer.summary` into the
  shape ``trace_summary()`` and ``metrics()`` report.

Phase names are part of the observable surface (docs/OBSERVABILITY.md):

====================  =======================================================
``step``              chunk slice + step kernel (successor expansion,
                      property conds, valid-lane compaction)
``canon``             canonicalization (identity when symmetry is off) +
                      fingerprinting of the candidate buffer
``dedup``             sort pre-dedup + claim-plane probe rounds + table
                      insert (parallel/hashset.py)
``append``            row/parent/ebits block appends at the log tail
``cold_probe``        the pre-commit merge-join against the evicted runs
                      (host windowing + device filter) — host-classed
``readback``          host-side scalar sync — host time, excluded from
                      HBM util
====================  =======================================================
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from .roofline import hbm_util_frac, peaks_for_device

# Canonical display order.  ``cold_probe`` is the tiered engine's
# pre-commit merge-join against the evicted runs (host searchsorted +
# device window filter).
PHASE_ORDER = (
    "step", "canon", "dedup", "cold_probe", "append", "readback",
)

# Host-side phases: excluded from the HBM-utilization denominator (they
# are not device time) but included in wave/call wall time.  Public so
# consumers picking a "bottleneck" phase (obs/report.py) can exclude the
# trace instrumentation's own cost the same way.
HOST_PHASES = frozenset({"readback", "cold_probe"})
_HOST_PHASES = HOST_PHASES


class WaveTracer:
    """Accumulates per-wave phase records into run totals.

    One engine host loop writes (``record_wave``); ``summary()`` may be
    called concurrently from any thread — the Explorer's ``/.metrics``
    handler polls it mid-run — so both sides serialize on an internal
    lock (a per-wave lock acquisition is noise next to a device
    dispatch).
    """

    def __init__(self, device, engine: str):
        self.engine = engine
        self.peaks = peaks_for_device(device)
        self.waves = 0
        self.phase_sec: Dict[str, float] = {}
        self.phase_bytes: Dict[str, int] = {}
        self._extra_totals: Dict[str, float] = {}
        self._lock = threading.Lock()

    def record_wave(
        self,
        phases: Dict[str, float],
        bytes_touched: Optional[Dict[str, int]] = None,
        **extra_counters: float,
    ) -> dict:
        """Fold one wave's record into the totals; returns the journal
        enrichment for that wave (``wave_breakdown`` / ``bytes`` /
        ``hbm_util_frac``).  ``extra_counters`` accumulate into the
        summary (e.g. a wave's probe rounds)."""
        bytes_touched = bytes_touched or {}
        with self._lock:
            self.waves += 1
            for name, sec in phases.items():
                self.phase_sec[name] = self.phase_sec.get(name, 0.0) + sec
            for name, b in bytes_touched.items():
                self.phase_bytes[name] = (
                    self.phase_bytes.get(name, 0) + int(b)
                )
            for name, v in extra_counters.items():
                self._extra_totals[name] = (
                    self._extra_totals.get(name, 0) + v
                )
        device_sec = sum(
            s for n, s in phases.items() if n not in _HOST_PHASES
        )
        util = hbm_util_frac(
            sum(bytes_touched.values()), device_sec,
            self.peaks["hbm_bytes_per_sec"],
        )
        record = {
            "wave_breakdown": {
                n: round(phases[n], 6)
                for n in PHASE_ORDER if n in phases
            },
            "hbm_util_frac": round(util, 6),
        }
        if bytes_touched:
            record["bytes"] = {
                n: int(bytes_touched[n])
                for n in PHASE_ORDER if n in bytes_touched
            }
        record.update(
            {k: round(v, 6) if isinstance(v, float) else v
             for k, v in extra_counters.items()}
        )
        return record

    def summary(self) -> dict:
        """Run-total reduction: phase seconds (and each phase's fraction
        of traced wall time), modeled bytes, and the aggregate
        ``hbm_util_frac`` over device phases.  Safe to call from any
        thread mid-run (snapshots under the tracer lock)."""
        with self._lock:
            waves = self.waves
            phase_sec = dict(self.phase_sec)
            phase_bytes = dict(self.phase_bytes)
            extra = dict(self._extra_totals)
        total = sum(phase_sec.values())
        device_sec = sum(
            s for n, s in phase_sec.items() if n not in _HOST_PHASES
        )
        out = {
            "engine": self.engine,
            "traced_waves": waves,
            "traced_sec": round(total, 4),
            "wave_breakdown": {
                n: round(phase_sec[n], 4)
                for n in PHASE_ORDER if n in phase_sec
            },
            "wave_breakdown_frac": {
                n: round(phase_sec[n] / total, 4)
                for n in PHASE_ORDER if n in phase_sec
            } if total > 0 else {},
            "bytes": {
                n: int(phase_bytes[n])
                for n in PHASE_ORDER if n in phase_bytes
            },
            "hbm_util_frac": round(
                hbm_util_frac(
                    sum(phase_bytes.values()), device_sec,
                    self.peaks["hbm_bytes_per_sec"],
                ), 6,
            ),
            "hbm_peak_bytes_per_sec": self.peaks["hbm_bytes_per_sec"],
            "hbm_peak_estimated": self.peaks["estimated"],
            "device_kind": self.peaks["device_kind"],
        }
        # The roofline verdict, named: the dominant DEVICE phase (host
        # readback is the trace instrumentation's own cost, excluded
        # like the HBM denominator above).  Part of the `trace:` line
        # check-tpu --trace prints, so supervised children surface it
        # without journal digging.
        device_phases = {
            n: s for n, s in phase_sec.items() if n not in _HOST_PHASES
        }
        if device_phases:
            out["bottleneck_phase"] = max(
                device_phases, key=device_phases.get
            )
        out.update({
            k: round(v, 4) if isinstance(v, float) else v
            for k, v in extra.items()
        })
        return out
