"""Shared wave-loop core for the single-chip and sharded wavefront engines.

Both engines run the same host-side loop around their fused device
program: call the program, read ONE stats vector back, fold counters and
discoveries, journal/metrics, maybe checkpoint, dispatch overflow flags
(grow in place or raise loudly), and decide termination.  Before this
module the two copies had already drifted (the sharded engine raised
where the single-chip one grew, and only the single-chip loop honored
the keep-partial-on-deadline rule); :class:`FusedWaveLoop` is the one
definition both engines drive, so `save_snapshot`/`resume_from`,
checkpoint cadence, cooperative cancel, and the in-place auto-grow
contract exist on both engines by construction rather than by copy.

This module also owns the **exchange bucket geometry** — the sharded
engine's per-destination all_to_all buckets (:func:`exchange_bucket_lanes`)
— as the single source of truth shared by the device program, the traced
byte model, and `accounting()`, so the reported payload shape can never
drift from what the device actually transmits (docs/SHARDED_SCALING.md).
"""

from __future__ import annotations

import logging
import time
from typing import NamedTuple, Optional

# Default per-destination bucket slack, in PERCENT of the even share
# u_sz/n.  The measured per-wave exchange occupancy (docs/SHARDED_SCALING.md:
# 0.28% of transmitted lanes at 8 shards, <1% even at the peak wave) says
# real candidates fill a few percent of the even share at most, so HALF the
# even share still carries >90% headroom — and the overflow-flag +
# retry-at-next-rung contract makes an undersized bucket a recompile, not
# a wrong answer.  Warm starts load the discovered rung from the knob
# cache (runtime/knob_cache.py) and skip the ramp entirely.
BUCKET_SLACK_DEFAULT = 50

# Doubling rung ladder: 50% of the even share, then 100%, 200%, ... until
# the bucket reaches the full u_sz buffer (the pre-bucketing shape, which
# cannot overflow: a shard never has more than u_sz candidates in total).
BUCKET_SLACK_MAX_RUNGS = 12


def exchange_bucket_lanes(u_sz: int, n: int, slack_pct: int) -> int:
    """Per-destination exchange bucket width in lanes: ``slack_pct`` percent
    of the even share ``u_sz/n``, rounded up to a 128-lane multiple (TPU
    lane tile), floored at 8 lanes, capped at ``u_sz`` (the full
    pre-bucketing buffer — always safe).  ``n == 1`` meshes elide the
    exchange entirely and keep the full buffer shape."""
    if n <= 1:
        return u_sz
    even = -(-u_sz // n)  # ceil
    want = -(-even * max(int(slack_pct), 1) // 100)
    want = max(8, ((want + 127) // 128) * 128 if want > 8 else want)
    return min(u_sz, want)


def next_bucket_slack(u_sz: int, n: int, slack_pct: int) -> Optional[int]:
    """The next rung of the bucket-slack ladder (doubling), or None when
    the bucket already spans the full ``u_sz`` buffer — at which point a
    bucket overflow is impossible by construction."""
    if exchange_bucket_lanes(u_sz, n, slack_pct) >= u_sz:
        return None
    grown = slack_pct * 2
    for _ in range(BUCKET_SLACK_MAX_RUNGS):
        if exchange_bucket_lanes(u_sz, n, grown) > exchange_bucket_lanes(
            u_sz, n, slack_pct
        ):
            return grown
        grown *= 2
    return None


# --- sort-geometry rung ladder (the dedup-sort analogue of the bucket
# ladder above; ROADMAP #1) ---------------------------------------------------
#
# The dedup sort pre-insert runs 3 co-sorted planes over the compaction
# buffer every wave; the worst-case buffer U = max(min(B, 16K),
# B/dedup_factor) is sized for a wave where EVERY candidate lane is valid,
# while the measured valid density (LoopVitals) is a few percent of it.
# ``sort_lanes`` is a power-of-two rung the engines compact into INSTEAD
# of U — the sort, probe rounds, and every U-sized gather downstream then
# touch rung lanes, not worst-case lanes.  The contract is exactly the
# bucket-slack ladder's: a wave whose valid candidates exceed the rung
# raises the non-committing flag-4 overflow, the host climbs one rung
# (×2, capped at the full U buffer — which reproduces the pre-ladder
# criterion exactly, so the top rung can never be wrong), and the
# discovered rung persists in the knob cache / tuned_kwargs so warm runs
# start past the ramp.  Downshifts are density-driven between committed
# quanta (:func:`downshift_sort_lanes`), with at-least-halving hysteresis
# so the compiled rung set stays small (the recompile-storm detector
# watches a thrashing ladder).
SORT_RUNG_MIN = 256

# Sizing headroom over the measured per-wave valid peak: quantum-averaged
# densities under-read the true in-wave peak, and an undersized rung costs
# a retry (never a wrong answer), so 4× balances "rarely retries" against
# "stops sorting dead lanes" (the report advisor's constant).
SORT_RUNG_HEADROOM = 4.0

# Committed density observations required before a downshift.  BFS
# density RAMPS over the first levels (tiny init frontier), so early
# peaks badly under-read steady state — acting on two waves of evidence
# measured as a downshift-then-climb-back thrash on 2pc(4); eight quanta
# of peak-tracking ride out the ramp (a fused quantum is up to 256
# waves, so production runs reach the window almost immediately).
SORT_TUNE_MIN_QUANTA = 8

# --- step-geometry rung ladder (the frontier-sized step; ROADMAP #1) ---------
#
# The OTHER buffer-proportional full-width pass: the expansion kernel and
# valid-lane compaction scan B = max_frontier × max_actions candidate
# lanes every wave, while the live frontier level is often a fraction of
# the chunk (56% of wave time on the post-PR-12 low-density gauge).
# ``step_lanes`` is a power-of-two rung on the per-wave CHUNK width (in
# frontier lanes): the chunk slice, the candidate batch (rung ×
# max_actions lanes), the valid compaction, and the dedup buffers all
# span the rung instead of the worst case.  A wave whose remaining level
# exceeds the rung raises the non-committing flag 128 and the host
# climbs one rung (×2, capped at max_frontier — where the flag cannot
# fire and behavior is exactly pre-ladder); the frontier-size tuner
# downshifts between committed quanta through the shared helpers below.
# The discovered rung rides the knob cache / tuned_kwargs like the sort
# rung.  Processing a level wider than max_frontier still chunks through
# multiple waves, exactly as before — the ladder only removes the dead
# lanes below the cap.
STEP_RUNG_MIN = 256
STEP_RUNG_HEADROOM = 4.0
STEP_TUNE_MIN_QUANTA = 8


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def clamp_rung(requested: int, min_rung: int) -> int:
    """Normalize a requested rung onto a ladder: next power of two,
    floored at ``min_rung``.  The full-buffer cap is applied live by the
    engines (``min(rung, full)``) because auto-grow can move the full
    width mid-run.  Shared by both ladders so they cannot drift."""
    return max(min_rung, _pow2_ceil(max(1, int(requested))))


def clamp_sort_lanes(requested: int) -> int:
    return clamp_rung(requested, SORT_RUNG_MIN)


def clamp_step_lanes(requested: int) -> int:
    return clamp_rung(requested, STEP_RUNG_MIN)


def next_rung(cur: int, full: int, min_rung: int) -> Optional[int]:
    """The next rung up (doubling, capped at ``full``), or None when the
    rung already spans the full buffer — at which point the overflow
    criterion is the pre-ladder condition and the remaining lever is the
    ladder-specific relax/raise path.  Shared by both ladders."""
    if cur >= full:
        return None
    return min(max(min_rung, cur * 2), full)


def next_sort_lanes(cur: int, u_sz: int) -> Optional[int]:
    """The next sort rung up; None once the sort spans the full ``u_sz``
    buffer (the rung overflow criterion then IS the pre-ladder dedup
    criterion and the remaining growth lever is
    ``relax_dedup_geometry``)."""
    return next_rung(cur, u_sz, SORT_RUNG_MIN)


def next_step_lanes(cur: int, full: int) -> Optional[int]:
    """The next step rung up; None once the chunk spans the full
    ``max_frontier`` (where the clamp flag cannot fire by construction)."""
    return next_rung(cur, full, STEP_RUNG_MIN)


def downshift_rung(
    cur: int, full: int, floor: int, peak: float,
    min_rung: int, headroom: float,
) -> Optional[int]:
    """The ONE downshift decision both ladders share, parameterized by
    (min, headroom) with at-least-halving hysteresis: the rung that
    holds the measured peak at ``headroom``× slack, or None when no
    at-least-halving move exists.  ``floor`` is the overflow-proven
    minimum (a rung this run already climbed past must never be
    revisited — the ladder-thrash mode the watch verb badges)."""
    want = max(
        min_rung,
        int(floor),
        _pow2_ceil(max(1, int(peak * headroom) + 1)),
    )
    want = min(want, full)
    if want * 2 <= cur:
        return want
    return None


def downshift_sort_lanes(
    cur: int, u_sz: int, floor: int, peak_valid: float
) -> Optional[int]:
    """Density-driven sort-rung downshift (see :func:`downshift_rung`):
    the rung holding the measured per-wave valid peak at
    ``SORT_RUNG_HEADROOM``× headroom."""
    return downshift_rung(
        cur, u_sz, floor, peak_valid, SORT_RUNG_MIN, SORT_RUNG_HEADROOM
    )


def downshift_step_lanes(
    cur: int, full: int, floor: int, peak_frontier: float
) -> Optional[int]:
    """Frontier-size-driven step-rung downshift (see
    :func:`downshift_rung`): the chunk rung holding the measured live
    frontier peak at ``STEP_RUNG_HEADROOM``× headroom."""
    return downshift_rung(
        cur, full, floor, peak_frontier, STEP_RUNG_MIN, STEP_RUNG_HEADROOM
    )


def climb_sort_rung(eng, full: int) -> Optional[str]:
    """The flag-4 rung-climb half of the growth rule, shared by both
    engines (the relax_dedup_geometry pattern — one definition so the
    retry semantics cannot drift): climb one rung toward ``full``,
    record the overflow-proven floor and peak evidence, and return the
    grow note.  None when the rung already spans the full buffer — the
    caller falls back to :func:`relax_dedup_geometry`."""
    cur = eng._sort_width()
    nxt = next_sort_lanes(cur, full)
    if nxt is None:
        return None
    eng._sort_lanes = nxt
    eng._sort_rung_floor = nxt
    # The overflow proved this wave's valid count exceeds the old rung.
    eng._sort_peak_valid = max(eng._sort_peak_valid, cur)
    return f"sort_lanes={nxt}"


def climb_step_rung(eng, full: int) -> Optional[str]:
    """The flag-128 rung climb (the step ladder's analog of
    :func:`climb_sort_rung`, shared by all three engines): climb one
    chunk rung toward ``full`` (= ``max_frontier`` / ``chunk_size``),
    record the overflow-proven floor and peak evidence, and return the
    grow note.  None when the chunk already spans the full width — which
    cannot be reached via flag 128 (the clamp flag is compiled out at
    the top rung), so None here means a logic error surfacing loudly."""
    cur = eng._step_width()
    nxt = next_step_lanes(cur, full)
    if nxt is None:
        return None
    eng._step_lanes = nxt
    eng._step_rung_floor = nxt
    # The clamp proved the live frontier exceeds the old rung.
    eng._step_peak_frontier = max(eng._step_peak_frontier, cur)
    return f"step_lanes={nxt}"


def fall_back_to_sort(eng) -> str:
    """The sortless → sort-rung fallback (the engines' flag dispatch
    under ``sortless``): flip the dedup path to the sorted fallback rung
    — the already-proven PR 12 ladder — re-journal the geometry event so
    journal readers (`watch`'s ``dedup=`` field and fallback-thrash
    badge) track the flip, and return the grow note.  Non-committing by
    the same contract as every other ladder move: the flagged wave never
    committed, so the re-run at the sorted program is exact.  The knob
    cache persists the flipped mode (``tuned_kwargs()['sortless']``), so
    the fallback is a per-workload selection, paid once."""
    eng._sortless = False
    if eng._journal:
        eng._journal.append("geometry", **eng._wl_geometry())
    return "sortless=0"


def reset_sort_rung_to_full(eng, old_full: int) -> None:
    """The relax-path tail: a FULL-buffer flag-4 overflow relaxed
    dedup_factor, so the rung resets to the new (larger) full width and
    the evidence records that one wave held ≥ ``old_full`` valid lanes
    (the density tuner must not shrink the new buffer back).  The
    geometry event is re-journaled so journal readers — the `watch`
    verb's ``sort_rung`` in particular — track the reset; the grow note
    alone carries no ``sort_lanes=`` and would leave them stale."""
    eng._sort_lanes = None
    eng._sort_peak_valid = max(eng._sort_peak_valid, old_full)
    if eng._journal:
        eng._journal.append("geometry", **eng._wl_geometry())


def _maybe_retune(eng, measured, ns: dict) -> bool:
    """The ONE measured-evidence → rung-downshift tuner both ladders
    share (parameterized by the attribute namespace ``ns`` — see
    ``_SORT_NS``/``_STEP_NS`` below — so the two tuners cannot drift):
    folds the quantum's measurement into the engine's running peak and
    applies a downshift once enough committed quanta accumulated.
    Returns True exactly when the rung changed — traced loops use it to
    refresh their phase programs."""
    apply = getattr(eng, ns["apply"], None)
    if apply is None or measured is None:
        return False
    if not getattr(eng, ns["tune"], False):
        # An EXPLICIT rung (warm start from the knob cache, or a pinned
        # measurement leg) is the caller's rung: the tuner must not
        # fight it.  The overflow ladder stays armed regardless — an
        # explicit rung that proves too small still climbs.
        return False
    full = getattr(eng, ns["full"])()
    cur = getattr(eng, ns["width"])()
    setattr(eng, ns["quanta"], getattr(eng, ns["quanta"]) + 1)
    peak_obs = measured * full if ns["scale_by_full"] else measured
    setattr(
        eng, ns["peak"], max(getattr(eng, ns["peak"]), peak_obs)
    )
    if getattr(eng, ns["quanta"]) < ns["min_quanta"]:
        return False
    want = downshift_rung(
        cur, full, getattr(eng, ns["floor"]), getattr(eng, ns["peak"]),
        ns["min_rung"], ns["headroom"],
    )
    if want is None:
        return False
    apply(want)
    return True


# The sort ladder's evidence is the measured valid DENSITY (a fraction
# of the full buffer — scaled back to lanes here); the step ladder's is
# the live frontier backlog, already in lanes.
_SORT_NS = dict(
    apply="_wl_apply_sort_rung", tune="_sort_tune",
    full="_wl_full_sort_lanes", width="_sort_width",
    quanta="_sort_quanta", peak="_sort_peak_valid",
    floor="_sort_rung_floor", min_rung=SORT_RUNG_MIN,
    headroom=SORT_RUNG_HEADROOM, min_quanta=SORT_TUNE_MIN_QUANTA,
    scale_by_full=True,
)
_STEP_NS = dict(
    apply="_wl_apply_step_rung", tune="_step_tune",
    full="_wl_full_step_lanes", width="_step_width",
    quanta="_step_quanta", peak="_step_peak_frontier",
    floor="_step_rung_floor", min_rung=STEP_RUNG_MIN,
    headroom=STEP_RUNG_HEADROOM, min_quanta=STEP_TUNE_MIN_QUANTA,
    scale_by_full=False,
)


def maybe_retune_sort(eng, density) -> bool:
    """Shared density→sort-rung downshift, called by every host loop
    after a committed quantum (fused and traced alike; engines without
    the ``_wl_apply_sort_rung`` hook are untouched)."""
    return _maybe_retune(eng, density, _SORT_NS)


def maybe_retune_step(eng, remaining) -> bool:
    """Frontier→step-rung downshift, same cadence and hysteresis as the
    sort tuner by construction (one shared helper): the evidence is the
    committed quantum's remaining frontier backlog (an underestimate of
    intra-quantum peaks, which the headroom absorbs — and an undersized
    rung is the non-committing flag 128, never a wrong answer)."""
    return _maybe_retune(eng, remaining, _STEP_NS)


def relax_dedup_geometry(chunk, dedup_factor, lanes_of, lane_cap,
                         chunk_label: str, chunk_floor: int = 2048):
    """The shared dedup-overflow growth rule: straight to the always-safe
    ``dedup_factor=1`` (intermediate stops measured as new worker-crash
    geometries, wavefront.py's `_grow`), halving the chunk while
    ``lanes_of(chunk, 1)`` exceeds the device-safe band.  Returns
    ``(dedup_factor, chunk, note)`` or None when even the floor chunk
    cannot fit the band (max_actions > 256)."""
    if dedup_factor <= 1:
        return None
    notes = ["dedup_factor=1"]
    c = chunk
    while c > chunk_floor and lanes_of(c, 1) > lane_cap:
        c //= 2
        notes.append(f"{chunk_label}={c}")
    if lanes_of(c, 1) > lane_cap:
        return None
    return 1, c, "; ".join(notes)


def land_on_sort_rung(eng, old_full: int) -> str:
    """The relax-path tail of a run whose rung the tuner owns (the
    narrow twin of :func:`reset_sort_rung_to_full`): a FULL-buffer
    flag-4 overflow relaxed ``dedup_factor`` to 1, and the compaction
    lands on the next power of two above the width that overflowed
    instead of the whole relaxed batch — a duplicate-dense batch fills a
    fraction of it.  The rung is the floor (the overflow proved the old
    width too small); a later overflow climbs it through
    :func:`climb_sort_rung`.  A claim-election run flips to the sort
    path in the same move: at one rung the sort path measured faster on
    such batches (PERF.md, 2pc 8 on a v5e).  Returns the grow note."""
    notes = []
    if eng._sortless:
        eng._sortless = False
        notes.append("sortless=0")
    rung = clamp_sort_lanes(old_full + 1)
    eng._sort_lanes = rung
    eng._sort_rung_floor = rung
    eng._sort_peak_valid = max(eng._sort_peak_valid, old_full)
    notes.append(f"sort_lanes={rung}")
    if eng._journal:
        eng._journal.append("geometry", **eng._wl_geometry())
    return "; ".join(notes)


class CheckpointCadence:
    """Mid-run checkpoint pacing shared by every host loop: due every
    ``every_waves`` waves (counted in whatever quantum the loop reports)
    or ``every_sec`` seconds, whichever the engine was configured with."""

    def __init__(self, every_waves: Optional[int], every_sec: Optional[float]):
        self.every_waves = every_waves
        self.every_sec = every_sec
        self._waves = 0
        self._last = time.monotonic()

    def due(self, waves_increment: int) -> bool:
        self._waves += waves_increment
        if self.every_waves is not None and self._waves >= self.every_waves:
            return True
        return (
            self.every_sec is not None
            and time.monotonic() - self._last >= self.every_sec
        )

    def mark(self) -> None:
        self._waves = 0
        self._last = time.monotonic()


class LoopVitals:
    """Always-on fused-loop vitals (docs/OBSERVABILITY.md "Always-on
    vitals"): cheap per-quantum counters and histograms recorded at the
    host-side call boundary every engine loop already crosses — never an
    extra device sync, so the trace=False device program stays
    byte-for-byte pinned.  One instance per engine run; writes land in
    the engine's :class:`~stateright_tpu.obs.metrics.MetricsRegistry`:

    - ``wave_latency_sec`` histogram — per-wave wall latency (a fused
      quantum of ``waves_per_call`` waves records its mean latency with
      weight waves_per_call; traced loops record each wave exactly);
    - ``waves_per_grow`` histogram — committed waves between
      overflow-triggered recoveries (how long a geometry survived
      before overflowing);
    - ``uniq_per_sec_ema`` / ``waves_per_sec_ema`` gauges — exponential
      moving averages over committed quanta (alpha 0.3: a few quanta of
      memory, mid-run readable from ``/.metrics``);
    - ``host_sec_total`` counter — host-side loop time: the fused loop
      accounts the between-calls gap (journal/metrics/checkpoint/grow
      dispatch); the traced loops report their measured ``readback``
      phase via :meth:`record_host` instead.  Either way, the
      time-in-host complement of the device time;
    - ``overflow_retries`` counter — every overflow-flagged wave that
      was recovered and re-run.  The separate ``grows`` counter
      (:func:`log_grow`) counts only ACTUAL geometry changes: a
      recovery that re-runs without growing (the tiered engine's
      spill-instead-of-grow) moves ``overflow_retries`` but not
      ``grows``;
    - ``valid_density_ema`` gauge + ``valid_density`` histogram — the
      measured per-wave VALID-candidate count as a fraction of the
      worst-case compaction/dedup ``U`` buffer (``cand_lanes``).  The
      numerator is the quantum's ``state_count`` delta divided by its
      wave count: ``state_count`` advances by exactly the
      boundary-passing valid successors each committed wave
      (wave_common.wave_eval's ``generated``), so the density needs NO
      extra readback — the fused program stays byte-for-byte pinned.
      Bounded at 1.0 by construction: the flag-4 overflow criterion
      fires on the SAME valid-lane count (hashset.compact_valid — "a
      stricter criterion than distinct keys"), so a committed wave's
      numerator can never exceed its ``U`` buffer.  This is the number
      the dedup-geometry ladder (ROADMAP #1) sizes against, and what
      the report advisor (obs/report.py) reads back out of the journal;
    - ``table_load_factor`` histogram (``load_factor``) — the hot-table
      load trajectory, one observation per committed quantum (the gauge
      form already rides ``table_occupancy``).
    """

    EMA_ALPHA = 0.3

    def __init__(self, registry, initial_unique: Optional[int] = None,
                 initial_states: Optional[int] = None):
        from ..obs.metrics import (
            COUNT_BUCKETS, FRACTION_BUCKETS, LATENCY_BUCKETS,
        )

        self._reg = registry
        self._latency_buckets = LATENCY_BUCKETS
        self._count_buckets = COUNT_BUCKETS
        self._fraction_buckets = FRACTION_BUCKETS
        self._uniq_ema: Optional[float] = None
        self._wave_ema: Optional[float] = None
        self._density_ema: Optional[float] = None
        self.last_density: Optional[float] = None
        # Baseline for the first quantum's uniq/s delta: the unique
        # count already committed before the loop starts (init seeding,
        # or a resumed snapshot's count — which must not read as "found
        # this call").  None = unknown; the first quantum then only
        # primes the baseline.  ``initial_states`` is the same baseline
        # for the density's generated-successors delta.
        self._last_unique = initial_unique
        self._last_states = initial_states
        self._waves_since_grow = 0
        self._host_mark: Optional[float] = None
        self._reg.inc("host_sec_total", 0.0)  # key exists from wave 0

    def call_started(self, now: float) -> None:
        """Account the host-side gap since the previous call ended
        (journal/metrics/checkpoint/grow work) as host time; the first
        call has no gap yet."""
        if self._host_mark is not None:
            self._reg.inc(
                "host_sec_total", max(0.0, now - self._host_mark)
            )

    def call_ended(self, now: float) -> None:
        self._host_mark = now

    def record_host(self, sec: float) -> None:
        """Directly account host-side seconds — the traced loops' path:
        their per-wave timers already isolate the host ``readback``
        phase inside the wave, so they report it here instead of the
        fused loop's between-calls gap."""
        self._reg.inc("host_sec_total", max(0.0, sec))

    def record_quantum(
        self, call_sec: float, waves: int, unique: int, committed: bool,
        states: Optional[int] = None, cand_lanes: Optional[int] = None,
        occupancy: Optional[float] = None,
    ) -> None:
        """Fold one device-call quantum into the vitals.  Aborted
        (flagged) quanta count latency but not rates: their unique delta
        is zero by construction and would drag the EMA to the floor.
        ``states``/``cand_lanes`` feed the density telemetry (see the
        class docstring), ``occupancy`` the load-factor trajectory."""
        waves = max(1, int(waves))
        self._reg.observe(
            "wave_latency_sec", call_sec / waves, count=waves,
            boundaries=self._latency_buckets,
        )
        self.last_density = None  # stale density must not journal on abort
        if not committed:
            return
        if occupancy is not None:
            self._reg.observe(
                "load_factor", occupancy,
                boundaries=self._fraction_buckets,
            )
        if states is not None and cand_lanes:
            if self._last_states is not None:
                density = (
                    max(0, states - self._last_states) / waves / cand_lanes
                )
                self.last_density = density
                self._density_ema = (
                    density if self._density_ema is None
                    else self._density_ema
                    + self.EMA_ALPHA * (density - self._density_ema)
                )
                self._reg.observe(
                    "valid_density", density, count=waves,
                    boundaries=self._fraction_buckets,
                )
                self._reg.update(
                    valid_density_ema=round(self._density_ema, 6),
                )
            self._last_states = states
        self._waves_since_grow += waves
        if call_sec > 0:
            wave_rate = waves / call_sec
            if self._last_unique is not None:
                uniq_rate = max(0, unique - self._last_unique) / call_sec
                self._uniq_ema = (
                    uniq_rate if self._uniq_ema is None
                    else self._uniq_ema
                    + self.EMA_ALPHA * (uniq_rate - self._uniq_ema)
                )
            self._wave_ema = (
                wave_rate if self._wave_ema is None
                else self._wave_ema
                + self.EMA_ALPHA * (wave_rate - self._wave_ema)
            )
            self._reg.update(
                waves_per_sec_ema=round(self._wave_ema, 4),
                **(
                    {"uniq_per_sec_ema": round(self._uniq_ema, 2)}
                    if self._uniq_ema is not None else {}
                ),
            )
        self._last_unique = unique

    def record_overflow_recovery(self) -> None:
        self._reg.inc("overflow_retries", 1)
        self._reg.observe(
            "waves_per_grow", max(1, self._waves_since_grow),
            boundaries=self._count_buckets,
        )
        self._waves_since_grow = 0


def journal_geometry(eng) -> None:
    """One ``geometry`` journal event at loop start (fused and traced
    alike): the engine's live geometry knobs plus the worst-case
    candidate-lane denominator the density telemetry divides by —
    everything the report advisor (obs/report.py) needs to turn measured
    densities back into recommended knobs.  Engines expose it via the
    optional ``_wl_geometry()`` hook."""
    geom = getattr(eng, "_wl_geometry", None)
    if eng._journal and geom is not None:
        eng._journal.append("geometry", **geom())


class WaveView(NamedTuple):
    """The host-visible summary of one fused program call, decoded from
    the engine's stats readback — everything the shared loop needs to
    journal, checkpoint, grow, and decide termination."""

    waves_this_call: int
    remaining: int  # frontier states left in the current level (global)
    depth: int
    flags: int
    unique: int
    states: int
    occupancy: float  # fingerprint-table load (sharded: fullest shard)
    discoveries: tuple  # ((prop_name, state_id), ...)
    extra: dict  # engine-specific journal enrichment (e.g. tail)


def loop_should_break(eng, view_remaining: int, depth: int, deadline) -> bool:
    """The shared termination tail (exact predicate order preserved from
    the pre-extraction loops): level drained / target depth / finish_when
    / target_state_count / wall deadline / cooperative stop.  Used by the
    fused driver below AND the engines' traced loops, so a traced run can
    never outlive (or under-live) a fused one."""
    opts = eng._options
    if view_remaining == 0:
        return True
    if (
        opts._target_max_depth is not None
        and depth + 1 >= opts._target_max_depth
    ):
        return True
    if opts._finish_when.matches(
        frozenset(eng._wl_discovered_names()), eng._properties
    ):
        return True
    if (
        opts._target_state_count is not None
        and opts._target_state_count <= eng._state_count
    ):
        return True
    if deadline is not None and time.monotonic() >= deadline:
        return True
    return eng._stop_requested.is_set()


class FusedWaveLoop:
    """The fused host loop, engine-agnostic.  The engine adapter (the
    checker itself) provides:

    - ``_wl_call(carry) -> carry`` — run the fused device program once;
    - ``_wl_view(carry) -> WaveView`` — the one stats readback, decoded;
    - ``_wl_set_discovery(name, id)`` — first-writer-wins discovery fold
      (called under the engine lock);
    - ``_wl_write_checkpoint(carry) -> dict`` — persist a mid-run
      snapshot, returning extra journal fields;
    - ``_wl_retryable_flags() -> int`` — flag bits the engine can grow
      in place (everything else raises);
    - ``_wl_grow(flags, carry) -> carry | None`` — in-place growth (may
      recompile programs / re-upload a fixed stats vector); None means
      the tripped knob cannot grow;
    - ``_wl_overflow_message(flags) -> str`` — the loud error text;
    - ``_wl_after_commit(carry, view) -> carry | None`` (OPTIONAL) — the
      spill/refill dispatch: called after every committed (flags == 0)
      wave, before the checkpoint cadence, so an engine with a tiered
      store (tiered/engine.py) can evict hot-tier partitions at its
      budget threshold and have the very next checkpoint persist the
      post-spill state.  Returning None keeps the carry;

    plus the shared checker attributes (`_options`, `_properties`,
    `_journal`, `_metrics`, `_lock`, `_stop_requested`, counters, and the
    checkpoint knobs).  An overflowing wave NEVER commits (both engines'
    device programs guarantee it), so growth re-runs the same chunk with
    no work lost and no host-visible side effects.
    """

    def __init__(self, eng):
        self.eng = eng

    def run(self, carry, deadline=None):
        from ..obs.timeline import SpanRecorder

        eng = self.eng
        cadence = CheckpointCadence(eng._ckpt_every_waves, eng._ckpt_every_sec)
        vitals = LoopVitals(
            eng._metrics,
            initial_unique=getattr(eng, "_unique_count", None),
            initial_states=getattr(eng, "_state_count", None),
        )
        # Host-tail span decomposition (obs/timeline.py): every named
        # section of the between-calls tail below runs under
        # ``spans.span(...)`` — two extra ``time.monotonic()`` calls per
        # section, no device traffic, so the trace=False fused program
        # stays byte-for-byte pinned.  The recorder flushes ONE
        # ``host_span`` journal event per quantum at the same boundary
        # ``vitals.call_started`` accounts into ``host_sec_total``.
        spans = SpanRecorder(eng._journal, eng._metrics)
        journal_geometry(eng)
        waves_total = 0
        while True:
            spans.quantum_start(time.monotonic())
            t_call = time.monotonic()
            vitals.call_started(t_call)
            with spans.step():
                carry = eng._wl_call(carry)
            with spans.span("readback"):
                view = eng._wl_view(carry)
            spans.collect(eng)
            t_done = time.monotonic()
            call_sec = t_done - t_call
            vitals.call_ended(t_done)
            spans.tail_start(t_done)
            cand_lanes = getattr(eng, "_wl_cand_lanes", None)
            vitals.record_quantum(
                call_sec, view.waves_this_call, view.unique,
                committed=view.flags == 0,
                states=view.states,
                cand_lanes=cand_lanes() if cand_lanes is not None else None,
                occupancy=view.occupancy,
            )
            waves_total += view.waves_this_call
            with eng._lock:
                eng._state_count = view.states
                eng._unique_count = view.unique
                eng._max_depth = view.depth + (1 if view.remaining else 0)
                for name, ident in view.discoveries:
                    eng._wl_set_discovery(name, ident)
            if eng._journal:
                with spans.span("journal"):
                    eng._journal.append(
                        "wave",
                        waves=waves_total,
                        remaining=view.remaining,
                        unique=view.unique,
                        states=view.states,
                        depth=view.depth,
                        flags=view.flags,
                        call_sec=round(call_sec, 4),
                        mono=round(t_call, 6),
                        occupancy=round(view.occupancy, 6),
                        **(
                            {"density": round(vitals.last_density, 6)}
                            if vitals.last_density is not None else {}
                        ),
                        **view.extra,
                    )
            eng._metrics.update(
                waves=waves_total,
                table_occupancy=round(view.occupancy, 6),
                last_call_sec=round(call_sec, 6),
            )
            eng._metrics.inc("device_call_sec_total", call_sec)
            eng._metrics.inc("device_calls", 1)
            if view.flags == 0:
                # Spill/refill rung (tiered engines only): evict AT the
                # committed boundary so the cadence block below persists
                # the post-spill tier state in the same pass.
                after_commit = getattr(eng, "_wl_after_commit", None)
                if after_commit is not None:
                    with spans.span("spill"):
                        carry = after_commit(carry, view) or carry
                # Density-driven sort-rung downshift and frontier-driven
                # step-rung downshift (engines with the hooks only): the
                # carry is rung-independent — only the per-wave scratch
                # buffers reshape — so a retune is a program swap
                # between calls, never a migration.
                with spans.span("retune"):
                    maybe_retune_sort(eng, vitals.last_density)
                    # remaining == 0 means the run is about to break — a
                    # downshift there would recompile for zero waves.
                    maybe_retune_step(eng, view.remaining or None)
            if (
                eng._checkpoint_path is not None
                and view.flags == 0
                and cadence.due(view.waves_this_call)
            ):
                with spans.span("checkpoint"):
                    t_ck = time.monotonic()
                    ck_extra = eng._wl_write_checkpoint(carry) or {}
                    cadence.mark()
                    if eng._journal:
                        eng._journal.append(
                            "checkpoint",
                            path=eng._checkpoint_path,
                            unique=view.unique,
                            depth=view.depth,
                            write_sec=round(time.monotonic() - t_ck, 4),
                            **ck_extra,
                        )
            if view.flags:
                fatal = view.flags & ~eng._wl_retryable_flags()
                if fatal:
                    raise RuntimeError(eng._wl_overflow_message(fatal))
                if eng._stop_requested.is_set() or (
                    deadline is not None and time.monotonic() >= deadline
                ):
                    # Growth costs a recompile + re-run; a run already
                    # past its budget (or asked to stop) keeps its
                    # partial result instead.  But the break lands on a
                    # FLAGGED wave, whose aborted insert may have
                    # scribbled keys into the fingerprint table: engines
                    # whose aborted waves mutate the table must erase
                    # them before the carry is persisted, or a resumed
                    # run would treat the wave's states as already
                    # visited and silently lose their subtrees (the
                    # sharded engine zeroes validity pre-insert, so it
                    # needs no hook).
                    cleanup = getattr(eng, "_wl_abort_cleanup", None)
                    if cleanup is not None:
                        carry = cleanup(carry) or carry
                    break
                with spans.span("grow"):
                    grown = eng._wl_grow(view.flags, carry)
                if grown is None:
                    raise RuntimeError(eng._wl_overflow_message(view.flags))
                vitals.record_overflow_recovery()
                carry = grown
                continue
            if loop_should_break(eng, view.remaining, view.depth, deadline):
                break
        # The final quantum's tail has no next call to account it into
        # ``host_sec_total`` via the between-calls gap — measure it here
        # (before the flush write) and fold it in directly, so the
        # journaled decomposition and the counter stay reconciled.
        vitals.record_host(spans.finish(time.monotonic()))
        return carry, waves_total


def finalize_run(eng, carry_dict: dict) -> None:
    """The shared run tail: stash the snapshot-ready carry, write the
    final completion checkpoint (a run directory always ends with a
    durable resumable snapshot), and journal ``engine_done``."""
    eng._carry_dev = carry_dict
    if eng._checkpoint_path is not None:
        eng._write_snapshot(eng._checkpoint_path, carry_dict)
        if eng._journal:
            eng._journal.append(
                "checkpoint",
                path=eng._checkpoint_path,
                unique=eng._unique_count,
                depth=eng._max_depth,
                final=True,
            )
    if eng._journal:
        eng._journal.append(
            "engine_done",
            unique=eng._unique_count,
            states=eng._state_count,
            depth=eng._max_depth,
        )


def fingerprints_of_rows(cm, rows_np, canon=None, sort=True):
    """Sorted uint64 fingerprints of a batch of packed state rows — the
    shared implementation behind both engines'
    ``discovered_fingerprints()``, so cross-engine discovery-set pins
    compare one definition: the device fingerprint of the row's leading
    ``fp_words``, through ``canon`` when symmetry is on — exactly what
    identifies a state everywhere else in the engines (dedup keys,
    shard routing, tiered cold keys).  Under symmetry the logged
    ORIGINAL row is whichever orbit member the traversal reached first
    — order-dependent by construction — so the identity (= canonical)
    fingerprint is the only traversal-invariant discovery-set pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.device_fp import device_fp64

    fpw = cm.fp_words or cm.state_width
    rows = jnp.asarray(rows_np)
    if canon is not None:
        rows = jax.vmap(canon)(rows)
    hi, lo = device_fp64(rows[:, :fpw])
    fps = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
        lo
    ).astype(np.uint64)
    # sort=False keeps row order: resharding re-owners each logged row by
    # its fingerprint and needs fps[i] to stay aligned with rows_np[i].
    return np.sort(fps) if sort else fps


def log_grow(eng, flags: int, grown: str, unique: int, depth: int) -> None:
    """Shared grow-event surfacing: a warning log line, a journaled
    ``grow`` record, and the ``grows`` metric — identical on both
    engines so supervisors, scrapers, and tests read one schema.  Only
    ACTUAL geometry changes come through here; overflow recoveries that
    re-run without growing (the tiered engine's spill-instead-of-grow)
    count in ``overflow_retries`` alone (:class:`LoopVitals`)."""
    eng._metrics.inc("grows", 1)
    logging.getLogger(eng.__class__.__module__).warning(
        "auto-tune: overflow flags=%d; growing in place (%s) at "
        "unique=%d depth=%d",
        flags, grown, unique, depth,
    )
    if eng._journal:
        eng._journal.append(
            "grow", flags=flags, grown=grown, unique=unique, depth=depth
        )
