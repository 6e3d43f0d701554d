"""The TPU wavefront checker.

This replaces the reference's thread-pool hot loop (pop job → evaluate
properties → expand successors → dedup insert → push, src/checker/
bfs.rs:177-335) with a *wavefront* BFS: the entire frontier is expanded at
once by a vmapped step kernel, deduplicated by a batched insert-if-absent
into an HBM-resident fingerprint table, and property conditions are fused
predicates over the whole wave.

The whole wave loop runs on device inside one ``lax.while_loop`` program —
the append-only state-row log, visited table, counters, and discovery slots
all live in HBM, and the host reads back a handful of scalars every
``waves_per_call`` waves.  States are identified by *BFS position* (the
order of first discovery): positions within a level are contiguous, so the
frontier read and the new-state append are contiguous block transfers, and
the only randomly-indexed memory is the fingerprint hash table.

Semantics parity with the host engine (core/engine.py):

- properties are evaluated when a unique state is *expanded* (the analog of
  pop-time evaluation), so states beyond ``target_max_depth`` or after an
  early exit are never evaluated — matching src/checker/bfs.rs:230-281;
- ``state_count`` counts boundary-passing generated successors pre-dedup
  plus init states; ``unique_state_count`` counts table insertions;
- eventually-bits travel with each table entry (parent's remaining bits),
  are cleared by the state's own satisfied conditions at expansion, and
  leftover bits at a terminal state (no valid successors) become
  counterexamples; the reference's documented join false-negative (ebits
  not part of the dedup key, src/checker/bfs.rs:295-315) is reproduced:
  first inserter's bits win;
- discoveries are first-writer-wins in deterministic wave order; paths are
  reconstructed by walking the parent-slot chain, decoding packed states,
  and re-executing the host model (core/path.py);
- with ``symmetry()`` and a canon-capable compiled model (parallel/
  canon.py), dedup keys on the fingerprint of the CANONICAL row while the
  row log stores the original — the device form of the reference DFS's
  dedup-on-representative/continue-with-original (src/checker/
  dfs.rs:309-334); counts are traversal-invariant because the canon spec
  sorts full records (docs/SYMMETRY.md).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, List, Optional

import numpy as np

from ..core.checker import Checker
from ..core.model import Expectation
from ..core.path import Path
from .compiled import CompiledModel, compiled_model_for

NO_SLOT_HOST = 0xFFFFFFFF

# Layout of the stats vector — the ONE array the host reads back per run()
# call (each distinct readback is a host sync).  Used by run()/seed()/the
# resume builder/the host loop; a new slot must be added here, nowhere else.
(
    STAT_LEVEL_START,
    STAT_LEVEL_END,
    STAT_TAIL,
    STAT_SC_LO,
    STAT_SC_HI,
    STAT_UNIQUE,
    STAT_DEPTH,
    STAT_FLAGS,
) = range(8)
# The dedup probe rounds run() spent at each stage, then the hand-offs
# into each stage taken by slice, both in hashset.stage_widths order
# (main stage, then the straggler ladder): counted over the call, zero
# on the way in.
STAT_ROUNDS = 8
STAT_SLICES = 12
STAT_DISC = 16  # disc[P] rides at [STAT_DISC : STAT_DISC + n_props]


def host_stats(level_start, level_end, tail, states, unique, depth, disc):
    """A stats vector in the layout above, built on the host (resume,
    and the tiered loop's snapshots); flags and probe rounds read 0."""
    head = [
        level_start, level_end, tail, states & 0xFFFFFFFF,
        (states >> 32) & 0xFFFFFFFF, unique, depth,
    ]
    return np.concatenate([
        np.array(head, np.uint32),
        np.zeros((STAT_DISC - STAT_FLAGS,), np.uint32),
        np.asarray(disc, np.uint32),
    ])

# Auto-tune growth bounds: the table's key planes cost 8 bytes a slot
# (2 GiB at the cap, plus a transient claim plane per insert) and the row
# log 4*state_width a position; growth stops at these bounds and the
# overflow surfaces as the ordinary loud RuntimeError.
_MAX_TABLE_CAPACITY = 1 << 28
_ROW_LOG_BYTE_BUDGET = 8 << 30
# Empirical device limits on the per-wave compact/dedup buffer
# U = unique_buffer_size(max_frontier * max_actions, dedup_factor): the
# v5e worker hard-CRASHES mid-wave ("kernel fault") instead of flagging
# when the buffer is too big, and the band depends on the state width.
# Safe / crash points measured in round 5 on a remotely attached v5e
# (2026-07-31), not yet re-derived on a local chip:
#   w=2  (2pc rm=10):  426K lanes safe, 1.7M lanes crash
#   w=42 (paxos c=3):  262K lanes safe (the headline's steady geometry)
#   w=77 (paxos c=6):  65K lanes safe, 524K lanes crash
# Two caps reproduce all five points: lanes <= the validated 426K AND
# lane-words (U*w) <= the validated 11M.  When auto-tune relaxes
# dedup_factor it halves max_frontier until U fits; halving the frontier
# alone cannot fix a dedup overflow (valid density is scale-free), but
# dd=1 can never overflow, so dd=1 plus a clamped frontier always
# terminates the growth sequence.
_MAX_UNIQUE_BUFFER = 425_984
_MAX_UNIQUE_LANE_WORDS = 11_010_048


def max_safe_unique_lanes(state_width: int) -> int:
    """The device-safe cap on the compact/dedup buffer's lane count for
    a model of this state width (see the validated points above)."""
    return min(
        _MAX_UNIQUE_BUFFER, _MAX_UNIQUE_LANE_WORDS // max(state_width, 1)
    )


class _OverflowRetry(Exception):
    """Internal: seed-time overflow aborted the run before any wave;
    auto-tune may restart the (empty) run with grown knobs."""

    def __init__(self, flag: int, message: str):
        super().__init__(message)
        self.flag = flag
        self.message = message


def _device_owned(x):
    """Force a host-uploaded array into a DEVICE-OWNED buffer before it
    ever reaches a donating program call.  ``jnp.asarray`` of a host
    numpy array may zero-copy borrow the host buffer on the CPU backend;
    DONATING such a borrowed buffer corrupts the run (observed on
    resumed runs in fresh processes with a warm persistent compile
    cache: previously-visited states re-inserted as new — 8417 "unique"
    states on the 1568-state 2pc(4) — or garbage parent chains at path
    reconstruction).  The eager elementwise add cannot be elided and
    materializes an XLA-owned output buffer that is safe to donate; a
    resume pays it once per array."""
    import jax.numpy as jnp

    return x + jnp.zeros((), x.dtype)


def _resize_flat(arr, new_len: int, fill):
    """Resize a flat device array, preserving the (new-length-bounded)
    prefix — the auto-tune path.  Shrink happens when a dedup-overflow
    growth halves ``max_frontier`` and with it the append-block pad; the
    committed log prefix is always shorter than the new length.

    Copy-growth unavoidably holds old + new live at once (donation cannot
    alias buffers of different sizes); the ×2 row-log growth step keeps
    the transient peak at 3× the old log, and the caller drops its last
    reference to the old array on return."""
    import jax
    import jax.numpy as jnp

    if new_len <= arr.shape[0]:
        return arr[:new_len]
    out = jnp.full((new_len,), fill, arr.dtype)
    return jax.lax.dynamic_update_slice(out, arr, (0,))

def snapshot_engine_key(cm, properties, symmetric: bool) -> str:
    """Process-stable compatibility key for engine snapshots.
    Deliberately avoids ``cache_key()`` (whose default embeds
    ``repr(model)``, which is identity-based for some models and would
    spuriously reject resumes in a new process); the packed init states
    hash in the model configuration instead.  Table/log geometry is NOT
    part of the key — a resume adopts the snapshot's persisted sizes
    (which may have been auto-tuned mid-run past the spawn arguments).
    Module-level (rather than a checker method) so the incremental
    store (incr/) can pre-check that a stored snapshot is seedable for
    a new spec WITHOUT spawning a checker that would die loudly on the
    mismatch."""
    import hashlib

    init_digest = hashlib.sha256(
        cm.init_packed().tobytes()
    ).hexdigest()[:16]
    return repr(
        (
            "rowlog-v3",  # flat row log + decoupled log_capacity (r4)
            type(cm).__qualname__,
            cm.state_width,
            cm.max_actions,
            tuple(p.name for p in properties),
            init_digest,
        )
        # A symmetry run's table holds CANONICAL fingerprints — not
        # resumable as a plain run (or vice versa).  Appended only
        # when on, so existing non-sym snapshots stay valid.
        + (("sym",) if symmetric else ())
    )


# Compiled device programs shared across checker instances (keyed by
# CompiledModel.cache_key() + engine shape knobs): re-tracing and re-jitting
# per spawn_tpu() call would otherwise dominate wall-clock.  Bounded FIFO:
# models with identity-repr cache keys would otherwise leak one program
# pair per spawn_tpu() call in long-lived processes.
_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_MAX = 32


class TpuChecker(Checker):
    """Single-device wavefront checker behind the standard Checker surface."""

    # A full-buffer flag-4 overflow on a tuner-owned rung relaxes and
    # lands on a narrow sort rung in one move (_grow); False keeps the
    # two-step dispatch: flip to the sort path, then relax to full width.
    _land_on_rung = True

    def __init__(
        self,
        options,
        capacity: int = 1 << 20,
        max_frontier: int = 1 << 15,  # per-chunk batch size, not a level cap
        # 8 measured fastest for the sparse-valid protocol models (paxos3:
        # 557k vs 353k uniq/s at dedup_factor=4, r5 probe) — it sizes the
        # valid-lane compaction buffer, which the probe rounds sweep.
        # Dense-valid models trip flag 4 and auto-tune relaxes toward 1,
        # so the default only changes their discovery path, not their
        # final geometry; batches under the 16K buffer floor never see it.
        dedup_factor: int = 8,
        sort_lanes: Optional[int] = None,
        sortless: Optional[bool] = None,
        step_lanes: Optional[int] = None,
        waves_per_call: Optional[int] = None,
        device=None,
        compiled: Optional[CompiledModel] = None,
        resume_from: Optional[str] = None,
        log_capacity: Optional[int] = None,
        auto_tune: bool = True,
        journal=None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every_waves: Optional[int] = None,
        checkpoint_every_sec: Optional[float] = None,
    ):
        """``capacity`` sizes the fingerprint table (slots; load is kept
        below 50%), ``log_capacity`` the append-only row log (positions =
        unique states; defaults to ``capacity``).  Decoupled because their
        per-entry costs differ by an order of magnitude: a table slot is 8
        bytes, a row-log position is ``4 * state_width`` (300+ bytes for
        the big register workloads) — a 2²⁶-slot table next to a
        12M-position log is how `paxos check 6` fits one 16 GB chip.

        ``auto_tune``: on a capacity-overflow flag (table overfull, row
        log full, dedup-buffer overflow) grow the tripped buffer IN PLACE
        and continue — the flagged wave never commits, the grown table is
        rebuilt from the committed row-log prefix on device, and no search
        work is redone.  Each growth recompiles (new buffer shapes), so
        sizing hints still save time, but no workload needs a hand-tuning
        session just to complete (VERDICT r3 weak #7).  Step-kernel
        encoding overflows are never retried: they mean the compiled
        model's layout cannot represent a reachable state.  Resumed runs
        adopt the snapshot's geometry and may auto-grow past it.

        ``sortless``: select the dedup path (docs/OBSERVABILITY.md
        "Sortless dedup and the rung ladders").  The default (None)
        resolves to the SORTLESS claim-plane election
        (hashset.insert_batch_claim — representatives elected inside
        the probe rounds, no 3-plane co-sort, density-insensitive)
        unless an explicit ``sort_lanes`` rung selects the sorted
        fallback path.  Under sortless, a flag-4 overflow of a
        TUNER-pinned compaction rung just climbs one rung (same as the
        sort path — the tuner guessed small, the election is fine).
        When the compaction buffer overflows at its full worst-case
        width (a duplicate-dense workload), one move relaxes
        ``dedup_factor`` to 1, FALLS BACK to the sort path, and lands
        it on the next power-of-two rung above the width that
        overflowed, as the tuner's floor (at one rung the sort path
        measured faster than the election on such batches); later
        overflows climb the rung.  Every move is non-committing — the
        wave re-runs, no work lost — and ``tuned_kwargs()`` persists
        the path and the rung, so a warm repeat starts there.  Passing
        BOTH ``sortless=True`` and ``sort_lanes`` keeps the election
        but makes the rung an explicit claim BUDGET: its overflow
        falls back at once, with no relax — the forcing knob tests/CI
        use to exercise the fallback on small models.

        ``sort_lanes``: the sorted fallback path's adaptive rung (the
        PR 12 ladder) — a power-of-two width for the per-wave
        compact/dedup-sort buffers, replacing the worst-case ``U =
        max(min(B, 16K), B/dedup_factor)``.  Passing it selects the
        sort path (see ``sortless``) warm-started at the rung; on the
        sort path with no rung the density tuner downshifts mid-run.
        A wave whose valid candidates exceed the rung overflows
        (flag 4, nothing commits) and the host retries one rung up —
        identical discovery sets at every rung, by construction.

        ``step_lanes``: the frontier-sized step rung (wave_loop.py's
        second ladder) — a power-of-two per-wave CHUNK width replacing
        ``max_frontier``, so the expansion kernel and valid-lane
        compaction scan ``step_lanes × max_actions`` candidate lanes
        instead of the full worst-case ``B``.  None starts at the full
        chunk and lets the frontier tuner downshift; a wave whose
        remaining level exceeds the rung raises the non-committing
        flag 128 and the host climbs one rung (×2, capped at
        ``max_frontier``).  The discovered rung rides the knob cache
        exactly like ``sort_lanes``.

        ``journal`` (a :class:`~stateright_tpu.runtime.journal.Journal`
        or a path) streams wave-level telemetry — per-call frontier
        size, unique states, dedup occupancy, device-call wall time,
        checkpoint/resume/grow events — as JSON lines (schema:
        docs/RUNTIME.md).  ``checkpoint_path`` enables periodic MID-RUN
        snapshots (atomic write + rename, ``save_snapshot`` format)
        every ``checkpoint_every_waves`` waves (counted in
        ``waves_per_call`` quanta — the host-loop granularity) or
        ``checkpoint_every_sec`` seconds (default 30 when only the path
        is given); a killed run resumes from the latest checkpoint via
        ``resume_from``.

        Visitors: a ``visitor()`` on the builder is supported at the
        granularity of one fused call (``waves_per_call`` waves): after
        each committed call the log rows it expanded are read back and
        every unique state is visited exactly once, at expansion, as a
        single-state path — BFS level order, log order within a level,
        no action prefix.  docs/OBSERVABILITY.md states the full
        contract."""
        super().__init__(options.model)
        import jax

        self._options = options
        self._compiled = compiled or compiled_model_for(options.model)
        # Symmetry reduction: dedup on the fingerprint of the CANONICAL
        # row while logging the original (the device form of
        # src/checker/dfs.rs:309-334).  Honored when the compiled model
        # declares a canonicalization; a silent fallback to no reduction
        # would report full-space counts as if they were reduced, so a
        # missing canon is a loud spawn error (VERDICT r5 missing #1).
        from .canon import make_canon

        self._canon = (
            make_canon(self._compiled)
            if options._symmetry is not None
            else None
        )
        if options._symmetry is not None and self._canon is None:
            raise ValueError(
                "spawn_tpu() with symmetry() requires the compiled model "
                f"to declare a canonicalization, but "
                f"{type(self._compiled).__name__} defines neither "
                "canon_spec() nor canon_rows (parallel/canon.py); use "
                "spawn_dfs() for host-side symmetry"
            )
        self._capacity = capacity
        self._log_capacity = log_capacity or capacity
        # An explicit log_capacity is a user memory-geometry decision;
        # auto-tune must not silently inflate it when the TABLE grows.
        self._log_capacity_explicit = log_capacity is not None
        self._dedup_factor = dedup_factor
        # Adaptive sort-geometry rung (wave_loop.py's ladder, ROADMAP
        # #1): ``sort_lanes`` sizes the per-wave compact/sort/probe
        # buffers to a power-of-two rung instead of the worst-case U.
        # None starts at the full buffer (today's program) and lets the
        # density-driven tuner downshift once measured evidence exists;
        # an explicit rung (a knob-cache warm start) skips the ramp.
        # Overflowing a rung is the non-committing flag 4: the host
        # climbs one rung and re-runs the chunk, no work lost.
        from .wave_loop import (
            SORT_RUNG_MIN, STEP_RUNG_MIN, clamp_sort_lanes,
            clamp_step_lanes,
        )

        self._sort_lanes = (
            None if sort_lanes is None else clamp_sort_lanes(sort_lanes)
        )
        # The density tuner only drives runs that did NOT pin a rung:
        # an explicit sort_lanes is a warm start (or a measurement leg)
        # the tuner must not fight; the overflow ladder stays armed.
        self._sort_tune = sort_lanes is None
        self._sort_rung_floor = SORT_RUNG_MIN
        self._sort_peak_valid = 0.0
        self._sort_quanta = 0
        # Flag-4 moves (metrics()): rung climbs, the landing after a
        # relax included, and sortless → sort flips.
        self._dedup_rung_climbs = 0
        self._dedup_fallbacks = 0
        # Dedup probe rounds run at each stage width, and hand-offs into
        # each taken by slice (metrics()).
        self._stage_rounds: Dict[int, int] = {}
        self._stage_slices: Dict[int, int] = {}
        # Dedup-path selection (the sortless claim-plane election is the
        # default; an explicit sort_lanes rung selects the sorted
        # fallback path — see the docstring).
        self._sortless = (
            (sort_lanes is None) if sortless is None else bool(sortless)
        )
        # Frontier-sized step rung (wave_loop.py's second ladder).
        self._step_lanes = (
            None if step_lanes is None else clamp_step_lanes(step_lanes)
        )
        self._step_tune = step_lanes is None
        self._step_rung_floor = STEP_RUNG_MIN
        self._step_peak_frontier = 0.0
        self._step_quanta = 0
        self._auto_tune = bool(auto_tune)
        self._max_frontier = max_frontier
        # Spawn-time guard on the compact/dedup buffer width: configs past
        # _MAX_UNIQUE_BUFFER hard-CRASH the TPU worker mid-wave instead of
        # flagging (see the constant's comment), so a requested geometry in
        # the crash band is clamped here — same rule the auto-tune growth
        # path applies — with a logged warning.
        from .hashset import unique_buffer_size

        a = self._compiled.max_actions
        u_cap = max_safe_unique_lanes(self._compiled.state_width)
        clamped = False
        while (
            self._max_frontier > 2048
            and unique_buffer_size(self._max_frontier * a, self._dedup_factor)
            > u_cap
        ):
            self._max_frontier //= 2
            clamped = True
        if (
            unique_buffer_size(self._max_frontier * a, self._dedup_factor)
            > u_cap
        ):
            # Over budget even at the floor frontier (max_actions > 256):
            # refuse loudly, like the _grow path — proceeding means a
            # worker crash, not an overflow flag.
            raise ValueError(
                f"chunk geometry (max_frontier={self._max_frontier}, "
                f"max_actions={a}, dedup_factor={dedup_factor}) exceeds "
                "the device-safe compact-buffer band even at the floor "
                "frontier; raise dedup_factor"
            )
        if clamped:
            import logging

            logging.getLogger(__name__).warning(
                "spawn_tpu: max_frontier clamped to %d (max_actions=%d, "
                "dedup_factor=%d): the requested chunk geometry exceeds "
                "the device-safe compact-buffer band",
                self._max_frontier, a, dedup_factor,
            )
        if waves_per_call is None:
            from .wave_common import default_waves_per_call

            waves_per_call = default_waves_per_call(options)
        self._waves_per_call = waves_per_call
        self._device = device or jax.devices()[0]
        self._properties = self._model.properties()
        if len(self._properties) > 32:
            raise ValueError("at most 32 properties supported on device")
        self._ev_indices = [
            i
            for i, p in enumerate(self._properties)
            if p.expectation is Expectation.EVENTUALLY
        ]
        self._discovery_slots: Dict[str, int] = {}
        self._state_count = 0
        self._unique_count = 0
        self._max_depth = 0
        self._done = threading.Event()
        self._errors: List[BaseException] = []
        self._lock = threading.Lock()
        self._resume_from = resume_from
        if options._visitor is not None:
            # The device visitor stream: the fused loop's post-commit
            # hook, present only when a visitor is attached.
            self._wl_after_commit = self._visit_committed
        from ..obs.metrics import MetricsRegistry

        self._metrics = MetricsRegistry()
        from ..runtime.journal import as_journal

        self._journal = as_journal(journal)
        self._checkpoint_path = checkpoint_path
        self._ckpt_every_waves = checkpoint_every_waves
        self._ckpt_every_sec = checkpoint_every_sec
        if (
            checkpoint_path is not None
            and checkpoint_every_waves is None
            and checkpoint_every_sec is None
        ):
            self._ckpt_every_sec = 30.0
        self._carry_dev: Optional[dict] = None  # full run state at stop
        self._final_load_factor: Optional[float] = None  # metrics() cache
        self._discoveries_cache: Optional[Dict[str, Path]] = None
        self._tables_dev: Optional[tuple] = None  # (parent, rows) on device

        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # --- device program ------------------------------------------------------

    def _build_run(self):
        """Build the fused multi-chunk program.

        States live in an APPEND-ONLY row log indexed by *BFS position*
        (the order states were first discovered), with explicit level
        boundaries: each loop iteration expands one chunk (≤ ``f``
        positions) of the current level, appends newly inserted states'
        rows at the tail, and advances ``depth`` only when a level is fully
        drained — so levels may be arbitrarily wide (no frontier-overflow
        failure mode) while depth/target semantics stay exactly those of a
        level-at-a-time BFS.

        Because positions in a level are contiguous, the chunk read is one
        contiguous ``dynamic_slice`` and the append is one contiguous
        ``dynamic_update_slice`` — the only randomly-indexed memory left is
        the hash table itself.  The log is a FLAT u32 buffer: a 2-D
        ``[positions, W]`` layout gets its minor dim tile-padded to 128
        lanes (W=42 → 3×, W=75 → 1.7× HBM — the round-3 store shipped that
        way and it capped paxos c≥4 and raft depth 12 on a 16 GB chip),
        and XLA re-imposes that layout on a transposed store, so flat +
        block access is the only padding-free shape.  Offsets can exceed
        2³¹ (u32 starts; validated on-device up to 10.7 GB buffers).

        Carry: (key_hi, key_lo, rows, parent, ebits, level_start,
        level_end, tail, sc_lo, sc_hi, unique_count, depth, disc[P],
        waves_left, flags, probe).  ``sc_lo``/``sc_hi`` form the 64-bit
        generated-state counter (no u64 on device); ``probe`` counts the
        call's dedup probe rounds and slice hand-offs per stage
        (``STAT_ROUNDS``, ``STAT_SLICES``).  flag
        values: 1 = table overfull (probe failure or beyond 50% load); 2 =
        row log full (unique states exceeded ``log_capacity``); 4 = insert
        dedup-buffer overflow; 8 = model step kernel capacity overflow.
        """
        import jax
        import jax.numpy as jnp

        from ..ops.device_fp import device_fp64
        from .hashset import (
            HashSet, compact_valid, insert_batch, insert_batch_claim,
            insert_batch_compact,
        )
        from .wave_common import wave_eval

        cm = self._compiled
        w = cm.state_width
        # State identity = the leading fp_words of a row (compiled.py);
        # trailing words ride along with the first-inserted representative.
        fpw = cm.fp_words or w
        # Symmetry: fingerprints (and only fingerprints) come from the
        # canonical row — the row log, parents, property evaluation, and
        # path re-execution all see the ORIGINAL rows, so discovery
        # traces stay bit-identical to reference semantics.
        canon = self._canon

        def fp_of(rows):
            rows_c = rows if canon is None else jax.vmap(canon)(rows)
            return device_fp64(rows_c[:, :fpw])
        a = cm.max_actions
        f = self._max_frontier  # worst-case chunk (seed/pad geometry)
        # The live step-geometry rung: the per-wave chunk width.  A wave
        # whose remaining level exceeds it raises the non-committing
        # flag 128 (compiled out at the top rung, where the clamp is
        # impossible) and the host climbs one rung.
        f_eff = self._step_width()
        cap = self._capacity
        qcap = self._log_capacity  # one row-log position per unique state
        pad = self._block_pad()  # append-block lanes past qcap
        dedup_factor = self._dedup_factor
        # Dedup path: the sortless claim-plane election by default; the
        # sorted fallback rung when selected (knob cache / explicit).
        sortless = self._sortless
        # The live sort-geometry rung: the compact/dedup/insert buffers
        # below span this width; everything downstream (probe rounds,
        # result gathers, the append-block compaction source) follows
        # the compacted buffer's shape automatically.  None = the
        # worst-case buffer of the LIVE (step-rung-sized) batch; pinned
        # only when a rung exists (sort path, or a sortless forcing
        # run capping the claim compaction buffer).
        sort_lanes = (
            None if self._sort_lanes is None else self._sort_width()
        )
        props = self._properties
        n_props = len(props)
        ev_indices = self._ev_indices
        target_depth = self._options._target_max_depth or 0

        # finish_when, mirrored on device (wave_common.py): the fused loop
        # exits as soon as the policy is satisfied, so e.g. time-to-first-
        # violation runs don't pay a host sync per chunk.
        from .wave_common import make_finish_when_device

        fw_found_matched = make_finish_when_device(
            self._options._finish_when, props
        )

        def fw_matched(disc):
            import jax.numpy as jnp

            return fw_found_matched(disc != jnp.uint32(0xFFFFFFFF))

        def wave_body(carry):
            (
                key_hi,
                key_lo,
                rows,
                parent,
                ebits,
                level_start,
                level_end,
                tail,
                sc_lo,
                sc_hi,
                unique_count,
                depth,
                disc,
                waves_left,
                flags,
                probe,
            ) = carry

            count = jnp.minimum(level_end - level_start, jnp.uint32(f_eff))
            lane = jnp.arange(f_eff, dtype=jnp.uint32)
            active = lane < count
            ids = level_start + lane  # BFS positions are the state ids
            states = jax.lax.dynamic_slice(
                rows, (level_start * jnp.uint32(w),), (f_eff * w,)
            ).reshape(f_eff, w)
            eb_chunk = jax.lax.dynamic_slice(
                ebits, (level_start,), (f_eff,)
            )

            disc_prev = disc
            disc, eb, nexts, valid, generated, step_flag = wave_eval(
                cm, props, ev_indices, states, active, ids, eb_chunk, disc,
                allow_two_phase=True,
            )

            flat_valid = valid.reshape(f_eff * a)
            if nexts is None:
                # TWO-PHASE expansion: compact the ~5% valid lanes FIRST,
                # then construct successors (word assembly + per-lane slot
                # re-sort — the expensive half of the step kernel) only
                # for the survivors, and fingerprint U lanes instead of B.
                from .hashset import compact_valid_indices

                v_orig, v_act, n_valid, v_overflow = compact_valid_indices(
                    flat_valid, dedup_factor, sort_lanes=sort_lanes
                )
                src_state = v_orig // jnp.uint32(a)
                lane_k = v_orig % jnp.uint32(a)
                par_rows = states[src_state]  # [U, w] gather
                nexts_u, _valid_u, lane_flags_u = jax.vmap(
                    cm.step_lane
                )(par_rows, lane_k)
                step_flag = step_flag | jnp.any(lane_flags_u & v_act)
                hi, lo = fp_of(nexts_u)
                compact_rows = nexts_u
                compact_src = src_state
            else:
                # Dedup + insert, in compact form: results come back
                # U-sized (one lane per distinct key), so the append below
                # costs O(distinct keys) instead of O(candidate lanes).
                flat = nexts.reshape(f_eff * a, w)
                hi_b, lo_b = fp_of(flat)
                v_hi, v_lo, v_orig, v_act, v_overflow = compact_valid(
                    hi_b, lo_b, flat_valid, dedup_factor,
                    sort_lanes=sort_lanes,
                )
                hi, lo = v_hi, v_lo
                compact_rows = None
                compact_src = None
            if sortless:
                # SORTLESS default: claim-plane election inside the
                # probe rounds (hashset.insert_batch_claim) — no
                # 3-plane co-sort; representatives (lowest lane of each
                # equal-key run) and the downstream indexing contract
                # are identical (u_origin is the identity map).
                (
                    table, _u_slot, u_new, u_origin, _u_active, probe_ok,
                    dd_overflow, stage_rounds, stage_slices,
                ) = insert_batch_claim(
                    HashSet(key_hi, key_lo), hi, lo, v_act,
                    with_rounds=True,
                )
            else:
                (
                    table, _u_slot, u_new, u_origin, _u_active, probe_ok,
                    dd_overflow, stage_rounds, stage_slices,
                ) = insert_batch_compact(
                    HashSet(key_hi, key_lo), hi, lo, v_act,
                    dedup_factor=1, with_rounds=True,
                )
            probe = probe + jnp.concatenate(
                [stage_rounds, stage_slices]
            ).astype(jnp.uint32)
            dd_overflow = dd_overflow | v_overflow
            n_new = jnp.sum(u_new, dtype=jnp.uint32)

            # An overflowing wave must NOT commit: the host grows the
            # tripped buffer in place (rebuilding the table from the row
            # log) and re-runs this chunk, so the carry it reads back has
            # to be exactly the pre-wave state.  The table itself may hold
            # the aborted wave's keys — every growth path rehashes it from
            # the committed log prefix, which erases them.
            flags = flags | jnp.where(probe_ok, 0, 1).astype(jnp.uint32)
            flags = flags | jnp.where(
                (unique_count + n_new) * 2 > jnp.uint32(cap), 1, 0
            ).astype(jnp.uint32)
            flags = flags | jnp.where(
                tail + n_new > jnp.uint32(qcap), 2, 0
            ).astype(jnp.uint32)
            flags = flags | jnp.where(dd_overflow, 4, 0).astype(jnp.uint32)
            flags = flags | jnp.where(step_flag, 8, 0).astype(jnp.uint32)
            if f_eff < f:
                # Step-rung clamp (flag 128, non-committing): the
                # remaining level exceeds the rung — the host climbs
                # one rung and re-runs; compiled out at the top rung,
                # where the clamp is impossible by construction.
                flags = flags | jnp.where(
                    level_end - level_start > jnp.uint32(f_eff), 128, 0
                ).astype(jnp.uint32)
            commit = flags == 0
            n_new = jnp.where(commit, n_new, jnp.uint32(0))
            count = jnp.where(commit, count, jnp.uint32(0))
            # Discoveries too: the re-run of an aborted chunk must see the
            # pre-wave discovery state, or first-discovery side effects
            # (e.g. eventually-bit awaiting masks) would diverge from a
            # committed execution of the same wave.
            disc = jnp.where(commit, disc, disc_prev)
            unique_count = unique_count + n_new
            new_lo = sc_lo + jnp.where(commit, generated, jnp.uint32(0))
            sc_hi = sc_hi + (new_lo < sc_lo).astype(jnp.uint32)
            sc_lo = new_lo

            # Select the newly inserted representatives (in sorted-key
            # order, matching position assignment) and APPEND their rows,
            # parent positions, and ebits as three contiguous block writes
            # — no table-sized scatters at all.  ``sel`` lanes beyond
            # n_new alias lane 0; their garbage lands at positions ≥ the
            # new tail, which only ever get (re)written by later appends
            # before any read (an aborted wave's whole block is such
            # garbage: tail does not advance).  First-inserter ebits
            # semantics are unchanged (u_origin is the lowest lane of each
            # key run).
            u = u_new.shape[0]
            from .wave_common import compact

            sel = compact(u_new, jnp.arange(u, dtype=jnp.uint32), pad)
            sel_u = u_origin[sel]  # lane in the compacted valid buffer
            if compact_rows is not None:  # two-phase: rows already built
                rows_blk = compact_rows[sel_u]  # [pad, w] gather
                src_state = compact_src[sel_u]
            else:
                idxs = v_orig[sel_u]  # original flat candidate lane
                rows_blk = flat[idxs]  # [pad, w] gather
                src_state = idxs // jnp.uint32(a)
            par_blk = level_start + src_state
            eb_blk = eb[src_state]
            rows = jax.lax.dynamic_update_slice(
                rows, rows_blk.reshape(-1), (tail * jnp.uint32(w),)
            )
            parent = jax.lax.dynamic_update_slice(parent, par_blk, (tail,))
            ebits = jax.lax.dynamic_update_slice(ebits, eb_blk, (tail,))
            tail = tail + n_new

            # Advance within the level; roll the level boundary when drained.
            level_start = level_start + count
            done_level = (level_start >= level_end) & commit
            depth = depth + done_level.astype(jnp.uint32)
            level_end = jnp.where(done_level, tail, level_end)

            return (
                table.key_hi,
                table.key_lo,
                rows,
                parent,
                ebits,
                level_start,
                level_end,
                tail,
                sc_lo,
                sc_hi,
                unique_count,
                depth,
                disc,
                waves_left - 1,
                flags,
                probe,
            )

        def wave_cond(carry):
            level_start = carry[5]
            level_end = carry[6]
            depth = carry[11]
            disc = carry[12]
            waves_left = carry[13]
            flags = carry[14]
            go = (level_start < level_end) & (waves_left > 0) & (flags == 0)
            if target_depth:
                # The next chunk would expand states at depth+1; the
                # reference skips jobs with depth >= target at pop time, so
                # states at the target depth are counted but not expanded.
                go = go & (depth < target_depth - 1)
            go = go & ~fw_matched(disc)
            return go

        waves_per_call = self._waves_per_call

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
        def run(key_hi, key_lo, rows, parent, ebits, stats):
            # All host-visible scalars travel in ONE small stats array,
            # one host sync per call.
            carry = (
                key_hi,
                key_lo,
                rows,
                parent,
                ebits,
                stats[STAT_LEVEL_START],
                stats[STAT_LEVEL_END],
                stats[STAT_TAIL],
                stats[STAT_SC_LO],
                stats[STAT_SC_HI],
                stats[STAT_UNIQUE],
                stats[STAT_DEPTH],
                stats[STAT_DISC : STAT_DISC + len(props)],
                jnp.int32(waves_per_call),
                jnp.uint32(0),
                jnp.zeros((STAT_DISC - STAT_ROUNDS,), jnp.uint32),
            )
            out = jax.lax.while_loop(wave_cond, wave_body, carry)
            stats_out = jnp.concatenate(
                [jnp.stack([out[5], out[6], out[7], out[8], out[9],
                            out[10], out[11], out[14]]), out[15], out[12]]
            )
            return out[0], out[1], out[2], out[3], out[4], stats_out

        eb0 = (1 << len(ev_indices)) - 1

        @jax.jit
        def seed(init_rows, n_init):
            """One dispatch creates EVERY device buffer and seeds the init
            states: the table planes, row log, parent links, and ebits are
            minted inside the program, and only the n_init packed init
            rows are uploaded."""
            from .wave_common import compact

            init_padded = jnp.zeros((f, w), jnp.uint32)
            init_padded = jax.lax.dynamic_update_slice(
                init_padded, init_rows, (0, 0)
            )
            key_hi = jnp.zeros((cap,), jnp.uint32)
            key_lo = jnp.zeros((cap,), jnp.uint32)
            rows = jnp.zeros(((qcap + pad) * w,), jnp.uint32)
            parent = jnp.full((qcap + pad,), NO_SLOT_HOST, jnp.uint32)
            ebits = jnp.zeros((qcap + pad,), jnp.uint32)

            hi, lo = fp_of(init_padded)
            seed_active = jnp.arange(f, dtype=jnp.uint32) < n_init
            # dedup_factor=1: the unique buffer covers the whole batch, so
            # seed failure is unambiguously a table-probe overflow — the
            # one condition growing ``capacity`` (flag 1) actually fixes.
            table, _slot, is_new, probe_ok, dd_overflow = insert_batch(
                HashSet(key_hi, key_lo), hi, lo, seed_active, dedup_factor=1
            )
            # Unique init states take positions 0..fcount in lane order.
            sel = compact(is_new, jnp.arange(f, dtype=jnp.uint32), f)
            rows = jax.lax.dynamic_update_slice(
                rows, init_padded[sel].reshape(-1), (jnp.uint32(0),)
            )
            ebits = jax.lax.dynamic_update_slice(
                ebits, jnp.full((f,), eb0, jnp.uint32), (jnp.uint32(0),)
            )
            fcount = jnp.sum(is_new, dtype=jnp.uint32)
            seed_fail = (~(probe_ok & ~dd_overflow)).astype(jnp.uint32)
            stats = jnp.concatenate(
                [
                    jnp.stack([
                        jnp.uint32(0),  # level_start
                        fcount,  # level_end
                        fcount,  # tail
                        n_init,  # sc_lo
                        jnp.uint32(0),  # sc_hi
                        fcount,  # unique_count
                        jnp.uint32(0),  # depth
                        seed_fail,  # flags (nonzero = seed overflow)
                    ]),
                    jnp.zeros((STAT_DISC - STAT_ROUNDS,), jnp.uint32),
                    jnp.full((len(props),), NO_SLOT_HOST, jnp.uint32),
                ]
            )
            return table.key_hi, table.key_lo, rows, parent, ebits, stats

        return seed, run

    def _programs(self):
        key = (
            self._compiled.cache_key(),
            # The two-phase gate is evaluated at trace time (wave_eval's
            # hasattr checks) — it must key the program, or a model whose
            # capability set changes (e.g. tests forcing the single-phase
            # branch) would silently re-run the wrong compiled program.
            hasattr(self._compiled, "step_valid")
            and hasattr(self._compiled, "step_lane"),
            # Symmetry is a trace-time branch (canonical-fp dedup): a
            # sym and a non-sym run of the same model must never share a
            # compiled program.
            self._canon is not None,
            self._capacity,
            self._log_capacity,
            self._max_frontier,
            self._dedup_factor,
            self._sortless,  # the dedup path is a trace-time branch
            self._sort_width(),  # the live sort-geometry rung
            self._step_width(),  # the live step-geometry rung
            self._waves_per_call,  # baked into run() as a constant
            tuple(p.expectation for p in self._properties),
            (
                self._options._finish_when._kind,
                tuple(sorted(self._options._finish_when._names)),
                tuple(p.name for p in self._properties),
            ),
            self._options._target_max_depth or 0,
        )
        from .wave_common import cached_program

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, self._build_run,
            label=f"{type(self).__name__}.fused",
            journal=self._journal,
            provenance=self._key_provenance(),
        )

    def _key_provenance(self) -> dict:
        """The human-readable knobs behind the program-cache keys — what
        a journaled ``compile`` event says CHANGED when a warm daemon
        recompiles (docs/OBSERVABILITY.md "Compile events")."""
        return {
            "model": type(self._compiled).__name__,
            "capacity": self._capacity,
            "log_capacity": self._log_capacity,
            "max_frontier": self._max_frontier,
            "dedup_factor": self._dedup_factor,
            "sortless": self._sortless,
            "sort_lanes": self._sort_width(),
            "step_lanes": self._step_width(),
            "waves_per_call": self._waves_per_call,
            "symmetry": self._canon is not None,
        }

    # --- host loop -----------------------------------------------------------

    def _run(self) -> None:
        try:
            self._check()
        except BaseException as e:  # propagate at join, like the host engine
            self._errors.append(e)
        finally:
            self._done.set()

    def _check(self) -> None:
        """Run to completion.  In-loop overflows grow in place inside
        ``_check_once``; the restart loop here only handles SEED-time
        overflow (raised before any search work exists).  The user
        deadline is fixed here, across attempts — a retry must not reset
        the clock."""
        import time as _time

        opts = self._options
        deadline = (
            _time.monotonic() + opts._timeout
            if opts._timeout is not None
            else None
        )
        attempts = 6 if self._auto_tune else 1
        for attempt in range(attempts):
            try:
                return self._check_once(deadline)
            except _OverflowRetry as o:
                grown = self._grow(o.flag) if attempt < attempts - 1 else None
                if grown is None:
                    raise RuntimeError(o.message) from None
                import logging

                logging.getLogger(__name__).warning(
                    "auto-tune: %s; restarting with %s", o.message, grown
                )
                if self._journal:
                    self._journal.append(
                        "grow", seed=True, flags=o.flag, grown=grown
                    )
                with self._lock:  # discard the aborted attempt's progress
                    self._discovery_slots.clear()
                    self._state_count = 0
                    self._unique_count = 0
                    self._max_depth = 0

    def _grow(self, flag: int):
        """Adjust the knob named by ``flag``; None if it cannot grow.

        Table growth is aggressive (×16 — slots are 8 bytes and every
        retry pays a recompile plus a partial re-run) and drags a
        defaulted row log with it; the row log alone grows ×2 (positions
        are 4·state_width bytes and copy-growth holds old + new at once);
        a dedup overflow relaxes the factor toward the always-safe 1.
        """
        row_bytes = 4 * self._compiled.state_width
        log_cap_bound = max(self._log_capacity, _ROW_LOG_BYTE_BUDGET // row_bytes)
        if flag & 1:
            if self._capacity >= _MAX_TABLE_CAPACITY:
                return None
            self._capacity = min(self._capacity * 16, _MAX_TABLE_CAPACITY)
            # A DEFAULTED log tracks the table (unique states need both a
            # slot and a position — growing one without the other just
            # schedules the next overflow); an explicit one is the user's
            # memory geometry and only grows on its own flag.  The drag is
            # ×2 like the log's own growth step, NOT straight to
            # capacity/2: a row-log position costs 4·state_width bytes, so
            # at w=77 a capacity/2 drag after the ×16 table jump would
            # allocate gigabytes past what the run needs and risk HBM
            # exhaustion in the copy-growth transient.
            if not self._log_capacity_explicit:
                self._log_capacity = min(
                    max(
                        self._log_capacity,
                        min(self._capacity // 2, self._log_capacity * 2),
                    ),
                    log_cap_bound,
                )
            return f"capacity={self._capacity} log_capacity={self._log_capacity}"
        if flag & 2:
            if self._log_capacity >= log_cap_bound:
                return None
            # ×2, not ×16: a row-log position costs 4·state_width bytes
            # and copy-growth transiently holds old + new logs at once.
            self._log_capacity = min(self._log_capacity * 2, log_cap_bound)
            return f"log_capacity={self._log_capacity}"
        if flag & 128:
            from .wave_loop import climb_step_rung

            # Step-rung ladder: the live frontier level exceeded the
            # chunk rung — climb one rung (×2, capped at max_frontier,
            # where the clamp flag is compiled out); the climbed rung
            # becomes the floor the frontier tuner may never revisit.
            return climb_step_rung(self, self._max_frontier)
        if flag & 4:
            from .hashset import unique_buffer_size
            from .wave_loop import (
                climb_sort_rung, fall_back_to_sort, land_on_sort_rung,
                relax_dedup_geometry, reset_sort_rung_to_full,
            )

            # EXPLICIT claim-budget cap first: ``sortless=True`` with a
            # caller-pinned ``sort_lanes`` is a budget ("elect within
            # this compaction width or don't bother"), not a tuner
            # guess — its overflow is the per-workload fallback signal,
            # not a climb (the forcing knob tests/CI use, and the one
            # spawn shape tuned_kwargs deliberately never emits: a
            # sortless run's pinned rung is a tuner detail, so a warm
            # repeat re-arms the tuner instead of inheriting a
            # one-notch-tight explicit cap).
            if self._sortless and not self._sort_tune:
                self._dedup_fallbacks += 1
                return fall_back_to_sort(self)
            # Compact-rung ladder next, on BOTH dedup paths: when the
            # compact/claim buffers run at a TUNER-pinned rung below
            # the full U, a flag-4 overflow means the rung was too
            # small — the density tuner downshifted it past a growing
            # level — not the path or the worst-case geometry.  Climb
            # one rung (×2, capped at U) and re-run; the climbed rung
            # becomes the floor the tuner may never revisit.  A
            # rung-level overflow must NOT abandon the claim election:
            # the sorted path in the identical situation just climbs,
            # and the sharded engine orders its flag-4 dispatch the
            # same way (climb before relax) — the rule lives in
            # wave_loop (climb_sort_rung), shared so the engines
            # cannot drift.
            full = self._wl_full_sort_lanes()
            note = climb_sort_rung(self, full)
            if note is not None:
                self._dedup_rung_climbs += 1
                return note
            # The FULL buffer overflowed: the valid batch exceeded the
            # compaction buffer at its worst-case width for this
            # dedup_factor (a duplicate-dense model).  Relax below and
            # land on a narrow sort rung in the same move.  Without
            # _land_on_rung (the tiered engine) a sortless run first
            # flips alone to the sorted fallback rung
            # (wave_loop.fall_back_to_sort) and relaxes on its next
            # overflow.  The flagged wave committed nothing, so either
            # re-run is exact; tuned_kwargs persists path and rung.
            if self._sortless and not self._land_on_rung:
                self._dedup_fallbacks += 1
                return fall_back_to_sort(self)
            # Straight to the always-safe 1, not stepwise (the
            # intermediate dd=2-at-doubled-frontier stop measured as a
            # NEW worker-crash geometry on the 61.5M-state 2pc run),
            # halving the frontier while U exceeds the device-safe band
            # — the rule lives in wave_loop.relax_dedup_geometry, shared
            # with the sharded engine's flag-4 retry so the two engines'
            # growth semantics cannot drift.
            a = self._compiled.max_actions
            u_cap = max_safe_unique_lanes(self._compiled.state_width)
            relaxed = relax_dedup_geometry(
                self._max_frontier,
                self._dedup_factor,
                lambda c, dd: unique_buffer_size(c * a, dd),
                u_cap,
                chunk_label="max_frontier",
            )
            if relaxed is None:
                # Already at dd=1, or even the floor frontier cannot
                # keep the buffer in the safe band (max_actions > 256):
                # refuse loudly rather than proceed into the
                # worker-crash band.
                return None
            self._dedup_factor, self._max_frontier, note = relaxed
            if self._land_on_rung and self._sort_tune:
                # The next rung above the width that overflowed, as the
                # tuner's floor; a sortless run flips to the sort path
                # (evidence + geometry re-journal in the shared helper).
                self._dedup_fallbacks += int(self._sortless)
                self._dedup_rung_climbs += 1
                return f"{note}; {land_on_sort_rung(self, full)}"
            # An explicit rung (or the tiered engine): the relaxed dd=1
            # geometry starts at its own full width.
            reset_sort_rung_to_full(self, full)
            return note
        return None

    def _check_once(self, deadline=None) -> None:
        import jax
        import jax.numpy as jnp

        cm = self._compiled
        props = self._properties

        def sized(arr_np, n):
            """Pad/trim a 1-D snapshot array to ``n`` (the tail padding
            holds garbage by construction, so resumes may use different
            block-pad tuning than the run that saved the snapshot)."""
            if arr_np.shape[0] < n:
                return np.concatenate(
                    [arr_np, np.zeros(n - arr_np.shape[0], arr_np.dtype)]
                )
            return arr_np[:n]

        if self._resume_from is not None:
            # A resume ADOPTS the snapshot's table/log geometry (table
            # slots depend on the capacity mask, and a run that auto-tuned
            # mid-flight persisted the GROWN sizes, not the spawn
            # arguments) — only model/property identity is key-checked.
            snap = np.load(self._resume_from, allow_pickle=False)
            if "capacity" not in snap.files:
                raise ValueError(
                    "snapshot predates the rowlog-v3 format (no persisted "
                    "geometry); re-run the original check to produce a "
                    "fresh snapshot"
                )
            self._capacity = int(snap["capacity"])
            self._log_capacity = int(snap["log_capacity"])

        f = self._max_frontier
        qcap = self._log_capacity
        pad = self._block_pad()

        with jax.default_device(self._device):
            seed, run = self._programs()
            if self._resume_from is not None:
                want_key = self._snapshot_key()
                got_key = str(snap["engine_key"])
                if got_key != want_key:
                    raise ValueError(
                        "snapshot does not match this checker configuration"
                        f" (snapshot {got_key}, expected {want_key})"
                    )
                # Every upload goes through _device_owned: these arrays
                # are DONATED to the run program, and donating a borrowed
                # host-upload buffer corrupts the run (see the helper).
                key_hi = _device_owned(jnp.asarray(snap["key_hi"]))
                key_lo = _device_owned(jnp.asarray(snap["key_lo"]))
                rows = _device_owned(jnp.asarray(
                    sized(np.asarray(snap["rows"]), (qcap + pad) * cm.state_width)
                ))
                parent = _device_owned(
                    jnp.asarray(sized(np.asarray(snap["parent"]), qcap + pad))
                )
                ebits = _device_owned(
                    jnp.asarray(sized(np.asarray(snap["ebits"]), qcap + pad))
                )
                disc_np = np.asarray(snap["disc"]).astype(np.uint32)
                stats = _device_owned(jnp.asarray(host_stats(
                    int(snap["level_start"]), int(snap["level_end"]),
                    int(snap["tail"]),
                    (int(snap["sc_hi"]) << 32) | int(snap["sc_lo"]),
                    int(snap["unique_count"]), int(snap["depth"]), disc_np,
                )))
                with self._lock:
                    self._state_count = (
                        int(snap["sc_hi"]) << 32
                    ) | int(snap["sc_lo"])
                    self._unique_count = int(snap["unique_count"])
                    self._max_depth = int(snap["depth"])
                    # States the saved run expanded were visited there.
                    self._visited_to = int(snap["level_start"])
                    # Discovery names derive from the persisted disc array
                    # and the property order, which the key above pins.
                    for p, prop in enumerate(props):
                        if int(disc_np[p]) != NO_SLOT_HOST:
                            self._discovery_slots[prop.name] = int(disc_np[p])
                if self._journal:
                    self._journal.append(
                        "resume",
                        path=self._resume_from,
                        unique=self._unique_count,
                        states=self._state_count,
                        depth=self._max_depth,
                    )
            else:
                # Seed init states: ONE upload (the packed init rows) +
                # ONE dispatch that creates every device buffer.
                init = cm.init_packed()
                n_init = init.shape[0]
                if n_init > f:
                    # The one level still bounded by the chunk size: seeding
                    # writes the init batch into the log in one program.
                    raise ValueError(
                        f"{n_init} init states exceed the chunk size "
                        f"({f}); raise spawn_tpu(max_frontier=...) to at "
                        "least the init-state count (interior levels are "
                        "unbounded)"
                    )
                key_hi, key_lo, rows, parent, ebits, stats = seed(
                    jnp.asarray(init.astype(np.uint32)), jnp.uint32(n_init)
                )
                stats_h = np.asarray(stats)
                if int(stats_h[STAT_FLAGS]):
                    # Same auto-tunable condition as the in-loop flag 1: a
                    # dense init batch can exhaust probing before wave 0.
                    raise _OverflowRetry(
                        1,
                        "init-state seeding overflowed the fingerprint "
                        "table; raise spawn_tpu(capacity=...)",
                    )
                self._state_count = n_init
                self._unique_count = int(stats_h[STAT_UNIQUE])
                self._visited_to = 0

            # The steady-state loop is the SHARED wave-loop core
            # (parallel/wave_loop.py) — journal/metrics/checkpoint
            # cadence, overflow dispatch (in-place auto-grow via
            # _wl_grow, loud raise otherwise), and termination live
            # there, identical to the sharded engine by construction.
            from .wave_loop import FusedWaveLoop, finalize_run

            self._run_fn = run
            self._loop_qcap, self._loop_pad = qcap, pad
            carry = (key_hi, key_lo, rows, parent, ebits, stats)
            carry, _waves = FusedWaveLoop(self).run(carry, deadline)
            key_hi, key_lo, rows, parent, ebits, stats = carry
            stats_h = self._last_stats_h
            if self._options._visitor is not None:
                # A stop on a flagged call keeps the waves that call
                # committed before the flagged one: visit them too.
                self._visit_expanded(rows, int(stats_h[STAT_LEVEL_START]))

            # Keep the device arrays; path reconstruction walks the parent
            # chain ON DEVICE and reads back only the chain (a full-table
            # pull would be GBs).
            self._tables_dev = (parent, rows)
            # Full run state, for snapshotting (via the shared finalize):
            # the reference cannot persist a run's visited set at all
            # (SURVEY §5); here the whole checker state is a handful of
            # dense arrays.  Scalars come from the last stats readback
            # (same npz keys as before).
            finalize_run(self, self._carry_from(
                key_hi, key_lo, rows, parent, ebits, stats_h
            ))

    # --- shared wave-loop adapter (parallel/wave_loop.py) --------------------

    def _wl_call(self, carry):
        return self._run_fn(*carry)

    def _wl_view(self, carry):
        from .wave_loop import WaveView

        # ONE small sync per waves_per_call chunks: every scalar the
        # host reads travels in the stats vector.
        stats_h = np.asarray(carry[5])
        self._last_stats_h = stats_h
        self._count_stage_rounds(
            stats_h[STAT_ROUNDS:STAT_SLICES], stats_h[STAT_SLICES:STAT_DISC]
        )
        remaining = int(stats_h[STAT_LEVEL_END]) - int(
            stats_h[STAT_LEVEL_START]
        )
        disc = []
        for p, prop in enumerate(self._properties):
            s = int(stats_h[STAT_DISC + p])
            if s != NO_SLOT_HOST:
                disc.append((prop.name, s))
        unique_h = int(stats_h[STAT_UNIQUE])
        return WaveView(
            waves_this_call=self._waves_per_call,
            remaining=remaining,
            depth=int(stats_h[STAT_DEPTH]),
            flags=int(stats_h[STAT_FLAGS]),
            unique=unique_h,
            states=(int(stats_h[STAT_SC_HI]) << 32)
            | int(stats_h[STAT_SC_LO]),
            occupancy=unique_h / self._capacity,
            discoveries=tuple(disc),
            extra={"tail": int(stats_h[STAT_TAIL])},
        )

    def _wl_set_discovery(self, name: str, slot: int) -> None:
        self._discovery_slots.setdefault(name, slot)

    def _wl_discovered_names(self):
        return self._discovery_slots

    def _wl_cand_lanes(self) -> int:
        """The worst-case compaction/dedup buffer width ``U`` of the
        LIVE (step-rung-sized) batch — the denominator of the density
        telemetry (wave_loop.LoopVitals): measured valid candidates per
        wave over THIS is the fraction of the compact/probe work that
        touches live lanes.  Deliberately SORT-rung-independent (the
        sort rung is sized FROM density × this width; a sort-rung-
        relative density would be self-referential), but it follows the
        step rung — a step-rung-sized wave generates proportionally
        fewer candidates, and the sort tuner must size against the
        buffer those waves actually fill.  Queried per quantum because
        auto-grow and both ladders may move the geometry mid-run."""
        return self._wl_full_sort_lanes()

    def _count_stage_rounds(self, stage_rounds, stage_slices) -> int:
        """Fold one readback of per-stage probe rounds and slice hand-offs
        (the insert's ``with_rounds`` vectors, or the fused call's
        ``STAT_ROUNDS`` and ``STAT_SLICES`` slots) into
        ``metrics()["dedup_stage_rounds"]`` and
        ``["dedup_stage_slices"]``, keyed by the live compaction width's
        stage widths; returns the rounds run."""
        from .hashset import stage_widths

        widths = stage_widths(self._sort_width())
        with self._lock:
            for counts, readback in (
                (self._stage_rounds, stage_rounds),
                (self._stage_slices, stage_slices),
            ):
                for width, n in zip(widths, readback.tolist()):
                    if n:
                        counts[width] = counts.get(width, 0) + int(n)
        return int(sum(stage_rounds.tolist()))

    # --- sort-geometry rung (wave_loop.py's ladder) --------------------------

    def _sort_width(self) -> int:
        """The EFFECTIVE per-wave compact/sort buffer width: the
        requested rung capped at the live worst-case ``U`` (auto-grow
        and the step rung may move U mid-run), or ``U`` itself when no
        rung is set.  The one number the device programs, cache keys,
        byte model, and knob-cache entries all derive from."""
        full = self._wl_full_sort_lanes()
        if self._sort_lanes is None:
            return full
        return min(self._sort_lanes, full)

    def _wl_full_sort_lanes(self) -> int:
        from .hashset import unique_buffer_size

        return unique_buffer_size(
            self._step_width() * self._compiled.max_actions,
            self._dedup_factor,
        )

    # --- step-geometry rung (wave_loop.py's second ladder) -------------------

    def _step_width(self) -> int:
        """The EFFECTIVE per-wave chunk width in frontier lanes: the
        step rung capped at the live ``max_frontier`` (auto-grow may
        halve it mid-run), or the full chunk when no rung is set."""
        full = self._max_frontier
        if self._step_lanes is None:
            return full
        return min(self._step_lanes, full)

    def _wl_full_step_lanes(self) -> int:
        return self._max_frontier

    def _wl_apply_step_rung(self, rung: int) -> None:
        """Apply a frontier-tuner downshift (wave_loop.
        maybe_retune_step): swap the knob, re-journal the geometry
        event, and rebuild the fused run program (once it exists) at the
        new shapes.  The loop carry is untouched: the rung only shapes
        per-wave scratch buffers (the row log, table, and positions are
        rung-independent)."""
        self._step_lanes = int(rung)
        self._step_quanta = 0
        if self._journal:
            self._journal.append("geometry", **self._wl_geometry())
        if getattr(self, "_run_fn", None) is not None:
            _seed, self._run_fn = self._programs()

    def _wl_apply_sort_rung(self, rung: int) -> None:
        """Apply a density-tuner downshift (wave_loop.maybe_retune_sort):
        swap the knob, re-journal the geometry event (the watch verb's
        source for the current rung), and rebuild the fused run program
        (once it exists) at the new shapes.  The loop carry is untouched:
        the rung only shapes per-wave scratch buffers."""
        self._sort_lanes = int(rung)
        self._sort_quanta = 0  # fresh evidence before another move
        # NOT mirrored into the metrics registry: metrics() reports the
        # live _sort_width(), and a stale registry copy would shadow a
        # later ladder climb (snapshot keys overwrite computed ones).
        if self._journal:
            self._journal.append("geometry", **self._wl_geometry())
        if getattr(self, "_run_fn", None) is not None:
            _seed, self._run_fn = self._programs()

    def _wl_geometry(self) -> dict:
        """The ``geometry`` journal event's payload (wave_loop.
        journal_geometry): live knobs + the density denominator, the
        advisor's ground truth for this run."""
        return {
            "engine": "tpu-wavefront",
            "capacity": self._capacity,
            "log_capacity": self._log_capacity,
            "max_frontier": self._max_frontier,
            "dedup_factor": self._dedup_factor,
            "sortless": self._sortless,
            "sort_lanes": self._sort_width(),
            "step_lanes": self._step_width(),
            "u_lanes": self._wl_cand_lanes(),
            "waves_per_call": self._waves_per_call,
        }

    def _wl_write_checkpoint(self, carry) -> dict:
        stats_h = self._last_stats_h
        self._write_snapshot(
            self._checkpoint_path,
            self._carry_from(
                carry[0], carry[1], carry[2], carry[3], carry[4], stats_h
            ),
        )
        return {"tail": int(stats_h[STAT_TAIL])}

    def _wl_retryable_flags(self) -> int:
        # 1 = table overfull, 2 = row log full, 4 = dedup-buffer
        # overflow (sortless fallback / sort-rung climb / dd relax),
        # 128 = step-rung clamp (climb one chunk rung): all grow in
        # place (auto_tune off raises the loud per-knob message from
        # _wl_grow instead).  8 (encoding overflow) is never
        # retryable.
        return 1 | 2 | 4 | 128

    def _wl_overflow_message(self, flags: int) -> str:
        if flags & 8:
            return (
                "the model step kernel flagged an encoding-capacity "
                "overflow (a successor exceeded the packed layout's "
                "bounds); the compiled model's capacity assumptions "
                "do not hold for this configuration"
            )
        return f"wavefront engine overflow flags={flags}"

    def _wl_abort_cleanup(self, carry):
        """Erase an aborted wave's fingerprint-table writes before a
        keep-partial (stop/deadline) break persists the carry: the
        growth path's rehash-from-committed-prefix, minus the growth.
        Without it a resume would find the aborted wave's keys already
        present, mark its states as duplicates, and silently drop
        their entire subtrees."""
        stats_h = self._last_stats_h
        key_hi, key_lo = self._rehash(carry[2], int(stats_h[STAT_TAIL]))
        return (key_hi, key_lo) + tuple(carry[2:])

    def _wl_grow(self, flags: int, carry):
        """In-place auto-tune growth for the fused loop (the shared
        core's grow hook): the flagged wave did not commit (see
        wave_body), so the carry is the exact pre-wave state — grow the
        tripped knobs (:meth:`_grow`, honoring the dragged-log rule),
        resize the row/parent/ebits buffers if the log geometry changed,
        rebuild the table from the committed row-log prefix (erasing any
        keys the aborted wave managed to write), recompile, and re-run
        the same chunk with no work redone.  Raises RuntimeError when
        the tripped knob cannot grow (or ``auto_tune`` is off)."""
        stats_h = self._last_stats_h
        tail_h = int(stats_h[STAT_TAIL])
        qcap, pad = self._loop_qcap, self._loop_pad
        msgs = {
            1: (
                f"fingerprint table overfull (capacity "
                f"{self._capacity}); raise spawn_tpu(capacity=...)"
            ),
            2: (
                f"the state row log is full (log_capacity {qcap}); "
                "raise spawn_tpu(log_capacity=...)"
            ),
            4: (
                "a wave generated more VALID successor candidates than "
                "the compaction/dedup buffers hold (batch/dedup_factor); "
                f"lower spawn_tpu(dedup_factor=...) (now "
                f"{self._dedup_factor}; 1 is always safe)"
            ),
            128: (
                "the step-rung ladder clamped a wave at the full chunk "
                "width — impossible by construction (the clamp flag is "
                "compiled out at the top rung); please report"
            ),
        }
        grown = []
        for bit in (1, 2, 4, 128):
            if flags & bit:
                if bit == 2 and self._log_capacity > qcap:
                    # A simultaneous table growth (bit 1, processed
                    # above) already dragged the log past the tripped
                    # size — the flag is addressed; raising here would
                    # kill a run whose log just grew.
                    grown.append(
                        f"log_capacity={self._log_capacity} (dragged)"
                    )
                    continue
                g = self._grow(bit) if self._auto_tune else None
                if g is None:
                    raise RuntimeError(msgs[bit])
                grown.append(g)
        from .wave_loop import log_grow

        log_grow(
            self, flags, "; ".join(grown), int(stats_h[STAT_UNIQUE]),
            int(stats_h[STAT_DEPTH]),
        )
        rows, parent, ebits = carry[2], carry[3], carry[4]
        new_qcap, new_pad = self._log_capacity, self._block_pad()
        if (new_qcap + new_pad) != (qcap + pad):
            n_new_len = new_qcap + new_pad
            rows = _resize_flat(
                rows, n_new_len * self._compiled.state_width, 0
            )
            parent = _resize_flat(parent, n_new_len, NO_SLOT_HOST)
            ebits = _resize_flat(ebits, n_new_len, 0)
            qcap, pad = new_qcap, new_pad
        key_hi, key_lo = self._rehash(rows, tail_h)
        self._loop_qcap, self._loop_pad = qcap, pad
        _seed, self._run_fn = self._programs()
        return (key_hi, key_lo, rows, parent, ebits, carry[5])

    def _visit_committed(self, carry, view) -> None:
        """The device visitor stream, on the fused loop's post-commit
        hook (bound in ``__init__`` only when a visitor is attached):
        the log positions expanded since the last visit — up to the
        call's new ``STAT_LEVEL_START`` — are read back and visited.
        An aborted call is not visited; the waves it committed before
        the flagged one are read with the next committed call."""
        self._visit_expanded(
            carry[2], int(self._last_stats_h[STAT_LEVEL_START])
        )

    def _visit_expanded(self, rows, upto: int) -> None:
        """Visit log positions ``[_visited_to, upto)`` in log order, each
        as a single-state path, reading the row log back one append
        block at a time (the log's padding keeps every block in
        bounds)."""
        import jax
        import jax.numpy as jnp

        cm = self._compiled
        w = cm.state_width
        blk = self._block_pad()
        visitor = self._options._visitor
        pos = self._visited_to
        while pos < upto:
            n = min(blk, upto - pos)
            block = np.asarray(jax.lax.dynamic_slice(
                rows, (jnp.uint32(pos * w),), (blk * w,)
            ))
            for row in block[: n * w].reshape(n, w):
                visitor.visit(self._model, Path([(cm.decode(row), None)]))
            pos += n
        self._visited_to = upto

    def _carry_from(self, key_hi, key_lo, rows, parent, ebits, stats_h):
        """Full run state as one dict — the ``save_snapshot`` npz layout
        (arrays may be device or host; scalars come from the last stats
        readback)."""
        return {
            "key_hi": key_hi,
            "key_lo": key_lo,
            "rows": rows,
            "parent": parent,
            "ebits": ebits,
            "level_start": stats_h[STAT_LEVEL_START],
            "level_end": stats_h[STAT_LEVEL_END],
            "tail": stats_h[STAT_TAIL],
            "sc_lo": stats_h[STAT_SC_LO],
            "sc_hi": stats_h[STAT_SC_HI],
            "unique_count": stats_h[STAT_UNIQUE],
            "depth": stats_h[STAT_DEPTH],
            "disc": stats_h[STAT_DISC:].copy(),
        }

    def _snapshot_extra(self) -> dict:
        """Extra npz fields an engine subclass persists beside the
        carry (the tiered engine's cold-tier state rides here) — so the
        atomic-write body and the base field set exist exactly once."""
        return {}

    def _write_snapshot(self, path: str, carry: dict) -> None:
        """Persist a carry dict atomically (write + rename), so a kill
        mid-checkpoint can never leave a torn snapshot where a resume
        would find it."""
        import os

        arrays = {k: np.asarray(v) for k, v in carry.items()}
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as fh:
            np.savez_compressed(
                fh,
                engine_key=self._snapshot_key(),
                # Geometry travels as data, not key material: a resume
                # adopts these (the run may have auto-tuned past the
                # spawn args).
                capacity=self._capacity,
                log_capacity=self._log_capacity,
                **self._snapshot_extra(),
                **arrays,
            )
        os.replace(tmp, path)

    def _block_pad(self) -> int:
        """Append-block lanes past the position log's capacity: one chunk's
        insert can mint up to U = max(min(B, 16K), B/dedup_factor) new
        states (hashset.py's unique-buffer size), and appends are whole
        U-blocks whose tail garbage must land in bounds."""
        b = self._max_frontier * self._compiled.max_actions
        from .hashset import unique_buffer_size

        u = unique_buffer_size(b, self._dedup_factor)
        return max(self._max_frontier, u)

    def _snapshot_key(self) -> str:
        return snapshot_engine_key(
            self._compiled, self._properties, self._canon is not None
        )

    def save_snapshot(self, path: str) -> None:
        """Persist the full checker state (visited table, row log, parent
        links, counters, discoveries) so a bounded run — e.g. stopped by
        ``timeout`` or ``target_state_count`` — can be resumed later with
        ``spawn_tpu(resume_from=path)``.  The reference has no checker
        persistence (its visited set is not persistable, SURVEY §5); on
        device the whole run state is dense arrays, so snapshots are a
        plain ``np.savez``.

        Note: to stay snapshot-ready, a finished checker keeps its key
        planes and ebits (12 bytes × capacity) on device alongside the
        row-log/parent arrays that path reconstruction already retains;
        dropping the checker object frees all of it.

        Engine tuning knobs that do not shape the persisted arrays —
        ``dedup_factor`` and the ``sort_lanes`` rung in particular — are
        deliberately NOT part of the snapshot key: a resume may use
        different tuning, in which case overflow-failure behavior (not
        correctness) can differ from the original run."""
        self.join()
        if self._carry_dev is None:
            raise RuntimeError("no run state to snapshot")
        self._write_snapshot(path, self._carry_dev)

    def tuned_kwargs(self) -> dict:
        """Engine kwargs right-sized to THIS run's final counts, so a
        fresh spawn of the same workload runs without any auto-tune
        growth pauses: a default-knob discovery run, then a measured run
        with the returned sizes (what the knob cache stores).  The table gets
        ≥2× the unique count (50% max load), the row log the exact count
        plus safety slack."""
        self.join()
        u = max(1, self._unique_count)
        return dict(
            capacity=1 << max(10, (2 * u).bit_length()),
            log_capacity=u + max(64, u // 64),
            max_frontier=self._max_frontier,
            dedup_factor=self._dedup_factor,
            # The discovered dedup path: a sortless→sort fallback is a
            # per-workload selection the knob cache must remember, so a
            # warm repeat skips the fallback retry entirely.
            sortless=int(self._sortless),
            # The discovered rungs — ONLY when one was actually pinned
            # (ladder climb, tuner, or explicit spawn): a warm spawn
            # from an explicit rung disarms the tuner, so persisting
            # the full worst-case width from a run too short to tune
            # would freeze that workload at full width forever (the
            # sharded snapshot's none-sentinel rule).  A SORTLESS run
            # never persists its sort rung: under the election the
            # rung is the claim compaction buffer's tuner detail, and
            # an explicit rung under sortless is the fallback-forcing
            # budget cap (_grow's flag-4 dispatch) — a warm repeat
            # must re-arm the tuner, not inherit a one-notch-tight
            # explicit cap that flips it onto the sort path.
            **(
                {"sort_lanes": self._sort_width()}
                if self._sort_lanes is not None and not self._sortless
                else {}
            ),
            **(
                {"step_lanes": self._step_width()}
                if self._step_lanes is not None else {}
            ),
        )

    def discovered_fingerprints(self):
        """Sorted uint64 IDENTITY fingerprints of every discovered
        unique state (the dedup-key fingerprints: original rows, or
        canonical rows under symmetry — wave_loop.fingerprints_of_rows
        documents why), for cross-engine discovery-set comparison — the
        sharded engine must reproduce this set bit-identically on every
        mesh size (tests/test_tpu_sharded.py), and the sortless and
        sort dedup paths on every geometry (tests/test_sortless.py).
        Pulls the committed row-log prefix to the host; size it like a
        path reconstruction, not a hot call."""
        self.join()
        if self._carry_dev is None:
            raise RuntimeError("no run state to fingerprint")
        from .wave_loop import fingerprints_of_rows

        w = self._compiled.state_width
        tail = int(self._carry_dev["tail"])
        rows = np.asarray(self._carry_dev["rows"])[: tail * w].reshape(
            tail, w
        )
        return fingerprints_of_rows(self._compiled, rows, self._canon)

    # --- Checker surface -----------------------------------------------------

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        return self._unique_count

    def max_depth(self) -> int:
        return self._max_depth

    def metrics(self) -> dict:
        """Live observability snapshot (names: docs/OBSERVABILITY.md).
        Safe to call mid-run — it reads the registry the host loop
        updates from scalars it already synced, never the device.  (A
        FINISHED checker's first call additionally reads the key
        planes' true load factor back once and caches it.)  The
        Explorer's ``GET /.metrics`` serves exactly this."""
        out = super().metrics()
        out.update(
            engine="tpu-wavefront",
            device=str(self._device),
            capacity=self._capacity,
            log_capacity=self._log_capacity,
            max_frontier=self._max_frontier,
            dedup_factor=self._dedup_factor,
            sortless=self._sortless,
            sort_lanes=self._sort_width(),
            # The PINNED rungs (0 = running at the full buffer with the
            # tuner armed) — what warm-start stores persist, vs the
            # live widths (what the programs actually compiled).
            sort_lanes_rung=self._sort_lanes or 0,
            step_lanes=self._step_width(),
            step_lanes_rung=self._step_lanes or 0,
            dedup_rung_climbs=self._dedup_rung_climbs,
            dedup_fallbacks=self._dedup_fallbacks,
        )
        with self._lock:
            stage_rounds = dict(sorted(self._stage_rounds.items()))
            stage_slices = dict(sorted(self._stage_slices.items()))
        out.update(
            dedup_stage_rounds=stage_rounds,
            dedup_stage_slices=stage_slices,
            dedup_probe_lanes_total=sum(
                w * n for w, n in stage_rounds.items()
            ),
        )
        snap = self._metrics.snapshot()
        # Table load factor: mid-run it is the loop's already-synced
        # occupancy (metrics() never touches the device); a finished
        # checker reports the key planes' actual occupied fraction via
        # ONE cached HashSet.load_factor readback — ground truth even
        # for engines whose tables hold more than unique states, and
        # immutable once the run is done, so repeated /.metrics polls
        # never re-reduce the key planes.
        out["table_load_factor"] = snap.get("table_occupancy", 0.0)
        if self._done.is_set() and self._carry_dev is not None:
            if self._final_load_factor is None:
                from .hashset import HashSet

                try:
                    self._final_load_factor = round(HashSet(
                        self._carry_dev["key_hi"],
                        self._carry_dev["key_lo"],
                    ).load_factor(), 6)
                except Exception:
                    # Snapshot arrays already freed mid-teardown: keep
                    # the loop's occupancy (and stop retrying).
                    self._final_load_factor = out["table_load_factor"]
            out["table_load_factor"] = self._final_load_factor
        out.update(snap)
        # Always-on vitals histograms (wave_latency_sec, waves_per_grow;
        # obs/metrics.py documents the snapshot shape) — one nested key
        # so flat scrapers keep a numbers-only top level.
        hists = self._metrics.snapshot_histograms()
        if hists:
            out["histograms"] = hists
        return out

    def _rehash_program(self):
        """Device program inserting one row-log chunk's fingerprints into
        a (fresh, larger) table — the auto-tune growth path.  Rows are the
        source of truth: every committed position holds exactly one
        distinct state, so the rebuild is chunked contiguous reads with
        ``dedup_factor=1`` inserts."""
        import jax
        import jax.numpy as jnp

        from ..ops.device_fp import device_fp64
        from .hashset import HashSet, insert_batch
        from .wave_common import cached_program

        cm = self._compiled
        w = cm.state_width
        fpw = cm.fp_words or w
        r = self._max_frontier
        canon = self._canon  # the log holds ORIGINAL rows; keys are canonical
        key = ("rehash", self._capacity, w, fpw, r, canon is not None,
               cm.cache_key() if canon is not None else None)

        def build():
            @partial(jax.jit, donate_argnums=(0, 1))
            def rehash_chunk(kh, kl, ok, rows, start, count):
                states = jax.lax.dynamic_slice(
                    rows, (start * jnp.uint32(w),), (r * w,)
                ).reshape(r, w)
                states_c = (
                    states if canon is None else jax.vmap(canon)(states)
                )
                hi, lo = device_fp64(states_c[:, :fpw])
                active = jnp.arange(r, dtype=jnp.uint32) < count
                table, _slot, _new, p_ok, _dd = insert_batch(
                    HashSet(kh, kl), hi, lo, active, dedup_factor=1
                )
                return table.key_hi, table.key_lo, ok & p_ok

            return rehash_chunk

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, build,
            label=f"{type(self).__name__}.rehash",
            journal=self._journal,
            provenance={"capacity": self._capacity,
                        "max_frontier": self._max_frontier},
        )

    def _rehash(self, rows, tail_h: int, start_h: int = 0):
        """Rebuild the fingerprint table (sized to the CURRENT
        ``self._capacity``) from the committed row-log positions
        ``[start_h, tail_h)`` — the whole prefix for the auto-tune
        growth path, a suffix segment for the tiered engine (whose hot
        tier only ever holds states committed since the last spill;
        tiered/engine.py).  The OK accumulator stays on device so chunk
        dispatches pipeline without a per-chunk host round trip (at
        bench scale that is thousands of chunks)."""
        import jax.numpy as jnp

        from .hashset import make_hashset

        prog = self._rehash_program()
        t = make_hashset(self._capacity)
        kh, kl = t.key_hi, t.key_lo
        ok = jnp.asarray(True)
        r = self._max_frontier
        for start in range(start_h, tail_h, r):
            kh, kl, ok = prog(
                kh,
                kl,
                ok,
                rows,
                jnp.uint32(start),
                jnp.uint32(min(r, tail_h - start)),
            )
        if not bool(ok):
            raise RuntimeError(
                "rehash after auto-tune growth could not place every "
                "committed state; the grown table is still overfull"
            )
        return kh, kl

    def _chain_program(self, length: int):
        """Device program walking a parent chain and gathering its rows:
        the readback is O(depth × W) instead of the full tables (which are
        GBs at bench capacities)."""
        import jax
        import jax.numpy as jnp

        from .wave_common import cached_program

        w = self._compiled.state_width
        n = self._log_capacity + self._block_pad()
        key = ("chain", w, n, length)

        def build():
            @jax.jit
            def chain(parent, rows, pos):
                def walk(i, c):
                    ch, s = c
                    ch = ch.at[i].set(s)
                    nxt = parent[jnp.minimum(s, jnp.uint32(n - 1))]
                    s = jnp.where(s == jnp.uint32(NO_SLOT_HOST), s, nxt)
                    return ch, s

                ch, _ = jax.lax.fori_loop(
                    0, length,
                    walk,
                    (jnp.full((length,), NO_SLOT_HOST, jnp.uint32), pos),
                )

                def gather(i, buf):
                    s = jnp.minimum(ch[i], jnp.uint32(n - 1))
                    row = jax.lax.dynamic_slice(
                        rows, (s * jnp.uint32(w),), (w,)
                    )
                    return jax.lax.dynamic_update_slice(
                        buf, row[None, :], (i, 0)
                    )

                out = jax.lax.fori_loop(
                    0, length, gather, jnp.zeros((length, w), jnp.uint32)
                )
                return ch, out

            return chain

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, build,
            label=f"{type(self).__name__}.chain",
            journal=self._journal,
            provenance={"length": length},
        )

    def _slot_path(self, slot: int) -> Path:
        import jax.numpy as jnp

        # Chain length bucketed to powers of two so a run's discoveries
        # share one compiled walk program.
        need = self._max_depth + 2
        length = 1 << max(4, (need - 1).bit_length())
        parent_dev, rows_dev = self._tables_dev
        n = self._log_capacity + self._block_pad()
        while True:
            chain_fn = self._chain_program(length)
            ch, rows_l = chain_fn(parent_dev, rows_dev, jnp.uint32(slot))
            ch = np.asarray(ch)
            rows_l = np.asarray(rows_l)
            chain = [i for i, s in enumerate(ch) if s != NO_SLOT_HOST]
            if len(chain) < length or length >= n:
                break
            # Every buffer lane came back valid: the chain may be
            # TRUNCATED.  The run's own max_depth under-estimates chain
            # length when the parent links predate this run — a seeded
            # incremental re-check (incr/recheck.py) carries a completed
            # store's parents, whose chains span the ORIGINAL run's
            # depth, not the seeded run's.  Double and re-walk.
            length *= 2
        chain.reverse()
        fps = [
            self._model.fingerprint(self._compiled.decode(rows_l[i]))
            for i in chain
        ]
        return Path.from_fingerprints(self._model, fps)

    def discoveries(self) -> Dict[str, Path]:
        self.join()
        if self._discoveries_cache is None:
            with self._lock:
                items = list(self._discovery_slots.items())
            self._discoveries_cache = {
                name: self._slot_path(slot) for name, slot in items
            }
        return dict(self._discoveries_cache)

    def try_discovery(self, name: str) -> Optional[Path]:
        # Non-blocking while the run is live (the Explorer polls status
        # mid-run); paths resolve once the run completes cleanly (a failed
        # run surfaces its error through join(), not here).
        if not self._done.is_set() or self._errors:
            return None
        return self.discoveries().get(name)

    def handles(self) -> List[threading.Thread]:
        return [self._thread]

    def is_done(self) -> bool:
        return self._done.is_set()

    def join(self) -> "TpuChecker":
        self._thread.join()
        if self._errors:
            raise self._errors[0]
        return self
