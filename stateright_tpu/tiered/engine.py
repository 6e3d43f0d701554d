"""Tiered out-of-core wavefront checker: HBM hot tier + host cold tier.

The in-HBM engines cap exact checking at what one chip's fingerprint
table holds (raft depth-12 ≈ 12.6M states was the practical ceiling,
PARITY.md).  This engine runs the SAME wavefront BFS under a fixed HBM
budget: the device hash set (parallel/hashset.py) is the *hot tier*, and
when its measured load factor (``HashSet.load_factor()``) crosses the
spill threshold, every fingerprint committed since the last spill is
evicted to the host :class:`~stateright_tpu.tiered.cold_store.ColdStore`
as a sorted immutable run and the hot table is reset — the TLC recipe
(Yu–Manolios–Lamport, PAPERS.md) lifted one level: disk→RAM becomes
HBM→host RAM (optionally disk under it).

Each wave then runs exactly the in-HBM pipeline — step kernel,
fingerprint, hot-tier ``insert_batch_compact`` dedup — plus one extra
stage: keys the hot tier reports NEW are merge-joined against the cold
tier by streaming the overlapping windows of each sorted run through the
device in bounded passes (a vmapped branchless binary search per pass,
``cold_chunk`` lanes at a time) BEFORE the append commits.  A key found
cold is a duplicate: its row is not appended, so BFS positions, parent
links, depth semantics, and the discovery set stay bit-identical to an
unconstrained run — pinned by ``discovered_fingerprints()`` equality in
tests/test_tiered.py.  (The hot tier keeps cold-hit keys as entries, so
repeat candidates of an evicted state are answered on-device without
another cold pass — a negative cache the next spill simply carries
along.)

The host loop IS the shared :class:`~stateright_tpu.parallel.wave_loop.
FusedWaveLoop` core: the engine adapts one host-driven wave per
``_wl_call`` through its own phase programs (per-wave sync is the
documented cost of the mode), spills ride the core's
``_wl_after_commit`` rung, and
overflow flags 2/4 reuse the shared in-place growth rules while flag 1
(table overfull) SPILLS instead of growing — the budget is a hard cap.
``spill`` / ``cold_probe`` events carry bytes and pass counts for the
obs roofline; snapshots embed the whole cold store (checkpoint.npz
container), so a killed deep run resumes mid-search with its tiers
intact under the supervisor.  docs/TIERED.md documents the layout,
eviction policy, pass semantics, and resume format.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import numpy as np

from ..parallel.wavefront import (
    _PROGRAM_CACHE,
    _PROGRAM_CACHE_MAX,
    NO_SLOT_HOST,
    STAT_FLAGS,
    STAT_UNIQUE,
    TpuChecker,
    _device_owned,
    _OverflowRetry,
    _resize_flat,
    host_stats,
)
from .cold_store import ColdStore

# Hot-table slot cost the budget maps onto: 8 B of key planes plus the
# insert's transient 4 B claim plane (hashset.py) — the peak HBM the
# table itself forces per slot.
_BYTES_PER_SLOT = 12
_MIN_CAPACITY = 256


def capacity_for_budget(memory_budget_mb: float) -> int:
    """Largest power-of-two hot-table capacity whose peak table bytes
    (key planes + transient claim plane) fit ``memory_budget_mb``.
    Fractional budgets are allowed — tests and CI force multi-spill runs
    on tiny models with budgets well under 1 MB."""
    import math

    if not math.isfinite(float(memory_budget_mb)) or memory_budget_mb <= 0:
        raise ValueError("memory_budget_mb must be a positive finite size")
    slots = int(float(memory_budget_mb) * (1 << 20)) // _BYTES_PER_SLOT
    if slots < _MIN_CAPACITY:
        # The budget is documented as a hard cap; silently rounding a
        # sub-floor budget UP to the minimum table would exceed it.
        raise ValueError(
            f"memory_budget_mb={memory_budget_mb} cannot hold the "
            f"{_MIN_CAPACITY}-slot minimum hot table "
            f"({_MIN_CAPACITY * _BYTES_PER_SLOT} bytes ≈ "
            f"{_MIN_CAPACITY * _BYTES_PER_SLOT / (1 << 20):.4f} MB)"
        )
    return 1 << (slots.bit_length() - 1)


class TieredTpuChecker(TpuChecker):
    """Budget-bounded wavefront checker behind the standard surface."""

    # Flag 4 at the full buffer keeps the two-step dispatch (TpuChecker).
    _land_on_rung = False

    def __init__(
        self,
        options,
        memory_budget_mb: Optional[float] = None,
        spill_threshold: float = 0.45,
        cold_chunk: int = 1 << 15,
        cold_max_runs: int = 8,
        cold_dir: Optional[str] = None,
        **kwargs,
    ):
        """``memory_budget_mb`` bounds the HOT fingerprint table (the
        component whose size caps the in-HBM engines); when given it
        derives ``capacity``, overriding any explicit one (pass
        ``capacity`` alone to force an exact table size).  The row
        log still holds every unique state's packed row (frontier reads
        and path reconstruction need it) and keeps the base engine's
        ``log_capacity`` + auto-grow behavior — the budget is the dedup
        set's, exactly the TLC split (states on the queue, fingerprints
        in the bounded set).

        ``spill_threshold``: hot-tier load factor at which a committed
        wave triggers eviction (must leave headroom under the insert's
        50% overfull flag).  ``cold_chunk``: lanes per cold-probe pass
        (power of two; each pass streams ``8 * cold_chunk`` bytes of one
        sorted run through the device).  ``cold_max_runs``: run count
        that triggers an LSM merge.  ``cold_dir``: optional directory —
        when set, runs live on disk memory-mapped (the disk tier).

        ``trace=True`` produces per-wave phase breakdowns: the tiered
        loop already dispatches its phase programs separately, so
        tracing only adds the per-phase sync, not a mode switch — the
        timings are of the program this engine runs.  Each phase is
        charged modeled bytes against the device's peak HBM bandwidth
        (obs/roofline.py); the phases are step / canon / dedup /
        readback / ``cold_probe`` (the pre-commit merge-join;
        host-classed like ``readback``, obs/trace.py) / append.
        Tracing surfaces: enriched ``wave`` journal records, a
        ``trace_summary`` journal event, ``metrics()``, and
        ``trace_summary()``.  Visitors are unsupported."""
        self._t_trace = bool(kwargs.pop("trace", False))
        self._t_trace_last = None
        self._tracer = None  # the WaveTracer of a trace=True run
        if self._t_trace and kwargs.get("resume_from") is not None:
            raise ValueError(
                "spawn_tpu_tiered(trace=True) does not support "
                "resume_from: tracing is a diagnostic mode; resume the "
                "run untraced and trace a fresh (bounded) run instead"
            )
        if options._visitor is not None:
            raise ValueError(
                "spawn_tpu_tiered() does not support visitors; use "
                "spawn_tpu for visitor-instrumented runs"
            )
        if not 0.0 < float(spill_threshold) <= 0.5:
            raise ValueError(
                "spill_threshold must be in (0, 0.5]: the insert flags "
                "the table overfull beyond 50% load"
            )
        if cold_chunk < 2 or cold_chunk & (cold_chunk - 1):
            raise ValueError("cold_chunk must be a power of two >= 2")
        if memory_budget_mb is not None:
            # The budget is AUTHORITATIVE: it overrides any capacity
            # riding along in merged kwargs (workload-spec defaults, a
            # warm-started cache entry), so a job that asked for a
            # budget can never silently run un-tiered at a huge table
            # while metrics() reports the budget.  To force an exact
            # table size, pass capacity alone.
            kwargs["capacity"] = capacity_for_budget(memory_budget_mb)
        # Every tiered attribute lands BEFORE super().__init__: the base
        # constructor starts the run thread as its last statement.
        self._memory_budget_mb = (
            None if memory_budget_mb is None else float(memory_budget_mb)
        )
        self._spill_threshold = float(spill_threshold)
        self._cold_chunk = int(cold_chunk)
        self._cold = ColdStore(spill_dir=cold_dir, max_runs=cold_max_runs)
        self._hot_entries = 0  # hot-table entries since the last spill
        self._spill_tail = 0  # row-log positions below this are cold-tiered
        self._t_level_start = 0
        self._t_level_end = 0
        self._t_tail = 0
        self._t_depth = 0
        self._t_unique = 0
        self._t_states = 0
        self._t_flags = 0
        self._t_disc = None  # device uint32[P] discovery slots
        self._t_disc_h = None
        self._t_cold_last = None  # last wave's cold-probe accounting
        self._t_host_spans = []  # in-call host spans for _wl_host_spans
        super().__init__(options, **kwargs)

    # --- budget enforcement ---------------------------------------------------

    def _grow(self, flag: int):
        """Flag 1 (table overfull) never grows in tiered mode — the
        budget is a hard cap and the in-loop recovery spills instead
        (``_wl_grow``); returning None here makes a SEED-time overflow
        (init states alone overfilling the budgeted table) a loud error
        rather than a silent budget violation.  Flags 2/4 keep the base
        rules: the row log and dedup buffers are outside the table
        budget."""
        if flag & 1:
            return None
        return super()._grow(flag)

    # --- phase programs (the in-HBM wave's kernels, dispatched apart) --------

    def _phase_programs(self):
        """The tiered wave's phase programs, cached like every other
        program set.  The key covers everything the closures trace over;
        host-driven knobs (finish_when, target depth) are NOT baked in —
        the tiered loop decides them per wave on the host."""
        key = (
            "phases",
            self._compiled.cache_key(),
            hasattr(self._compiled, "step_valid")
            and hasattr(self._compiled, "step_lane"),
            self._canon is not None,
            self._max_frontier,
            self._dedup_factor,
            self._sortless,  # the dedup path is a trace-time branch
            self._sort_width(),  # the live sort-geometry rung
            self._step_width(),  # the live step-geometry rung
            self._block_pad(),
            tuple(p.expectation for p in self._properties),
        )
        from ..parallel.wave_common import cached_program

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key,
            self._build_phase_programs,
            label=f"{type(self).__name__}.phases",
            journal=self._journal,
            provenance=self._key_provenance(),
        )

    def _build_phase_programs(self):
        """The wave as four separately-dispatched phase programs — the
        SAME kernels as the in-HBM engine's fused ``wave_body``, cut at
        the phase boundaries the roofline models (step kernel /
        canon+fingerprint / dedup-sort+probe / append), so the cold
        merge-join can run between insert and append and ``trace=True``
        can time each phase with ``block_until_ready``.  Commit order,
        dedup keys, position assignment, and discovery folding are
        identical to the fused path; level/depth bookkeeping lives on
        the host (one sync per wave is the tiered mode's cost)."""
        import jax
        import jax.numpy as jnp

        from ..ops.device_fp import device_fp64
        from ..parallel.hashset import (
            HashSet, compact_valid_indices, insert_batch_claim,
            insert_batch_compact,
        )
        from ..parallel.wave_common import compact, wave_eval

        cm = self._compiled
        w = cm.state_width
        fpw = cm.fp_words or w
        canon = self._canon
        a = cm.max_actions
        f_eff = self._step_width()  # the live step-geometry rung
        pad = self._block_pad()
        dedup_factor = self._dedup_factor
        sortless = self._sortless  # the dedup path (claim vs sort)
        sort_lanes = (
            None if self._sort_lanes is None else self._sort_width()
        )
        props = self._properties
        ev_indices = self._ev_indices

        @jax.jit
        def t_step(rows, ebits, disc, level_start, level_end):
            count = jnp.minimum(level_end - level_start, jnp.uint32(f_eff))
            lane = jnp.arange(f_eff, dtype=jnp.uint32)
            active = lane < count
            ids = level_start + lane
            states = jax.lax.dynamic_slice(
                rows, (level_start * jnp.uint32(w),), (f_eff * w,)
            ).reshape(f_eff, w)
            eb_chunk = jax.lax.dynamic_slice(
                ebits, (level_start,), (f_eff,)
            )
            disc, eb, nexts, valid, generated, step_flag = wave_eval(
                cm, props, ev_indices, states, active, ids, eb_chunk,
                disc, allow_two_phase=True,
            )
            flat_valid = valid.reshape(f_eff * a)
            v_orig, v_act, n_valid, v_overflow = compact_valid_indices(
                flat_valid, dedup_factor, sort_lanes=sort_lanes
            )
            if nexts is None:
                # Two-phase: construct successors only for the compacted
                # valid lanes (the fused path's phase B).
                src_state = v_orig // jnp.uint32(a)
                cand_rows, _vu, lane_flags_u = jax.vmap(cm.step_lane)(
                    states[src_state], v_orig % jnp.uint32(a)
                )
                step_flag = step_flag | jnp.any(lane_flags_u & v_act)
                cand_src = src_state
            else:
                # Single-phase: compact the constructed rows.  Same keys
                # and representatives as the fused compact_valid-on-keys
                # order (compaction preserves lane order).
                cand_rows = nexts.reshape(f_eff * a, w)[v_orig]
                cand_src = v_orig // jnp.uint32(a)
            return (
                disc, eb, states, cand_rows, cand_src, v_act,
                n_valid, v_overflow, generated, step_flag,
            )

        @jax.jit
        def t_fp(cand_rows):
            rows_c = (
                cand_rows if canon is None else jax.vmap(canon)(cand_rows)
            )
            return device_fp64(rows_c[:, :fpw])

        @partial(jax.jit, donate_argnums=(0, 1))
        def t_insert(key_hi, key_lo, hi, lo, cand_act):
            if sortless:
                (
                    table, _u_slot, u_new, u_origin, _u_active, probe_ok,
                    dd_overflow, rounds,
                ) = insert_batch_claim(
                    HashSet(key_hi, key_lo), hi, lo, cand_act,
                    with_rounds=True,
                )
            else:
                (
                    table, _u_slot, u_new, u_origin, _u_active, probe_ok,
                    dd_overflow, rounds,
                ) = insert_batch_compact(
                    HashSet(key_hi, key_lo), hi, lo, cand_act,
                    dedup_factor=1, with_rounds=True,
                )
            n_new = jnp.sum(u_new, dtype=jnp.uint32)
            return (
                table.key_hi, table.key_lo, u_new, u_origin, n_new,
                probe_ok, dd_overflow, rounds,
            )

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def t_append(rows, parent, ebits, cand_rows, cand_src, eb, u_new,
                     u_origin, level_start, tail):
            u = u_new.shape[0]
            sel = compact(u_new, jnp.arange(u, dtype=jnp.uint32), pad)
            sel_u = u_origin[sel]
            rows_blk = cand_rows[sel_u]
            src_state = cand_src[sel_u]
            par_blk = level_start + src_state
            eb_blk = eb[src_state]
            rows = jax.lax.dynamic_update_slice(
                rows, rows_blk.reshape(-1), (tail * jnp.uint32(w),)
            )
            parent = jax.lax.dynamic_update_slice(parent, par_blk, (tail,))
            ebits = jax.lax.dynamic_update_slice(ebits, eb_blk, (tail,))
            return rows, parent, ebits

        return {
            "step": t_step, "fp": t_fp, "insert": t_insert,
            "append": t_append,
        }

    def _phase_wave_bytes(self, probe_rounds: int, two_phase: bool) -> dict:
        """Modeled HBM bytes touched by one traced wave, per phase
        (obs/roofline.py documents the model and its biases).  Buffer-
        proportional, not count-proportional: the device streams full
        fixed-width buffers regardless of how many lanes are live, so
        charging the full widths is what matches what HBM actually
        moves.  The chunk/candidate widths are the LIVE step rung
        (``_step_width``) — ``bytes.step`` drops in proportion to it —
        and the compact/canon/dedup widths the LIVE compact width, so
        ``bytes.dedup`` drops with the sort rung.  On the sortless
        default path ``bytes.dedup`` carries NO sort term at all (the
        claim election probes, it never sorts)."""
        from ..obs.roofline import copy_bytes, probe_bytes, sort_bytes

        cm = self._compiled
        w = cm.state_width
        fpw = cm.fp_words or w
        a = cm.max_actions
        f_eff = self._step_width()
        b = f_eff * a
        u_sz = self._sort_width()
        pad = self._block_pad()
        # step: chunk read + candidate construction + the valid-lane
        # index compaction scan.  Two-phase constructs only U rows (and
        # gathers their U parents); single-phase materializes all B.
        step = f_eff * w * 4 + b * 4 + copy_bytes(u_sz, w)
        if not two_phase:
            step += b * w * 4
        canon = (copy_bytes(u_sz, w) if self._canon is not None else 0)
        canon += u_sz * fpw * 4 + 2 * u_sz * 4
        if self._sortless:
            # Claim election: probe rounds over the compact width plus
            # the claim-plane scatter/readback — no sort planes, no
            # representative re-compaction.
            dedup = probe_bytes(u_sz, probe_rounds) + 2 * u_sz * 4
        else:
            dedup = (
                sort_bytes(u_sz, 3)
                + probe_bytes(u_sz, probe_rounds)
                + 4 * u_sz * 4  # representative compaction planes
            )
        append = copy_bytes(pad, w) + 2 * copy_bytes(pad, 1) + u_sz * 4
        return {
            "step": step, "canon": canon, "dedup": dedup, "append": append,
        }

    # --- the tiered wave (one _wl_call) ---------------------------------------

    def _tiered_programs(self):
        """Cold-filter device programs, cached like every other program
        set.  ``query`` projects the insert's new-key lanes to (hi, lo)
        queries (inactive lanes become the unreachable all-ones
        sentinel) plus the min/max new key for host-side window pruning;
        ``probe`` merge-joins the queries against ONE sorted run chunk —
        a branchless lower-bound binary search, log2(cold_chunk) steps,
        all lanes in lockstep; ``fresh`` folds the accumulated found
        mask out of the append mask."""
        import jax
        import jax.numpy as jnp

        from ..parallel.wave_common import cached_program

        # The query buffers span the live insert compact width.
        u_sz = self._sort_width()
        chunk = self._cold_chunk
        key = ("tiered-cold-v2", u_sz, chunk)

        def build():
            sent = jnp.uint32(0xFFFFFFFF)

            @jax.jit
            def query(hi, lo, u_new, u_origin):
                q_hi = jnp.where(u_new, hi[u_origin], sent)
                q_lo = jnp.where(u_new, lo[u_origin], sent)
                # Lexicographic min/max of the new keys by MASKED
                # two-stage reductions: the sortless claim election
                # (hashset.insert_batch_claim, the default dedup path)
                # returns winners in LANE order, so the sorted-buffer
                # first/last-lane trick no longer applies; the
                # reductions are order-independent and cover the sorted
                # fallback path identically.
                mn_hi = jnp.min(jnp.where(u_new, q_hi, sent))
                mn_lo = jnp.min(
                    jnp.where(u_new & (q_hi == mn_hi), q_lo, sent)
                )
                mx_hi = jnp.max(jnp.where(u_new, q_hi, jnp.uint32(0)))
                mx_lo = jnp.max(
                    jnp.where(
                        u_new & (q_hi == mx_hi), q_lo, jnp.uint32(0)
                    )
                )
                return q_hi, q_lo, mn_hi, mn_lo, mx_hi, mx_lo

            @partial(jax.jit, donate_argnums=(0,))
            def probe(found, q_hi, q_lo, c_hi, c_lo):
                # Branchless lower bound over the sorted chunk: pos ends
                # at min(#elements < q, chunk-1); the equality check at
                # pos decides membership (a present key always has
                # #less < chunk, so the cap never masks a hit).  Chunk
                # tails are padded with the all-ones sentinel, which no
                # real fingerprint can equal (hashset.py).
                pos = jnp.zeros(q_hi.shape, jnp.uint32)
                half = chunk >> 1
                while half:
                    at = pos + jnp.uint32(half - 1)
                    ph = c_hi[at]
                    pl = c_lo[at]
                    less = (ph < q_hi) | ((ph == q_hi) & (pl < q_lo))
                    pos = jnp.where(less, pos + jnp.uint32(half), pos)
                    half >>= 1
                hit = (c_hi[pos] == q_hi) & (c_lo[pos] == q_lo)
                return found | hit

            @jax.jit
            def fresh_of(u_new, found):
                fresh = u_new & ~found
                return fresh, jnp.sum(fresh, dtype=jnp.uint32)

            return {"query": query, "probe": probe, "fresh": fresh_of}

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, build,
            label="TieredTpuChecker.cold",
            journal=self._journal,
            provenance={"u_lanes": u_sz, "cold_chunk": chunk},
        )

    def _cold_filter(self, hi, lo, u_new, u_origin, n_new_hot):
        """Merge-join the wave's hot-tier-new keys against every cold
        run: host-side ``searchsorted`` prunes each run to the window
        overlapping [min, max] new key, and the window streams through
        the device ``cold_chunk`` lanes per pass.  Returns ``(fresh,
        n_fresh, accounting)``."""
        import jax.numpy as jnp

        tp = self._tiered_programs()
        q_hi, q_lo, mn_hi, mn_lo, mx_hi, mx_lo = tp["query"](
            hi, lo, u_new, u_origin
        )
        lo_key = (int(np.asarray(mn_hi)) << 32) | int(np.asarray(mn_lo))
        hi_key = (int(np.asarray(mx_hi)) << 32) | int(np.asarray(mx_lo))
        chunk = self._cold_chunk
        found = jnp.zeros(q_hi.shape, jnp.bool_)
        passes = 0
        runs_touched = 0
        window_entries = 0
        for run in self._cold.runs:
            a = int(np.searchsorted(run, np.uint64(lo_key), side="left"))
            b = int(np.searchsorted(run, np.uint64(hi_key), side="right"))
            if a >= b:
                continue
            runs_touched += 1
            window_entries += b - a
            for off in range(a, b, chunk):
                seg = np.asarray(run[off:off + chunk])
                if seg.shape[0] < chunk:
                    seg = np.concatenate([
                        seg,
                        np.full(
                            chunk - seg.shape[0],
                            np.uint64(0xFFFFFFFFFFFFFFFF),
                        ),
                    ])
                c_hi = (seg >> np.uint64(32)).astype(np.uint32)
                c_lo = seg.astype(np.uint32)
                found = tp["probe"](
                    found, q_hi, q_lo, jnp.asarray(c_hi), jnp.asarray(c_lo)
                )
                passes += 1
        fresh, n_fresh_d = tp["fresh"](u_new, found)
        n_fresh = int(np.asarray(n_fresh_d))
        acct = {
            "passes": passes,
            "bytes": passes * chunk * 8,
            "runs_touched": runs_touched,
            "window_entries": window_entries,
            "new": n_new_hot,
            "hits": n_new_hot - n_fresh,
        }
        return fresh, n_fresh, acct

    def _wl_call(self, carry):
        """One tiered wave: the phase programs (step / fingerprint / hot
        insert / append — the SAME kernels as the fused loop) with the
        cold merge-join between insert and append.  Host bookkeeping
        commits only at flags == 0, like the fused program's on-device
        commit gate; an aborted wave leaves every counter and buffer
        (except the hot table, which recovery rebuilds or spills) at
        its pre-wave state."""
        import jax.numpy as jnp

        key_hi, key_lo, rows, parent, ebits = carry
        self._t_trace_last = None  # set per COMMITTED wave below
        td = self._options._target_max_depth or 0
        if (
            self._t_level_end <= self._t_level_start
            or (td and self._t_depth >= td - 1)
        ):
            # Drained level (a completed snapshot being resumed — the
            # fused loop's device wave_cond gates this) or the next wave
            # would expand past the target depth: report a clean no-op
            # and let the shared termination tail stop the loop.  The
            # drained guard matters for correctness: a zero-count wave
            # would still roll the level boundary and bump the depth.
            self._t_flags = 0
            self._t_cold_last = None
            return carry
        trace = self._t_trace
        if trace:
            import jax

            t_entry = time.perf_counter()
        progs = self._phase_programs()
        f_eff = self._step_width()  # the live step-geometry rung
        count = min(self._t_level_end - self._t_level_start, f_eff)
        disc_prev = self._t_disc  # t_step does not donate it
        if trace:
            t = [time.perf_counter()]
        (
            disc, eb, _states, cand_rows, cand_src, cand_act,
            _n_valid_d, v_ovf_d, gen_d, stepflag_d,
        ) = progs["step"](
            rows, ebits, disc_prev,
            jnp.uint32(self._t_level_start), jnp.uint32(self._t_level_end),
        )
        if trace:
            jax.block_until_ready(cand_rows)
            t.append(time.perf_counter())
        hi, lo = progs["fp"](cand_rows)
        if trace:
            jax.block_until_ready(lo)
            t.append(time.perf_counter())
        (
            key_hi, key_lo, u_new, u_origin, n_new_d, probe_ok_d,
            dd_ovf_d, rounds_d,
        ) = progs["insert"](key_hi, key_lo, hi, lo, cand_act)
        if trace:
            jax.block_until_ready(key_lo)
            t.append(time.perf_counter())
        n_new_hot = int(np.asarray(n_new_d))
        rounds = self._count_stage_rounds(np.asarray(rounds_d))
        flags = 0
        if (
            not bool(np.asarray(probe_ok_d))
            or (self._hot_entries + n_new_hot) * 2 > self._capacity
        ):
            flags |= 1
        if bool(np.asarray(dd_ovf_d)) or bool(np.asarray(v_ovf_d)):
            flags |= 4
        if bool(np.asarray(stepflag_d)):
            flags |= 8
        if (
            f_eff < self._max_frontier
            and self._t_level_end - self._t_level_start > f_eff
        ):
            # Step-rung clamp (flag 128, non-committing): climb one
            # chunk rung and re-run — the base engine's contract.
            flags |= 128

        if trace:
            t.append(time.perf_counter())  # readback: the scalar syncs

        cold = None
        fresh, n_fresh = u_new, n_new_hot
        if flags == 0 and n_new_hot and self._cold.run_count:
            t_cp = time.monotonic()
            fresh, n_fresh, cold = self._cold_filter(
                hi, lo, u_new, u_origin, n_new_hot
            )
            # Host-side cold windowing inside the call window: handed to
            # the shared loop's SpanRecorder via _wl_host_spans so the
            # timeline decomposes it without a second timer pass.
            self._t_host_spans.append(
                ("cold_probe", t_cp, time.monotonic() - t_cp)
            )
        if trace:
            t.append(time.perf_counter())
        if flags == 0 and self._t_tail + n_fresh > self._log_capacity:
            flags |= 2

        if flags == 0:
            rows, parent, ebits = progs["append"](
                rows, parent, ebits, cand_rows, cand_src, eb, fresh,
                u_origin, jnp.uint32(self._t_level_start),
                jnp.uint32(self._t_tail),
            )
            if trace:
                jax.block_until_ready(ebits)
                t.append(time.perf_counter())
            self._hot_entries += n_new_hot
            self._t_tail += n_fresh
            self._t_unique += n_fresh
            self._t_states += int(np.asarray(gen_d))
            self._t_level_start += count
            if self._t_level_start >= self._t_level_end:
                self._t_depth += 1
                self._t_level_end = self._t_tail
            if cold is not None:
                if self._journal:
                    self._journal.append(
                        "cold_probe",
                        depth=self._t_depth,
                        unique=self._t_unique,
                        **cold,
                    )
                self._metrics.inc("cold_probe_passes_total", cold["passes"])
                self._metrics.inc("cold_probe_bytes_total", cold["bytes"])
                self._metrics.inc("cold_hits_total", cold["hits"])
        # An aborted wave's discoveries REVERT, like the fused loop's
        # on-device `disc = where(commit, disc, disc_prev)`: a kept
        # discovery would change the re-run's awaiting mask (wave_eval
        # prunes expansion of states that contribute nothing once a
        # property is discovered), generating different successors than
        # a committed execution — breaking the bit-identical pin.
        # Decided HERE, after every flag (incl. the late row-log check
        # above) is final, so a flag-2 abort cannot leak discoveries.
        self._t_disc = disc if flags == 0 else disc_prev
        self._t_disc_h = np.asarray(self._t_disc)
        self._t_flags = flags
        self._t_cold_last = cold
        if trace and flags == 0:
            from ..parallel.wave_common import two_phase_capable

            # The phases partition the call: host work before the step
            # dispatch and after the append counts as readback.
            phases = {
                "step": t[1] - t[0],
                "canon": t[2] - t[1],
                "dedup": t[3] - t[2],
                "readback": (
                    (t[4] - t[3]) + (t[0] - t_entry)
                    + (time.perf_counter() - t[6])
                ),
                "cold_probe": t[5] - t[4],
                "append": t[6] - t[5],
            }
            # Modeled device bytes: the in-HBM phase model (these ARE
            # its kernels) — cold_probe bytes stay out of the HBM model
            # (host-classed, obs/trace.py) and ride the cold accounting
            # instead.
            self._t_trace_last = self._tracer.record_wave(
                phases,
                self._phase_wave_bytes(
                    rounds, two_phase_capable(self._compiled),
                ),
                probe_rounds=rounds,
            )
        return (key_hi, key_lo, rows, parent, ebits)

    def _wl_view(self, carry):
        from ..parallel.wave_loop import WaveView

        disc = []
        for p, prop in enumerate(self._properties):
            s = int(self._t_disc_h[p])
            if s != NO_SLOT_HOST:
                disc.append((prop.name, s))
        extra = {
            "tail": self._t_tail,
            "hot_entries": self._hot_entries,
            "cold_runs": self._cold.run_count,
        }
        if self._t_cold_last is not None:
            extra["cold_passes"] = self._t_cold_last["passes"]
            extra["cold_bytes"] = self._t_cold_last["bytes"]
        if self._t_trace_last is not None:
            # Traced runs: the wave's phase breakdown rides the shared
            # loop's journal "wave" event.
            extra.update(self._t_trace_last)
        return WaveView(
            waves_this_call=1,
            remaining=self._t_level_end - self._t_level_start,
            depth=self._t_depth,
            flags=self._t_flags,
            unique=self._t_unique,
            states=self._t_states,
            occupancy=self._hot_entries / self._capacity,
            discoveries=tuple(disc),
            extra=extra,
        )

    def _wl_host_spans(self):
        """Fused-loop hook (obs/timeline.py ``SpanRecorder.collect``):
        drain the in-call host spans ``_wl_call`` measured itself —
        the cold-run windowing (``cold_probe``), which runs on the host
        INSIDE the device-call window and would otherwise vanish into
        the opaque ``call_sec``."""
        spans = self._t_host_spans
        if spans:
            self._t_host_spans = []
        return spans

    # --- spill / recovery -----------------------------------------------------

    def _wl_after_commit(self, carry, view):
        """The eviction trigger, on the shared loop's post-commit rung.
        The per-wave decision uses the host-tracked occupancy
        (``view.occupancy`` = hot entries / capacity, exact by
        bookkeeping: inserts add ``n_new_hot``, spills reset, recovery
        rehashes set the segment count) — no device traffic on the
        common path.  At the spill decision point the MEASURED
        ``HashSet.load_factor()`` readback confirms against the key
        planes themselves (one scalar sync per SPILL, not per wave) and
        is what the ``spill`` journal event and ``hot_load_factor``
        metric record."""
        if view.occupancy >= self._spill_threshold:
            from ..parallel.hashset import HashSet

            lf = HashSet(carry[0], carry[1]).load_factor()
            self._metrics.update(hot_load_factor=round(lf, 6))
            carry = self._spill(carry, reason="threshold", load_factor=lf)
        return carry

    def _spill(self, carry, reason: str, load_factor: float):
        """Evict the hot tier: fingerprints of row-log positions
        ``[spill_tail, tail)`` become one sorted immutable cold run
        (computed FROM THE LOG, so keys an aborted insert scribbled
        into the table can never leak into the cold tier), the hot
        table resets to empty, and the watermark advances.  Hot-tier
        cold-hit cache entries are simply dropped — they are in an
        earlier run already."""
        key_hi, key_lo, rows, parent, ebits = carry
        from ..parallel.hashset import make_hashset

        start, end = self._spill_tail, self._t_tail
        t0 = time.monotonic()
        fps = self._segment_fingerprints(rows, start, end)
        self._cold.add_run(fps)
        self._hot_entries = 0
        self._spill_tail = end
        if self._journal:
            self._journal.append(
                "spill",
                reason=reason,
                entries=int(fps.shape[0]),
                bytes=int(fps.nbytes),
                start=start,
                end=end,
                load_factor=round(float(load_factor), 6),
                cold_runs=self._cold.run_count,
                cold_entries=self._cold.entries,
                spill_sec=round(time.monotonic() - t0, 4),
            )
        self._metrics.inc("spills", 1)
        self._metrics.inc("spill_bytes_total", int(fps.nbytes))
        self._metrics.update(
            cold_runs=self._cold.run_count,
            cold_entries=self._cold.entries,
            cold_bytes=self._cold.nbytes,
        )
        t = make_hashset(self._capacity)
        return (t.key_hi, t.key_lo, rows, parent, ebits)

    def _segment_fp_program(self):
        """Device program fingerprinting one row-log chunk — the spill
        readback (O(segment) through the device fp kernel, canonical
        keys when symmetry is on, exactly what the hot tier stored)."""
        import jax
        import jax.numpy as jnp

        from ..ops.device_fp import device_fp64
        from ..parallel.wave_common import cached_program

        cm = self._compiled
        w = cm.state_width
        fpw = cm.fp_words or w
        r = self._max_frontier
        canon = self._canon
        key = ("tiered-segfp", w, fpw, r, canon is not None,
               cm.cache_key() if canon is not None else None)

        def build():
            @jax.jit
            def seg_fp(rows, start):
                states = jax.lax.dynamic_slice(
                    rows, (start * jnp.uint32(w),), (r * w,)
                ).reshape(r, w)
                states_c = (
                    states if canon is None else jax.vmap(canon)(states)
                )
                return device_fp64(states_c[:, :fpw])

            return seg_fp

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, build,
            label="TieredTpuChecker.segfp",
            journal=self._journal,
            provenance={"max_frontier": r},
        )

    def _segment_fingerprints(self, rows, start: int, end: int):
        """uint64 dedup-key fingerprints of row-log positions
        ``[start, end)``, in log order (the cold store sorts)."""
        import jax.numpy as jnp

        if end <= start:
            return np.zeros((0,), np.uint64)
        prog = self._segment_fp_program()
        r = self._max_frontier
        out = []
        for off in range(start, end, r):
            hi, lo = prog(rows, jnp.uint32(off))
            n = min(r, end - off)
            hi = np.asarray(hi)[:n].astype(np.uint64)
            lo = np.asarray(lo)[:n].astype(np.uint64)
            out.append((hi << np.uint64(32)) | lo)
        return np.concatenate(out)

    def _wl_grow(self, flags: int, carry):
        """In-place recovery for an aborted tiered wave.  Flags 2/4 use
        the base growth rules (row log ×2, dedup relax toward 1); flag 1
        SPILLS — the memory budget pins the table capacity, and after
        eviction the empty hot tier re-runs the same chunk (its states
        now answered by the cold tier).  Either way the hot table is
        rebuilt from scratch, erasing any keys the aborted insert
        wrote: a spill re-derives the run from the row log, a non-spill
        recovery rehashes the committed ``[spill_tail, tail)`` segment."""
        from ..parallel.wave_loop import log_grow

        key_hi, key_lo, rows, parent, ebits = carry
        notes = []
        spill = False
        for bit in (2, 4, 128):
            if flags & bit:
                g = self._grow(bit) if self._auto_tune else None
                if g is None:
                    return None
                notes.append(g)
        if flags & 1:
            if self._hot_entries:
                spill = True
                notes.append(
                    f"spill (budget pins capacity={self._capacity})"
                )
            else:
                # The table is already empty (the previous recovery just
                # spilled): this ONE wave's distinct new keys overflow
                # the budgeted table, so eviction cannot converge —
                # shrink the chunk until each wave inserts less than the
                # table holds.  The floor is deliberately tiny: at a
                # pathological budget, crawling 8 states a wave is still
                # correct, and a loud refusal only remains for chunks
                # that cannot shrink further.
                if self._max_frontier <= 8:
                    return None
                self._max_frontier //= 2
                notes.append(f"max_frontier={self._max_frontier}")
        log_grow(
            self, flags, "; ".join(notes), self._t_unique, self._t_depth
        )
        new_qcap = self._log_capacity
        new_pad = self._block_pad()
        if (new_qcap + new_pad) != (self._loop_qcap + self._loop_pad):
            n_len = new_qcap + new_pad
            rows = _resize_flat(
                rows, n_len * self._compiled.state_width, 0
            )
            parent = _resize_flat(parent, n_len, NO_SLOT_HOST)
            ebits = _resize_flat(ebits, n_len, 0)
        self._loop_qcap, self._loop_pad = new_qcap, new_pad
        carry = (key_hi, key_lo, rows, parent, ebits)
        if spill:
            return self._spill(
                carry, reason="overflow",
                load_factor=self._hot_entries / self._capacity,
            )
        kh, kl = self._rehash(rows, self._t_tail, self._spill_tail)
        # The rebuilt table holds exactly the committed segment — any
        # cold-duplicate cache entries the old table carried are gone
        # (they live in earlier runs), so the occupancy bookkeeping must
        # match or the flag-1 gate and journal occupancy would
        # overestimate until the next spill.
        self._hot_entries = self._t_tail - self._spill_tail
        return (kh, kl, rows, parent, ebits)

    def _wl_overflow_message(self, flags: int) -> str:
        if flags & 8:
            return super()._wl_overflow_message(flags)
        return f"tiered engine overflow flags={flags}"

    def _wl_abort_cleanup(self, carry):
        """The keep-partial-break analog of the base hook, scoped to
        the tiers: rebuild the hot table from the committed
        ``[spill_tail, tail)`` segment so a persisted carry never
        carries an aborted wave's keys (a resume would otherwise drop
        that wave's states as hot-tier duplicates)."""
        kh, kl = self._rehash(carry[2], self._t_tail, self._spill_tail)
        self._hot_entries = self._t_tail - self._spill_tail
        return (kh, kl, carry[2], carry[3], carry[4])

    # --- run setup / teardown (the host side of _check_once) ------------------

    def _check_once(self, deadline=None) -> None:
        import jax
        import jax.numpy as jnp

        cm = self._compiled
        props = self._properties

        def sized(arr_np, n):
            if arr_np.shape[0] < n:
                return np.concatenate(
                    [arr_np, np.zeros(n - arr_np.shape[0], arr_np.dtype)]
                )
            return arr_np[:n]

        if self._resume_from is not None:
            snap = np.load(self._resume_from, allow_pickle=False)
            if "tiered_spill_tail" not in snap.files:
                raise ValueError(
                    "snapshot was not written by the tiered engine (no "
                    "persisted cold tier); resume it with spawn_tpu, or "
                    "re-run the tiered check to produce a tiered snapshot"
                )
            if self._memory_budget_mb is not None and (
                capacity_for_budget(self._memory_budget_mb)
                != int(snap["capacity"])
            ):
                # The budget is authoritative (never silently overridden
                # while metrics() reports it), but a resume must adopt
                # the snapshot's table — the two promises can only both
                # hold when they agree, so a mismatch is a loud error
                # naming both sides, like the engine-key check below.
                raise ValueError(
                    f"resume memory_budget_mb={self._memory_budget_mb} "
                    f"implies a "
                    f"{capacity_for_budget(self._memory_budget_mb)}-slot "
                    f"hot table, but the snapshot was written at "
                    f"capacity={int(snap['capacity'])}; resume with the "
                    "snapshot's original budget (or with capacity "
                    "kwargs alone to adopt its geometry)"
                )
            # Adopt the snapshot's geometry, like the base engine.
            self._capacity = int(snap["capacity"])
            self._log_capacity = int(snap["log_capacity"])

        f = self._max_frontier
        qcap = self._log_capacity
        pad = self._block_pad()

        with jax.default_device(self._device):
            seed, _run = self._programs()
            if self._resume_from is not None:
                want_key = self._snapshot_key()
                got_key = str(snap["engine_key"])
                if got_key != want_key:
                    raise ValueError(
                        "snapshot does not match this checker configuration"
                        f" (snapshot {got_key}, expected {want_key})"
                    )
                key_hi = _device_owned(jnp.asarray(snap["key_hi"]))
                key_lo = _device_owned(jnp.asarray(snap["key_lo"]))
                rows = _device_owned(jnp.asarray(sized(
                    np.asarray(snap["rows"]), (qcap + pad) * cm.state_width
                )))
                parent = _device_owned(jnp.asarray(
                    sized(np.asarray(snap["parent"]), qcap + pad)
                ))
                ebits = _device_owned(jnp.asarray(
                    sized(np.asarray(snap["ebits"]), qcap + pad)
                ))
                disc_np = np.asarray(snap["disc"]).astype(np.uint32)
                self._t_disc = _device_owned(jnp.asarray(disc_np))
                self._t_disc_h = disc_np
                self._t_level_start = int(snap["level_start"])
                self._t_level_end = int(snap["level_end"])
                self._t_tail = int(snap["tail"])
                self._t_depth = int(snap["depth"])
                self._t_unique = int(snap["unique_count"])
                self._t_states = (
                    int(snap["sc_hi"]) << 32
                ) | int(snap["sc_lo"])
                self._spill_tail = int(snap["tiered_spill_tail"])
                self._hot_entries = int(snap["tiered_hot_entries"])
                self._cold = ColdStore.from_arrays(
                    np.asarray(snap["tiered_cold_fps"]),
                    np.asarray(snap["tiered_cold_lens"]),
                    spill_dir=self._cold.spill_dir,
                    max_runs=self._cold.max_runs,
                )
                with self._lock:
                    self._state_count = self._t_states
                    self._unique_count = self._t_unique
                    self._max_depth = self._t_depth
                    for p, prop in enumerate(props):
                        if int(disc_np[p]) != NO_SLOT_HOST:
                            self._discovery_slots[prop.name] = int(disc_np[p])
                if self._journal:
                    self._journal.append(
                        "resume",
                        path=self._resume_from,
                        unique=self._t_unique,
                        states=self._t_states,
                        depth=self._t_depth,
                        cold_runs=self._cold.run_count,
                        cold_entries=self._cold.entries,
                        spill_tail=self._spill_tail,
                    )
            else:
                init = cm.init_packed()
                n_init = init.shape[0]
                if n_init > f:
                    raise ValueError(
                        f"{n_init} init states exceed the chunk size "
                        f"({f}); raise max_frontier to at least the "
                        "init-state count (interior levels are unbounded)"
                    )
                key_hi, key_lo, rows, parent, ebits, stats = seed(
                    jnp.asarray(init.astype(np.uint32)), jnp.uint32(n_init)
                )
                stats_h = np.asarray(stats)
                if int(stats_h[STAT_FLAGS]):
                    raise _OverflowRetry(
                        1,
                        "init-state seeding overflowed the budgeted "
                        "fingerprint table; raise memory_budget_mb (or "
                        "pass capacity=) past the init-state count",
                    )
                fcount = int(stats_h[STAT_UNIQUE])
                self._t_level_start = 0
                self._t_level_end = fcount
                self._t_tail = fcount
                self._t_depth = 0
                self._t_unique = fcount
                self._t_states = n_init
                self._hot_entries = fcount
                self._spill_tail = 0
                self._t_disc = _device_owned(jnp.asarray(
                    np.full((len(props),), NO_SLOT_HOST, np.uint32)
                ))
                self._t_disc_h = np.asarray(self._t_disc)
                with self._lock:
                    self._state_count = n_init
                    self._unique_count = fcount

            from ..parallel.wave_loop import FusedWaveLoop, finalize_run

            if self._t_trace:
                from ..obs.trace import WaveTracer

                self._tracer = WaveTracer(self._device, "tpu-tiered")
            self._loop_qcap, self._loop_pad = qcap, pad
            carry = (key_hi, key_lo, rows, parent, ebits)
            carry, _waves = FusedWaveLoop(self).run(carry, deadline)
            key_hi, key_lo, rows, parent, ebits = carry
            self._tables_dev = (parent, rows)
            if self._tracer is not None and self._journal:
                self._journal.append(
                    "trace_summary", **self._tracer.summary()
                )
            finalize_run(self, self._carry_from(
                key_hi, key_lo, rows, parent, ebits, self._stats_np()
            ))

    def _stats_np(self) -> np.ndarray:
        """Host bookkeeping in the base engine's stats-vector layout, so
        ``_carry_from`` / snapshots share one npz schema."""
        return host_stats(
            self._t_level_start, self._t_level_end, self._t_tail,
            self._t_states, self._t_unique, self._t_depth, self._t_disc_h,
        )

    def _wl_write_checkpoint(self, carry) -> dict:
        self._write_snapshot(
            self._checkpoint_path,
            self._carry_from(
                carry[0], carry[1], carry[2], carry[3], carry[4],
                self._stats_np(),
            ),
        )
        return {
            "tail": self._t_tail,
            "cold_runs": self._cold.run_count,
            "cold_entries": self._cold.entries,
        }

    def _snapshot_key(self) -> str:
        # Tiered snapshots are NOT plain-engine resumable (the hot table
        # holds only the post-spill suffix), and vice versa.
        return super()._snapshot_key() + "+tiered-v1"

    def _snapshot_extra(self) -> dict:
        """The tier state beside the base snapshot fields: the cold
        store's runs (concatenated + per-run lengths, so a resume
        restores the exact run shape), the spill watermark, and the
        hot-entry count — all inside the one checkpoint.npz container
        the supervisor already rotates atomically (the atomic-write
        body itself lives once, in the base ``_write_snapshot``)."""
        cold_fps, cold_lens = self._cold.to_arrays()
        return {
            "tiered_cold_fps": cold_fps,
            "tiered_cold_lens": cold_lens,
            "tiered_spill_tail": self._spill_tail,
            "tiered_hot_entries": self._hot_entries,
        }

    # --- surface --------------------------------------------------------------

    def tuned_kwargs(self) -> dict:
        """Right-sized kwargs for a repeat run — with ``capacity``
        PINNED at this run's budgeted size (the base rule of ≥2× the
        unique count would silently un-tier the workload)."""
        out = super().tuned_kwargs()
        out["capacity"] = self._capacity
        return out

    def _wl_geometry(self) -> dict:
        out = super()._wl_geometry()
        out["engine"] = "tpu-tiered"
        out["spill_threshold"] = self._spill_threshold
        if self._memory_budget_mb is not None:
            out["memory_budget_mb"] = self._memory_budget_mb
        return out

    def metrics(self) -> dict:
        out = super().metrics()
        out.update(
            engine="tpu-tiered",
            spill_threshold=self._spill_threshold,
            cold_chunk=self._cold_chunk,
            cold_runs=self._cold.run_count,
            cold_entries=self._cold.entries,
            cold_bytes=self._cold.nbytes,
            hot_entries=self._hot_entries,
            spill_tail=self._spill_tail,
            trace=self._t_trace,
        )
        if self._memory_budget_mb is not None:
            out["memory_budget_mb"] = self._memory_budget_mb
        if self._tracer is not None:
            out["trace_summary"] = self._tracer.summary()
        return out

    def trace_summary(self) -> dict:
        """The finished traced run's roofline reduction: per-phase
        seconds (``wave_breakdown``), modeled bytes, and
        ``hbm_util_frac`` against the device's peak table.  Requires
        ``trace=True``."""
        self.join()
        if self._tracer is None:
            raise RuntimeError(
                "trace_summary() requires spawn_tpu_tiered(trace=True)"
            )
        return self._tracer.summary()
