"""Multi-chip wavefront checking: frontier + visited set sharded over a mesh.

The reference scales with OS threads sharing one DashMap and a job market
(src/job_market.rs, SURVEY §2.7).  The TPU-native analog shards *both* the
frontier and the fingerprint table across chips by fingerprint ownership:

- every fingerprint has one owner shard (a second hash of the fp modulo the
  mesh size), so a local insert on the owner IS the global dedup — no
  cross-chip locking, the moral equivalent of DashMap's hash-sharded locks;
- each wave, every chip expands its local frontier, buckets the successor
  candidates by owner, and exchanges them with a single ``all_to_all`` over
  ICI — the collective replacement for the job market's split_and_push;
- termination and counts are ``psum`` reductions: the frontier is globally
  empty exactly when every shard's insert produced nothing new.

Parent links cross shards, so table entries store a *global id*
(shard << slot_bits | slot); the host walks these across the stacked
per-shard tables for path reconstruction.

Hash-random ownership keeps shards statistically balanced (the job-market
rebalancing analog); skew shows up only as idle lanes in a chunked wave.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np

from ..core.checker import Checker
from ..core.model import Expectation
from ..core.path import Path
from .compiled import CompiledModel, compiled_model_for

NO_GID = 0xFFFFFFFF

# One u32 stats vector per shard carries every host-visible scalar (the
# single-chip engine's STAT_* pattern, wavefront.py): each readback is a
# host sync, so per-call scalars travel in one transfer.
(
    S_LEVEL_START,
    S_LEVEL_END,
    S_TAIL,
    S_SC_LO,
    S_SC_HI,
    S_UNIQUE_G,
    S_UNIQUE_L,
    S_CAND_LO,
    S_CAND_HI,
    S_DEPTH,
    S_FLAGS,
    S_WAVES_LEFT,
) = range(12)
S_DISC = 12  # disc[P] rides at [S_DISC : S_DISC + n_props]

# Compiled shard_map programs shared across checker instances, exactly like
# the single-chip engine's cache (wavefront.py): without it every
# spawn_tpu_sharded() pays tens of seconds of re-trace + re-lower +
# program load even when XLA's persistent cache already has the binary —
# profiling the 1-device-mesh smoke on hardware showed the "run" was
# almost entirely this host-side work.
_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_MAX = 16


def _owner_mix(hi, lo):
    import jax.numpy as jnp

    from ..ops.device_fp import _fmix32, _rotl

    # Independent of both the key planes and the slot hash.
    return _fmix32(lo ^ _rotl(hi, 7) ^ jnp.uint32(0xA511E9B3))


def _owner_mix_host(hi: int, lo: int) -> int:
    """Bit-identical host evaluation of :func:`_owner_mix` (one int at a
    time), so seeding needs no device round trip to place init states —
    pinned against the device mix by
    tests/test_tpu_sharded.py::test_owner_mix_host_matches_device."""
    from ..ops.fingerprint import _fmix32

    M = 0xFFFFFFFF
    return _fmix32((lo ^ (((hi << 7) | (hi >> 25)) & M) ^ 0xA511E9B3) & M)


def _owner_mix_host_np(hi, lo):
    """Vectorized host evaluation of :func:`_owner_mix` over uint32
    numpy arrays — the bulk re-owner for resharding (every logged row
    re-routed by fingerprint) and for tiered-sharded seeding.  Pinned
    bit-identical to the scalar host mix (and therefore to the device
    mix) by tests/test_tiered_sharded.py."""
    hi = np.asarray(hi, np.uint32)
    lo = np.asarray(lo, np.uint32)
    rot = (hi << np.uint32(7)) | (hi >> np.uint32(25))
    h = lo ^ rot ^ np.uint32(0xA511E9B3)
    h ^= h >> np.uint32(16)
    h = h * np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h = h * np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


class ShardedTpuChecker(Checker):
    """Wavefront checker running one program per mesh device via shard_map."""

    def __init__(
        self,
        options,
        mesh=None,
        capacity: int = 1 << 20,
        chunk_size: int = 1 << 11,
        dedup_factor: int = 4,
        compiled: Optional[CompiledModel] = None,
        resume_from: Optional[str] = None,
        journal=None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every_waves: Optional[int] = None,
        checkpoint_every_sec: Optional[float] = None,
        bucket_slack: Optional[int] = None,
        sort_lanes: Optional[int] = None,
        sortless: Optional[bool] = None,
        step_lanes: Optional[int] = None,
        waves_per_call: Optional[int] = None,
    ):
        """Same checkpoint/journal hooks as the single-chip engine
        (wavefront.py): ``journal`` streams wave-level telemetry as JSON
        lines, ``checkpoint_path`` + a cadence knob write periodic
        atomic mid-run snapshots, and ``resume_from`` continues a saved
        run.  A sharded snapshot is bound to the MESH SIZE (global ids
        encode the owner shard), but adopts the snapshot's per-shard
        capacity and chunk geometry as data.

        ``bucket_slack``: per-destination exchange bucket width, in
        PERCENT of the even share ``u_sz/n`` (wave_loop.py's
        ``exchange_bucket_lanes``; default 50).  The all_to_all ships
        ``[n, bucket, W+3]`` per shard instead of the former fixed
        ``[n, u_sz, W+3]`` — ~n× less transmitted per wave at the
        measured occupancies (docs/SHARDED_SCALING.md).  A wave whose
        candidates overflow any destination bucket commits NOTHING,
        raises flag 32, and the host retries the same chunk at the next
        rung (slack ×2) — the engine's standard overflow-flag + retry
        contract.  Warm starts pass the discovered rung back in (the
        knob cache persists it) and skip the ramp.

        ``sort_lanes``: the adaptive sort-geometry rung (wavefront.py's
        knob, shared ladder in wave_loop.py): a power-of-two width for
        the per-shard pre-exchange compact/dedup-sort buffers — the
        owner-bucketing argsort, the exchange buckets
        (``exchange_bucket_lanes`` is slack% of the RUNG's even share),
        and the post-exchange insert all shrink with it.  None starts at
        the full worst-case ``U`` and lets the density tuner downshift;
        a wave whose valid candidates exceed the rung raises the
        non-committing flag 4 and the host retries one rung up.  The
        discovered rung rides the knob cache and snapshots exactly like
        ``bucket_slack``.

        ``sortless``: the dedup-path selection (wavefront.py documents
        the contract; default = the claim-plane election unless an
        explicit ``sort_lanes`` selects the sorted fallback).  On this
        engine the election replaces the OWNER-SIDE insert's pre-dedup
        sort; the local pre-exchange ``prededup`` sort survives on
        meshes wider than one shard — the exchange ships only distinct
        keys, and electing without a table to claim into would need a
        scratch table per wave — but is skipped entirely on 1-shard
        meshes, where the claim insert IS the global dedup.

        ``step_lanes``: the frontier-sized chunk rung (wavefront.py's
        knob, shared ladder in wave_loop.py) — the per-wave chunk slice,
        candidate batch, compact buffers, and the exchange buckets
        derived from them all span the rung instead of the worst-case
        ``chunk_size`` width.  A shard whose remaining level exceeds
        the rung raises the non-committing flag 128; the host climbs
        one rung and re-runs."""
        super().__init__(options.model)
        import jax

        if options._visitor is not None:
            raise ValueError("spawn_tpu_sharded() does not support visitors")
        self._options = options
        self._compiled = compiled or compiled_model_for(options.model)
        # Symmetry: dedup — and therefore OWNER ROUTING — keys on the
        # canonical row's fingerprint, so every member of an orbit lands
        # on one shard and the owner's local insert stays the global
        # dedup; stores keep the original rows (wavefront.py's policy,
        # docs/SYMMETRY.md).  Missing canon capability raises loudly,
        # like the single-chip engine.
        from .canon import make_canon

        self._canon = (
            make_canon(self._compiled)
            if options._symmetry is not None
            else None
        )
        if options._symmetry is not None and self._canon is None:
            raise ValueError(
                "spawn_tpu_sharded() with symmetry() requires the "
                "compiled model to declare a canonicalization, but "
                f"{type(self._compiled).__name__} defines neither "
                "canon_spec() nor canon_rows (parallel/canon.py); use "
                "spawn_dfs() for host-side symmetry"
            )
        if mesh is None:
            mesh = jax.sharding.Mesh(np.array(jax.devices()), ("shards",))
        self._mesh = mesh
        self._n = mesh.devices.size
        # Per-shard capacity: the largest power of two fitting the budget
        # (open addressing needs a power of two; the mesh size need not be).
        self._cap_s = 1 << max(capacity // self._n, 1 << 10).bit_length() - 1
        self._slot_bits = self._cap_s.bit_length() - 1
        # Global ids are shard << slot_bits | slot in one uint32; strict
        # < 32 keeps the all-ones NO_GID sentinel unreachable and the shift
        # from wrapping (shard bits must cover shard n-1, so ceil(log2 n)).
        if self._slot_bits + max(self._n - 1, 1).bit_length() >= 32:
            raise ValueError("capacity too large for 32-bit global ids")
        # Same spawn-time crash-band guard as the single-chip engine
        # (wavefront.max_safe_unique_lanes): buffers past the validated
        # band hard-crash the TPU worker mid-wave, and this engine has no
        # auto-tune retry to recover — clamp the chunk here, loudly.
        # The binding buffer is the POST-EXCHANGE insert over n*u_sz
        # receive lanes (each shard receives one u_sz bucket from every
        # peer), so the per-shard u_sz is bounded at cap/n; the payload
        # rides w+3 words per lane, which the width-dependent cap uses.
        from .hashset import unique_buffer_size
        from .wavefront import max_safe_unique_lanes

        a = self._compiled.max_actions
        u_cap = max_safe_unique_lanes(self._compiled.state_width + 3)
        clamped = False
        while (
            chunk_size > 2048
            and self._n * unique_buffer_size(chunk_size * a, dedup_factor)
            > u_cap
        ):
            chunk_size //= 2
            clamped = True
        if self._n * unique_buffer_size(chunk_size * a, dedup_factor) > u_cap:
            raise ValueError(
                f"chunk geometry (chunk_size={chunk_size}, max_actions="
                f"{a}, dedup_factor={dedup_factor}) exceeds the device-"
                "safe compact-buffer band even at the floor chunk; raise "
                "dedup_factor or use a narrower model"
            )
        if clamped:
            import logging

            logging.getLogger(__name__).warning(
                "spawn_tpu_sharded: chunk_size clamped to %d "
                "(max_actions=%d, dedup_factor=%d): requested geometry "
                "exceeds the device-safe compact-buffer band",
                chunk_size, a, dedup_factor,
            )
        self._chunk = chunk_size
        self._dedup_factor = dedup_factor
        from .wave_loop import BUCKET_SLACK_DEFAULT

        self._bucket_slack = (
            BUCKET_SLACK_DEFAULT if bucket_slack is None
            else int(bucket_slack)
        )
        if self._bucket_slack < 1:
            raise ValueError("bucket_slack must be a positive percentage")
        self._bucket_retries = 0  # overflow-retry rungs climbed this run
        # Adaptive sort-geometry rung (wave_loop.py's ladder; the
        # single-chip engine's knob, wavefront.py documents the
        # contract).  None = full worst-case buffer until the density
        # tuner has evidence; an explicit rung is a warm start.
        from .wave_loop import (
            SORT_RUNG_MIN, STEP_RUNG_MIN, clamp_sort_lanes,
            clamp_step_lanes,
        )

        self._sort_lanes = (
            None if sort_lanes is None else clamp_sort_lanes(sort_lanes)
        )
        # Explicit rung = warm start: the density tuner stands down
        # (the single-chip rule, wavefront.py).
        self._sort_tune = sort_lanes is None
        self._sort_rung_floor = SORT_RUNG_MIN
        self._sort_peak_valid = 0.0
        self._sort_quanta = 0
        self._sort_retries = 0  # flag-4 rung climbs this run
        # Dedup-path selection + the frontier-sized step rung
        # (wavefront.py's knobs; one shared ladder in wave_loop.py).
        self._sortless = (
            (sort_lanes is None) if sortless is None else bool(sortless)
        )
        self._step_lanes = (
            None if step_lanes is None else clamp_step_lanes(step_lanes)
        )
        self._step_tune = step_lanes is None
        self._step_rung_floor = STEP_RUNG_MIN
        self._step_peak_frontier = 0.0
        self._step_quanta = 0
        self._step_retries = 0  # flag-128 rung climbs this run
        if waves_per_call is None:
            from .wave_common import default_waves_per_call

            waves_per_call = default_waves_per_call(options)
        elif int(waves_per_call) < 1:
            # waves_per_call=0 would seed every run() call with an
            # exhausted budget: the device loop returns immediately with
            # no progress and the host loop spins forever.
            raise ValueError("waves_per_call must be >= 1")
        self._waves_per_call = int(waves_per_call)
        self._properties = self._model.properties()
        self._ev_indices = [
            i
            for i, p in enumerate(self._properties)
            if p.expectation is Expectation.EVENTUALLY
        ]
        self._discovery_gids: Dict[str, int] = {}
        self._state_count = 0
        self._unique_count = 0
        self._max_depth = 0
        self._done = threading.Event()
        self._errors: List[BaseException] = []
        self._lock = threading.Lock()
        self._tables_host: Optional[tuple] = None
        self._tables_dev: Optional[tuple] = None
        self._discoveries_cache: Optional[Dict[str, Path]] = None
        self._accounting: dict = {}
        self._resume_from = resume_from
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

        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # --- exchange geometry ---------------------------------------------------

    def _step_width(self) -> int:
        """The EFFECTIVE per-wave chunk width in frontier lanes
        (wavefront.py's `_step_width`, same contract): the step rung
        capped at the live ``chunk_size``."""
        full = self._chunk
        if self._step_lanes is None:
            return full
        return min(self._step_lanes, full)

    def _u_sz(self) -> int:
        """Current compaction/dedup buffer width (hashset.py's single
        definition), from the LIVE chunk/dedup knobs — auto-grow and
        the step rung may have moved them mid-run."""
        from .hashset import unique_buffer_size

        return unique_buffer_size(
            self._step_width() * self._compiled.max_actions,
            self._dedup_factor,
        )

    def _sort_width(self) -> int:
        """The EFFECTIVE pre-exchange compact/sort buffer width: the
        sort-geometry rung capped at the live worst-case ``U``
        (wavefront.py's `_sort_width`, same contract).  Everything
        downstream — the owner argsort, the exchange buckets, the
        post-exchange insert — derives its shape from this number."""
        full = self._u_sz()
        if self._sort_lanes is None:
            return full
        return min(self._sort_lanes, full)

    def _bucket_lanes(self) -> int:
        """Per-destination exchange bucket width at the CURRENT slack
        rung — the one source of truth (wave_loop.exchange_bucket_lanes)
        shared by the device programs (this engine's and the
        tiered-sharded engine's) and ``accounting()``, so reported payload geometry can never drift
        from what the device transmits.  Sized from the SORT width (the
        buffer the exchange actually buckets), so the dedup rung shrinks
        transmitted bytes too; the cap at the full sort buffer keeps the
        top slack rung overflow-free by construction (a shard never has
        more candidates than its sort buffer holds)."""
        from .wave_loop import exchange_bucket_lanes

        return exchange_bucket_lanes(
            self._sort_width(), self._n, self._bucket_slack
        )

    # --- device program ------------------------------------------------------

    def _build_run(self):
        """Fused multi-chunk program, the sharded analog of the single-chip
        engine: each shard drains a FIFO slot queue of its own states with
        *global* BFS-level boundaries (depth advances only when a psum says
        every shard finished the level), exchanging successor candidates
        over ICI each chunk.  The whole loop runs inside one shard_map'd
        ``while_loop`` — the host syncs once per ``waves`` chunks instead
        of once per chunk per wave.

        All loop-control decisions (work-remaining, flags, finish_when,
        depth gating) derive from psum reductions, so every shard takes the
        same branch — a requirement for collectives inside the loop body.

        Exchange-buffer memory: candidates are locally pre-deduped before
        bucketing (hashset.prededup) and then routed into PER-DESTINATION
        BUCKETS, so the all_to_all operates on ``[n, bkt, W+3]`` uint32
        per shard with ``bkt = exchange_bucket_lanes(U, n, bucket_slack)``
        (≈ ``U/n · slack``, wave_loop.py) and
        ``U = max(min(chunk*max_actions, 16K), chunk*max_actions /
        dedup_factor)`` — transmitted bytes per wave scale with the real
        per-destination share instead of the full ``U`` buffer (the n²
        wall docs/SHARDED_SCALING.md measured).  A destination bucket
        overflow raises flag 32 and the wave commits nothing; the host
        retries the chunk at the next slack rung.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..ops.device_fp import device_fp64
        from .hashset import (
            HashSet, compact_valid, insert_batch_claim,
            insert_batch_compact, prededup,
        )
        from .wave_common import make_finish_when_device, wave_eval

        cm = self._compiled
        w = cm.state_width
        fpw = cm.fp_words or w  # identity = leading words (compiled.py)
        # Symmetry: fingerprints (dedup keys AND shard owners) come from
        # the canonical row; stores/queues/exchange payloads carry the
        # ORIGINAL rows (wavefront.py's policy).
        canon = self._canon

        def fp_of(rows):
            rows_c = rows if canon is None else jax.vmap(canon)(rows)
            return device_fp64(rows_c[:, :fpw])

        a = cm.max_actions
        f = self._chunk  # worst-case chunk (queue/seed geometry)
        # The live step-geometry rung: the per-wave chunk slice; a
        # shard whose remaining level exceeds it raises the
        # non-committing flag 128 (compiled out at the top rung).
        f_eff = self._step_width()
        n = self._n
        cap_s = self._cap_s
        qcap = cap_s
        slot_bits = self._slot_bits
        props = self._properties
        ev_indices = self._ev_indices
        dedup_factor = self._dedup_factor
        # Dedup path (wavefront.py's contract): claim election on the
        # owner-side insert by default; 1-shard meshes additionally
        # skip the local prededup sort (the insert IS the global
        # dedup there).
        sortless = self._sortless
        # The live sort-geometry rung: the pre-exchange compact/dedup
        # buffers below span this width, so the owner argsort, bucket
        # scatters, and exchange payload all follow it.  None = the
        # worst-case buffer of the live batch (sortless default).
        sort_lanes = (
            None if self._sort_lanes is None else self._sort_width()
        )
        b = f_eff * a  # per-shard candidate lanes (pre-compaction)
        # Per-destination exchange bucket (wave_loop.exchange_bucket_lanes
        # via _bucket_lanes — the same number accounting() reports).
        bkt = self._bucket_lanes()
        target_depth = self._options._target_max_depth or 0
        fw_found_matched = make_finish_when_device(
            self._options._finish_when, props
        )
        u = jnp.uint32

        def go_from(level_start, level_end, depth, disc, waves_left, flags):
            work = jax.lax.psum(level_end - level_start, "shards") > u(0)
            found = (
                jax.lax.psum((disc != u(NO_GID)).astype(u), "shards") > u(0)
            )
            go = work & (waves_left > 0) & (flags == u(0))
            go = go & ~fw_found_matched(found)
            if target_depth:
                go = go & (depth < u(target_depth - 1))
            return go

        def body(carry):
            (
                key_hi,
                key_lo,
                store,
                parent,
                ebits,
                queue,
                level_start,
                level_end,
                tail,
                sc_lo,
                sc_hi,
                unique_g,
                unique_l,
                cand_lo,
                cand_hi,
                depth,
                disc,
                waves_left,
                flags,
                _go,
            ) = carry
            me = jax.lax.axis_index("shards").astype(u)

            count = jnp.minimum(level_end - level_start, u(f_eff))
            chunk = jax.lax.dynamic_slice(queue, (level_start,), (f_eff,))
            lane = jnp.arange(f_eff, dtype=u)
            active = lane < count
            safe_slots = jnp.where(active, chunk, 0)
            states = store[safe_slots]

            # Shared expansion-time evaluation; ids are global this time.
            # ``disc_prev`` is kept so a retryable-overflow wave (which
            # must commit NOTHING — the host re-runs the same chunk at
            # grown knobs) can revert its discovery candidates too, the
            # single-chip engine's abort contract.
            my_gids = (me << u(slot_bits)) | safe_slots
            disc_prev = disc
            disc, eb, nexts, valid, gen_local, step_flag = wave_eval(
                cm, props, ev_indices, states, active, my_gids,
                ebits[safe_slots], disc, allow_two_phase=True,
            )
            generated = jax.lax.psum(gen_local, "shards")

            # Local pre-dedup BEFORE the exchange: one stable sort elects a
            # representative per distinct local key, so only distinct keys
            # (U = B/dedup_factor lanes, not B) pay for the owner bucketing
            # scatters, the single packed all_to_all, and the owner-side
            # row scatters.  Candidate batches are ~95% invalid/duplicate
            # lanes; profiling the single-chip engine showed exactly these
            # B-indexed row operations dominating the chunk.
            flat_valid = valid.reshape(b)
            if nexts is None:
                # TWO-PHASE expansion (same contract as the single-chip
                # engine, wavefront.py): compact the valid lane indices
                # first, construct successors via ``step_lane`` only for
                # the survivors, and fingerprint U lanes instead of B.
                from .hashset import compact_valid_indices

                v_orig, v_act, _n_valid, local_overflow = (
                    compact_valid_indices(
                        flat_valid, dedup_factor, sort_lanes=sort_lanes
                    )
                )
                rows_v, _valid_v, lane_flags_v = jax.vmap(cm.step_lane)(
                    states[v_orig // u(a)], v_orig % u(a)
                )
                step_flag = step_flag | jnp.any(lane_flags_v & v_act)
                v_hi, v_lo = fp_of(rows_v)
                if sortless and n == 1:
                    # 1-shard sortless: no exchange to minimize, so the
                    # claim insert below IS the global dedup — skip the
                    # local prededup sort entirely.
                    u_hi, u_lo, u_valid = v_hi, v_lo, v_act
                    rows_u = rows_v
                    orig_lane = v_orig
                else:
                    u_hi, u_lo, u_origin0, u_valid, _never = prededup(
                        v_hi, v_lo, v_act, dedup_factor=1
                    )
                    rows_u = rows_v[u_origin0]
                    orig_lane = v_orig[u_origin0]
            else:
                flat = nexts.reshape(b, w)
                hi, lo = fp_of(flat)
                # Same two-stage shrink as the single-chip engine: compact
                # the sparse valid lanes first (hashset.compact_valid,
                # shared so the overflow criterion cannot drift), then
                # dedup the compacted buffer — the sort and every
                # downstream scatter work on real keys, not the
                # sentinel-padded majority.
                v_hi, v_lo, v_orig, v_act, local_overflow = compact_valid(
                    hi, lo, flat_valid, dedup_factor,
                    sort_lanes=sort_lanes,
                )
                if sortless and n == 1:
                    u_hi, u_lo, u_valid = v_hi, v_lo, v_act
                    orig_lane = v_orig
                    rows_u = flat[orig_lane]
                else:
                    u_hi, u_lo, u_origin0, u_valid, _never = prededup(
                        v_hi, v_lo, v_act, dedup_factor=1
                    )
                    orig_lane = v_orig[u_origin0]
                    rows_u = flat[orig_lane]
            u_sz = u_hi.shape[0]
            gid_u = my_gids[orig_lane // u(a)]
            eb_u = eb[orig_lane // u(a)]

            def any_shard(x):
                return jax.lax.psum(x.astype(u), "shards") > u(0)

            # Retryable overflows are detected BEFORE any state mutation,
            # so an overflowing wave can commit NOTHING: validity is
            # masked off (the insert/store/queue writes become no-ops),
            # counters and ``disc`` revert, and level_start does not
            # advance — the host grows the tripped knob (dedup_factor /
            # bucket_slack) and re-runs the exact same chunk with no
            # work lost and no table rebuild needed.
            g_lovf = any_shard(local_overflow)
            # Step-rung clamp (flag 128, non-committing; compiled out
            # at the top rung): any shard's remaining level exceeding
            # the chunk rung aborts the wave mesh-wide — the host
            # climbs one rung and re-runs.
            g_sovf = (
                any_shard(level_end - level_start > u(f_eff))
                if f_eff < f else jnp.zeros((), jnp.bool_)
            )
            if n == 1:
                # One-shard mesh: every key's owner is self, so the whole
                # bucket/sort/all_to_all exchange is an identity — elide
                # it at trace time and reuse the already-computed keys
                # (this is most of the former 1-device overhead vs the
                # single-chip engine).
                g_bovf = jnp.zeros((), jnp.bool_)
                commit = ~(g_lovf | g_sovf)
                rw, rg, reb = rows_u, gid_u, eb_u
                rv = u_valid & commit
                rhi, rlo = u_hi, u_lo
            else:
                # Bucket the representatives by owner shard; exchange
                # over ICI.
                owner = _owner_mix(u_hi, u_lo) % u(n)
                key = jnp.where(u_valid, owner, u(n))
                order = jnp.argsort(key, stable=True)
                key_s = key[order]
                # Bucket sizes as n+1 dense reductions — NOT a
                # scatter-add: every lane collides into one of n+1 cells,
                # and TPU scatter serializes colliding updates (profiled
                # at seconds per chunk).
                counts = jnp.stack(
                    [jnp.sum((key == u(d)).astype(u)) for d in range(n + 1)]
                )
                # BUCKETED exchange: each destination gets a ``bkt``-lane
                # bucket (a slack-scaled slice of the even share u_sz/n,
                # wave_loop.exchange_bucket_lanes) instead of the full
                # u_sz buffer — transmitted bytes shrink ~n× while the
                # measured occupancies say real candidates fill a few
                # percent of even the slim bucket.  A destination count
                # past the bucket raises flag 32; nothing commits.
                g_bovf = any_shard(jnp.any(counts[:n] > u(bkt)))
                commit = ~(g_lovf | g_bovf | g_sovf)
                offsets = jnp.concatenate(
                    [jnp.zeros((1,), u), jnp.cumsum(counts)[:-1]]
                )
                pos = jnp.arange(u_sz, dtype=u) - offsets[key_s]
                dst = jnp.where(key_s < n, key_s, u(n))  # drop invalid

                # Pack the row + its parent gid, ebits, and validity into
                # one [n, bkt, W+3] buffer so a SINGLE all_to_all (one
                # collective launch per chunk, not four) carries the whole
                # exchange.  Lanes past a bucket's width drop out of the
                # scatter (mode="drop"); on an aborted wave the validity
                # column is zeroed, so receivers insert nothing.
                payload = jnp.concatenate(
                    [
                        rows_u,
                        gid_u[:, None],
                        eb_u[:, None],
                        (u_valid & commit).astype(u)[:, None],
                    ],
                    axis=1,
                )
                send = jnp.zeros((n, bkt, w + 3), u)
                send = send.at[dst, pos].set(payload[order], mode="drop")
                recv = jax.lax.all_to_all(
                    send, "shards", split_axis=0, concat_axis=0, tiled=False
                )

                # Local insert — the owner's insert IS the global dedup;
                # the compact form keeps the store/parent/queue scatters
                # proportional to distinct received keys.
                flatrecv = recv.reshape(n * bkt, w + 3)
                rw = flatrecv[:, :w]
                rg = flatrecv[:, w]
                reb = flatrecv[:, w + 1]
                rv = flatrecv[:, w + 2] != u(0)
                rhi, rlo = fp_of(rw)

            # Commit gating for the global counters (the psums are
            # shard-invariant, so every shard takes the same branch).
            generated = jnp.where(commit, generated, u(0))
            new_lo = sc_lo + generated
            sc_hi = sc_hi + (new_lo < sc_lo).astype(u)
            sc_lo = new_lo
            # Accounting: distinct candidates this shard contributes to
            # the exchange this wave (the all_to_all payload's real
            # occupancy); 64-bit via a lo/hi pair, like the state counter
            # — the one counter proportional to total candidates.
            new_cand_lo = cand_lo + jnp.sum(u_valid & commit, dtype=u)
            cand_hi = cand_hi + (new_cand_lo < cand_lo).astype(u)
            cand_lo = new_cand_lo
            disc = jnp.where(commit, disc, disc_prev)
            count = jnp.where(commit, count, u(0))
            # dedup_factor=1: the receive batch is already per-sender
            # deduped, so its distinct-key count can approach the full
            # batch (disjoint keys per shard) — a divided buffer here
            # would spuriously overflow on waves the old code handled.
            # dd_overflow is structurally False here (dedup_factor=1
            # gives the insert a buffer covering its whole receive
            # batch) but stays wired into the FATAL flag 64 below: if
            # the sizing rule ever changes, dropped received states must
            # be a loud error, never a silently wrong "verified" result
            # (the tiered-sharded loop keeps the same invariant guard).
            if sortless:
                # Claim-plane election (hashset.insert_batch_claim):
                # the receive batch probes directly, winners are the
                # lowest receive lane of each key run, and r_origin is
                # the identity map — the gathers below elide.
                (
                    table, r_slot, r_new, r_origin, _r_active, probe_ok,
                    dd_overflow,
                ) = insert_batch_claim(
                    HashSet(key_hi, key_lo), rhi, rlo, rv
                )
                rows_r, rg_r, reb_r = rw, rg, reb
            else:
                (
                    table, r_slot, r_new, r_origin, _r_active, probe_ok,
                    dd_overflow,
                ) = insert_batch_compact(
                    HashSet(key_hi, key_lo), rhi, rlo, rv, dedup_factor=1
                )
                rows_r = rw[r_origin]
                rg_r = rg[r_origin]
                reb_r = reb[r_origin]
            sslot = jnp.where(r_new, r_slot, u(cap_s))
            store = store.at[sslot].set(rows_r, mode="drop")
            parent = parent.at[sslot].set(rg_r, mode="drop")
            ebits = ebits.at[sslot].set(reb_r, mode="drop")
            n_new = jnp.sum(r_new, dtype=u)
            unique_l = unique_l + n_new
            unique_g = unique_g + jax.lax.psum(n_new, "shards")

            # Append new slots at this shard's queue tail.  The drop
            # sentinel is the always-out-of-bounds all-ones index, NOT
            # qcap+f: auto-grow may halve the chunk mid-run, and a
            # sentinel derived from the CURRENT f would land in bounds
            # of the larger originally-minted queue buffer.
            qpos = tail + jnp.cumsum(r_new.astype(u)) - 1
            qidx = jnp.where(r_new, qpos, u(0xFFFFFFFF))
            queue = queue.at[qidx].set(r_slot, mode="drop")
            tail = tail + n_new

            # Advance within the level; the boundary is global.
            level_start = level_start + count
            rem_g = jax.lax.psum(level_end - level_start, "shards")
            done_level = rem_g == u(0)
            depth = depth + done_level.astype(u)
            level_end = jnp.where(done_level, tail, level_end)

            flags = flags | jnp.where(any_shard(~probe_ok), 1, 0).astype(u)
            flags = flags | jnp.where(
                any_shard(unique_l * u(2) > u(cap_s)), 1, 0
            ).astype(u)
            flags = flags | jnp.where(any_shard(tail > u(qcap)), 2, 0).astype(u)
            # The insert's own dedup buffer runs at dedup_factor=1 over
            # the receive batch, so its overflow is structurally
            # impossible (the buffer covers the whole batch); flag 4 is
            # exactly the pre-exchange compaction overflow, which the
            # host can retry because the aborted wave committed nothing.
            flags = flags | jnp.where(g_lovf, 4, 0).astype(u)
            flags = flags | jnp.where(g_bovf, 32, 0).astype(u)
            flags = flags | jnp.where(g_sovf, 128, 0).astype(u)
            flags = flags | jnp.where(
                any_shard(dd_overflow), 64, 0
            ).astype(u)
            flags = flags | jnp.where(any_shard(step_flag), 8, 0).astype(u)

            waves_left = waves_left - 1
            go = go_from(level_start, level_end, depth, disc, waves_left, flags)
            return (
                table.key_hi,
                table.key_lo,
                store,
                parent,
                ebits,
                queue,
                level_start,
                level_end,
                tail,
                sc_lo,
                sc_hi,
                unique_g,
                unique_l,
                cand_lo,
                cand_hi,
                depth,
                disc,
                waves_left,
                flags,
                go,
            )

        def cond(carry):
            return carry[-1]

        waves_per_call = self._waves_per_call

        def run_shard(key_hi, key_lo, store, parent, ebits, queue, stats):
            # stats: one [S_DISC + P] u32 vector per shard — every
            # host-visible scalar in ONE readback (wavefront's STAT_*
            # pattern).  The
            # waves budget is a program constant, so calls need no
            # per-call upload either.
            carry = (
                key_hi,
                key_lo,
                store,
                parent,
                ebits,
                queue,
                stats[S_LEVEL_START],
                stats[S_LEVEL_END],
                stats[S_TAIL],
                stats[S_SC_LO],
                stats[S_SC_HI],
                stats[S_UNIQUE_G],
                stats[S_UNIQUE_L],
                stats[S_CAND_LO],
                stats[S_CAND_HI],
                stats[S_DEPTH],
                stats[S_DISC:],
                jnp.int32(waves_per_call),
                stats[S_FLAGS],
                jnp.zeros((), jnp.bool_),
            )
            carry = carry[:-1] + (
                go_from(
                    carry[6], carry[7], carry[15], carry[16], carry[17],
                    carry[18],
                ),
            )
            out = jax.lax.while_loop(cond, body, carry)
            stats_out = jnp.concatenate(
                [
                    jnp.stack(
                        [
                            out[6],
                            out[7],
                            out[8],
                            out[9],
                            out[10],
                            out[11],
                            out[12],
                            out[13],
                            out[14],
                            out[15],
                            out[18],
                            out[17].astype(u),
                        ]
                    ),
                    out[16],
                ]
            )
            return (
                out[0],
                out[1],
                out[2],
                out[3],
                out[4],
                out[5],
                stats_out,
            )

        shard = P("shards")
        specs = (shard,) * 7
        run = jax.jit(
            jax.shard_map(
                run_shard,
                mesh=self._mesh,
                in_specs=specs,
                out_specs=(shard,) * 7,
            ),
            donate_argnums=(0, 1, 2, 3, 4, 5, 6),
        )
        return run

    def _programs(self):
        key = (
            self._compiled.cache_key(),
            # Two-phase capability is a trace-time branch (wave_eval's
            # hasattr gate) — key it, as in wavefront.py:_programs.
            hasattr(self._compiled, "step_valid")
            and hasattr(self._compiled, "step_lane"),
            # Symmetry is a trace-time branch, keyed like the two-phase
            # gate (wavefront.py:_programs).
            self._canon is not None,
            self._cap_s,
            self._chunk,
            self._dedup_factor,
            self._sortless,  # the dedup path is a trace-time branch
            self._sort_width(),  # the live sort-geometry rung
            self._step_width(),  # the live step-geometry rung
            self._bucket_slack,  # shapes the exchange buckets
            self._waves_per_call,  # baked into run() as a constant
            tuple((d.platform, d.id) for d in self._mesh.devices.flat),
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
            label="ShardedTpuChecker.fused",
            journal=self._journal,
            provenance=self._key_provenance(),
        )

    def _key_provenance(self) -> dict:
        """Human-readable knobs behind the program-cache keys (the
        journaled ``compile`` events' attribution field —
        docs/OBSERVABILITY.md "Compile events")."""
        return {
            "model": type(self._compiled).__name__,
            "shards": self._n,
            "capacity_per_shard": self._cap_s,
            "chunk_size": self._chunk,
            "dedup_factor": self._dedup_factor,
            "sortless": self._sortless,
            "sort_lanes": self._sort_width(),
            "step_lanes": self._step_width(),
            "bucket_slack": self._bucket_slack,
            "waves_per_call": self._waves_per_call,
            "symmetry": self._canon is not None,
        }

    def _seed_program(self, seed_w: int):
        """Init-state seeding program, cached like the run program (the
        trace + lower alone costs seconds per checker otherwise).

        Mints EVERY device buffer internally (table planes, store, parent,
        ebits, queue) and emits the run loop's stats vector, so the whole
        spawn costs one upload (the packed per-shard init rows) + one
        dispatch.  A seed insert overflow surfaces as flag 16 in
        the stats vector; the run program's go-gate refuses to start on
        nonzero flags, and the host loop raises the seeding error."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..ops.device_fp import device_fp64
        from .hashset import insert_batch

        cm = self._compiled
        cap_s = self._cap_s
        f = self._chunk
        qcap = cap_s
        w = cm.state_width
        fpw = cm.fp_words or w
        canon = self._canon  # table keys are canonical fps (symmetry)
        eb0 = (1 << len(self._ev_indices)) - 1
        n_props = len(self._properties)
        key = (
            "seed",
            cm.cache_key(),
            canon is not None,
            cap_s,
            f,
            seed_w,
            eb0,
            n_props,
            tuple((d.platform, d.id) for d in self._mesh.devices.flat),
        )

        def seed_shard(packed):
            from .hashset import HashSet
            from .wave_common import compact

            u = jnp.uint32

            def pv(x):
                # Buffers minted INSIDE the shard_map body are typed
                # shard-invariant; mark them varying so they can join
                # while_loop carries with the (varying) seeded keys.
                return jax.lax.pcast(x, "shards", to="varying")

            sts = packed[0, :, :w]
            val = packed[0, :, w] != u(0)
            table = HashSet(
                key_hi=pv(jnp.zeros((cap_s,), u)),
                key_lo=pv(jnp.zeros((cap_s,), u)),
            )
            store = pv(jnp.zeros((cap_s, w), u))
            parent = pv(jnp.full((cap_s,), u(NO_GID)))
            ebits_buf = pv(jnp.zeros((cap_s,), u))
            sts_c = sts if canon is None else jax.vmap(canon)(sts)
            hi, lo = device_fp64(sts_c[:, :fpw])
            table, slot, is_new, probe_ok, dd_overflow = insert_batch(
                table, hi, lo, val
            )
            sslot = jnp.where(is_new, slot, u(cap_s))
            store = store.at[sslot].set(sts, mode="drop")
            ebits_buf = ebits_buf.at[sslot].set(u(eb0), mode="drop")
            n_new = jnp.sum(is_new, dtype=u)
            queue = pv(jnp.zeros((qcap + f,), u))
            queue = queue.at[: is_new.shape[0]].set(
                compact(is_new, slot, is_new.shape[0])
            )
            ok = probe_ok & ~dd_overflow
            sc = jax.lax.psum(jnp.sum(val, dtype=u), "shards")
            unique_g = jax.lax.psum(n_new, "shards")
            seed_fail = jax.lax.psum((~ok).astype(u), "shards")
            zero = pv(jnp.zeros((), u))
            stats = jnp.concatenate(
                [
                    jnp.stack([
                        zero,  # level_start
                        n_new,  # level_end
                        n_new,  # tail
                        sc,  # sc_lo
                        zero,  # sc_hi
                        unique_g,
                        n_new,  # unique_l
                        zero,  # cand_lo
                        zero,  # cand_hi
                        zero,  # depth
                        jnp.where(seed_fail > u(0), u(16), zero),  # flags
                        zero,  # waves_left
                    ]),
                    pv(jnp.full((n_props,), u(NO_GID))),
                ]
            )
            return (
                table.key_hi,
                table.key_lo,
                store,
                parent,
                ebits_buf,
                queue,
                stats,
            )

        def build():
            sp = P("shards")
            return jax.jit(
                jax.shard_map(
                    seed_shard,
                    mesh=self._mesh,
                    in_specs=(sp,),
                    out_specs=(sp,) * 7,
                )
            )

        from .wave_common import cached_program

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, build,
            label="ShardedTpuChecker.seed",
            journal=self._journal,
            provenance={"shards": self._n, "seed_w": seed_w},
        )

    # --- host loop -----------------------------------------------------------

    def _run(self) -> None:
        try:
            self._check()
        except BaseException as e:
            self._errors.append(e)
        finally:
            self._done.set()

    def _seed_initial(self, shard):
        """Host-side owner routing + the seed program: one upload + one
        dispatch mints every device buffer (the spawn-cost story in
        ``_seed_program``)."""
        import jax
        import jax.numpy as jnp

        cm = self._compiled
        n = self._n
        # Seed init states host-side: fingerprints and owners computed
        # on the HOST (bit-identical by the pinned host/device fp
        # parity), so the whole spawn is one upload + one seed
        # dispatch — the seed program mints every device buffer and
        # the run loop's stats vector itself.
        from ..ops.fingerprint import fp64_words

        init = cm.init_packed()
        n_init = init.shape[0]
        fpw = cm.fp_words or cm.state_width
        if self._canon is not None:
            # Owner placement must use the CANONICAL fingerprint (the
            # dedup/routing key); evaluated on the CPU backend via
            # the same traced kernel, so it is bit-identical to the
            # device's without a device round trip.  The shards still
            # receive (and store) the original rows.
            from .canon import canon_batch_host

            fp_rows = canon_batch_host(cm, init)
        else:
            fp_rows = init
        fps = [fp64_words(row[:fpw].tolist()) for row in fp_rows]
        owner = np.array(
            [
                _owner_mix_host((fp >> 32) & 0xFFFFFFFF, fp & 0xFFFFFFFF)
                % n
                for fp in fps
            ],
            np.uint32,
        )

        # Per-shard seed batches, padded to a common width; validity
        # rides as one extra word column so the upload is one array.
        seed_w = max(int((owner == d).sum()) for d in range(n)) or 1
        packed_np = np.zeros((n, seed_w, cm.state_width + 1), np.uint32)
        for d in range(n):
            idx = np.flatnonzero(owner == d)
            packed_np[d, : len(idx), : cm.state_width] = init[idx]
            packed_np[d, : len(idx), cm.state_width] = 1

        seed = self._seed_program(int(seed_w))
        out = seed(jax.device_put(jnp.asarray(packed_np), shard))

        self._state_count = n_init
        self._unique_count = len(set(fps))
        return out

    def _check(self) -> None:
        import time as _time

        import jax
        import jax.numpy as jnp

        opts = self._options
        props = self._properties
        n = self._n
        deadline = (
            _time.monotonic() + opts._timeout if opts._timeout is not None else None
        )

        # Global (host-side numpy) views of the stacked per-shard tables are
        # only pulled at the end; during the run everything stays sharded.
        from jax.sharding import NamedSharding, PartitionSpec as P

        shard = NamedSharding(self._mesh, P("shards"))
        k_stats = S_DISC + len(props)

        if self._resume_from is not None:
            # A resume ADOPTS the snapshot's per-shard geometry (cap_s
            # shapes the slot mask and the gid encoding, chunk the queue
            # backstop); only model identity + MESH SIZE are key-checked
            # — gids embed the owner shard, so a snapshot cannot move to
            # a different mesh size.
            snap = np.load(self._resume_from, allow_pickle=False)
            if (
                "n_shards" in snap.files
                and int(snap["n_shards"]) != self._n
            ):
                # The dedicated LOUD mesh-size error (the generic key
                # mismatch below also catches it for old snapshots, but
                # names neither size): gids encode the owner shard, so a
                # snapshot is bound to the exact mesh width that wrote it.
                raise ValueError(
                    f"sharded snapshot was written on a "
                    f"{int(snap['n_shards'])}-shard mesh and cannot "
                    f"resume on {self._n} shards directly: global "
                    "state ids encode the owner shard, so the only "
                    f"valid direct-resume size is "
                    f"{int(snap['n_shards'])} shards; to continue this "
                    f"run on a {self._n}-shard mesh, re-key the "
                    "snapshot first with the `reshard` verb "
                    "(stateright_tpu.tiered.reshard.reshard_snapshot) "
                    "and resume the converted snapshot with the "
                    "tiered-sharded engine"
                )
            want_key = self._snapshot_key()
            got_key = str(snap["engine_key"])
            if got_key != want_key:
                raise ValueError(
                    "snapshot does not match this sharded checker "
                    f"configuration (snapshot {got_key}, expected "
                    f"{want_key})"
                )
            self._cap_s = int(snap["cap_s"])
            self._slot_bits = self._cap_s.bit_length() - 1
            self._chunk = int(snap["chunk"])
            if "bucket_slack" in snap.files:
                # Adopt the saved run's discovered bucket rung so a
                # resume never re-pays the overflow-retry ramp.
                self._bucket_slack = int(snap["bucket_slack"])
            if "sort_lanes" in snap.files:
                # Same for the discovered sort-geometry rung (0 = the
                # saved run ran at the full buffer).  An adopted rung is
                # a PROVEN rung: the density tuner stands down, exactly
                # as for an explicit spawn argument.
                saved_rung = int(snap["sort_lanes"])
                if saved_rung:
                    self._sort_lanes = saved_rung
                    self._sort_tune = False
            if "sortless" in snap.files:
                # Adopt the saved run's dedup path: a resume of a
                # fallen-back run must not re-pay the fallback retry.
                self._sortless = bool(int(snap["sortless"]))
            if "step_lanes" in snap.files:
                saved_step = int(snap["step_lanes"])
                if saved_step:
                    self._step_lanes = saved_step
                    self._step_tune = False
            from .wavefront import _device_owned

            def up(x):
                # Sharded upload, forced into DEVICE-OWNED buffers: the
                # run program donates every argument, and donating a
                # borrowed host-upload buffer corrupts the run (see
                # wavefront._device_owned).
                return _device_owned(jax.device_put(jnp.asarray(x), shard))

            key_hi = up(snap["key_hi"])
            key_lo = up(snap["key_lo"])
            store = up(snap["store"])
            parent = up(snap["parent"])
            ebits = up(snap["ebits"])
            queue = up(snap["queue"])
            stats_np = np.asarray(snap["stats"]).astype(np.uint32)
            stats = up(stats_np.reshape(-1))
            snap_h = stats_np.astype(np.int64).reshape(n, k_stats)
            with self._lock:
                self._state_count = (
                    int(snap_h[0, S_SC_HI]) << 32
                ) | int(snap_h[0, S_SC_LO])
                self._unique_count = int(snap_h[0, S_UNIQUE_G])
                self._max_depth = int(snap_h[0, S_DEPTH])
                for d in range(n):
                    for p, prop in enumerate(props):
                        g = int(snap_h[d, S_DISC + p])
                        if g != NO_GID:
                            self._discovery_gids.setdefault(prop.name, g)
            if self._journal:
                self._journal.append(
                    "resume",
                    path=self._resume_from,
                    unique=self._unique_count,
                    states=self._state_count,
                    depth=self._max_depth,
                )
        else:
            key_hi, key_lo, store, parent, ebits, queue, stats = (
                self._seed_initial(shard)
            )

        # The steady-state loop is the SHARED wave-loop core
        # (parallel/wave_loop.py) — journal/metrics/checkpoint cadence,
        # overflow dispatch (grow in place for dedup/bucket overflows,
        # loud raise otherwise), and termination live there, identical
        # to the single-chip engine by construction.
        from .wave_loop import FusedWaveLoop, finalize_run

        self._run_fn = self._programs()
        carry = (key_hi, key_lo, store, parent, ebits, queue, stats)
        carry, waves_total = FusedWaveLoop(self).run(carry, deadline)
        key_hi, key_lo, store, parent, ebits, queue, stats = carry
        stats_h = self._last_stats_h.copy()
        # A keep-partial stop (deadline/cancel during a retryable
        # overflow) can leave flag bits in the final readback; the
        # flagged wave committed nothing, so the rest of the vector is
        # the exact pre-wave state and a resume must start flag-clean.
        stats_h[:, S_FLAGS] = 0

        # Weak-scaling accounting: lockstep waves, the bucketed all_to_all
        # payload, and its measured occupancy/skew (docs/SHARDED_SCALING.md;
        # replaces the former unquantified "statistically balanced" claim).
        cand_h = (
            stats_h[:, S_CAND_HI].astype(np.int64) << 32
        ) | stats_h[:, S_CAND_LO].astype(np.int64)
        uniq_h = stats_h[:, S_UNIQUE_L].astype(np.int64)
        self._accounting = self._build_accounting(waves_total, cand_h, uniq_h)

        # Keep the device arrays; path reconstruction pulls them lazily —
        # most runs never reconstruct a path (same policy as the
        # single-chip engine).
        self._tables_dev = (parent, store)
        # Full run state for save_snapshot (the single-chip engine's
        # snapshot-ready policy, via the shared finalize): bounded sharded
        # runs persist and resume exactly like single-chip ones.
        finalize_run(self, {
            "key_hi": key_hi,
            "key_lo": key_lo,
            "store": store,
            "parent": parent,
            "ebits": ebits,
            "queue": queue,
            "stats": stats_h.astype(np.uint32),
        })

    # --- shared wave-loop adapter (parallel/wave_loop.py) --------------------

    def _wl_call(self, carry):
        return self._run_fn(*carry)

    def _wl_view(self, carry):
        from .wave_loop import WaveView

        props = self._properties
        n = self._n
        stats_h = (
            np.asarray(carry[6])
            .reshape(n, S_DISC + len(props))
            .astype(np.int64)
        )
        self._last_stats_h = stats_h
        # Per-shard skew gauges from the SAME readback (no extra sync).
        self._update_shard_metrics(
            stats_h[:, S_LEVEL_END] - stats_h[:, S_LEVEL_START],
            stats_h[:, S_UNIQUE_L],
            (stats_h[:, S_CAND_HI] << 32) | stats_h[:, S_CAND_LO],
        )
        remaining = int(
            (stats_h[:, S_LEVEL_END] - stats_h[:, S_LEVEL_START]).sum()
        )
        disc = []
        for d in range(n):
            for p, prop in enumerate(props):
                g = int(stats_h[d, S_DISC + p])
                if g != NO_GID:
                    disc.append((prop.name, g))
        return WaveView(
            waves_this_call=self._waves_per_call
            - int(np.uint32(stats_h[0, S_WAVES_LEFT]).astype(np.int32)),
            remaining=remaining,
            depth=int(stats_h[0, S_DEPTH]),
            flags=int(stats_h[0, S_FLAGS]),
            unique=int(stats_h[0, S_UNIQUE_G]),
            states=(int(stats_h[0, S_SC_HI]) << 32)
            | int(stats_h[0, S_SC_LO]),
            # Binding constraint: the FULLEST shard's table load.
            occupancy=float(stats_h[:, S_UNIQUE_L].max()) / self._cap_s,
            discoveries=tuple(disc),
            extra={},
        )

    def _wl_set_discovery(self, name: str, gid: int) -> None:
        self._discovery_gids.setdefault(name, gid)

    def _wl_discovered_names(self):
        return self._discovery_gids

    def _wl_cand_lanes(self) -> int:
        """Density denominator (wave_loop.LoopVitals): the mesh-global
        worst-case compaction width — every shard's ``U`` buffer —
        matching the psum'd generated-successor numerator.  Rung-
        independent, like the single-chip engine's (the rung is sized
        FROM this density)."""
        return self._n * self._u_sz()

    def _wl_full_sort_lanes(self) -> int:
        """The PER-SHARD worst-case width the rung is clamped to; with
        the mesh-global density this makes ``density × full`` the
        average per-shard valid count — what a shard's rung must hold
        (skew is absorbed by the tuner headroom, and an undersized rung
        is a retry, never a wrong answer)."""
        return self._u_sz()

    def _wl_apply_sort_rung(self, rung: int) -> None:
        """Apply a density-tuner downshift (wave_loop.maybe_retune_sort):
        swap the knob, re-journal the geometry event, and — in fused
        mode — rebuild the run program.  The carry (tables, store,
        queue, stats) is rung-independent."""
        self._sort_lanes = int(rung)
        self._sort_quanta = 0
        # Not mirrored into the metrics registry — metrics() reports
        # the live _sort_width(); a stale registry copy would shadow a
        # later ladder climb (wavefront.py's rule).
        if self._journal:
            self._journal.append("geometry", **self._wl_geometry())
        if getattr(self, "_run_fn", None) is not None:
            self._run_fn = self._programs()

    def _wl_full_step_lanes(self) -> int:
        return self._chunk

    def _wl_apply_step_rung(self, rung: int) -> None:
        """Apply a frontier-tuner downshift (wave_loop.
        maybe_retune_step) — the step-ladder twin of the sort hook
        above; same journal/recompile contract."""
        self._step_lanes = int(rung)
        self._step_quanta = 0
        if self._journal:
            self._journal.append("geometry", **self._wl_geometry())
        if getattr(self, "_run_fn", None) is not None:
            self._run_fn = self._programs()

    def _wl_geometry(self) -> dict:
        """The ``geometry`` journal event payload (wave_loop.
        journal_geometry) — the advisor's knob ground truth, incl. the
        exchange-bucket rung the bucket_slack recommendation is
        relative to."""
        return {
            "engine": "tpu-sharded",
            "shards": self._n,
            "capacity_per_shard": self._cap_s,
            "chunk_size": self._chunk,
            "dedup_factor": self._dedup_factor,
            "sortless": self._sortless,
            "sort_lanes": self._sort_width(),
            "step_lanes": self._step_width(),
            "bucket_slack": self._bucket_slack,
            "exchange_bucket_lanes": (
                0 if self._n == 1 else self._bucket_lanes()
            ),
            "u_lanes": self._wl_cand_lanes(),
            "waves_per_call": self._waves_per_call,
        }

    @staticmethod
    def _skew(arr) -> float:
        m = float(np.asarray(arr, np.float64).mean())
        return round(float(np.asarray(arr).max()) / m, 4) if m > 0 else 1.0

    def _update_shard_metrics(self, frontier, unique_l, cand) -> None:
        """Per-shard gauges + max/mean skew, refreshed from the stats
        readback the loop already holds (never an extra device sync):
        ``shard_frontier`` (remaining frontier backlog), ``shard_unique``
        (owner-table inserts), ``shard_exchange_bytes`` (cumulative
        useful exchange payload contributed) — each a flat numeric dict,
        which obs/prometheus.py renders as ONE labeled gauge family per
        name — plus the scalar ``*_skew_max_over_mean`` gauges the
        ROADMAP #2/#3 load-balance story watches
        (docs/OBSERVABILITY.md "Density and skew telemetry")."""
        w3 = (self._compiled.state_width + 3) * 4
        xbytes = [
            0 if self._n == 1 else int(c) * w3 for c in cand
        ]
        self._metrics.update(
            shard_frontier={
                str(d): int(frontier[d]) for d in range(self._n)
            },
            shard_unique={
                str(d): int(unique_l[d]) for d in range(self._n)
            },
            shard_exchange_bytes={
                str(d): xbytes[d] for d in range(self._n)
            },
            frontier_skew_max_over_mean=self._skew(frontier),
            unique_skew_max_over_mean=self._skew(unique_l),
            exchange_skew_max_over_mean=self._skew(cand),
        )

    def _wl_write_checkpoint(self, carry) -> dict:
        self._write_snapshot(
            self._checkpoint_path,
            {
                "key_hi": carry[0],
                "key_lo": carry[1],
                "store": carry[2],
                "parent": carry[3],
                "ebits": carry[4],
                "queue": carry[5],
                "stats": self._last_stats_h.astype(np.uint32),
            },
        )
        return {}

    def _wl_retryable_flags(self) -> int:
        # 4 = pre-exchange compaction/dedup overflow, 32 = exchange
        # bucket overflow, 128 = step-rung clamp: all detected before
        # any state mutation, so the aborted wave committed nothing and
        # a grown re-run is exact.  Table (1) / queue (2) growth would
        # change the gid encoding that parent links and snapshots bake
        # in, so those stay loud errors on this engine.
        return 4 | 32 | 128

    def _wl_overflow_message(self, flags: int) -> str:
        if flags & 16:
            return (
                "init-state seeding overflowed the insert buffers; "
                "raise capacity or lower dedup_factor"
            )
        if flags & 1:
            return (
                f"sharded fingerprint table overfull (per-shard "
                f"capacity {self._cap_s}); raise capacity"
            )
        if flags & 2:
            return (
                "a shard's frontier queue overflowed its backstop "
                "bound; raise capacity"
            )
        if flags & 8:
            return (
                "the model step kernel flagged an encoding-capacity "
                "overflow (a successor exceeded the packed layout's "
                "bounds); the compiled model's capacity assumptions "
                "do not hold for this configuration"
            )
        if flags & 4:
            return (
                "a shard's chunk had more VALID successor candidates "
                "than its compaction/dedup buffers hold even at "
                f"dedup_factor=1 (now {self._dedup_factor}); lower "
                "chunk_size"
            )
        if flags & 32:
            return (
                "the per-destination exchange bucket overflowed at the "
                f"full-buffer rung (bucket_slack={self._bucket_slack}) — "
                "this cannot happen by construction; please report"
            )
        if flags & 64:
            return (
                "the owner-side insert dedup buffer overflowed — "
                "impossible by construction at dedup_factor=1 over the "
                "receive batch; please report"
            )
        if flags & 128:
            return (
                "the step-rung ladder clamped a wave at the full chunk "
                "width — impossible by construction (the clamp is "
                "compiled out at the top rung); please report"
            )
        return f"sharded engine overflow flags={flags}"

    def _grow_knobs(self, flags: int):
        """The knob half of in-place growth, shared with the
        tiered-sharded engine's recovery: relax ``dedup_factor`` straight to 1 (flag 4,
        the rule shared with wavefront.py via wave_loop) and/or climb
        the exchange bucket-slack ladder (flag 32).  Both knobs only
        shape per-wave scratch buffers — never the table, store, queue,
        or gid encoding — so the re-run at grown shapes is exact.
        Returns the grow-note string, or None when the tripped knob
        cannot grow."""
        from .wave_loop import (
            climb_step_rung, log_grow, next_bucket_slack,
            relax_dedup_geometry,
        )

        notes = []
        if flags & 128:
            # Step-rung clamp: the fullest shard's remaining level
            # exceeded the chunk rung — climb one rung (shared ladder
            # rule, wave_loop.climb_step_rung).
            note = climb_step_rung(self, self._chunk)
            if note is None:
                return None
            self._step_retries += 1
            notes.append(note)
        if flags & 4:
            from .wave_loop import climb_sort_rung, reset_sort_rung_to_full

            # Sort-rung ladder first (the shared wave_loop rule, same as
            # the single-chip _grow): a flag-4 overflow at a rung below
            # the full U means the RUNG was too small; climb one rung
            # and re-run.  Only at the full buffer does the flag mean
            # the pre-ladder condition.
            full = self._u_sz()
            note = climb_sort_rung(self, full)
            if note is not None:
                self._sort_retries += 1
                notes.append(note)
            else:
                from .hashset import unique_buffer_size
                from .wavefront import max_safe_unique_lanes

                a = self._compiled.max_actions
                u_cap = max_safe_unique_lanes(
                    self._compiled.state_width + 3
                )
                relaxed = relax_dedup_geometry(
                    self._chunk,
                    self._dedup_factor,
                    lambda c, dd: self._n * unique_buffer_size(c * a, dd),
                    u_cap,
                    chunk_label="chunk_size",
                )
                if relaxed is None:
                    return None
                self._dedup_factor, self._chunk, note = relaxed
                # The full buffer overflowed on valid count: the relaxed
                # dd=1 geometry starts at its own full width (evidence +
                # geometry re-journal in the shared helper).
                reset_sort_rung_to_full(self, full)
                notes.append(note)
        if flags & 32:
            # Evaluate the slack ladder against the SAME width the live
            # buckets derive from (_bucket_lanes uses the sort rung):
            # stepping it against the worst-case U would double the
            # slack without widening the actual (tile-rounded) bucket
            # and deterministically re-fail the same chunk.
            nxt = next_bucket_slack(
                self._sort_width(), self._n, self._bucket_slack
            )
            if nxt is None:
                return None
            self._bucket_slack = nxt
            self._bucket_retries += 1
            notes.append(f"bucket_slack={nxt}")
        log_grow(
            self, flags, "; ".join(notes),
            self._unique_count, self._max_depth,
        )
        return "; ".join(notes)

    def _wl_grow(self, flags: int, carry):
        """In-place growth for the fused loop (the shared wave-loop
        core's grow hook): grow the knobs, then — because the aborted
        wave committed nothing, so the stats readback IS the exact
        pre-wave state — clear the flag bits in the host copy, re-upload
        it (one small transfer per retry; every other carry is reused
        as-is), recompile at the new shapes, and hand the loop the
        patched carry to re-run the same chunk."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._grow_knobs(flags) is None:
            return None
        from .wavefront import _device_owned

        stats_np = self._last_stats_h.astype(np.uint32).copy()
        stats_np[:, S_FLAGS] = 0
        shard = NamedSharding(self._mesh, P("shards"))
        stats = _device_owned(
            jax.device_put(jnp.asarray(stats_np.reshape(-1)), shard)
        )
        self._run_fn = self._programs()
        return carry[:6] + (stats,)

    def _build_accounting(self, waves_total: int, cand_h, uniq_h) -> dict:
        """The weak-scaling accounting dict from measured per-shard
        counters (``cand_h``/``uniq_h``: int64[n]); shared with the
        tiered-sharded engine so the payload geometry and occupancy
        definitions cannot drift between them.

        The ``all_to_all_bytes_*`` keys derive from the ACTUAL bucket
        geometry (``_bucket_lanes()``, the same wave_loop function the
        device program compiled against) — never hand-computed from the
        static ``u_sz`` buffer shape — so the doc generator and the
        benchmark read one source of truth.  If the slack rung ramped mid-run, the
        final (largest) bucket is reported: committed pre-ramp waves
        shipped smaller buckets, so totals are a slight over- and
        occupancy a slight under-statement, in the conservative
        direction."""
        cm = self._compiled
        n = self._n
        f = self._chunk
        u_sz = self._sort_width()  # the buffer the exchange buckets
        bkt = self._bucket_lanes()
        return {
            "shards": n,
            "waves": waves_total,
            "chunk_size": f,
            "exchange_lanes_per_shard": u_sz,
            # The discovered rungs + their retry counts, the
            # bucket_slack pattern (knob cache / warm-start evidence).
            "sort_lanes": u_sz,
            "sort_retries": self._sort_retries,
            "sortless": int(self._sortless),
            "step_lanes": self._step_width(),
            "step_retries": self._step_retries,
            # The bucketed payload shape: each shard ships one
            # [bkt, W+3] bucket per destination per wave.
            "exchange_bucket_lanes": 0 if n == 1 else bkt,
            "bucket_slack": self._bucket_slack,
            "bucket_retries": self._bucket_retries,
            # On a 1-shard mesh the whole exchange is elided at trace
            # time (owner is always self), so no bytes move at all.
            "exchange_elided": n == 1,
            "all_to_all_bytes_per_wave_per_shard": (
                0 if n == 1
                else int(n * bkt * (cm.state_width + 3) * 4)
            ),
            "all_to_all_bytes_total": (
                0 if n == 1
                else int(
                    waves_total * n * n * bkt * (cm.state_width + 3) * 4
                )
            ),
            "candidates_sent_per_shard": cand_h.tolist(),
            # Fraction of TRANSMITTED lanes carrying a real candidate:
            # each shard ships [n, bkt] lanes per wave (one bkt-wide
            # bucket per destination), so the denominator is
            # waves * n^2 * bkt across the mesh — occupancy *
            # all_to_all_bytes_total = useful bytes.
            # 0.0 when elided: nothing is transmitted, so the identity
            # occupancy × all_to_all_bytes_total = useful bytes holds.
            "exchange_occupancy": (
                float(cand_h.sum() / (waves_total * n * n * bkt))
                if waves_total and n > 1
                else 0.0
            ),
            "exchange_payload_bytes_total": int(
                cand_h.sum() * (cm.state_width + 3) * 4
            ) if n > 1 else 0,
            "unique_per_shard": uniq_h.tolist(),
            "unique_skew_max_over_mean": (
                float(uniq_h.max() / uniq_h.mean()) if uniq_h.sum() else 1.0
            ),
        }

    def _snapshot_key(self) -> str:
        """Process-stable compatibility key for sharded snapshots — the
        single-chip engine's recipe (model identity via the packed init
        digest, never ``repr``) plus the MESH SIZE, which global ids
        encode and so cannot change across a resume.  Per-shard capacity
        and chunk geometry travel as npz data and are adopted."""
        import hashlib

        cm = self._compiled
        init_digest = hashlib.sha256(
            cm.init_packed().tobytes()
        ).hexdigest()[:16]
        return repr(
            (
                "sharded-v1",
                type(cm).__qualname__,
                cm.state_width,
                cm.max_actions,
                tuple(p.name for p in self._properties),
                init_digest,
                self._n,
            )
            # Canonical-fp tables are not resumable as plain ones (and
            # vice versa); appended only when on, like wavefront.py.
            + (("sym",) if self._canon is not None else ())
        )

    def _write_snapshot(self, path: str, carry: dict) -> None:
        """Atomic (write + rename) persistence of the full sharded run
        state, in ``save_snapshot`` format."""
        import os

        arrays = {k: np.asarray(v) for k, v in carry.items()}
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as fh:
            np.savez_compressed(
                fh,
                engine_key=self._snapshot_key(),
                cap_s=self._cap_s,
                chunk=self._chunk,
                # Mesh width travels as explicit data too (not just key
                # material) so a wrong-mesh resume can say WHICH sizes
                # disagree; bucket_slack rides along so resumes skip the
                # overflow-retry ramp the saved run already climbed.
                n_shards=self._n,
                bucket_slack=self._bucket_slack,
                # The discovered sort rung rides along like the bucket
                # rung (0 = running at the full buffer), so a resume
                # skips the sort ladder's ramp too.
                sort_lanes=self._sort_lanes or 0,
                # The dedup path + step rung, same sentinel rules: a
                # resume must not re-pay the sortless fallback or the
                # step ladder's climb ramp.
                sortless=int(self._sortless),
                step_lanes=self._step_lanes or 0,
                **arrays,
            )
        os.replace(tmp, path)

    def save_snapshot(self, path: str) -> None:
        """Persist the full sharded checker state so a bounded run can be
        resumed with ``spawn_tpu_sharded(resume_from=path)`` on a mesh of
        the SAME SIZE (global ids encode the owner shard).  Same npz
        recipe as the single-chip engine's snapshots."""
        self.join()
        if self._carry_dev is None:
            raise RuntimeError("no run state to snapshot")
        self._write_snapshot(path, self._carry_dev)

    def tuned_kwargs(self) -> dict:
        """Engine kwargs right-sized to THIS run's final knobs (the
        single-chip engine's warm-start pattern): a fresh spawn of the
        same workload on the same mesh starts past the overflow-retry
        ramp — ``bucket_slack`` in particular is the discovered exchange
        rung the knob cache persists (runtime/knob_cache.py)."""
        self.join()
        return dict(
            capacity=self._cap_s * self._n,
            chunk_size=self._chunk,
            dedup_factor=self._dedup_factor,
            bucket_slack=self._bucket_slack,
            # The discovered sort rung (the second ladder the knob
            # cache persists — warm runs skip both ramps) — ONLY when
            # one was actually pinned AND the run ended on the sort
            # path; persisting the full worst-case width would disarm
            # every warm repeat's density tuner, and a SORTLESS run's
            # rung is the claim compaction buffer's tuner detail — an
            # explicit sort_lanes under sortless means a fallback-
            # forcing budget cap on the single-chip engine, so a warm
            # repeat must re-arm the tuner instead (wavefront.py's and
            # the serve scheduler's rule).
            **(
                {"sort_lanes": self._sort_width()}
                if self._sort_lanes is not None and not self._sortless
                else {}
            ),
            # The discovered dedup path + step rung (wavefront.py's
            # persistence rules: the path always, a rung only when
            # pinned).
            sortless=int(self._sortless),
            **(
                {"step_lanes": self._step_width()}
                if self._step_lanes is not None else {}
            ),
        )

    def discovered_fingerprints(self):
        """Sorted uint64 fingerprints of every discovered unique state
        (fingerprints of the ORIGINAL stored rows), for cross-engine
        discovery-set comparison against the single-chip engine — the
        bit-identity pin behind every scale claim
        (tests/test_tpu_sharded.py).  Pulls the per-shard stores to the
        host; size it like a path reconstruction, not a hot call."""
        self.join()
        if self._carry_dev is None:
            raise RuntimeError("no run state to fingerprint")
        from .wave_loop import fingerprints_of_rows

        n, cap_s, w = self._n, self._cap_s, self._compiled.state_width
        store = np.asarray(self._carry_dev["store"]).reshape(n, cap_s, w)
        queue = np.asarray(self._carry_dev["queue"]).reshape(n, -1)
        stats = np.asarray(self._carry_dev["stats"]).reshape(
            n, S_DISC + len(self._properties)
        )
        rows = [
            store[d, queue[d, : int(stats[d, S_TAIL])]] for d in range(n)
        ]
        return fingerprints_of_rows(
            self._compiled, np.concatenate(rows, axis=0), self._canon
        )

    # --- Checker surface -----------------------------------------------------

    def accounting(self) -> dict:
        """Weak-scaling accounting of the finished run: lockstep wave
        count, the (static) all_to_all payload per wave, its measured
        occupancy, and per-shard unique counts with the max/mean skew —
        the quantified form of this engine's load-balance story (the
        reference rebalances dynamically via its job market,
        src/job_market.rs:140-167; hash ownership balances statically and
        this dict is the evidence)."""
        self.join()
        return dict(self._accounting)

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        return self._unique_count

    def max_depth(self) -> int:
        return self._max_depth

    def metrics(self) -> dict:
        """Live observability snapshot (names: docs/OBSERVABILITY.md);
        safe to call mid-run.  Includes the weak-scaling accounting once
        the run completes."""
        out = super().metrics()
        out.update(
            engine="tpu-sharded",
            shards=self._n,
            capacity=self._cap_s * self._n,
            capacity_per_shard=self._cap_s,
            chunk_size=self._chunk,
            dedup_factor=self._dedup_factor,
            sortless=self._sortless,
            sort_lanes=self._sort_width(),
            # Pinned rung vs live width: wavefront.py's rule.
            sort_lanes_rung=self._sort_lanes or 0,
            step_lanes=self._step_width(),
            step_lanes_rung=self._step_lanes or 0,
            bucket_slack=self._bucket_slack,
            exchange_bucket_lanes=(
                0 if self._n == 1 else self._bucket_lanes()
            ),
        )
        snap = self._metrics.snapshot()
        # Fullest shard's table load (= unique_max/cap_s here: every
        # sharded table entry is one unique state); same key as the
        # single-chip and tiered engines so /.metrics readers see one
        # name everywhere (docs/OBSERVABILITY.md).
        out["table_load_factor"] = snap.get("table_occupancy", 0.0)
        out.update(snap)
        hists = self._metrics.snapshot_histograms()
        if hists:
            out["histograms"] = hists
        if self._accounting:
            out["accounting"] = dict(self._accounting)
        return out

    def _gid_path(self, gid: int) -> Path:
        # The lazy ~GB-scale host pull happens at most once (guarded: two
        # concurrent path reconstructions must not both pull), and a query
        # against a run that never finished cleanly fails with a clear
        # error instead of unpacking None.
        with self._lock:
            if self._tables_host is None:
                if self._tables_dev is None:
                    raise RuntimeError(
                        "no run state to reconstruct paths from (the "
                        "checker did not complete cleanly)"
                    )
                parent_dev, store_dev = self._tables_dev
                n, cap_s, w = (
                    self._n, self._cap_s, self._compiled.state_width,
                )
                self._tables_host = (
                    np.asarray(parent_dev).reshape(n, cap_s),
                    np.asarray(store_dev).reshape(n, cap_s, w),
                )
            parent, store = self._tables_host
        chain: List[int] = []
        g = gid
        while g != NO_GID:
            chain.append(g)
            g = int(parent[g >> self._slot_bits, g & (self._cap_s - 1)])
        chain.reverse()
        fps = [
            self._model.fingerprint(
                self._compiled.decode(
                    store[g >> self._slot_bits, g & (self._cap_s - 1)]
                )
            )
            for g in chain
        ]
        return Path.from_fingerprints(self._model, fps)

    def discoveries(self) -> Dict[str, Path]:
        self.join()
        if self._discoveries_cache is None:
            with self._lock:
                items = list(self._discovery_gids.items())
            self._discoveries_cache = {
                name: self._gid_path(g) for name, g in items
            }
        return dict(self._discoveries_cache)

    def try_discovery(self, name: str) -> Optional[Path]:
        # Non-blocking while the run is live; a failed run surfaces its
        # error through join(), not here.
        if not self._done.is_set() or self._errors:
            return None
        return self.discoveries().get(name)

    def handles(self) -> List[threading.Thread]:
        return [self._thread]

    def is_done(self) -> bool:
        return self._done.is_set()

    def join(self) -> "ShardedTpuChecker":
        self._thread.join()
        if self._errors:
            raise self._errors[0]
        return self
