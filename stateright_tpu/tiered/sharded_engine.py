"""Tiered × sharded: pod-scale exact checking under a per-shard memory
budget.

Composes the two scale levers the engines grew separately:

- the SHARDED axis (parallel/sharded.py): frontier + fingerprint space
  owner-partitioned over a mesh, candidates exchanged per wave with one
  bucketed ``all_to_all``;
- the TIERED axis (tiered/engine.py): the hot fingerprint table bounded
  by ``memory_budget_mb``, evicted partitions living as sorted cold runs
  merged-joined back in before commit.

The composition is owner-local by construction: every fingerprint has
one owner shard, so shard ``d``'s cold runs hold only fingerprints shard
``d`` owns — the pre-commit cold merge-join needs NO cross-shard lookup,
exactly like the hot insert.  Each shard gets its own :class:`ColdStore`
(under ``cold_dir/shard_<d>/`` when disk-backed), its own spill
watermark, and its own budget-pinned hot table of ``capacity_for_budget``
slots.

Unlike the base sharded engine, the log is the BFS-ordered row log
itself (the tiered engine's layout), not slot-indexed storage: global
ids are ``log_position * n_shards + shard``, which stay valid across
spills, hot-table rebuilds, AND log growth — and which an offline
re-keying pass (tiered/reshard.py) can translate to a different mesh
width, something the base engine's ``shard << slot_bits | slot`` ids
cannot do.

The host drives one wave per ``_wl_call`` through phase programs cut
from the in-HBM sharded engine's fused body (step / canon / prededup /
exchange / insert / append), with the cold filter between insert and
append — the same
shape as the single-chip tiered loop, under the shared
:class:`FusedWaveLoop`.  Snapshots embed the full per-shard tier state
(``ts_*`` keys); key planes are NOT persisted — a resume rebuilds them
from the committed log segment, so a kill can never leave a snapshot
with an aborted wave's keys.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from ..parallel.sharded import (
    _PROGRAM_CACHE,
    _PROGRAM_CACHE_MAX,
    NO_GID,
    ShardedTpuChecker,
    _owner_mix,
    _owner_mix_host_np,
)
from .cold_store import ColdStore
from .engine import capacity_for_budget


class TieredShardedTpuChecker(ShardedTpuChecker):
    """Sharded wavefront checker with budget-bounded per-shard hot
    tables and owner-local cold tiers."""

    def __init__(
        self,
        options,
        memory_budget_mb: Optional[float] = None,
        spill_threshold: float = 0.45,
        cold_max_runs: int = 8,
        cold_dir: Optional[str] = None,
        **kwargs,
    ):
        """``memory_budget_mb`` bounds EACH SHARD's hot fingerprint
        table (the tiered engine's budget semantics, applied per
        device): when given it derives the per-shard capacity,
        overriding any explicit ``capacity``.  ``spill_threshold`` /
        ``cold_max_runs`` / ``cold_dir`` keep the tiered engine's
        contracts; with ``cold_dir`` set, shard ``d`` spills under
        ``cold_dir/shard_<d>/`` — sibling stores never share a
        directory, so concurrent spills cannot clobber or cross-adopt
        runs (tests/test_tiered.py pins this).

        ``trace=True`` is refused: the phase-timed breakdown exists
        on the single-chip tiered engine only
        (``spawn_tpu_tiered(trace=True)``)."""
        if kwargs.pop("trace", False):
            raise ValueError(
                "spawn_tpu_tiered_sharded(trace=True) is not supported: "
                "run the phase-timed trace on spawn_tpu_tiered instead"
            )
        if not 0.0 < float(spill_threshold) <= 0.5:
            raise ValueError(
                "spill_threshold must be in (0, 0.5]: the insert flags "
                "the table overfull beyond 50% load"
            )
        import jax

        mesh = kwargs.get("mesh")
        n = mesh.devices.size if mesh is not None else len(jax.devices())
        # The budget derives the PER-SHARD capacity; the base
        # constructor floors cap_s at 1024, so the true (possibly
        # smaller) budgeted capacity is re-pinned at the top of _check
        # — safe, the run thread is the only _cap_s consumer.
        self._ts_cap_s: Optional[int] = None
        if memory_budget_mb is not None:
            self._ts_cap_s = capacity_for_budget(memory_budget_mb)
            kwargs["capacity"] = self._ts_cap_s * n
        self._memory_budget_mb = (
            None if memory_budget_mb is None else float(memory_budget_mb)
        )
        self._spill_threshold = float(spill_threshold)
        self._cold_max_runs = int(cold_max_runs)
        self._cold_dir = cold_dir
        self._colds = [
            ColdStore(
                spill_dir=(
                    None if cold_dir is None
                    else os.path.join(cold_dir, f"shard_{d}")
                ),
                max_runs=self._cold_max_runs,
            )
            for d in range(n)
        ]
        # Per-shard host bookkeeping (the tiered engine's scalars, one
        # lane per shard).  Log positions, not table slots.
        self._ts_level_start = np.zeros(n, np.int64)
        self._ts_level_end = np.zeros(n, np.int64)
        self._ts_tails = np.zeros(n, np.int64)
        self._ts_spill_tails = np.zeros(n, np.int64)
        self._ts_hot = np.zeros(n, np.int64)
        self._ts_cand = np.zeros(n, np.int64)
        self._ts_spill_counts = np.zeros(n, np.int64)
        self._ts_flag1_shards = np.zeros(n, bool)
        self._ts_planes_dirty = False
        self._ts_log_cap = 0  # per-shard row-log capacity (grows, flag 2)
        self._ts_pad = 0  # fixed slice padding, minted at run start
        self._t_depth = 0
        self._t_unique = 0
        self._t_states = 0
        self._t_flags = 0
        self._t_disc = None  # device uint32[n, P] discovery gids
        self._t_disc_h = None
        self._ts_cold_last = None  # last wave's cold-probe accounting
        # The base constructor starts the run thread as its LAST
        # statement; every tiered attribute must exist before it.
        super().__init__(options, **kwargs)

    # --- device programs ------------------------------------------------------

    def _ts_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self._mesh, P("shards"))

    def _ts_up(self, x):
        """Sharded upload into DEVICE-OWNED buffers (the programs donate
        their log/plane arguments; see wavefront._device_owned)."""
        import jax
        import jax.numpy as jnp

        from ..parallel.wavefront import _device_owned

        return _device_owned(
            jax.device_put(jnp.asarray(x), self._ts_sharding())
        )

    def _ts_programs(self):
        """The engine-specific phase programs (step over the row log,
        fresh-masked append, spill segment fingerprinting, plane rebuild
        and clear), cached like every other program set.  canon /
        prededup / exchange / insert live in :meth:`_phase_programs`,
        keyed without the log geometry, so a log growth does not
        rebuild them."""
        key = (
            "tiered-sharded",
            self._compiled.cache_key(),
            hasattr(self._compiled, "step_valid")
            and hasattr(self._compiled, "step_lane"),
            self._canon is not None,
            self._cap_s,
            self._chunk,
            self._dedup_factor,
            self._sortless,
            self._sort_width(),
            self._step_width(),
            self._bucket_slack,
            self._ts_log_cap,
            self._ts_pad,
            tuple((d.platform, d.id) for d in self._mesh.devices.flat),
            tuple(p.expectation for p in self._properties),
        )
        from ..parallel.wave_common import cached_program

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, self._ts_build,
            label="TieredShardedTpuChecker.programs",
            journal=self._journal,
            provenance=self._key_provenance(),
        )

    def _ts_build(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..ops.device_fp import device_fp64
        from ..parallel.hashset import (
            HashSet, compact_valid_indices, insert_batch_claim,
        )
        from ..parallel.wave_common import wave_eval

        cm = self._compiled
        w = cm.state_width
        fpw = cm.fp_words or w
        canon = self._canon
        a = cm.max_actions
        f_eff = self._step_width()
        n = self._n
        props = self._properties
        ev_indices = self._ev_indices
        dedup_factor = self._dedup_factor
        sort_lanes = (
            None if self._sort_lanes is None else self._sort_width()
        )
        b = f_eff * a
        seg = self._ts_pad  # fixed window width for segfp/rehash
        u = jnp.uint32
        shard = P("shards")

        def sharded(fn, n_in, donate=()):
            return jax.jit(
                jax.shard_map(
                    fn, mesh=self._mesh,
                    in_specs=(shard,) * n_in, out_specs=shard,
                ),
                donate_argnums=donate,
            )

        def fp_of(rows):
            rows_c = rows if canon is None else jax.vmap(canon)(rows)
            return device_fp64(rows_c[:, :fpw])

        def step_shard(rows2d, ebits1d, disc, ctrl):
            # The base step over the ROW LOG instead of a slot queue:
            # the frontier is the log slice [level_start, level_end),
            # consumed f_eff lanes at a time; gids encode the log
            # position (pos * n + shard), stable across spills and log
            # growth.  The pad past log_cap keeps the dynamic_slice
            # from ever clamping (level_start <= log_cap, f_eff <= pad).
            me = jax.lax.axis_index("shards").astype(u)
            level_start = ctrl[0, 0]
            level_end = ctrl[0, 1]
            count = jnp.minimum(level_end - level_start, u(f_eff))
            states = jax.lax.dynamic_slice(
                rows2d, (level_start, u(0)), (f_eff, w)
            )
            eb_in = jax.lax.dynamic_slice(
                ebits1d, (level_start,), (f_eff,)
            )
            lane = jnp.arange(f_eff, dtype=u)
            active = lane < count
            my_gids = (level_start + lane) * u(n) + me
            disc_v, eb, nexts, valid, gen_local, step_flag = wave_eval(
                cm, props, ev_indices, states, active, my_gids, eb_in,
                disc[0], allow_two_phase=True,
            )
            flat_valid = valid.reshape(b)
            v_orig, v_act, _n_valid, local_overflow = (
                compact_valid_indices(
                    flat_valid, dedup_factor, sort_lanes=sort_lanes
                )
            )
            if nexts is None:
                rows_v, _vv, lane_flags_v = jax.vmap(cm.step_lane)(
                    states[v_orig // u(a)], v_orig % u(a)
                )
                step_flag = step_flag | jnp.any(lane_flags_v & v_act)
            else:
                rows_v = nexts.reshape(b, w)[v_orig]
            gid_v = my_gids[v_orig // u(a)]
            eb_v = eb[v_orig // u(a)]
            return (
                disc_v[None], rows_v, gid_v, eb_v, v_act,
                local_overflow[None], gen_local.astype(u)[None],
                step_flag[None],
            )

        def append_shard(rows2d, parent1d, ebits1d, rw, rg, reb,
                         r_origin, fresh, ctrl):
            # The base append with the FRESH mask in place of r_new:
            # lanes the cold filter disqualified (already in a cold
            # run) are dropped — their hot-table entry stays as the
            # negative cache, exactly the single-chip tiered rule.
            tail = ctrl[0, 0]
            fr = fresh[0]
            pos = tail + jnp.cumsum(fr) - u(1)
            idx = jnp.where(fr != u(0), pos, u(0xFFFFFFFF))
            rows2d = rows2d.at[idx].set(rw[r_origin], mode="drop")
            parent1d = parent1d.at[idx].set(rg[r_origin], mode="drop")
            ebits1d = ebits1d.at[idx].set(reb[r_origin], mode="drop")
            return rows2d, parent1d, ebits1d

        def segfp_shard(rows2d, ctrl):
            # One seg-wide spill window: canonical fingerprints of the
            # log slice starting at ctrl[0,0] (the caller masks the
            # valid count host-side; lanes past it are padding).
            off = ctrl[0, 0]
            states = jax.lax.dynamic_slice(rows2d, (off, u(0)), (seg, w))
            return fp_of(states)

        def rehash_shard(kh, kl, rows2d, ctrl):
            # One seg-wide plane-rebuild window: re-insert the log
            # slice [off, off+cnt) into the hot planes.  Log entries
            # are distinct by construction, so the claim insert is
            # duplicate-free and probe_ok is the only failure mode.
            off = ctrl[0, 0]
            cnt = ctrl[0, 1]
            states = jax.lax.dynamic_slice(rows2d, (off, u(0)), (seg, w))
            hi, lo = fp_of(states)
            act = jnp.arange(seg, dtype=u) < cnt
            table, _slot, _new, _orig, _ra, probe_ok, _dd = (
                insert_batch_claim(HashSet(kh, kl), hi, lo, act)
            )
            return table.key_hi, table.key_lo, probe_ok[None]

        def clear_shard(kh, kl, mask):
            # Zero the planes of spilling shards only (mask is per-shard
            # 0/1); non-spilling shards keep their live entries.
            keep = mask[0, 0] == u(0)
            return jnp.where(keep, kh, u(0)), jnp.where(keep, kl, u(0))

        return {
            "step": sharded(step_shard, 4),
            "append": sharded(append_shard, 9, donate=(0, 1, 2)),
            "segfp": sharded(segfp_shard, 2),
            "rehash": sharded(rehash_shard, 4, donate=(0, 1)),
            "clear": sharded(clear_shard, 3, donate=(0, 1)),
        }

    def _phase_programs(self):
        """The canon / prededup / exchange / insert phase programs —
        the in-HBM sharded engine's fused-body kernels, dispatched apart
        so the cold filter can run between insert and append — cached
        like every other program set."""
        key = (
            "phases",
            self._compiled.cache_key(),
            hasattr(self._compiled, "step_valid")
            and hasattr(self._compiled, "step_lane"),
            self._canon is not None,
            self._cap_s,
            self._chunk,
            self._dedup_factor,
            self._sortless,  # the dedup path is a trace-time branch
            self._sort_width(),  # the live sort-geometry rung
            self._step_width(),  # the live step-geometry rung
            self._bucket_slack,  # shapes the exchange buckets
            tuple((d.platform, d.id) for d in self._mesh.devices.flat),
            tuple(p.expectation for p in self._properties),
        )
        from ..parallel.wave_common import cached_program

        return cached_program(
            _PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key,
            self._build_phase_programs,
            label="TieredShardedTpuChecker.phases",
            journal=self._journal,
            provenance=self._key_provenance(),
        )

    def _build_phase_programs(self):
        """One shard_map program per phase — canon+fp / local
        dedup-sort / exchange / table insert — with the same kernels as
        the in-HBM engine's fused ``body``.  All cross-shard reductions
        are host sums over per-shard outputs, so the only collective is
        the all_to_all itself."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..ops.device_fp import device_fp64
        from ..parallel.hashset import (
            HashSet, insert_batch_claim, insert_batch_compact, prededup,
        )

        cm = self._compiled
        w = cm.state_width
        fpw = cm.fp_words or w
        canon = self._canon

        def fp_of(rows):
            rows_c = rows if canon is None else jax.vmap(canon)(rows)
            return device_fp64(rows_c[:, :fpw])

        n = self._n
        sortless = self._sortless  # the dedup path (claim vs sort)
        bkt = self._bucket_lanes()  # per-destination exchange bucket
        u = jnp.uint32
        shard = P("shards")

        def sharded(fn, n_in, donate=()):
            return jax.jit(
                jax.shard_map(
                    fn, mesh=self._mesh,
                    in_specs=(shard,) * n_in, out_specs=shard,
                ),
                donate_argnums=donate,
            )

        def canon_shard(rows_v):
            hi, lo = fp_of(rows_v)
            return hi, lo

        def prededup_shard(hi, lo, rows_v, gid_v, eb_v, v_act):
            if sortless and n == 1:
                # 1-shard sortless: the claim insert IS the global
                # dedup — this phase is the identity, exactly the fused
                # body's elision.
                n_cand = jnp.sum(v_act, dtype=u)
                return hi, lo, rows_v, gid_v, eb_v, v_act, n_cand[None]
            # dd=1 over the already-compacted buffer, exactly the fused
            # body's local pre-dedup: representatives in sorted key
            # order, one lane per distinct local key.
            u_hi, u_lo, u_origin0, u_valid, _never = prededup(
                hi, lo, v_act, dedup_factor=1
            )
            rows_u = rows_v[u_origin0]
            gid_u = gid_v[u_origin0]
            eb_u = eb_v[u_origin0]
            n_cand = jnp.sum(u_valid, dtype=u)
            return u_hi, u_lo, rows_u, gid_u, eb_u, u_valid, n_cand[None]

        def exchange_shard(u_hi, u_lo, rows_u, gid_u, eb_u, u_valid):
            # Bucket by owner + the single packed all_to_all (the fused
            # body's BUCKETED exchange block), plus the receiver-side
            # re-fingerprint of the arrived rows.  The per-shard
            # bucket-overflow flag rides back so the host can abort the
            # wave before the append commits anything and retry it at
            # the next slack rung (the fused loop's contract).
            u_sz = u_hi.shape[0]
            owner = _owner_mix(u_hi, u_lo) % u(n)
            key = jnp.where(u_valid, owner, u(n))
            order = jnp.argsort(key, stable=True)
            key_s = key[order]
            counts = jnp.stack(
                [jnp.sum((key == u(d)).astype(u)) for d in range(n + 1)]
            )
            bucket_ovf = jnp.any(counts[:n] > u(bkt))
            offsets = jnp.concatenate(
                [jnp.zeros((1,), u), jnp.cumsum(counts)[:-1]]
            )
            pos = jnp.arange(u_sz, dtype=u) - offsets[key_s]
            dst = jnp.where(key_s < n, key_s, u(n))
            payload = jnp.concatenate(
                [
                    rows_u,
                    gid_u[:, None],
                    eb_u[:, None],
                    u_valid.astype(u)[:, None],
                ],
                axis=1,
            )
            send = jnp.zeros((n, bkt, w + 3), u)
            send = send.at[dst, pos].set(payload[order], mode="drop")
            recv = jax.lax.all_to_all(
                send, "shards", split_axis=0, concat_axis=0, tiled=False
            )
            flatrecv = recv.reshape(n * bkt, w + 3)
            rw = flatrecv[:, :w]
            rhi, rlo = fp_of(rw)
            return (
                rw, flatrecv[:, w], flatrecv[:, w + 1],
                flatrecv[:, w + 2], rhi, rlo, bucket_ovf[None],
            )

        def insert_shard(key_hi, key_lo, rhi, rlo, rv):
            if sortless:
                (
                    table, r_slot, r_new, r_origin, _ra, probe_ok,
                    dd_overflow, rounds, _slices,
                ) = insert_batch_claim(
                    HashSet(key_hi, key_lo), rhi, rlo,
                    rv.astype(jnp.bool_), with_rounds=True,
                )
            else:
                (
                    table, r_slot, r_new, r_origin, _ra, probe_ok,
                    dd_overflow, rounds, _slices,
                ) = insert_batch_compact(
                    HashSet(key_hi, key_lo), rhi, rlo,
                    rv.astype(jnp.bool_), dedup_factor=1,
                    with_rounds=True,
                )
            return (
                table.key_hi, table.key_lo, r_slot, r_new, r_origin,
                probe_ok[None], dd_overflow[None], jnp.sum(rounds)[None],
            )

        return {
            "canon": sharded(canon_shard, 1),
            "prededup": sharded(prededup_shard, 6),
            "exchange": sharded(exchange_shard, 6),
            "insert": sharded(insert_shard, 5, donate=(0, 1)),
        }

    # --- the tiered-sharded wave (one _wl_call) -------------------------------

    def _wl_call(self, carry):
        """One wave: step → canon → prededup → exchange → insert, one
        combined flag readback, the owner-local cold filter, then the
        fresh-masked append.  Host bookkeeping commits only at
        flags == 0; an aborted wave leaves every counter and the log at
        its pre-wave state (the hot planes, which the insert already
        consumed, are marked dirty and rebuilt by recovery)."""
        key_hi, key_lo, rows, parent, ebits = carry
        n = self._n
        backlog = self._ts_level_end - self._ts_level_start
        td = self._options._target_max_depth or 0
        if int(backlog.sum()) <= 0 or (td and self._t_depth >= td - 1):
            # Drained level (a completed snapshot being resumed) or the
            # next wave would expand past the target depth: clean no-op;
            # the shared termination tail stops the loop.
            self._t_flags = 0
            self._ts_cold_last = None
            return carry
        f_eff = self._step_width()
        if f_eff < self._chunk and int(backlog.max()) > f_eff:
            # Step-rung clamp (flag 128), decided BEFORE dispatch — the
            # host knows the backlog, so unlike the fused loop no device
            # work is wasted discovering it.
            self._t_flags = 128
            self._ts_cold_last = None
            return carry
        progs = self._ts_programs()
        base = self._phase_programs()
        counts = np.minimum(backlog, f_eff)
        ctrl_np = np.zeros((n, 2), np.uint32)
        ctrl_np[:, 0] = self._ts_level_start
        ctrl_np[:, 1] = self._ts_level_end
        disc_prev = self._t_disc  # step does not donate it
        (
            disc, rows_v, gid_v, eb_v, v_act,
            local_ovf_d, gen_d, stepflag_d,
        ) = progs["step"](rows, ebits, disc_prev, self._ts_up(ctrl_np))
        hi, lo = base["canon"](rows_v)
        u_hi, u_lo, rows_u, gid_u, eb_u, u_valid, n_cand_d = (
            base["prededup"](hi, lo, rows_v, gid_v, eb_v, v_act)
        )
        if n > 1:
            rw, rg, reb, rv, rhi, rlo, bucket_ovf_d = base["exchange"](
                u_hi, u_lo, rows_u, gid_u, eb_u, u_valid
            )
        else:
            rw, rg, reb, rv = rows_u, gid_u, eb_u, u_valid
            rhi, rlo = u_hi, u_lo
            bucket_ovf_d = None
        key_hi, key_lo, _r_slot, r_new, r_origin, probe_ok_d, dd_ovf_d, \
            _rounds_d = base["insert"](key_hi, key_lo, rhi, rlo, rv)

        # ONE combined flag readback (the insert already ran — flags
        # 4/32 therefore cost a plane rebuild on recovery, accepted:
        # rung climbs are rare next to waves, and the good path saves a
        # pre-insert host sync every wave).
        flags = 0
        if np.asarray(local_ovf_d).any():
            flags |= 4
        if bucket_ovf_d is not None and np.asarray(bucket_ovf_d).any():
            flags |= 32
        if np.asarray(stepflag_d).any():
            flags |= 8
        if np.asarray(dd_ovf_d).any():
            flags |= 64
        r_new_h = np.asarray(r_new).reshape(n, -1).astype(bool)
        n_new_h = r_new_h.sum(axis=1).astype(np.int64)
        probe_ok_h = np.asarray(probe_ok_d).reshape(n).astype(bool)
        over = (~probe_ok_h) | (
            (self._ts_hot + n_new_h) * 2 > self._cap_s
        )
        if over.any():
            flags |= 1
            self._ts_flag1_shards = over.copy()

        # Owner-local cold filter: each shard's new keys are checked
        # against ITS OWN cold runs only (ownership routing guarantees
        # a fingerprint can never be cold on another shard).
        cold = None
        fresh_h = r_new_h.copy()
        if flags == 0 and n_new_h.sum():
            queried = hits = shards_touched = 0
            rhi_h = rlo_h = None
            for d in range(n):
                if not n_new_h[d] or not self._colds[d].run_count:
                    continue
                if rhi_h is None:
                    rhi_h = np.asarray(rhi).reshape(n, -1)
                    rlo_h = np.asarray(rlo).reshape(n, -1)
                lanes = np.flatnonzero(r_new_h[d])
                fps = (
                    rhi_h[d, lanes].astype(np.uint64) << np.uint64(32)
                ) | rlo_h[d, lanes].astype(np.uint64)
                hit = self._colds[d].contains(fps)
                if hit.any():
                    fresh_h[d, lanes[hit]] = False
                queried += int(lanes.size)
                hits += int(hit.sum())
                shards_touched += 1
            if shards_touched:
                cold = {
                    "queried": queried,
                    "hits": hits,
                    "shards": shards_touched,
                }
        n_fresh_h = fresh_h.sum(axis=1).astype(np.int64)
        if flags == 0 and bool(
            ((self._ts_tails + n_fresh_h) > self._ts_log_cap).any()
        ):
            flags |= 2

        if flags:
            # The old planes were donated to the insert; the new ones
            # hold the aborted wave's keys — recovery rebuilds them
            # from the committed log segment.  Discoveries revert (the
            # single-chip tiered rule: a kept discovery would change
            # the re-run's awaiting mask and break the bit pin).
            self._ts_planes_dirty = True
            self._t_disc = disc_prev
            self._t_flags = flags
            self._ts_cold_last = None
            return (key_hi, key_lo, rows, parent, ebits)

        tail_ctrl = np.zeros((n, 2), np.uint32)
        tail_ctrl[:, 0] = self._ts_tails
        rows, parent, ebits = progs["append"](
            rows, parent, ebits, rw, rg, reb, r_origin,
            self._ts_up(fresh_h.astype(np.uint32)),
            self._ts_up(tail_ctrl),
        )
        self._ts_hot += n_new_h  # cold hits stay as the negative cache
        self._ts_tails += n_fresh_h
        self._t_unique += int(n_fresh_h.sum())
        self._t_states += int(np.asarray(gen_d).astype(np.int64).sum())
        self._ts_cand += np.asarray(n_cand_d).reshape(n).astype(np.int64)
        self._ts_level_start = self._ts_level_start + counts
        if bool((self._ts_level_start >= self._ts_level_end).all()):
            self._t_depth += 1
            self._ts_level_end = self._ts_tails.copy()
        self._t_disc = disc
        self._t_disc_h = np.asarray(disc)
        if cold is not None:
            if self._journal:
                self._journal.append(
                    "cold_probe",
                    depth=self._t_depth,
                    unique=self._t_unique,
                    **cold,
                )
            self._metrics.inc("cold_probe_queries_total", cold["queried"])
            self._metrics.inc("cold_hits_total", cold["hits"])
        self._t_flags = 0
        self._ts_cold_last = cold
        return (key_hi, key_lo, rows, parent, ebits)

    def _wl_view(self, carry):
        from ..parallel.wave_loop import WaveView

        n = self._n
        props = self._properties
        backlog = self._ts_level_end - self._ts_level_start
        self._update_shard_metrics(backlog, self._ts_tails, self._ts_cand)
        disc = []
        if self._t_disc_h is not None:
            for d in range(n):
                for p, prop in enumerate(props):
                    g = int(self._t_disc_h[d, p])
                    if g != NO_GID:
                        disc.append((prop.name, g))
        extra = {
            "tail": int(self._ts_tails.sum()),
            "hot_entries": int(self._ts_hot.max()),
            "cold_runs": int(sum(c.run_count for c in self._colds)),
        }
        if self._ts_cold_last is not None:
            extra["cold_queried"] = self._ts_cold_last["queried"]
            extra["cold_hits"] = self._ts_cold_last["hits"]
        return WaveView(
            waves_this_call=1,
            remaining=int(backlog.sum()),
            depth=self._t_depth,
            flags=self._t_flags,
            unique=self._t_unique,
            states=self._t_states,
            # Binding constraint: the FULLEST shard's budgeted table.
            occupancy=float(self._ts_hot.max()) / self._cap_s,
            discoveries=tuple(disc),
            extra=extra,
        )

    def _update_shard_metrics(self, frontier, unique_l, cand) -> None:
        super()._update_shard_metrics(frontier, unique_l, cand)
        n = self._n
        cold_entries = np.array(
            [c.entries for c in self._colds], np.int64
        )
        self._metrics.update(
            shard_hot_entries={
                str(d): int(self._ts_hot[d]) for d in range(n)
            },
            shard_cold_entries={
                str(d): int(cold_entries[d]) for d in range(n)
            },
            shard_spills={
                str(d): int(self._ts_spill_counts[d]) for d in range(n)
            },
            cold_skew_max_over_mean=self._skew(cold_entries),
        )

    # --- spill / recovery -----------------------------------------------------

    def _wl_after_commit(self, carry, view):
        """Per-shard eviction on the shared loop's post-commit rung:
        every shard past the threshold spills in one lockstep pass.
        The measured global load factor confirms the host bookkeeping
        (one scalar sync per spill, not per wave)."""
        over = (
            self._ts_hot.astype(np.float64) / self._cap_s
            >= self._spill_threshold
        )
        if not over.any():
            return carry
        from ..parallel.hashset import HashSet

        lf = float(HashSet(carry[0], carry[1]).load_factor())
        self._metrics.update(hot_load_factor=round(lf, 6))
        return self._ts_spill(
            carry, np.flatnonzero(over), reason="threshold",
            clear_planes=True,
        )

    def _ts_spill(self, carry, shards, reason: str, clear_planes: bool):
        """Evict the chosen shards' hot tiers: fingerprints of each
        shard's log segment [spill_tail, tail) become one sorted cold
        run in that shard's own store (computed FROM THE LOG, so keys
        an aborted insert scribbled can never leak cold), watermarks
        advance, and — with ``clear_planes`` (the committed-boundary
        path) — the spilled shards' planes are zeroed on device.  The
        overflow-recovery path passes ``clear_planes=False``: its
        planes are dirty anyway and the full rebuild that follows
        supersedes a clear."""
        key_hi, key_lo, rows, parent, ebits = carry
        n = self._n
        shards = np.asarray(shards, np.int64)
        t0 = time.monotonic()
        progs = self._ts_programs()
        seg = self._ts_pad
        starts = self._ts_spill_tails.copy()
        ends = self._ts_tails.copy()
        spilling = np.zeros(n, bool)
        spilling[shards] = True
        spans = np.where(spilling, ends - starts, 0)
        per_shard = [[] for _ in range(n)]
        off = 0
        max_span = int(spans.max())
        while off < max_span:
            # Lockstep windows: every dispatch slices all shards (idle
            # ones read a zero-count window); the host keeps only the
            # valid prefix of each spilling shard.
            cnts = np.clip(spans - off, 0, seg)
            ctrl_np = np.zeros((n, 2), np.uint32)
            ctrl_np[:, 0] = np.where(spilling, starts + off, 0)
            ctrl_np[:, 1] = cnts
            hi, lo = progs["segfp"](rows, self._ts_up(ctrl_np))
            hi_h = np.asarray(hi).reshape(n, seg)
            lo_h = np.asarray(lo).reshape(n, seg)
            for d in shards:
                c = int(cnts[d])
                if c:
                    per_shard[d].append(
                        (
                            hi_h[d, :c].astype(np.uint64)
                            << np.uint64(32)
                        ) | lo_h[d, :c].astype(np.uint64)
                    )
            off += seg
        spill_sec = round(time.monotonic() - t0, 4)
        for d in shards:
            fps = (
                np.concatenate(per_shard[d])
                if per_shard[d] else np.zeros((0,), np.uint64)
            )
            self._colds[d].add_run(fps)
            self._ts_spill_counts[d] += 1
            if self._journal:
                self._journal.append(
                    "spill",
                    shard=int(d),
                    reason=reason,
                    entries=int(fps.shape[0]),
                    bytes=int(fps.nbytes),
                    start=int(starts[d]),
                    end=int(ends[d]),
                    load_factor=round(
                        float(self._ts_hot[d]) / self._cap_s, 6
                    ),
                    cold_runs=self._colds[d].run_count,
                    cold_entries=self._colds[d].entries,
                    spill_sec=spill_sec,
                )
            self._metrics.inc("spills", 1)
            self._metrics.inc("spill_bytes_total", int(fps.nbytes))
            self._ts_spill_tails[d] = ends[d]
            self._ts_hot[d] = 0
        self._metrics.update(
            cold_runs=int(sum(c.run_count for c in self._colds)),
            cold_entries=int(sum(c.entries for c in self._colds)),
            cold_bytes=int(sum(c.nbytes for c in self._colds)),
        )
        if clear_planes:
            mask_np = np.zeros((n, 1), np.uint32)
            mask_np[shards, 0] = 1
            key_hi, key_lo = progs["clear"](
                key_hi, key_lo, self._ts_up(mask_np)
            )
        return (key_hi, key_lo, rows, parent, ebits)

    def _ts_rebuild_planes(self, rows):
        """Fresh hot planes from the committed log: re-insert every
        shard's [spill_tail, tail) segment in lockstep seg-wide
        windows.  Used at seed, at resume (planes are never persisted),
        and by overflow recovery (erasing an aborted insert's keys)."""
        n = self._n
        progs = self._ts_programs()
        seg = self._ts_pad
        zeros = np.zeros(n * self._cap_s, np.uint32)
        key_hi = self._ts_up(zeros)
        key_lo = self._ts_up(zeros)
        starts = self._ts_spill_tails
        spans = self._ts_tails - starts
        off = 0
        max_span = int(spans.max()) if n else 0
        while off < max_span:
            ctrl_np = np.zeros((n, 2), np.uint32)
            ctrl_np[:, 0] = np.minimum(starts + off, self._ts_tails)
            ctrl_np[:, 1] = np.clip(spans - off, 0, seg)
            key_hi, key_lo, ok = progs["rehash"](
                key_hi, key_lo, rows, self._ts_up(ctrl_np)
            )
            if not np.asarray(ok).all():
                raise RuntimeError(
                    "hot-table rebuild failed a probe bound below the "
                    "50% spill gate — impossible by construction; "
                    "please report"
                )
            off += seg
        return key_hi, key_lo

    def _wl_grow(self, flags: int, carry):
        """In-place recovery for an aborted wave.  Flags 4/32/128 use
        the base knob ladders (_grow_knobs); flag 1 SPILLS the
        overfull shards (the budget pins their capacity) or — if a
        shard's table is already empty — shrinks the chunk until one
        wave's distinct keys fit; flag 2 doubles the row log (gids
        encode log positions, so growth never re-keys anything).  Any
        dirty planes are rebuilt from the committed log at the end."""
        from ..parallel.wave_loop import log_grow

        base_bits = flags & (4 | 32 | 128)
        if base_bits and self._grow_knobs(base_bits) is None:
            return None
        key_hi, key_lo, rows, parent, ebits = carry
        notes = []
        if flags & 1:
            over = self._ts_flag1_shards
            spill_shards = np.flatnonzero(over & (self._ts_hot > 0))
            stuck = over & (self._ts_hot == 0)
            if spill_shards.size:
                carry = self._ts_spill(
                    carry, spill_shards, reason="overflow",
                    clear_planes=False,
                )
                key_hi, key_lo, rows, parent, ebits = carry
                notes.append(
                    f"spill shards={spill_shards.tolist()} (budget "
                    f"pins per-shard capacity={self._cap_s})"
                )
            if stuck.any():
                if self._chunk <= 8:
                    return None
                self._chunk = max(8, self._chunk // 2)
                notes.append(f"chunk_size={self._chunk}")
        if flags & 2:
            new_cap = self._ts_log_cap * 2
            if (new_cap + self._ts_pad) * self._n >= 0xFFFFFFFF:
                return None
            rows, parent, ebits = self._ts_grow_log(
                rows, parent, ebits, new_cap
            )
            self._ts_log_cap = new_cap
            notes.append(f"log_capacity={new_cap}")
        if notes:
            log_grow(
                self, flags & 3, "; ".join(notes),
                self._t_unique, self._t_depth,
            )
        if self._ts_planes_dirty:
            key_hi, key_lo = self._ts_rebuild_planes(rows)
            # The rebuilt tables hold exactly the committed segments —
            # cold-duplicate cache entries are gone (they live in
            # earlier runs), so the bookkeeping must match.
            self._ts_hot = (
                self._ts_tails - self._ts_spill_tails
            ).astype(np.int64)
            self._ts_planes_dirty = False
        return (key_hi, key_lo, rows, parent, ebits)

    def _ts_grow_log(self, rows, parent, ebits, new_cap: int):
        """Double the per-shard row log (host round trip; growth is
        rare and the log is the one buffer that must survive).  gids
        encode positions, not slots, so nothing is re-keyed."""
        n, w = self._n, self._compiled.state_width
        old_lp = self._ts_log_cap + self._ts_pad
        new_lp = new_cap + self._ts_pad
        rows_n = np.zeros((n, new_lp, w), np.uint32)
        rows_n[:, :old_lp] = np.asarray(rows).reshape(n, old_lp, w)
        parent_n = np.full((n, new_lp), NO_GID, np.uint32)
        parent_n[:, :old_lp] = np.asarray(parent).reshape(n, old_lp)
        ebits_n = np.zeros((n, new_lp), np.uint32)
        ebits_n[:, :old_lp] = np.asarray(ebits).reshape(n, old_lp)
        return (
            self._ts_up(rows_n.reshape(n * new_lp, w)),
            self._ts_up(parent_n.reshape(n * new_lp)),
            self._ts_up(ebits_n.reshape(n * new_lp)),
        )

    def _wl_retryable_flags(self) -> int:
        # Unlike the base sharded engine, table (1) and log (2)
        # overflows ARE recoverable here: the budget spills instead of
        # growing, and log growth never re-keys (positional gids).
        return 1 | 2 | 4 | 32 | 128

    def _wl_overflow_message(self, flags: int) -> str:
        if flags & (8 | 64):
            return super()._wl_overflow_message(flags)
        if flags & 1:
            return (
                "a single wave inserted more distinct new keys than a "
                f"shard's budgeted hot table holds (per-shard capacity "
                f"{self._cap_s}) even at the floor chunk; raise "
                "memory_budget_mb"
            )
        return f"tiered-sharded engine overflow flags={flags}"

    # --- run setup / teardown (the host side of _check) -----------------------

    def _check(self) -> None:
        opts = self._options
        deadline = (
            time.monotonic() + opts._timeout
            if opts._timeout is not None else None
        )
        if self._ts_cap_s is not None:
            # Re-pin the budgeted per-shard capacity under the base
            # constructor's 1024-slot floor (see __init__); this thread
            # is the only consumer during the run.
            self._cap_s = self._ts_cap_s
            self._slot_bits = max(1, self._cap_s.bit_length() - 1)
        if self._resume_from is not None:
            carry = self._ts_resume()
        else:
            self._ts_log_cap = self._cap_s
            self._ts_pad = self._chunk
            if (
                (self._ts_log_cap + self._ts_pad) * self._n
                >= 0xFFFFFFFF
            ):
                raise ValueError(
                    "capacity too large for 32-bit global ids"
                )
            carry = self._ts_seed()
        from ..parallel.wave_loop import FusedWaveLoop, finalize_run

        carry, waves_total = FusedWaveLoop(self).run(carry, deadline)
        self._accounting = self._build_accounting(
            waves_total, self._ts_cand.copy(), self._ts_tails.copy()
        )
        self._tables_dev = (carry[3], carry[2])  # parent, rows
        finalize_run(self, self._ts_carry_dict(carry))

    def _ts_seed(self):
        """Host-side seeding: canonical fingerprints + owner routing on
        the host (bit-identical by the pinned host/device fp and mix
        parity), per-shard in-order dedup, one upload, then a device
        plane rebuild over the seeded prefix."""
        cm = self._compiled
        n = self._n
        w = cm.state_width
        from ..ops.fingerprint import fp64_words

        init = cm.init_packed()
        n_init = init.shape[0]
        fpw = cm.fp_words or w
        if self._canon is not None:
            from ..parallel.canon import canon_batch_host

            fp_rows = canon_batch_host(cm, init)
        else:
            fp_rows = init
        fps = np.array(
            [fp64_words(row[:fpw].tolist()) for row in fp_rows],
            np.uint64,
        )
        owner = (
            _owner_mix_host_np(
                (fps >> np.uint64(32)).astype(np.uint32),
                (fps & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            ).astype(np.int64) % n
        )
        lp = self._ts_log_cap + self._ts_pad
        rows_np = np.zeros((n, lp, w), np.uint32)
        parent_np = np.full((n, lp), NO_GID, np.uint32)
        ebits_np = np.zeros((n, lp), np.uint32)
        eb0 = (1 << len(self._ev_indices)) - 1
        tails = np.zeros(n, np.int64)
        for d in range(n):
            seen = set()
            kept = []
            for i in np.flatnonzero(owner == d):
                f = int(fps[i])
                if f not in seen:
                    seen.add(f)
                    kept.append(int(i))
            c = len(kept)
            if c * 2 > self._cap_s:
                raise RuntimeError(
                    "init-state seeding overflowed the budgeted "
                    f"per-shard fingerprint table (shard {d}: {c} "
                    f"distinct seeds vs capacity {self._cap_s}); raise "
                    "memory_budget_mb (or pass capacity=) past the "
                    "init-state count"
                )
            if c:
                rows_np[d, :c] = init[kept]
                ebits_np[d, :c] = eb0
            tails[d] = c
        rows = self._ts_up(rows_np.reshape(n * lp, w))
        parent = self._ts_up(parent_np.reshape(n * lp))
        ebits = self._ts_up(ebits_np.reshape(n * lp))
        self._ts_tails = tails
        self._ts_spill_tails = np.zeros(n, np.int64)
        self._ts_level_start = np.zeros(n, np.int64)
        self._ts_level_end = tails.copy()
        self._ts_hot = tails.copy()
        self._t_depth = 0
        self._t_unique = int(tails.sum())
        self._t_states = n_init
        n_props = len(self._properties)
        self._t_disc = self._ts_up(
            np.full((n, n_props), NO_GID, np.uint32)
        )
        self._t_disc_h = np.asarray(self._t_disc)
        key_hi, key_lo = self._ts_rebuild_planes(rows)
        with self._lock:
            self._state_count = n_init
            self._unique_count = self._t_unique
        return (key_hi, key_lo, rows, parent, ebits)

    def _ts_resume(self):
        n = self._n
        snap = np.load(self._resume_from, allow_pickle=False)
        if "ts_tails" not in snap.files:
            raise ValueError(
                "snapshot was not written by the tiered-sharded engine "
                "(no per-shard tier state); resume it with the engine "
                "that wrote it, or convert a sharded snapshot with the "
                "`reshard` verb (stateright_tpu.tiered.reshard)"
            )
        if "n_shards" in snap.files and int(snap["n_shards"]) != n:
            raise ValueError(
                f"tiered-sharded snapshot was written on a "
                f"{int(snap['n_shards'])}-shard mesh and cannot resume "
                f"on {n} shards directly: global state ids encode the "
                "owner shard; run the `reshard` verb "
                "(stateright_tpu.tiered.reshard.reshard_snapshot) to "
                f"re-key it onto a {n}-shard mesh, or re-run on a "
                f"{int(snap['n_shards'])}-shard mesh"
            )
        if self._memory_budget_mb is not None and (
            capacity_for_budget(self._memory_budget_mb)
            != int(snap["cap_s"])
        ):
            # The budget is authoritative, but a resume must adopt the
            # snapshot's table — both promises hold only when they
            # agree (the single-chip tiered rule).
            raise ValueError(
                f"resume memory_budget_mb={self._memory_budget_mb} "
                f"implies a "
                f"{capacity_for_budget(self._memory_budget_mb)}-slot "
                f"per-shard hot table, but the snapshot was written at "
                f"cap_s={int(snap['cap_s'])}; resume with the "
                "snapshot's original budget (or with capacity kwargs "
                "alone to adopt its geometry)"
            )
        want_key = self._snapshot_key()
        got_key = str(snap["engine_key"])
        if got_key != want_key:
            raise ValueError(
                "snapshot does not match this tiered-sharded checker "
                f"configuration (snapshot {got_key}, expected "
                f"{want_key})"
            )
        self._cap_s = int(snap["cap_s"])
        self._slot_bits = max(1, self._cap_s.bit_length() - 1)
        self._chunk = int(snap["chunk"])
        if "bucket_slack" in snap.files:
            self._bucket_slack = int(snap["bucket_slack"])
        if "sort_lanes" in snap.files and int(snap["sort_lanes"]):
            self._sort_lanes = int(snap["sort_lanes"])
            self._sort_tune = False
        if "sortless" in snap.files:
            self._sortless = bool(int(snap["sortless"]))
        if "step_lanes" in snap.files and int(snap["step_lanes"]):
            self._step_lanes = int(snap["step_lanes"])
            self._step_tune = False
        self._ts_log_cap = int(snap["ts_log_cap"])
        w = self._compiled.state_width
        rows_h = np.asarray(snap["rows"]).reshape(n, -1, w)
        parent_h = np.asarray(snap["parent"]).reshape(n, -1)
        ebits_h = np.asarray(snap["ebits"]).reshape(n, -1)
        lp = rows_h.shape[1]
        pad = lp - self._ts_log_cap
        if pad < self._chunk:
            # Re-establish the mint invariant (pad >= chunk: every
            # dynamic_slice window fits) for snapshots written by a
            # narrower-pad config (e.g. a resharded one).
            new_lp = self._ts_log_cap + self._chunk
            r2 = np.zeros((n, new_lp, w), np.uint32)
            r2[:, :lp] = rows_h
            p2 = np.full((n, new_lp), NO_GID, np.uint32)
            p2[:, :lp] = parent_h
            e2 = np.zeros((n, new_lp), np.uint32)
            e2[:, :lp] = ebits_h
            rows_h, parent_h, ebits_h = r2, p2, e2
            pad = self._chunk
            lp = new_lp
        self._ts_pad = pad
        if lp * n >= 0xFFFFFFFF:
            raise ValueError("capacity too large for 32-bit global ids")
        rows = self._ts_up(rows_h.reshape(n * lp, w))
        parent = self._ts_up(parent_h.reshape(n * lp))
        ebits = self._ts_up(ebits_h.reshape(n * lp))
        self._ts_level_start = np.asarray(
            snap["ts_level_start"], np.int64
        ).copy()
        self._ts_level_end = np.asarray(
            snap["ts_level_end"], np.int64
        ).copy()
        self._ts_tails = np.asarray(snap["ts_tails"], np.int64).copy()
        self._ts_spill_tails = np.asarray(
            snap["ts_spill_tails"], np.int64
        ).copy()
        self._ts_cand = np.asarray(snap["ts_cand"], np.int64).copy()
        self._t_depth = int(snap["ts_depth"])
        self._t_unique = int(snap["ts_unique"])
        self._t_states = int(snap["ts_states"])
        disc_np = np.asarray(snap["disc"]).astype(np.uint32)
        self._t_disc = self._ts_up(disc_np)
        self._t_disc_h = disc_np
        fps = np.asarray(snap["ts_cold_fps"])
        lens = np.asarray(snap["ts_cold_lens"], np.int64)
        runs_per = np.asarray(snap["ts_cold_runs_per_shard"], np.int64)
        self._colds = []
        fp_off = len_off = 0
        for d in range(n):
            k = int(runs_per[d])
            d_lens = lens[len_off:len_off + k]
            cnt = int(d_lens.sum())
            self._colds.append(
                ColdStore.from_arrays(
                    fps[fp_off:fp_off + cnt], d_lens,
                    spill_dir=(
                        None if self._cold_dir is None
                        else os.path.join(self._cold_dir, f"shard_{d}")
                    ),
                    max_runs=self._cold_max_runs,
                )
            )
            fp_off += cnt
            len_off += k
        # Planes are never persisted: rebuild from the committed log
        # (a kill between checkpoint and spill can therefore never
        # resurrect an aborted insert's keys).
        key_hi, key_lo = self._ts_rebuild_planes(rows)
        self._ts_hot = (
            self._ts_tails - self._ts_spill_tails
        ).astype(np.int64)
        with self._lock:
            self._state_count = self._t_states
            self._unique_count = self._t_unique
            self._max_depth = self._t_depth
            for d in range(n):
                for p, prop in enumerate(self._properties):
                    g = int(disc_np[d, p])
                    if g != NO_GID:
                        self._discovery_gids.setdefault(prop.name, g)
        if self._journal:
            self._journal.append(
                "resume",
                path=self._resume_from,
                unique=self._t_unique,
                states=self._t_states,
                depth=self._t_depth,
                cold_runs=int(sum(c.run_count for c in self._colds)),
                cold_entries=int(sum(c.entries for c in self._colds)),
            )
        return (key_hi, key_lo, rows, parent, ebits)

    # --- snapshots ------------------------------------------------------------

    def _snapshot_key(self) -> str:
        return super()._snapshot_key() + "+tiered-sharded-v1"

    def _ts_carry_dict(self, carry) -> dict:
        cold_fps = []
        cold_lens = []
        runs_per = np.zeros(self._n, np.int64)
        for d, c in enumerate(self._colds):
            f, l = c.to_arrays()
            cold_fps.append(f)
            cold_lens.append(l)
            runs_per[d] = l.shape[0]
        n_props = len(self._properties)
        return {
            "rows": carry[2],
            "parent": carry[3],
            "ebits": carry[4],
            "disc": (
                self._t_disc_h if self._t_disc_h is not None
                else np.full((self._n, n_props), NO_GID, np.uint32)
            ),
            "ts_level_start": self._ts_level_start.astype(np.int64),
            "ts_level_end": self._ts_level_end.astype(np.int64),
            "ts_tails": self._ts_tails.astype(np.int64),
            "ts_spill_tails": self._ts_spill_tails.astype(np.int64),
            "ts_cand": self._ts_cand.astype(np.int64),
            "ts_depth": np.int64(self._t_depth),
            "ts_unique": np.int64(self._t_unique),
            "ts_states": np.uint64(self._t_states),
            "ts_log_cap": np.int64(self._ts_log_cap),
            "ts_cold_fps": (
                np.concatenate(cold_fps)
                if cold_fps else np.zeros((0,), np.uint64)
            ),
            "ts_cold_lens": (
                np.concatenate(cold_lens)
                if cold_lens else np.zeros((0,), np.int64)
            ),
            "ts_cold_runs_per_shard": runs_per,
        }

    def _wl_write_checkpoint(self, carry) -> dict:
        self._write_snapshot(
            self._checkpoint_path, self._ts_carry_dict(carry)
        )
        return {
            "tail": int(self._ts_tails.sum()),
            "cold_runs": int(sum(c.run_count for c in self._colds)),
            "cold_entries": int(sum(c.entries for c in self._colds)),
        }

    # --- surface --------------------------------------------------------------

    def discovered_fingerprints(self):
        self.join()
        if self._carry_dev is None:
            raise RuntimeError("no run state to fingerprint")
        from ..parallel.wave_loop import fingerprints_of_rows

        n, w = self._n, self._compiled.state_width
        rows = np.asarray(self._carry_dev["rows"]).reshape(n, -1, w)
        segs = [rows[d, : int(self._ts_tails[d])] for d in range(n)]
        return fingerprints_of_rows(
            self._compiled, np.concatenate(segs, axis=0), self._canon
        )

    def _gid_path(self, gid: int):
        from ..core.path import Path

        with self._lock:
            if self._tables_host is None:
                if self._tables_dev is None:
                    raise RuntimeError(
                        "no run state to reconstruct paths from (the "
                        "checker did not complete cleanly)"
                    )
                parent_dev, rows_dev = self._tables_dev
                n, w = self._n, self._compiled.state_width
                self._tables_host = (
                    np.asarray(parent_dev).reshape(n, -1),
                    np.asarray(rows_dev).reshape(n, -1, w),
                )
            parent, rows = self._tables_host
        n = self._n
        chain = []
        g = gid
        while g != NO_GID:
            chain.append(g)
            g = int(parent[g % n, g // n])
        chain.reverse()
        fps = [
            self._model.fingerprint(
                self._compiled.decode(rows[g % n, g // n])
            )
            for g in chain
        ]
        return Path.from_fingerprints(self._model, fps)

    def _wl_geometry(self) -> dict:
        g = super()._wl_geometry()
        g.update(
            engine="tpu-tiered-sharded",
            memory_budget_mb=self._memory_budget_mb,
            spill_threshold=self._spill_threshold,
            log_capacity=self._ts_log_cap,
            waves_per_call=1,
        )
        return g

    def metrics(self) -> dict:
        out = super().metrics()
        out.update(
            engine="tpu-tiered-sharded",
            memory_budget_mb=self._memory_budget_mb,
            spill_threshold=self._spill_threshold,
            cold_runs=int(sum(c.run_count for c in self._colds)),
            cold_entries=int(sum(c.entries for c in self._colds)),
            cold_bytes=int(sum(c.nbytes for c in self._colds)),
        )
        return out
