"""Direct unit tests for the hash set's geometry helpers
(parallel/hashset.py): ``unique_buffer_size`` is THE compaction-buffer
width every overflow criterion and byte model derives from, and
``prededup`` / ``compact_valid`` / ``compact_valid_indices`` are the
device stages the tiered engine's eviction-threshold math builds on —
edge cases at ``dedup_factor=1`` and at full buffers were previously
only covered through whole-engine goldens."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from stateright_tpu.parallel.hashset import (  # noqa: E402
    compact_valid,
    compact_valid_indices,
    insert_batch,
    make_hashset,
    prededup,
    unique_buffer_size,
)


# --- unique_buffer_size: the single width definition -------------------------


def test_unique_buffer_size_dedup_factor_one_covers_whole_batch():
    # dd=1 is the always-safe geometry: the buffer spans every lane, so
    # the overflow criterion (n > size) can never fire.
    for b in (1, 7, 1 << 10, 1 << 14, 1 << 17):
        assert unique_buffer_size(b, 1) == b


def test_unique_buffer_size_floor_and_division():
    # Small batches: the min(B, 16K) floor wins over B/dd.
    assert unique_buffer_size(1 << 10, 4) == 1 << 10
    assert unique_buffer_size(1 << 14, 8) == 1 << 14
    # Past the 16K floor the division takes over.
    assert unique_buffer_size(1 << 17, 4) == 1 << 15
    assert unique_buffer_size(1 << 17, 8) == 1 << 14
    # Integer division truncates, never rounds up.
    assert unique_buffer_size(100_000, 3) == 100_000 // 3


def test_unique_buffer_size_monotone_in_dedup_factor():
    b = 1 << 17
    prev = b + 1
    for dd in (1, 2, 4, 8, 16):
        u = unique_buffer_size(b, dd)
        assert u <= prev
        prev = u


# --- prededup ----------------------------------------------------------------


def _keys(vals):
    """uint64 test keys split into (hi, lo) planes."""
    vals = np.asarray(vals, np.uint64)
    return (
        jnp.asarray((vals >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray(vals.astype(np.uint32)),
    )


def test_prededup_elects_lowest_lane_in_sorted_key_order():
    hi, lo = _keys([30, 10, 30, 20, 10, 10, 40, 20])
    active = jnp.ones((8,), jnp.bool_)
    u_hi, u_lo, u_origin, u_active, overflow = prededup(hi, lo, active, 1)
    n = int(jnp.sum(u_active))
    assert n == 4 and not bool(overflow)
    keys = (
        np.asarray(u_hi[:n]).astype(np.uint64) << np.uint64(32)
    ) | np.asarray(u_lo[:n]).astype(np.uint64)
    assert keys.tolist() == [10, 20, 30, 40]  # sorted key order
    # The representative is the LOWEST original lane of each run — the
    # first-inserter ebits semantics depend on it.
    assert np.asarray(u_origin[:n]).tolist() == [1, 3, 0, 6]


def test_prededup_full_buffer_all_distinct_dd1_no_overflow():
    # dd=1, every lane active and distinct: the buffer is exactly full —
    # the boundary the overflow comparison (> not >=) must not trip.
    b = 64
    hi, lo = _keys(np.arange(1, b + 1, dtype=np.uint64))
    u_hi, u_lo, u_origin, u_active, overflow = prededup(
        hi, lo, jnp.ones((b,), jnp.bool_), 1
    )
    assert not bool(overflow)
    assert int(jnp.sum(u_active)) == b
    assert np.asarray(u_origin).tolist() == list(range(b))


def test_prededup_overflow_fires_past_buffer():
    # More distinct keys than the dd-shrunk buffer holds: loud flag.
    # (The buffer floors at min(B, 16K), so B must exceed 16K lanes.)
    b = 1 << 15
    dd = 4
    u = unique_buffer_size(b, dd)
    assert u < b
    hi, lo = _keys(np.arange(1, b + 1, dtype=np.uint64))
    *_rest, overflow = prededup(hi, lo, jnp.ones((b,), jnp.bool_), dd)
    assert bool(overflow)


def test_prededup_inactive_lanes_do_not_count():
    hi, lo = _keys([5, 6, 7, 8])
    active = jnp.asarray([True, False, True, False])
    u_hi, u_lo, u_origin, u_active, overflow = prededup(hi, lo, active, 1)
    assert int(jnp.sum(u_active)) == 2
    assert not bool(overflow)


# --- the overflow-criterion pair (a pinned contract) --------------------------
#
# compact_valid / compact_valid_indices flag on the VALID-LANE count;
# prededup flags on the DISTINCT-REPRESENTATIVE count.  Both comparisons
# are strict (> not >=): exactly-full commits, one past trips.  The
# engines' flag 4 (and the sort-rung ladder's retry criterion) derive
# from these two, so the boundary is pinned here at exactly-u_sz and
# u_sz+1 for BOTH.


def test_compact_valid_overflow_boundary_exact_and_plus_one():
    b = 1 << 15
    dd = 4
    v_sz = unique_buffer_size(b, dd)
    assert v_sz < b
    hi, lo = _keys(np.arange(1, b + 1, dtype=np.uint64))
    exactly = jnp.asarray(np.arange(b) < v_sz)
    *_r, ovf = compact_valid(hi, lo, exactly, dd)
    assert not bool(ovf)
    *_r, i_ovf = compact_valid_indices(exactly, dd)
    assert not bool(i_ovf)
    plus_one = jnp.asarray(np.arange(b) < v_sz + 1)
    *_r, ovf = compact_valid(hi, lo, plus_one, dd)
    assert bool(ovf)
    *_r, i_ovf = compact_valid_indices(plus_one, dd)
    assert bool(i_ovf)


def test_compact_valid_counts_valid_lanes_not_distinct_keys():
    # ONE distinct key on v_sz+1 valid lanes still trips the flag: the
    # criterion is valid lanes, deliberately stricter than distinct
    # keys (the compaction buffer must hold every valid lane BEFORE the
    # dedup sort can collapse duplicates).
    b = 1 << 15
    dd = 4
    v_sz = unique_buffer_size(b, dd)
    hi, lo = _keys(np.ones((b,), np.uint64))
    valid = jnp.asarray(np.arange(b) < v_sz + 1)
    *_r, ovf = compact_valid(hi, lo, valid, dd)
    assert bool(ovf)


def test_prededup_overflow_boundary_exact_and_plus_one():
    # Distinct-representative criterion at the same u_sz boundary:
    # exactly u distinct keys (each on TWO valid lanes — twice the
    # buffer in valid lanes) commits; u+1 distinct keys trips.  The
    # duplicate-heavy exactly-full case is precisely where the two
    # criteria diverge: compact_valid WOULD flag this batch.
    b = 1 << 15
    dd = 4
    u = unique_buffer_size(b, dd)
    vals = np.repeat(np.arange(1, u + 1, dtype=np.uint64), b // u)
    hi, lo = _keys(vals)
    active = jnp.ones((b,), jnp.bool_)
    *_r, ovf = prededup(hi, lo, active, dd)
    assert not bool(ovf)
    *_r, cv_ovf = compact_valid(hi, lo, active, dd)
    assert bool(cv_ovf)  # the stricter valid-lane criterion fires
    vals_plus = vals.copy()
    vals_plus[-1] = np.uint64(u + 1)  # u+1 distinct keys
    hi, lo = _keys(vals_plus)
    *_r, ovf = prededup(hi, lo, active, dd)
    assert bool(ovf)


# --- the sort_lanes rung (wave_loop.py's sort-geometry ladder) ----------------


def test_sort_lanes_rung_shrinks_buffers_and_boundary():
    b = 1 << 12
    rung = 256
    hi, lo = _keys(np.arange(1, b + 1, dtype=np.uint64))
    exactly = jnp.asarray(np.arange(b) < rung)
    v_hi, v_lo, v_orig, v_act, ovf = compact_valid(
        hi, lo, exactly, 1, sort_lanes=rung
    )
    assert v_hi.shape[0] == rung  # the buffer IS the rung
    assert not bool(ovf)
    i_orig, i_act, n_valid, i_ovf = compact_valid_indices(
        exactly, 1, sort_lanes=rung
    )
    assert i_orig.shape[0] == rung and not bool(i_ovf)
    plus_one = jnp.asarray(np.arange(b) < rung + 1)
    *_r, ovf = compact_valid(hi, lo, plus_one, 1, sort_lanes=rung)
    assert bool(ovf)
    u_hi, u_lo, u_origin, u_active, p_ovf = prededup(
        hi, lo, plus_one, 1, sort_lanes=rung
    )
    assert u_hi.shape[0] == rung
    assert bool(p_ovf)  # rung+1 distinct representatives


def test_sort_lanes_rung_results_match_full_buffer_prefix():
    # A rung that holds the batch is invisible: the compacted prefix —
    # keys, origins, representatives — is bit-identical to the full
    # worst-case buffer's (the discovery-set bit-equality gate, at the
    # unit level).
    rng = np.random.default_rng(12)
    b = 1 << 12
    rung = 512
    vals = rng.integers(1, 1 << 40, size=b, dtype=np.uint64)
    valid_np = rng.random(b) < 0.05  # ~200 valid lanes, under the rung
    hi, lo = _keys(vals)
    valid = jnp.asarray(valid_np)
    full = compact_valid(hi, lo, valid, 1)
    slim = compact_valid(hi, lo, valid, 1, sort_lanes=rung)
    n = int(valid_np.sum())
    assert not bool(full[-1]) and not bool(slim[-1])
    for fu, sl in zip(full[:-1], slim[:-1]):
        assert np.array_equal(np.asarray(fu)[:n], np.asarray(sl)[:n])
    pfull = prededup(hi, lo, valid, 1)
    pslim = prededup(hi, lo, valid, 1, sort_lanes=rung)
    k = int(jnp.sum(pfull[3]))
    assert int(jnp.sum(pslim[3])) == k
    for fu, sl in zip(pfull[:-1], pslim[:-1]):
        assert np.array_equal(np.asarray(fu)[:k], np.asarray(sl)[:k])


def test_insert_batch_compact_sort_lanes_same_table():
    # Insert-if-absent through a rung-sized buffer lands the same table
    # contents as the full-buffer insert when distinct keys fit the rung.
    rng = np.random.default_rng(3)
    b = 1 << 10
    rung = 256
    vals = rng.integers(1, 1 << 40, size=rung // 2, dtype=np.uint64)
    vals = np.concatenate([vals] * (b // vals.shape[0]))  # duplicates
    hi, lo = _keys(vals)
    active = jnp.ones((b,), jnp.bool_)
    from stateright_tpu.parallel.hashset import insert_batch_compact

    t0, *_r0, ok0, ovf0 = insert_batch_compact(
        make_hashset(1 << 12), hi, lo, active, dedup_factor=1
    )
    t1, *_r1, ok1, ovf1 = insert_batch_compact(
        make_hashset(1 << 12), hi, lo, active, dedup_factor=1,
        sort_lanes=rung,
    )
    assert bool(ok0) and bool(ok1)
    assert not bool(ovf0) and not bool(ovf1)
    assert np.array_equal(np.asarray(t0.key_hi), np.asarray(t1.key_hi))
    assert np.array_equal(np.asarray(t0.key_lo), np.asarray(t1.key_lo))


# --- compact_valid / compact_valid_indices -----------------------------------


def test_compact_valid_identity_at_dd1_full_valid():
    # Every lane valid at dd=1: compaction is the identity permutation
    # and the VALID-lane overflow criterion sits exactly at the boundary.
    b = 128
    hi, lo = _keys(np.arange(1, b + 1, dtype=np.uint64))
    valid = jnp.ones((b,), jnp.bool_)
    v_hi, v_lo, v_orig, v_act, overflow = compact_valid(hi, lo, valid, 1)
    assert not bool(overflow)
    assert int(jnp.sum(v_act)) == b
    assert np.asarray(v_orig).tolist() == list(range(b))
    assert np.array_equal(np.asarray(v_hi), np.asarray(hi))


def test_compact_valid_overflow_on_valid_count():
    # The criterion counts VALID lanes (stricter than distinct keys): a
    # duplicate-heavy batch must still trip it when valid > buffer.
    b = 1 << 15
    dd = 4
    vals = np.ones((b,), np.uint64)  # ONE distinct key, all lanes valid
    hi, lo = _keys(vals)
    *_rest, overflow = compact_valid(hi, lo, jnp.ones((b,), jnp.bool_), dd)
    assert bool(overflow)


def test_compact_valid_indices_matches_compact_valid():
    # The index-only variant (two-phase engines) must pick the same
    # lanes in the same order as the key-compacting one.
    rng = np.random.default_rng(7)
    b = 256
    vals = rng.integers(1, 1 << 40, size=b, dtype=np.uint64)
    valid_np = rng.random(b) < 0.3
    hi, lo = _keys(vals)
    valid = jnp.asarray(valid_np)
    v_hi, v_lo, v_orig, v_act, ovf = compact_valid(hi, lo, valid, 4)
    i_orig, i_act, n_valid, i_ovf = compact_valid_indices(valid, 4)
    assert bool(ovf) == bool(i_ovf) is False
    assert int(n_valid) == int(valid_np.sum())
    n = int(n_valid)
    assert np.array_equal(np.asarray(v_orig)[:n], np.asarray(i_orig)[:n])
    assert np.array_equal(np.asarray(v_act), np.asarray(i_act))
    # And the gathered keys really are the valid lanes' keys, in order.
    assert np.asarray(v_hi)[:n].tolist() == [
        int(v >> np.uint64(32)) for v in vals[valid_np]
    ]


def test_compact_valid_zero_valid_lanes():
    b = 64
    hi, lo = _keys(np.arange(1, b + 1, dtype=np.uint64))
    v_hi, v_lo, v_orig, v_act, overflow = compact_valid(
        hi, lo, jnp.zeros((b,), jnp.bool_), 1
    )
    assert not bool(overflow)
    assert int(jnp.sum(v_act)) == 0


# --- load_factor: the cheap occupancy readback -------------------------------


def test_load_factor_readback():
    t = make_hashset(1 << 10)
    assert t.load_factor() == 0.0
    vals = np.arange(1, 129, dtype=np.uint64)
    hi, lo = _keys(vals)
    t, _slot, is_new, probe_ok, _ovf = insert_batch(
        t, hi, lo, jnp.ones((128,), jnp.bool_), dedup_factor=1
    )
    assert bool(probe_ok)
    assert int(jnp.sum(is_new)) == 128
    assert t.load_factor() == pytest.approx(128 / 1024)
    # Re-inserting the same keys adds no occupancy.
    t, _slot, is_new, probe_ok, _ovf = insert_batch(
        t, hi, lo, jnp.ones((128,), jnp.bool_), dedup_factor=1
    )
    assert int(jnp.sum(is_new)) == 0
    assert t.load_factor() == pytest.approx(128 / 1024)


# --- insert_batch_claim: the sortless claim-plane election -------------------


def _claim_insert(vals, active=None, capacity=1 << 8):
    from stateright_tpu.parallel.hashset import insert_batch_claim

    hi, lo = _keys(vals)
    n = len(vals)
    act = (
        jnp.ones((n,), jnp.bool_) if active is None
        else jnp.asarray(np.asarray(active, bool))
    )
    return insert_batch_claim(make_hashset(capacity), hi, lo, act)


def test_claim_election_all_duplicates_batch():
    # Every lane the same key: exactly one winner, and it is lane 0 —
    # the lowest lane of the (single) equal-key run.
    t, slot, new, origin, act, ok, ovf = _claim_insert([7] * 64)
    new = np.asarray(new)
    assert bool(ok) and not bool(ovf)
    assert new.sum() == 1 and new[0]
    # origin is the identity map (the sorted path's indexing contract).
    assert np.array_equal(np.asarray(origin), np.arange(64))


def test_claim_election_zero_valid_wave():
    t, slot, new, origin, act, ok, ovf = _claim_insert(
        [1, 2, 3, 4], active=[False] * 4
    )
    assert bool(ok)
    assert int(np.asarray(new).sum()) == 0
    assert t.load_factor() == 0.0


def test_claim_election_capacity_full_table():
    # More distinct keys than table slots: probing exhausts and the
    # call reports failure (probe_ok False) — the engines' flag-1
    # dispatch falls back to the sort path before growing the table.
    from stateright_tpu.parallel.hashset import insert_batch_claim

    vals = np.arange(1, 65, dtype=np.uint64)
    hi, lo = _keys(vals)
    t, _s, _n, _o, _a, ok, _ovf = insert_batch_claim(
        make_hashset(32), hi, lo, jnp.ones((64,), jnp.bool_)
    )
    assert not bool(ok)


def test_claim_election_colliding_fingerprint_lanes():
    # A tiny table forces distinct keys to contend for the same probe
    # slots (hash collisions): every distinct key must still land, the
    # winner of each equal-key run must still be its lowest lane, and
    # duplicates of different keys must never cross-resolve.
    rng = np.random.default_rng(7)
    vals = rng.integers(1, 40, size=200).astype(np.uint64)
    t, slot, new, origin, act, ok, ovf = _claim_insert(
        vals, capacity=1 << 7
    )
    new = np.asarray(new)
    slot = np.asarray(slot)
    assert bool(ok) and not bool(ovf)
    first = {}
    for i, v in enumerate(vals.tolist()):
        first.setdefault(v, i)
    assert {i for i in range(200) if new[i]} == set(first.values())
    # Winner slots hold exactly the winner's key.
    kh = np.asarray(t.key_hi)
    kl = np.asarray(t.key_lo)
    for i in range(200):
        if new[i]:
            key = (int(kh[slot[i]]) << 32) | int(kl[slot[i]])
            assert key == int(vals[i])


def test_claim_election_matches_prededup_representatives():
    # Election-vs-prededup representative equality: the claim winners
    # are exactly prededup's lowest-lane representatives, and the
    # resulting tables hold the identical key set.
    from stateright_tpu.parallel.hashset import (
        insert_batch_claim, insert_batch_compact,
    )

    rng = np.random.default_rng(3)
    vals = rng.integers(1, 500, size=1024).astype(np.uint64)
    active = rng.random(1024) > 0.4
    hi, lo = _keys(vals)
    act = jnp.asarray(active)

    tc, c_slot, c_new, c_origin, _ca, c_ok, c_ovf = insert_batch_claim(
        make_hashset(1 << 11), hi, lo, act
    )
    ts, _s, u_new, u_origin, u_active, s_ok, s_ovf = insert_batch_compact(
        make_hashset(1 << 11), hi, lo, act, dedup_factor=1
    )
    assert bool(c_ok) and bool(s_ok)
    claim_reps = {int(i) for i in np.where(np.asarray(c_new))[0]}
    sorted_reps = {
        int(o) for o, n in zip(
            np.asarray(u_origin).tolist(), np.asarray(u_new).tolist()
        ) if n
    }
    assert claim_reps == sorted_reps
    k_claim = set(
        zip(np.asarray(tc.key_hi).tolist(), np.asarray(tc.key_lo).tolist())
    )
    k_sort = set(
        zip(np.asarray(ts.key_hi).tolist(), np.asarray(ts.key_lo).tolist())
    )
    assert k_claim == k_sort


def _sequential_table(vals, capacity):
    """A plain sequential insert-if-absent (linear probing from the same
    home slots) of ``vals``, as numpy key planes."""
    from stateright_tpu.parallel.hashset import home_slot

    kh = np.zeros(capacity, np.uint32)
    kl = np.zeros(capacity, np.uint32)
    hi, lo = (np.asarray(x) for x in _keys(vals))
    home = np.asarray(home_slot(jnp.asarray(hi), jnp.asarray(lo), capacity))
    for h, l, s in zip(hi.tolist(), lo.tolist(), home.tolist()):
        while (kh[s], kl[s]) not in ((0, 0), (h, l)):
            s = (s + 1) % capacity
        kh[s], kl[s] = h, l
    return kh, kl


def _ladder_rounds_model(kh, kl, hi, lo, active, capacity):
    """Per-stage probe rounds of lockstep parallel probing with lowest-lane
    election, in numpy, in hashset.stage_widths order: the main stage runs
    while more lanes are unresolved than the first narrower rung holds,
    each rung while more than the next holds, the last to completion."""
    from stateright_tpu.parallel.hashset import STRAGGLER_LADDER, home_slot

    kh, kl, hi, lo = (np.array(x) for x in (kh, kl, hi, lo))
    b = hi.shape[0]
    slot = np.asarray(home_slot(jnp.asarray(hi), jnp.asarray(lo), capacity))
    slot = slot.astype(np.int64)
    done = ~np.asarray(active)
    lane = np.arange(b)
    rungs = [w for w in STRAGGLER_LADDER if w < b]
    per_stage = []
    for nxt in rungs + [0]:
        r = 0
        while (~done).sum() > nxt:
            cur_h, cur_l = kh[slot], kl[slot]
            present = (cur_h == hi) & (cur_l == lo)
            empty = (cur_h == 0) & (cur_l == 0)
            want = ~done & empty
            claim = np.full(capacity, b)
            np.minimum.at(claim, slot[want], lane[want])
            won = want & (claim[slot] == lane)
            kh[slot[won]], kl[slot[won]] = hi[won], lo[won]
            done = done | present | won
            advance = ~done & ~empty & ~present
            slot = np.where(advance, (slot + 1) % capacity, slot)
            r += 1
        per_stage.append(r)
    rung_rounds = dict(zip(rungs, per_stage[1:]))
    return [per_stage[0]] + [rung_rounds.get(w, 0) for w in STRAGGLER_LADDER]


def _ladder_batch(kind):
    """(prefilled keys, batch keys, batch active mask, capacity)."""
    rng = np.random.default_rng(11)
    if kind == "tail":  # just past 16K lanes into an empty table
        n = (1 << 14) + 256
        vals = rng.integers(1, 3000, size=n).astype(np.uint64)
        return np.zeros(0, np.uint64), vals, np.ones(n, bool), 1 << 13
    # A table at 45% load; the batch mixes present keys with new ones,
    # each drawn several times.
    n, capacity = {"all_stages": (40_000, 1 << 17),
                   "under_16k": (10_000, 1 << 15),
                   "zero_valid": (40_000, 1 << 17)}[kind]
    keys = rng.choice(1 << 62, size=int(0.45 * capacity) + n // 2,
                      replace=False).astype(np.uint64) + np.uint64(1)
    pre = keys[: int(0.45 * capacity)]
    pool = np.concatenate([pre[: n // 8], keys[len(pre):]])
    vals = rng.choice(pool, size=n)
    return pre, vals, np.full(n, kind != "zero_valid"), capacity


@pytest.mark.parametrize("batch", ["tail", "all_stages", "under_16k",
                                   "zero_valid"])
@pytest.mark.parametrize("path", ["claim", "compact"])
def test_claim_election_straggler_tail_batch(path, batch):
    # Both insert paths down the straggler ladder equal a plain
    # sequential insert-if-absent: the same key set, ``new`` on exactly
    # the lowest lane of each new key, every representative's slot
    # holding its key, and the rounds counted at each stage width.
    from stateright_tpu.parallel.hashset import (
        HashSet, STRAGGLER_LADDER, home_slot, insert_batch_claim,
        insert_batch_compact,
    )

    pre, vals, active, capacity = _ladder_batch(batch)
    n = len(vals)
    kh0, kl0 = _sequential_table(pre, capacity)
    hi, lo = _keys(vals)
    table = HashSet(jnp.asarray(kh0), jnp.asarray(kl0))
    if path == "claim":
        t, slot, new, origin, rep, ok, _ovf, rounds, _sl = insert_batch_claim(
            table, hi, lo, jnp.asarray(active), with_rounds=True
        )
    else:
        t, slot, new, origin, rep, ok, _ovf, rounds, _sl = (
            insert_batch_compact(
                table, hi, lo, jnp.asarray(active), dedup_factor=1,
                with_rounds=True,
            )
        )
    assert bool(ok)
    slot, new, origin, rep = (np.asarray(x) for x in (slot, new, origin, rep))
    if path == "claim":  # per input lane: the representatives are the
        first = {}       # lowest active lane of each key
        for i, v in enumerate(vals.tolist()):
            if active[i]:
                first.setdefault(v, i)
        rep = np.zeros(n, bool)
        rep[list(first.values())] = True

    kh, kl = np.asarray(t.key_hi), np.asarray(t.key_lo)
    want_kh, want_kl = _sequential_table(
        np.concatenate([pre, vals[active]]), capacity
    )
    stored = set(zip(kh.tolist(), kl.tolist()))
    assert stored == set(zip(want_kh.tolist(), want_kl.tolist()))

    present = set(pre.tolist())
    new_lanes = {int(origin[i]) for i in np.flatnonzero(new)}
    lowest = {}
    for i, v in enumerate(vals.tolist()):
        if active[i] and v not in present:
            lowest.setdefault(v, i)
    assert new_lanes == set(lowest.values())
    for i in np.flatnonzero(rep):
        v = int(vals[origin[i]])
        assert (int(kh[slot[i]]) << 32) | int(kl[slot[i]]) == v

    rounds = np.asarray(rounds).tolist()
    assert len(rounds) == 1 + len(STRAGGLER_LADDER)
    skipped = [w >= n for w in STRAGGLER_LADDER]
    assert all(r == 0 for r, s in zip(rounds[1:], skipped) if s)
    if batch == "zero_valid":
        assert rounds == [0] * len(rounds)
    if batch == "all_stages":
        assert all(r > 0 for r in rounds)
    if path == "claim":
        model = _ladder_rounds_model(kh0, kl0, hi, lo, active, capacity)
        assert rounds == model
        assert sum(rounds) == sum(model)
    elif active.any():
        # Each round moves a lane one slot: the deepest representative
        # needed at least its displacement plus one.
        u_hi, u_lo = (np.asarray(x)[origin[rep]] for x in (hi, lo))
        home = np.asarray(home_slot(jnp.asarray(u_hi), jnp.asarray(u_lo),
                                    capacity))
        depth = (slot[rep].astype(np.int64) - home) % capacity
        assert sum(rounds) >= int(depth.max()) + 1


# --- slice hand-offs: bit-identical to the compaction they skip --------------

_SLICE_B = 1 << 15  # the buffer width: main stage, then all three rungs
_SLICE_CAP = 1 << 16


@pytest.fixture(scope="module")
def slice_fixture():
    """A table at 45% load (linear probing there builds chains of dozens
    of slots) and a key pool that mixes its keys with new ones, plus one
    jitted insert per path and buffer width, so every count reuses them.

    The prefix side inserts through the public entry points.  The
    shifted side runs the same batch behind one leading inactive lane,
    which makes the first hand-off compact: the claim path through
    :func:`insert_batch_claim`, the sort path through :func:`prededup`'s
    buffer shifted by one and handed to the ladder as
    :func:`insert_batch_compact` hands it."""
    from stateright_tpu.parallel.hashset import (
        HashSet, _probe_ladder, insert_batch_claim, insert_batch_compact,
    )

    rng = np.random.default_rng(29)
    keys = rng.choice(1 << 62, size=int(0.45 * _SLICE_CAP) + _SLICE_B,
                      replace=False).astype(np.uint64) + np.uint64(1)
    pre = keys[: int(0.45 * _SLICE_CAP)]
    kh0, kl0 = _sequential_table(pre, _SLICE_CAP)
    table = HashSet(jnp.asarray(kh0), jnp.asarray(kl0))
    pool = np.concatenate([pre[: len(pre) // 4], keys[len(pre):]])

    def shift(x):
        return jnp.concatenate([x[:1] & ~x[:1], x])

    @jax.jit
    def claim_prefix(hi, lo, act):
        t, slot, new, _o, _a, ok, _v, rounds, slices = insert_batch_claim(
            table, hi, lo, act, with_rounds=True
        )
        return t, slot, new, ok, rounds, slices

    @jax.jit
    def claim_shifted(hi, lo, act):
        t, slot, new, _o, _a, ok, _v, rounds, slices = insert_batch_claim(
            table, shift(hi), shift(lo), shift(act), with_rounds=True
        )
        return t, slot[1:], new[1:], ok, rounds, slices

    @jax.jit
    def compact_prefix(hi, lo, act):
        t, slot, new, _o, _a, ok, _v, rounds, slices = (
            insert_batch_compact(
                table, hi, lo, act, dedup_factor=1, with_rounds=True
            )
        )
        return t, slot, new, ok, rounds, slices

    @jax.jit
    def compact_shifted(hi, lo, act):
        u_hi, u_lo, _origin, u_act, _ovf = prededup(hi, lo, act, 1)
        claim = table.key_hi ^ table.key_hi
        kh, kl, slot, done, new, rounds, slices = _probe_ladder(
            table, claim, shift(u_hi), shift(u_lo), shift(u_act),
            elect=False,
        )
        return (
            HashSet(kh, kl), slot[1:], new[1:], jnp.all(done), rounds,
            slices,
        )

    runs = {
        "claim": (claim_prefix, claim_shifted),
        "compact": (compact_prefix, compact_shifted),
    }
    return pool, runs


@pytest.mark.parametrize(
    "count", [0, 1, 256, 257, 2048, 2049, 16384, 16385, _SLICE_B]
)
@pytest.mark.parametrize("path", ["claim", "compact"])
def test_slice_hand_off_bit_identical_to_compaction(
    slice_fixture, path, count
):
    # A batch whose valid lanes are a prefix enters the ladder by
    # slices; the same keys in the same order one lane later must enter
    # by compaction and land the same planes, per-lane results, probe
    # rounds and deeper hand-offs, bit for bit.
    from stateright_tpu.parallel.hashset import STRAGGLER_LADDER

    pool, runs = slice_fixture
    rng = np.random.default_rng(count)
    # Inactive lanes carry keys of their own, as the engines' do.
    vals = rng.choice(pool, size=_SLICE_B)
    hi, lo = _keys(vals)
    act = jnp.arange(_SLICE_B) < count
    # Lanes entering the ladder: the sort path's are the distinct keys.
    entering = count if path == "claim" else len(set(vals[:count].tolist()))
    by_prefix, by_shift = (
        [np.asarray(x) for x in jax.tree_util.tree_leaves(run(hi, lo, act))]
        for run in runs[path]
    )
    kh, kl, slot, new, ok, rounds, slices = by_prefix
    s_kh, s_kl, s_slot, s_new, s_ok, s_rounds, s_slices = by_shift
    assert bool(ok) and bool(s_ok)
    assert np.array_equal(kh, s_kh) and np.array_equal(kl, s_kl)
    assert np.array_equal(slot, s_slot) and np.array_equal(new, s_new)
    assert rounds.tolist() == s_rounds.tolist()
    assert len(slices) == 1 + len(STRAGGLER_LADDER)
    # The first hand-off (into 16,384 lanes) slices only where the main
    # stage ran no rounds over a nonempty prefix; deeper hand-offs see
    # the same lanes on both sides and take the same route.
    assert slices[0] == s_slices[0] == 0
    assert slices[1] == int(0 < entering <= STRAGGLER_LADDER[0])
    assert s_slices[1] == 0
    assert slices[2:].tolist() == s_slices[2:].tolist()
    if count and path == "claim":
        assert int(new.sum()) > 0 and rounds.sum() > 0
