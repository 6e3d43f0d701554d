"""HBM-resident fingerprint hash set with batched insert-if-absent.

The reference dedups successors through a lock-sharded concurrent hash map —
one contended insert per generated state (src/checker/bfs.rs:301-315).  The
TPU equivalent is a device-resident open-addressing table keyed by the
64-bit packed-state fingerprint, stored as two uint32 planes (no u64 on TPU
vector lanes), with whole *waves* of candidate keys inserted at once.

The cost model drives the shape of the algorithm.  On TPU, random-index
gather/scatter runs ~25M elems/s while a multi-operand sort runs the whole
batch in one pass (~25M keys in ~80ms) — so every full-batch probe round is
expensive and BFS waves carry heavy duplication (each state is generated by
several parents).  Two insertion strategies share the claim-plane probe
core: the engines' DEFAULT is the sortless :func:`insert_batch_claim`
(representatives elected inside the probe rounds via a min-claim scatter
— no sort at all, cost proportional to the probing lanes), with the
sorted :func:`insert_batch_compact` staying as the per-workload fallback
rung (knob cache selected).  The sorted form runs in three stages:

1. **Sort pre-dedup**: sort (hi, lo, lane) — deterministically electing the
   lowest lane of each equal-key run as representative — and compact the
   representatives into a ``dedup_factor``× smaller buffer.  One sort
   replaces the probe rounds that duplicate lanes would have burned.
2. **Claim-plane probe rounds** over the unique buffer: each unresolved
   lane gathers its probe slot; key present → duplicate of an earlier wave;
   empty → contenders scatter their lane id into a claim plane and read it
   back, the unique winner scatters its key (the two key planes can never
   interleave words from different lanes — no phantom keys); occupied by
   another key → linear probe.  Rounds stop once the unresolved count fits
   the first straggler stage.
3. **Straggler ladder**: each round costs its stage's full width in
   gathers, scatters and the sorts XLA puts before them, however few
   lanes still probe, so the unresolved lanes step down a geometric
   ladder of narrower stages (:data:`STRAGGLER_LADDER`: 16,384, 2,048
   and 256 lanes).  A stage runs rounds while its unresolved count
   exceeds the next stage's width, then compacts the unresolved lane ids
   (order-preserving, so the lowest lane stays lowest) and gathers their
   keys and slots into the next; the last stage runs to completion.
   Where the unresolved lanes are already a prefix of the stage (it ran
   no rounds over a compacted batch, as on most waves, or none are
   left) that compaction is the identity, so the next stage is entered
   by static slices instead, with the same lanes in the same order, and
   a stage with nothing to probe runs no round.  A stage not narrower
   than the batch is never entered.  One deep chain must not make the
   full batch repeat forty rounds.  Both insert paths share the ladder
   (:func:`_probe_ladder`), and report the rounds run at each stage and
   the hand-offs taken by slice.

Empty slots are (0, 0); fingerprints are guaranteed nonzero
(ops.device_fp, mirroring the reference's NonZeroU64 fingerprints,
src/lib.rs:341).  The all-ones fingerprint is reserved as the inactive-lane
sentinel and is likewise unreachable: ``device_fp64`` (and the bit-identical
host mixers) remap it to 0xFFFF…FFFE, so no real state can ever collide
with the sentinel and be dropped.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..ops.device_fp import _fmix32, _rotl

_U32 = jnp.uint32
NO_SLOT = jnp.uint32(0xFFFFFFFF)
_SENTINEL = jnp.uint32(0xFFFFFFFF)


class HashSet(NamedTuple):
    """Key planes of the open-addressing table; capacity is a power of two."""

    key_hi: jax.Array  # uint32[capacity]
    key_lo: jax.Array  # uint32[capacity]

    @property
    def capacity(self) -> int:
        return self.key_hi.shape[0]

    def load_factor(self) -> float:
        """Occupied-slot fraction, read directly from the key planes —
        one device reduction + a scalar sync.  Ground truth independent
        of any host-side bookkeeping: the in-HBM engines surface it on
        finished runs (``metrics()['table_load_factor']``, cached), and
        the tiered engine reads it at each spill decision to confirm
        (and journal) the measured hot-tier load the eviction acted on
        (tiered/engine.py)."""
        occupied = jnp.sum(
            (self.key_hi != 0) | (self.key_lo != 0), dtype=jnp.uint32
        )
        return float(occupied) / self.capacity


def make_hashset(capacity: int) -> HashSet:
    if capacity & (capacity - 1):
        raise ValueError("capacity must be a power of two")
    return HashSet(
        key_hi=jnp.zeros((capacity,), _U32),
        key_lo=jnp.zeros((capacity,), _U32),
    )


def home_slot(hi, lo, capacity: int):
    """Initial probe slot for a key; a second independent mix of the 64-bit
    fingerprint so table position isn't correlated with the key planes."""
    return _fmix32(hi ^ _rotl(lo, 16) ^ _U32(0x7FEB352D)) & _U32(capacity - 1)


def _probe_rounds(kh, kl, claim, hi, lo, slot, done, stop_count: int,
                  elect: bool):
    """Probe/claim rounds until ≤ ``stop_count`` lanes remain unresolved (or
    ``4 × capacity`` rounds elapse).  The trailing element of the returned
    tuple is the executed round count — the one measured input the roofline's
    probe-bytes model needs (obs/roofline.py).

    ``elect=True`` is the SORTLESS mode (:func:`insert_batch_claim`):
    the batch may carry duplicate keys, and the claim scatter becomes a
    deterministic ``min`` so the LOWEST contending lane wins each slot.
    Equal-key lanes always probe in lockstep (same home slot, same
    observations, same advance decision every round), so when a lane of
    key K finally wins a slot, the contender set includes every
    unresolved K-lane and the min IS the lowest original K-lane — the
    exact representative :func:`prededup`'s stable sort elects.  Losers
    of the same key retry the slot and resolve ``present`` against the
    winner's key next round; losers of a different key linear-probe on.
    Every wanted slot is won (the min contender is a contender), so a
    slot with a stale claim is always occupied and can never block a
    later round's election."""
    capacity = kh.shape[0]
    mask = _U32(capacity - 1)
    lane = jnp.arange(hi.shape[0], dtype=_U32)

    def cond(carry):
        _kh, _kl, _claim, _slot, done, _new, rounds = carry
        undone = jnp.sum(~done, dtype=jnp.int32)
        return (undone > stop_count) & (rounds < 4 * capacity)

    def body(carry):
        kh, kl, claim, slot, done, is_new, rounds = carry
        cur_hi = kh[slot]
        cur_lo = kl[slot]
        present = (cur_hi == hi) & (cur_lo == lo)
        empty = (cur_hi == 0) & (cur_lo == 0)
        found = ~done & present
        want = ~done & empty
        claim_idx = jnp.where(want, slot, _U32(capacity))
        if elect:
            claim = claim.at[claim_idx].min(lane, mode="drop")
        else:
            claim = claim.at[claim_idx].set(lane, mode="drop")
        won = want & (claim[slot] == lane)
        key_idx = jnp.where(won, slot, _U32(capacity))
        kh = kh.at[key_idx].set(hi, mode="drop")
        kl = kl.at[key_idx].set(lo, mode="drop")
        done = done | found | won
        # Occupied by a different key -> linear probe; claim losers retry
        # the slot (they will see the winner's key next round).
        advance = ~done & ~empty & ~present
        slot = jnp.where(advance, (slot + _U32(1)) & mask, slot)
        return kh, kl, claim, slot, done, is_new | won, rounds + 1

    init = (
        kh, kl, claim, slot, done, done & ~done, jnp.zeros((), jnp.int32)
    )
    return jax.lax.while_loop(cond, body, init)


# Lane widths of the straggler stages below the main one (module docstring,
# stage 3): each rung is an eighth of the one above it.
STRAGGLER_LADDER = (1 << 14, 1 << 11, 1 << 8)


def stage_widths(b: int) -> Tuple[int, ...]:
    """The lane width behind each entry of the per-stage round vector an
    insert over a ``b``-lane buffer reports: the main stage, then every
    ladder rung (a rung not narrower than ``b`` is skipped and reads 0)."""
    return (b,) + STRAGGLER_LADDER


def _ladder_stage(kh, kl, claim, hi, lo, slot, done, rungs, elect):
    """Probe rounds at this stage's width until the unresolved lanes fit
    ``rungs[0]``, then hand them down the remaining rungs and write
    their results back.  Returns ``(kh, kl, slot, done, is_new,
    rounds[1 + len(rungs)], slices[len(rungs)])``; ``slices`` is 1 at
    each rung entered by slice."""
    from .wave_common import compact

    w = hi.shape[0]
    nxt = rungs[0] if rungs else 0
    kh, kl, claim, slot, done, is_new, rounds = _probe_rounds(
        kh, kl, claim, hi, lo, slot, done, nxt, elect
    )
    if not rungs:
        return (
            kh, kl, slot, done, is_new, rounds[None],
            jnp.zeros((0,), jnp.int32),
        )

    undone = ~done
    n = jnp.sum(undone, dtype=_U32)
    lane = jnp.arange(w, dtype=_U32)
    t_act = lane[:nxt] < n
    # Unresolved lanes that are already the prefix [0, n) — a stage that
    # ran no rounds over a compacted batch, or none left — compact to
    # themselves (and lane 0 past n): the next stage then takes static
    # slices, and its results go back by a slice update in place of the
    # cumsum, gathers and scatters.  ``ref`` is the compaction either
    # way; only the scatter-back reads it.  No cond takes the table
    # planes, so they stay where the rounds keep them (VMEM at 2^23).
    prefix = jnp.all(undone == (lane < n))

    def by_slice():
        return (
            jnp.where(t_act, lane[:nxt], _U32(0)), hi[:nxt], lo[:nxt],
            slot[:nxt],
        )

    def by_compaction():
        ref = compact(undone, lane, nxt)
        return ref, hi[ref], lo[ref], slot[ref]

    ref, t_hi, t_lo, t_slot = jax.lax.cond(prefix, by_slice, by_compaction)
    kh, kl, t_slot, t_done, t_new, t_rounds, t_slices = _ladder_stage(
        kh, kl, claim, t_hi, t_lo, t_slot, ~t_act, rungs[1:], elect,
    )

    def put_slice():
        def put(x, t):
            return jnp.concatenate([jnp.where(t_act, t, x[:nxt]), x[nxt:]])

        return put(slot, t_slot), put(done, t_done), put(is_new, t_new)

    def put_scatter():
        back = jnp.where(t_act, ref, _U32(w))
        return (
            slot.at[back].set(t_slot, mode="drop"),
            done.at[back].set(t_done, mode="drop"),
            is_new.at[back].set(t_new, mode="drop"),
        )

    slot, done, is_new = jax.lax.cond(prefix, put_slice, put_scatter)
    sliced = (prefix & (n > 0)).astype(jnp.int32)
    return (
        kh, kl, slot, done, is_new,
        jnp.concatenate([rounds[None], t_rounds]),
        jnp.concatenate([sliced[None], t_slices]),
    )


def _probe_ladder(table: HashSet, claim, hi, lo, active, elect: bool):
    """Insert-if-absent probing shared by both insert paths: the main
    stage at the batch width, then the straggler ladder.  Returns ``(kh,
    kl, slot, done, is_new, stage_rounds, stage_slices)``, int32 vectors
    over :func:`stage_widths` of the batch: the rounds run at each stage
    (0 at a skipped rung; the executed round count is their sum), and 1
    at each rung entered by slice (0 at the main stage)."""
    rungs = tuple(w for w in STRAGGLER_LADDER if w < hi.shape[0])
    kh, kl, slot, done, is_new, rounds, slices = _ladder_stage(
        table.key_hi, table.key_lo, claim, hi, lo,
        home_slot(hi, lo, table.capacity), ~active, rungs, elect,
    )
    skipped = jnp.zeros((len(STRAGGLER_LADDER) - len(rungs),), jnp.int32)
    stage_rounds = jnp.concatenate([rounds[:1], skipped, rounds[1:]])
    stage_slices = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), skipped, slices]
    )
    return kh, kl, slot, done, is_new, stage_rounds, stage_slices


def unique_buffer_size(b: int, dedup_factor: int) -> int:
    """THE worst-case compaction/dedup buffer width: ``max(min(B, 16K),
    B/dedup_factor)`` lanes for a B-lane candidate batch.  Single
    definition — the three compaction entry points below and every
    host-side consumer (engine block padding, sharded accounting) must
    size from here so the overflow criterion and the reported payload
    geometry cannot drift from the device's.  The entry points accept a
    ``sort_lanes`` override — the adaptive sort-geometry rung
    (wave_loop.py's ladder) — which shrinks the WORKING buffer below
    this worst case; the overflow criteria then fire at the rung, and
    the host retry ladder makes an undersized rung a recompile, never a
    wrong answer."""
    return max(min(b, 1 << 14), b // dedup_factor)


def _effective_buffer(b: int, dedup_factor: int, sort_lanes) -> int:
    """The working compaction width: the ``sort_lanes`` rung when given
    (clamped to the batch — a rung can never usefully exceed the lanes
    that feed it), else the worst-case :func:`unique_buffer_size`."""
    if sort_lanes is not None:
        return max(1, min(int(sort_lanes), b))
    return unique_buffer_size(b, dedup_factor)


def compact_valid_indices(valid, dedup_factor: int = 4, sort_lanes=None):
    """Index half of :func:`compact_valid`: compact the valid LANE INDICES
    only — the two-phase engines construct successor rows after
    compaction, so keys to compact do not exist yet.  Returns
    ``(v_orig, v_act, n_valid, overflow)``.  ``overflow`` fires on the
    VALID-LANE count exceeding the buffer (see :func:`compact_valid` for
    the criterion contract); ``sort_lanes`` substitutes the rung for the
    worst-case width."""
    import jax.numpy as jnp

    from .wave_common import compact

    b = valid.shape[0]
    v_sz = _effective_buffer(b, dedup_factor, sort_lanes)
    v_orig = compact(valid, jnp.arange(b, dtype=_U32), v_sz)
    n_valid = jnp.sum(valid, dtype=_U32)
    v_act = jnp.arange(v_sz, dtype=_U32) < n_valid
    overflow = n_valid > _U32(v_sz)
    return v_orig, v_act, n_valid, overflow


def compact_valid(hi, lo, valid, dedup_factor: int = 4, sort_lanes=None):
    """Compact a sparse-valid key batch to a ``max(min(B, 16K),
    B/dedup_factor)`` buffer (or the ``sort_lanes`` rung when given) with
    three 1-word scatters, so the dedup sort and everything downstream
    work on real keys instead of the sentinel-padded majority (~95% of
    wave candidates are invalid).

    Returns ``(v_hi, v_lo, v_orig, v_act, overflow)``; ``v_orig`` maps
    buffer lanes back to original ones.

    OVERFLOW CRITERION (a contract, pinned by
    tests/test_hashset_geometry.py): the flag fires on the **VALID-lane
    count** — ``n_valid > v_sz`` — NOT on distinct keys.  It is exactly
    full at ``n_valid == v_sz`` and trips at ``v_sz + 1``.  This is a
    stricter criterion than :func:`prededup`'s distinct-representative
    count: a duplicate-heavy model can trip it where an un-compacted
    dedup would not.  It fails loudly and ``dedup_factor=1`` with no
    rung is always safe.  Shared by the single-chip and sharded engines
    so the criterion cannot drift.
    """
    from .wave_common import compact

    b = hi.shape[0]
    v_sz = _effective_buffer(b, dedup_factor, sort_lanes)
    # ONE B-sized scatter (the lane indices), then U-sized gathers for the
    # payload: B-sized random-index ops run ~25M elems/s on TPU, so each
    # one avoided is ~10 ms/chunk at bench batch sizes.  Inactive buffer
    # lanes gather lane 0's key — garbage ``v_act`` masks downstream.
    v_orig = compact(valid, jnp.arange(b, dtype=_U32), v_sz)
    v_hi = hi[v_orig]
    v_lo = lo[v_orig]
    n_valid = jnp.sum(valid, dtype=_U32)
    v_act = jnp.arange(v_sz, dtype=_U32) < n_valid
    overflow = n_valid > _U32(v_sz)
    return v_hi, v_lo, v_orig, v_act, overflow


def prededup(hi, lo, active, dedup_factor: int = 4, sort_lanes=None):
    """Stage 1 alone: elect one representative per distinct key and
    compact to a ``max(min(B, 16K), B/dedup_factor)``-lane buffer (or
    the ``sort_lanes`` rung when given).

    Returns ``(u_hi, u_lo, u_origin, u_active, overflow)`` in sorted key
    order; ``u_origin`` is the representative's original lane (the lowest
    of its equal-key run).  Shared by the insert below and the sharded
    engine's pre-exchange dedup so the two cannot drift.

    OVERFLOW CRITERION (a contract, pinned by
    tests/test_hashset_geometry.py): the flag fires on the
    **DISTINCT-REPRESENTATIVE count** — distinct keys ``> u`` — NOT on
    valid lanes.  It is exactly full at ``u`` distinct keys and trips at
    ``u + 1``; a duplicate-heavy batch whose distinct keys fit the
    buffer never trips it, however many lanes are active (the looser
    half of the criterion pair :func:`compact_valid` documents).
    """
    b = hi.shape[0]
    lane = jnp.arange(b, dtype=_U32)

    # Inactive lanes become the all-ones sentinel key and sort to the end;
    # two sort keys + stable == three keys (lanes are distinct, so stable
    # order within an equal-(hi,lo) run IS lane order), but the comparator
    # is a third smaller — measurably faster to compile and run on TPU.
    shi = jnp.where(active, hi, _SENTINEL)
    slo = jnp.where(active, lo, _SENTINEL)
    shi_s, slo_s, lane_s = jax.lax.sort(
        [shi, slo, lane], num_keys=2, is_stable=True
    )
    act_s = ~((shi_s == _SENTINEL) & (slo_s == _SENTINEL))
    prev_eq = jnp.concatenate(
        [
            jnp.zeros((1,), jnp.bool_),
            (shi_s[1:] == shi_s[:-1]) & (slo_s[1:] == slo_s[:-1]),
        ]
    )
    rep = act_s & ~prev_eq  # representative: first lane of each key run

    from .wave_common import compact

    u = _effective_buffer(b, dedup_factor, sort_lanes)
    u_hi = compact(rep, shi_s, u)
    u_lo = compact(rep, slo_s, u)
    u_origin = compact(rep, lane_s, u)
    u_active = compact(rep, rep, u)
    overflow = jnp.sum(rep, dtype=jnp.int32) > u
    return u_hi, u_lo, u_origin, u_active, overflow


def insert_batch_compact(
    table: HashSet, hi, lo, active, dedup_factor: int = 4,
    with_rounds: bool = False, sort_lanes=None,
):
    """Insert-if-absent a batch of keys, returning the *compact* form.

    ``hi``/``lo``: uint32[B] fingerprints; ``active``: bool[B] lanes to
    insert.  ``dedup_factor``: the unique-key buffer is B/dedup_factor
    lanes (min 16K); if a batch has more distinct new keys than that, the
    call reports failure (raise the engine's frontier/batch knobs).

    Returns ``(table, u_slot[U], u_new[U], u_origin[U], u_active[U],
    probe_ok, dedup_overflow)`` where U = max(min(B, 16K), B/dedup_factor):
    one lane per distinct key in the batch (its representative = the lowest
    original lane, ``u_origin``), in sorted key order.  ``u_new`` marks
    keys newly inserted this call; ``u_slot`` is the table slot.

    ``sort_lanes`` substitutes the adaptive sort-geometry rung for the
    worst-case U (wave_loop.py's ladder): the pre-dedup sort, the probe
    rounds, and every U-sized result buffer then span the rung instead.

    ``with_rounds=True`` appends two more elements, int32 vectors over
    :func:`stage_widths` of the buffer: the probe rounds run at each
    stage, whose sum is the executed round count, and the hand-offs into
    each stage taken by slice.  The engines count them
    (``metrics()["dedup_stage_rounds"]``, ``["dedup_stage_slices"]``)
    and the tiered loops feed the rounds to the roofline's probe-bytes
    model (obs/roofline.py).

    This compact form is the engine hot path: per-chunk candidate batches
    carry ~95% invalid/duplicate lanes, and scattering B-indexed state
    rows (42 words each for paxos) dominated the chunk at ~2/3 of its
    wall-clock — U-sized row scatters make that cost proportional to the
    *distinct* keys instead.
    """
    u_hi, u_lo, u_origin, u_active, overflow = prededup(
        hi, lo, active, dedup_factor, sort_lanes=sort_lanes
    )
    # Stages 2-3 over the unique buffer; distinct keys, so the claim is a
    # plain set.
    claim = table.key_hi ^ table.key_hi  # varying-typed zero plane
    kh, kl, u_slot, u_done, u_new, rounds, slices = _probe_ladder(
        table, claim, u_hi, u_lo, u_active, elect=False
    )

    out = (
        HashSet(kh, kl), u_slot, u_new, u_origin, u_active,
        jnp.all(u_done), overflow,
    )
    return (out + (rounds, slices)) if with_rounds else out


def insert_batch_claim(
    table: HashSet, hi, lo, active, with_rounds: bool = False,
):
    """SORTLESS insert-if-absent: claim-plane-only representative
    election (ROADMAP #1's stretch; the engines' default dedup path).

    Unlike :func:`insert_batch_compact`, the batch is NOT pre-deduped —
    duplicate keys probe together and the election happens inside the
    probe rounds themselves: contenders for an empty slot scatter their
    lane id with a deterministic ``min`` into the claim plane the hash
    set already owns, the lowest lane wins the slot and writes its key,
    and same-key losers resolve ``present`` against the winner's key on
    the next round — no 3-plane co-sort, no ``sort_lanes`` buffer, and
    per-wave cost proportional to the lanes actually probing instead of
    a worst-case sort width.  The elected representative is the LOWEST
    active lane of each equal-key run (see :func:`_probe_rounds`), i.e.
    bit-identical to :func:`prededup`'s choice — parents, ebits, and
    min-BFS-position discovery semantics are preserved (pinned by
    tests/test_hashset_geometry.py + tests/test_sortless.py).

    Results come back PER INPUT LANE (the "u buffer" is the input
    buffer): ``(table, slot[B], new[B], origin[B], active[B], probe_ok,
    dedup_overflow)`` where ``origin`` is the identity map (so callers
    shared with the sorted path index unchanged), ``new`` marks exactly
    the winner lane of each newly inserted key, and ``dedup_overflow``
    is structurally False — there is no representative buffer to
    overflow; the caller's :func:`compact_valid` criterion is the only
    capacity gate on this path.  ``with_rounds=True`` appends the
    per-stage probe rounds and slice hand-offs, as in
    :func:`insert_batch_compact`.

    The one failure mode the election shares with the sorted path is
    probe exhaustion (``probe_ok`` False): the engines raise flag 1 and
    grow the table, as on the sorted path.  When the caller's
    compaction buffer overflows at its full width (a duplicate-dense
    batch), the single-chip engine relaxes ``dedup_factor`` and falls
    back to the sort path at a narrow rung in one move
    (wave_loop.land_on_sort_rung); the sharded engine relaxes and stays
    on the election at full width.  Either way a pathological batch
    costs a recompile, never a wrong answer.
    """
    # The claim plane, initialized to the all-ones sentinel (no real
    # lane id) so the min-election starts pristine; derived from the
    # key plane to stay varying-typed under shard_map.  Deep probe chains
    # and their same-key followers, which move in lockstep and so stay
    # electable, finish down the straggler ladder.
    claim = (table.key_hi ^ table.key_hi) | _SENTINEL
    kh, kl, slot, done, is_new, rounds, slices = _probe_ladder(
        table, claim, hi, lo, active, elect=True
    )
    lane = jnp.arange(hi.shape[0], dtype=_U32)

    out = (
        HashSet(kh, kl), slot, is_new, lane, active,
        jnp.all(done), jnp.zeros((), jnp.bool_),
    )
    return (out + (rounds, slices)) if with_rounds else out


def insert_batch(
    table: HashSet, hi, lo, active, dedup_factor: int = 4
) -> Tuple[HashSet, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Insert-if-absent with per-input-lane results (duplicates fine).

    Returns ``(table, slot[B] uint32, is_new[B] bool, probe_ok bool,
    dedup_overflow bool)``: ``is_new`` marks exactly one lane per newly
    inserted key (the lowest lane of each equal-key run).  ``slot`` is
    the key's table slot on every *representative* lane — newly inserted
    or already present — and ``NO_SLOT`` only on non-representative
    duplicate lanes, so gate writes on ``is_new``, never on
    ``slot != NO_SLOT``.  ``probe_ok``
    False means probing exhausted the table (overfull); ``dedup_overflow``
    True means the batch held more distinct keys than B/dedup_factor — a
    separate failure with a separate fix (lower ``dedup_factor``, not the
    table capacity).  Prefer :func:`insert_batch_compact` in hot paths —
    the scatter back to B lanes here costs two extra B-index scatters.
    """
    b = hi.shape[0]
    table, u_slot, u_new, u_origin, u_active, probe_ok, overflow = (
        insert_batch_compact(table, hi, lo, active, dedup_factor)
    )
    slot = hi | _SENTINEL  # all-ones, derived varying for shard_map
    is_new = active & ~active
    safe_origin = jnp.where(u_active, u_origin, _U32(b))
    slot = slot.at[safe_origin].set(u_slot, mode="drop")
    is_new = is_new.at[safe_origin].set(u_new, mode="drop")
    return table, slot, is_new, probe_ok, overflow
