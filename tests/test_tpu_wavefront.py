"""TPU wavefront checker: device fingerprint, device hash set, compiled-model
step parity, and golden-count/discovery-set equivalence with the host oracle.

The decisive test per SURVEY §4: CPU and TPU checkers must produce identical
discovery sets and unique-state counts on the BASELINE configs.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from stateright_tpu.models.twophase import TwoPhaseSys  # noqa: E402
from stateright_tpu.ops.device_fp import device_fp64  # noqa: E402
from stateright_tpu.ops.fingerprint import fp64_words  # noqa: E402
from stateright_tpu.parallel.hashset import (  # noqa: E402
    insert_batch,
    make_hashset,
)

from .conftest import TPU_SKIP_REASON, _platform  # noqa: E402


def test_device_fp_matches_host():
    rng = np.random.default_rng(7)
    for width in (1, 2, 3, 5):
        words = rng.integers(0, 2**32, size=(32, width), dtype=np.uint32)
        hi, lo = device_fp64(jnp.asarray(words))
        for i in range(32):
            host = fp64_words(words[i].tolist())
            assert ((int(hi[i]) << 32) | int(lo[i])) == host


def test_device_fp_nonzero():
    # The nonzero rule exists so (0,0) can mark empty hash slots.
    words = jnp.zeros((4, 2), jnp.uint32)
    hi, lo = device_fp64(words)
    assert all((int(h) | int(l)) != 0 for h, l in zip(hi, lo))


def test_hashset_insert_matches_python_set():
    rng = np.random.default_rng(3)
    table = make_hashset(1 << 11)
    seen = set()
    for _ in range(6):
        # Narrow key range forces duplicates within and across batches.
        keys = rng.integers(1, 2**13, size=192, dtype=np.uint64)
        hi = jnp.asarray((keys >> 32).astype(np.uint32))
        lo = jnp.asarray((keys & 0xFFFFFFFF).astype(np.uint32))
        active = jnp.asarray(rng.random(192) < 0.9)
        table, slot, is_new, probe_ok, dd_overflow = insert_batch(
            table, hi, lo, active
        )
        assert bool(probe_ok) and not bool(dd_overflow)
        active_np = np.asarray(active)
        inserted = {int(k) for k, a in zip(keys, active_np) if a}
        assert int(jnp.sum(is_new)) == len(inserted - seen)
        # Each newly inserted key has exactly one winning lane, and the
        # winners occupy distinct slots.
        slots = np.asarray(slot)
        new_np = np.asarray(is_new)
        winner_keys = [int(k) for i, k in enumerate(keys) if new_np[i]]
        assert len(winner_keys) == len(set(winner_keys))
        winner_slots = [int(slots[i]) for i in np.flatnonzero(new_np)]
        assert len(winner_slots) == len(set(winner_slots))
        seen |= inserted


@pytest.fixture(scope="module")
def twophase3():
    return TwoPhaseSys(rm_count=3)


def _reachable(model):
    from collections import deque

    seen, order, q = set(), [], deque(model.init_states())
    while q:
        s = q.popleft()
        if s in seen:
            continue
        seen.add(s)
        order.append(s)
        q.extend(ns for ns in model.next_states(s) if ns not in seen)
    return order


def test_twophase_encode_decode_roundtrip(twophase3):
    cm = twophase3.compiled()
    for s in _reachable(twophase3):
        assert cm.decode(cm.encode(s)) == s


def test_twophase_step_parity(twophase3):
    """Device successors == host successors on every reachable state."""
    cm = twophase3.compiled()
    states = _reachable(twophase3)
    enc = jnp.asarray(np.stack([cm.encode(s) for s in states]))
    nexts, valid = jax.jit(jax.vmap(cm.step))(enc)
    nexts, valid = np.asarray(nexts), np.asarray(valid)
    for i, s in enumerate(states):
        host = sorted(cm.encode(ns).tobytes() for ns in twophase3.next_states(s))
        dev = sorted(
            nexts[i, j].tobytes() for j in range(cm.max_actions) if valid[i, j]
        )
        assert host == dev


def test_twophase_property_conds_parity(twophase3):
    cm = twophase3.compiled()
    props = twophase3.properties()
    states = _reachable(twophase3)
    enc = jnp.asarray(np.stack([cm.encode(s) for s in states]))
    conds = np.asarray(jax.jit(jax.vmap(cm.property_conds))(enc))
    for i, s in enumerate(states):
        for p, prop in enumerate(props):
            assert bool(conds[i, p]) == bool(prop.condition(twophase3, s))


def _assert_checker_parity(model, **tpu_kwargs):
    host = model.checker().spawn_bfs().join()
    # Default to the (virtual) CPU backend: fast and always present.  The
    # real-TPU path is exercised by bench.py and the tpu-marked smoke test.
    tpu_kwargs.setdefault("device", jax.devices("cpu")[0])
    tpu = model.checker().spawn_tpu(**tpu_kwargs).join()
    assert tpu.unique_state_count() == host.unique_state_count()
    assert tpu.state_count() == host.state_count()
    assert tpu.max_depth() == host.max_depth()
    hd, td = host.discoveries(), tpu.discoveries()
    assert sorted(td) == sorted(hd)
    # Paths re-execute the host model, so building them validates them.
    for name, path in td.items():
        assert len(path) >= 1
    return host, tpu


def test_twophase3_golden_tpu(twophase3):
    """2pc with 3 RMs: 288 unique states (reference examples/2pc.rs:153-154),
    identical counts and discovery set between host BFS and TPU wavefront."""
    _host, tpu = _assert_checker_parity(
        twophase3, capacity=1 << 14, max_frontier=1 << 9
    )
    assert tpu.unique_state_count() == 288


@pytest.mark.slow
def test_twophase5_golden_tpu():
    """2pc with 5 RMs: 8,832 unique states (examples/2pc.rs:158-159)."""
    model = TwoPhaseSys(rm_count=5)
    _host, tpu = _assert_checker_parity(
        model, capacity=1 << 15, max_frontier=1 << 11
    )
    assert tpu.unique_state_count() == 8832


@pytest.mark.slow
def test_levels_wider_than_chunk_match_host():
    """A BFS level far wider than max_frontier is processed in chunks from
    the slot queue instead of failing; counts, depth, and discoveries still
    match the host oracle exactly (2pc(5)'s peak level is ~2,000 states,
    checked here with 128-state chunks)."""
    model = TwoPhaseSys(rm_count=5)
    _host, tpu = _assert_checker_parity(
        model, capacity=1 << 15, max_frontier=1 << 7
    )
    assert tpu.unique_state_count() == 8832


def test_target_max_depth_with_chunked_levels():
    """Depth gating must trigger at level boundaries, not chunk boundaries."""
    model = TwoPhaseSys(rm_count=5)
    host = model.checker().target_max_depth(6).spawn_bfs().join()
    tpu = (
        model.checker()
        .target_max_depth(6)
        .spawn_tpu(capacity=1 << 15, max_frontier=1 << 7)
        .join()
    )
    assert tpu.unique_state_count() == host.unique_state_count()
    assert tpu.max_depth() == host.max_depth()


# --- eventually-property machinery on device ---------------------------------

# The fixture moved to the package (models/fixtures.py) so the symmetry
# tests and PARITY's compiled-model inventory can reference it; re-exported
# here for the sibling test modules that import it from this one.
from stateright_tpu.models.fixtures import (  # noqa: E402
    TrapCounter,
    TrapCounterCompiled,  # noqa: F401  (re-export)
)


def test_eventually_parity_with_host():
    model = TrapCounter()
    host, tpu = _assert_checker_parity(
        model, capacity=1 << 10, max_frontier=1 << 4
    )
    names = sorted(tpu.discoveries())
    # "reaches one" holds on every path: no counterexample. "reaches limit"
    # is violated via the trap dead end; "trapped" is observed.
    assert names == ["reaches limit", "trapped"]
    ce = tpu.discoveries()["reaches limit"]
    assert ce.last_state() == model.trap_state


def test_eventually_satisfied_at_terminal_not_reported():
    # Without the trap edge every path ends at `limit`, satisfying the
    # property at the terminal state itself — the bit clears before the
    # terminal check, so no counterexample (src/checker/bfs.rs:326-333).
    model = TrapCounter(trap_at=10**6)
    tpu = (
        model.checker()
        .spawn_tpu(
            capacity=1 << 10,
            max_frontier=1 << 4,
            device=jax.devices("cpu")[0],
        )
        .join()
    )
    assert "reaches limit" not in tpu.discoveries()
    assert "reaches one" not in tpu.discoveries()


@pytest.mark.tpu
def test_twophase3_golden_on_default_device():
    """Smoke test on the default backend (the real TPU when present)."""
    model = TwoPhaseSys(rm_count=3)
    tpu = model.checker().spawn_tpu(capacity=1 << 14, max_frontier=1 << 9).join()
    assert tpu.unique_state_count() == 288


def test_tpu_marked_tests_skip_off_chip(request):
    """Off the chip every `tpu`-marked test carries the conftest's skip, so
    a device-scale golden never runs on the CPU backend; on the chip none
    does."""
    off_chip = _platform != "tpu"
    for item in request.session.items:
        if item.get_closest_marker("tpu"):
            reasons = [m.kwargs.get("reason") for m in item.iter_markers("skip")]
            assert (TPU_SKIP_REASON in reasons) == off_chip, item.nodeid


def test_checkpoint_resume_matches_straight_run(tmp_path):
    """A bounded run snapshots its full device state (visited table, store,
    parents, frontier queue, counters) and resumes to exactly the totals of
    an uninterrupted run.  The reference has no checker persistence at all
    (SURVEY §5: its visited set is not persistable)."""
    model = TwoPhaseSys(rm_count=5)
    partial = (
        model.checker()
        .target_state_count(3000)
        .spawn_tpu(capacity=1 << 15, max_frontier=1 << 7)
        .join()
    )
    assert partial.unique_state_count() < 8832
    snap = str(tmp_path / "run.npz")
    partial.save_snapshot(snap)

    resumed = (
        model.checker()
        .spawn_tpu(capacity=1 << 15, max_frontier=1 << 7, resume_from=snap)
        .join()
    )
    straight = (
        model.checker().spawn_tpu(capacity=1 << 15, max_frontier=1 << 7).join()
    )
    assert resumed.unique_state_count() == straight.unique_state_count() == 8832
    assert resumed.state_count() == straight.state_count()
    assert resumed.max_depth() == straight.max_depth()
    assert sorted(resumed.discoveries()) == sorted(straight.discoveries())
    resumed.assert_properties()

    # Geometry is NOT key material: a resume adopts the snapshot's
    # table/log sizes (an auto-tuned run persists its GROWN geometry, so
    # the original spawn arguments must still resume it).
    adopted = (
        model.checker()
        .spawn_tpu(capacity=1 << 16, max_frontier=1 << 7, resume_from=snap)
        .join()
    )
    assert adopted.unique_state_count() == 8832

    # A different MODEL must still be rejected loudly.
    with pytest.raises(ValueError, match="snapshot does not match"):
        TwoPhaseSys(rm_count=4).checker().spawn_tpu(
            capacity=1 << 15, max_frontier=1 << 7, resume_from=snap
        ).join()


@pytest.mark.slow
def test_auto_tune_grows_overfull_table():
    """A capacity far below the state count completes anyway: the engine
    restarts with a grown table instead of failing into a hand-tuning
    loop (VERDICT r3 weak #7).  2pc(3) has 288 unique states, so a
    256-slot table trips the 50%-load flag almost immediately."""
    model = TwoPhaseSys(rm_count=3)
    tpu = model.checker().spawn_tpu(capacity=1 << 8, max_frontier=1 << 9).join()
    assert tpu.unique_state_count() == 288

    with pytest.raises(RuntimeError, match="table overfull"):
        model.checker().spawn_tpu(
            capacity=1 << 8, max_frontier=1 << 9, auto_tune=False
        ).join()


@pytest.mark.slow
def test_auto_tune_grows_full_row_log():
    """log_capacity sizes the row log independently of the table; an
    undersized log grows on retry, and without auto_tune fails loudly
    naming the knob."""
    model = TwoPhaseSys(rm_count=3)
    tpu = (
        model.checker()
        .spawn_tpu(capacity=1 << 14, max_frontier=1 << 9, log_capacity=256)
        .join()
    )
    assert tpu.unique_state_count() == 288

    with pytest.raises(RuntimeError, match="row log is full"):
        model.checker().spawn_tpu(
            capacity=1 << 14,
            max_frontier=1 << 9,
            log_capacity=256,
            auto_tune=False,
        ).join()


def test_log_capacity_smaller_than_table_exact():
    """A decoupled (table=2^14, log=512) geometry — the `paxos check 6`
    memory shape in miniature — still produces exact counts, depth, and
    discoveries."""
    model = TwoPhaseSys(rm_count=3)
    host = model.checker().spawn_bfs().join()
    tpu = (
        model.checker()
        .spawn_tpu(capacity=1 << 14, max_frontier=1 << 9, log_capacity=512)
        .join()
    )
    assert tpu.unique_state_count() == host.unique_state_count() == 288
    assert tpu.max_depth() == host.max_depth()
    assert tpu.state_count() == host.state_count()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


@pytest.mark.slow
def test_twophase10_depth_bounded_differential():
    """`2pc check 10` — the largest reference bench workload (bench.sh:27)
    — depth-bounded so the host oracle fits suite runtime.  Full scale
    runs in bench.py's reference-suite phase, golden-gated at 61,515,776
    unique states / depth 32 (device, 2026-07-31; depth-8 differential
    pinned 256,660 both engines)."""
    model = TwoPhaseSys(rm_count=10)
    host = model.checker().target_max_depth(7).spawn_bfs().join()
    tpu = (
        TwoPhaseSys(rm_count=10)
        .checker()
        .target_max_depth(7)
        .spawn_tpu(capacity=1 << 20, max_frontier=1 << 11, dedup_factor=1)
        .join()
    )
    assert host.unique_state_count() == tpu.unique_state_count()
    assert host.state_count() == tpu.state_count()
    assert tpu.max_depth() == host.max_depth() == 7
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


def test_auto_tune_dedup_growth_clamps_frontier():
    """Relaxing dedup_factor must keep the compact/dedup buffer inside the
    device-safe band by halving max_frontier: a 1.7M-lane buffer (2pc
    rm=10 at f=2^15, dd=1) crashes the TPU worker outright, while both
    neighboring 426K-lane configs run to graceful overflow flags
    (isolation matrix, 2026-07-31)."""
    from stateright_tpu.models.twophase import TwoPhaseSys
    from stateright_tpu.parallel.hashset import unique_buffer_size
    from stateright_tpu.parallel.wavefront import (
        TpuChecker, max_safe_unique_lanes,
    )

    ck = TpuChecker.__new__(TpuChecker)  # knob logic only; no run thread
    ck._compiled = TwoPhaseSys(rm_count=10).compiled()
    ck._capacity = 1 << 20
    ck._log_capacity = 1 << 20
    ck._log_capacity_explicit = False
    ck._dedup_factor = 4
    ck._max_frontier = 1 << 15
    # Default sort-rung state (the full buffer, tuner-owned) on the SORT
    # path: the flag-4 growth goes straight to the dd relax and lands on
    # a narrow rung, not a rung climb.
    ck._sortless = False
    ck._sort_lanes = None
    ck._sort_tune = True
    ck._step_lanes = None
    ck._sort_peak_valid = 0.0
    ck._dedup_rung_climbs = ck._dedup_fallbacks = 0
    ck._journal = None  # the relax tail re-journals geometry when set
    msg = ck._grow(4)
    assert ck._dedup_factor == 1
    assert "max_frontier" in msg
    assert "sort_lanes=" in msg
    assert (
        unique_buffer_size(
            ck._max_frontier * ck._compiled.max_actions, ck._dedup_factor
        )
        <= max_safe_unique_lanes(ck._compiled.state_width)
    )
    # A small model's buffer already fits: no frontier change.
    ck._compiled = TwoPhaseSys(rm_count=3).compiled()
    ck._dedup_factor = 4
    ck._max_frontier = 1 << 13
    msg = ck._grow(4)
    assert ck._dedup_factor == 1
    assert "max_frontier" not in msg


def test_grow_refuses_when_floor_frontier_still_over_budget():
    """max_actions > 256 cannot fit the safe band even at the floor
    frontier: _grow must refuse (None -> loud RuntimeError upstream), not
    proceed into the worker-crash band."""
    from stateright_tpu.parallel.wavefront import TpuChecker

    class WideCM:
        max_actions = 512
        state_width = 2

    ck = TpuChecker.__new__(TpuChecker)
    ck._compiled = WideCM()
    ck._capacity = 1 << 20
    ck._log_capacity = 1 << 20
    ck._log_capacity_explicit = False
    ck._dedup_factor = 4
    ck._max_frontier = 1 << 15
    ck._sortless = False  # sort path: no fallback move left either
    ck._sort_lanes = None  # full-buffer rung: nothing left to climb
    ck._step_lanes = None
    ck._sort_peak_valid = 0.0
    ck._journal = None
    assert ck._grow(4) is None


def test_spawn_clamps_crash_band_geometry():
    """A requested (max_frontier, dedup_factor) in the worker-crash band
    is clamped at spawn, not run as-is."""
    from stateright_tpu.models.twophase import TwoPhaseSys
    from stateright_tpu.parallel.hashset import unique_buffer_size
    from stateright_tpu.parallel.wavefront import max_safe_unique_lanes

    ck = (
        TwoPhaseSys(rm_count=10)
        .checker()
        .target_max_depth(1)
        .spawn_tpu(max_frontier=1 << 15, dedup_factor=1)
    )
    ck.join()
    assert ck._max_frontier < (1 << 15)
    assert (
        unique_buffer_size(
            ck._max_frontier * ck._compiled.max_actions, 1
        )
        <= max_safe_unique_lanes(ck._compiled.state_width)
    )


def test_table_growth_drags_log_x2_not_to_half_capacity():
    """The defaulted row log follows a table growth by ×2 (its own growth
    step), NOT straight to capacity/2: at 4·state_width bytes a position,
    a capacity/2 drag after the ×16 table jump can allocate gigabytes
    past what the run needs (w=77: observed as an HBM-pressure risk on
    `paxos check 6`)."""
    import bench
    from stateright_tpu.parallel.wavefront import TpuChecker

    ck = TpuChecker.__new__(TpuChecker)
    ck._compiled = bench.paxos_model(6).compiled()
    ck._capacity = 1 << 24
    ck._log_capacity = 1 << 23
    ck._log_capacity_explicit = False
    ck._dedup_factor = 4
    ck._max_frontier = 8192
    msg = ck._grow(1)
    assert ck._capacity == 1 << 28
    assert ck._log_capacity == 1 << 24, msg  # ×2 drag
