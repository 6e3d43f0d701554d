"""The sortless claim-plane dedup + frontier-sized step (ISSUE 14).

Fingerprint-bit-identity matrix: the SORTLESS default (claim-plane
representative election, hashset.insert_batch_claim) and the
``step_lanes`` chunk rung must land the exact discovery set of the
sorted fixed-geometry path on every engine — single-chip fused,
sharded at 1/2/4/8 virtual shards, tiered under forced
eviction, symmetry through the golden orbit count — including
forced-overflow runs: a tiny forced step rung climbs via the
non-committing flag 128, and a sortless run forced onto a tiny
compaction buffer FALLS BACK to the sort-rung path mid-run
(``grow sortless=0``) with no lost work, and a duplicate-dense default
run that overflows the full buffer lands on a narrow sort rung.

The reference in every gate is ``sortless=False`` with ``sort_lanes``
pinned past the full buffer — the PR 12 fixed-geometry sort path."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.models.twophase import TwoPhaseSys  # noqa: E402
from stateright_tpu.parallel.wave_loop import (  # noqa: E402
    SORT_RUNG_MIN, STEP_RUNG_MIN,
)
from stateright_tpu.runtime.journal import read_journal  # noqa: E402

RM = 4
GOLDEN = 1568
FULL = 1 << 30  # clamps to the full buffer = the fixed-geometry path


def _cpu():
    return jax.devices("cpu")[0]


def _mesh(n):
    return jax.sharding.Mesh(np.array(jax.devices("cpu")[:n]), ("shards",))


def _model():
    return TwoPhaseSys(rm_count=RM)


@pytest.fixture(scope="module")
def reference_fps():
    ck = _model().checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
        sortless=False, sort_lanes=FULL,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    return ck.discovered_fingerprints()


def _grows(journal, needle):
    return [
        e for e in read_journal(journal)
        if e["event"] == "grow" and needle in str(e.get("grown", ""))
    ]


def test_sortless_is_the_default_and_fused_bit_identical(
    tmp_path, reference_fps
):
    journal = str(tmp_path / "sortless.jsonl")
    ck = _model().checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
        journal=journal,
    ).join()
    m = ck.metrics()
    assert m["sortless"] is True  # the default path
    assert ck.unique_state_count() == GOLDEN
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)
    # The geometry journal event carries the dedup path + step rung.
    geoms = [
        e for e in read_journal(journal) if e["event"] == "geometry"
    ]
    assert geoms and geoms[0]["sortless"] is True
    assert geoms[0]["step_lanes"] == 1 << 9
    # The knob cache remembers the (un-fallen-back) path.
    assert ck.tuned_kwargs()["sortless"] == 1


def test_dedup_stage_rounds_in_metrics():
    # 2pc 5 at the default geometry: every wave's valid lanes fit the
    # 16,384-lane rung, so the main stage hands off at once and that rung
    # does the first rounds.  A call per wave counts the same rounds as
    # the default call of many waves.
    from stateright_tpu.parallel.hashset import STRAGGLER_LADDER

    readings = []
    for waves_per_call in (1, None):
        ck = TwoPhaseSys(rm_count=5).checker().spawn_tpu(
            device=_cpu(), waves_per_call=waves_per_call,
        ).join()
        assert ck.unique_state_count() == 8832
        m = ck.metrics()
        rounds = m["dedup_stage_rounds"]
        assert rounds[1 << 14] > 0
        assert set(STRAGGLER_LADDER) <= set(rounds)
        assert m["dedup_probe_lanes_total"] == sum(
            w * n for w, n in rounds.items()
        )
        readings.append(rounds)
    assert readings[0] == readings[1]


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_sortless_sharded_bit_identical(shards, tmp_path, reference_fps):
    ck = _model().checker().spawn_tpu_sharded(
        mesh=_mesh(shards), capacity=1 << 14, chunk_size=1 << 7,
        journal=str(tmp_path / f"sh{shards}.jsonl"),
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)
    acc = ck.accounting()
    assert acc["sortless"] == 1


def test_sortless_tiered_forced_eviction_bit_identical(reference_fps):
    ck = _model().checker().spawn_tpu_tiered(
        memory_budget_mb=0.01, max_frontier=1 << 6,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert ck.metrics()["spills"] >= 1
    assert ck.metrics()["sortless"] is True
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)


def test_sortless_symmetry_golden_166():
    # The Ip & Dill perfect-canonicalization sort stays where symmetry
    # needs it; dedup on the canonical fingerprints is claim-elected.
    model = TwoPhaseSys(rm_count=4)
    sym = model.checker().symmetry().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
    ).join()
    ref = model.checker().symmetry().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
        sortless=False, sort_lanes=FULL,
    ).join()
    assert sym.unique_state_count() == 166
    assert ref.unique_state_count() == 166
    assert np.array_equal(
        sym.discovered_fingerprints(), ref.discovered_fingerprints()
    )


def test_forced_fallback_to_sort_rung_mid_run(tmp_path, reference_fps):
    """sortless=True + a tiny sort_lanes caps the claim compaction
    buffer (the forcing knob): the first overflowing wave raises the
    non-committing flag 4, the engine FALLS BACK to the sort-rung path
    (grow note ``sortless=0``), the sort ladder takes over — and the
    discovery set stays bit-identical."""
    journal = str(tmp_path / "fallback.jsonl")
    ck = _model().checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
        sortless=True, sort_lanes=SORT_RUNG_MIN, journal=journal,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)
    assert _grows(journal, "sortless=0"), "fallback never fired"
    m = ck.metrics()
    assert m["sortless"] is False  # flipped mid-run
    # The knob cache persists the per-workload selection.
    assert ck.tuned_kwargs()["sortless"] == 0
    # A geometry event re-journaled at the flip carries the new path.
    geoms = [
        e for e in read_journal(journal) if e["event"] == "geometry"
    ]
    assert any(g.get("sortless") is False for g in geoms)


@pytest.fixture(scope="module")
def dense_run(tmp_path_factory):
    """A default-knob 2pc 6 check from the spec's kwargs: its chunks
    (one per level) carry up to 60,802 valid lanes, past the 32,768-lane
    dd=8 compaction buffer, so flag 4 fires at the full buffer."""
    journal = str(tmp_path_factory.mktemp("dense") / "dense.jsonl")
    ck = TwoPhaseSys(rm_count=6).checker().spawn_tpu(
        capacity=1 << 20, max_frontier=1 << 13, device=_cpu(),
        journal=journal,
    ).join()
    return ck, journal


def test_full_buffer_overflow_lands_on_narrow_sort_rung(dense_run):
    """One flag-4 move relaxes dedup_factor, falls back to the sort path
    and lands on the next rung above the overflowed width (65,536 lanes,
    not the 262,144-lane relaxed batch); the set is bit-identical to the
    sort path at full width."""
    ck, journal = dense_run
    ref = TwoPhaseSys(rm_count=6).checker().spawn_tpu(
        capacity=1 << 20, max_frontier=1 << 13, device=_cpu(),
        dedup_factor=1, sortless=False, sort_lanes=FULL,
    ).join()
    assert ref.metrics()["sort_lanes"] == 1 << 18
    assert np.array_equal(
        ck.discovered_fingerprints(), ref.discovered_fingerprints()
    )
    m = ck.metrics()
    assert m["sortless"] is False
    assert m["dedup_factor"] == 1
    assert m["sort_lanes"] == 1 << 16
    assert m["dedup_fallbacks"] == 1
    assert m["dedup_rung_climbs"] >= 1
    flag4 = [
        e for e in read_journal(journal)
        if e["event"] == "grow" and e["flags"] & 4
    ]
    assert len(flag4) == 1
    assert flag4[0]["grown"] == "dedup_factor=1; sortless=0; sort_lanes=65536"


def test_landed_rung_warm_start_skips_the_overflow(dense_run, tmp_path):
    ck, _ = dense_run
    journal = str(tmp_path / "warm.jsonl")
    warm = TwoPhaseSys(rm_count=6).checker().spawn_tpu(
        device=_cpu(), journal=journal, **ck.tuned_kwargs(),
    ).join()
    assert not [
        e for e in read_journal(journal)
        if e["event"] == "grow" and e["flags"] & 4
    ]
    m = warm.metrics()
    assert m["sortless"] is False
    assert m["sort_lanes"] == 1 << 16
    assert np.array_equal(
        warm.discovered_fingerprints(), ck.discovered_fingerprints()
    )


def test_forced_tiny_step_rung_climbs_and_bit_identical(
    tmp_path, reference_fps
):
    """A forced tiny step rung clamps (flag 128, nothing commits), the
    host climbs one rung at a time, and the set is bit-identical; the
    discovered rung rides metrics()/tuned_kwargs like the sort rung."""
    journal = str(tmp_path / "step.jsonl")
    ck = _model().checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
        step_lanes=STEP_RUNG_MIN, journal=journal,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)
    climbs = _grows(journal, "step_lanes=")
    assert climbs and all(e["flags"] & 128 for e in climbs)
    m = ck.metrics()
    assert m["step_lanes"] > STEP_RUNG_MIN
    assert ck.tuned_kwargs()["step_lanes"] == m["step_lanes"]


def test_forced_tiny_step_rung_sharded(tmp_path, reference_fps):
    journal = str(tmp_path / "step_sh.jsonl")
    ck = _model().checker().spawn_tpu_sharded(
        mesh=_mesh(2), capacity=1 << 14, chunk_size=1 << 9,
        step_lanes=STEP_RUNG_MIN, journal=journal,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)
    acc = ck.accounting()
    if acc["step_retries"]:
        assert _grows(journal, "step_lanes=")


def test_forced_tiny_step_rung_tiered(reference_fps):
    ck = _model().checker().spawn_tpu_tiered(
        memory_budget_mb=0.01, max_frontier=1 << 9,
        step_lanes=STEP_RUNG_MIN,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert ck.metrics()["spills"] >= 1
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)


def test_sharded_snapshot_persists_path_and_step_rung(tmp_path):
    """A sharded snapshot carries the dedup path and step rung (the
    bucket_slack pattern): a resumed run adopts them instead of
    re-paying the fallback/climb ramps."""
    snap = str(tmp_path / "snap.npz")
    ck = _model().checker().target_state_count(400).spawn_tpu_sharded(
        mesh=_mesh(2), capacity=1 << 14, chunk_size=1 << 7,
        step_lanes=STEP_RUNG_MIN,
    ).join()
    ck.save_snapshot(snap)
    resumed = _model().checker().spawn_tpu_sharded(
        mesh=_mesh(2), capacity=1 << 14, chunk_size=1 << 7,
        resume_from=snap,
    ).join()
    assert resumed.unique_state_count() == GOLDEN
    m = resumed.metrics()
    assert m["sortless"] is True
    assert m["step_lanes_rung"] >= STEP_RUNG_MIN  # adopted, tuner off
