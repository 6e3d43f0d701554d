"""The adaptive dedup-sort geometry ladder (parallel/wave_loop.py's
``sort_lanes`` rung): forced tiny-rung overflow-retry runs must land the
bit-identical discovery set on every engine (single-chip fused, sharded
at 1/2/4/8 virtual shards, tiered), the density tuner must downshift a
default run once it has evidence, and the tiered engine's traced byte
model must reflect the rung (``bytes.dedup`` is the regression gauge).

The fixed-geometry reference in every gate is ``sort_lanes`` pinned past
the full worst-case buffer — that clamps to today's pre-ladder geometry
and disarms the density tuner, so the comparison is rung-vs-no-rung on
otherwise identical programs."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.models.twophase import TwoPhaseSys  # noqa: E402
from stateright_tpu.parallel.wave_loop import SORT_RUNG_MIN  # noqa: E402
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
        sort_lanes=FULL,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    return ck.discovered_fingerprints()


def _rung_grows(journal):
    return [
        e for e in read_journal(journal)
        if e["event"] == "grow"
        and e.get("flags", 0) & 4
        and "sort_lanes=" in str(e.get("grown", ""))
    ]


def test_forced_tiny_rung_single_chip_fused_bit_identical(
    tmp_path, reference_fps
):
    """The acceptance gate: a run started at the smallest rung overflows,
    climbs the ladder (journaled grow, flags&4, no lost work), and still
    lands the exact fingerprint set of the fixed-geometry path."""
    journal = str(tmp_path / "rung.jsonl")
    ck = _model().checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
        sort_lanes=SORT_RUNG_MIN, journal=journal,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    grows = _rung_grows(journal)
    assert grows, "the tiny rung never overflowed — the forcing is dead"
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)
    # The discovered rung is surfaced everywhere the knob cache reads.
    m = ck.metrics()
    assert m["sort_lanes"] > SORT_RUNG_MIN
    assert ck.tuned_kwargs()["sort_lanes"] == m["sort_lanes"]


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_forced_tiny_rung_sharded_bit_identical(
    shards, tmp_path, reference_fps
):
    """Sharded meshes at every width: the rung shapes the pre-exchange
    sort, the owner bucketing, AND the exchange buckets — the fingerprint
    set must still be bit-identical to the single-chip fixed path."""
    journal = str(tmp_path / f"rung_sh{shards}.jsonl")
    ck = _model().checker().spawn_tpu_sharded(
        mesh=_mesh(shards), capacity=1 << 14, chunk_size=1 << 7,
        sort_lanes=SORT_RUNG_MIN, journal=journal,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)
    acc = ck.accounting()
    assert acc["sort_lanes"] >= SORT_RUNG_MIN
    if acc["sort_retries"]:
        assert _rung_grows(journal)


def test_forced_tiny_rung_tiered_bit_identical(tmp_path, reference_fps):
    """The tiered engine inherits the ladder through the shared loop:
    a budget that forces spills plus a tiny rung must still reproduce
    the unconstrained fixed-geometry set bit for bit."""
    ck = _model().checker().spawn_tpu_tiered(
        memory_budget_mb=0.01, max_frontier=1 << 6,
        sort_lanes=SORT_RUNG_MIN,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert ck.metrics()["spills"] >= 1
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)


def test_density_tuner_downshifts_default_run(tmp_path, reference_fps):
    """A DEFAULT run (no explicit rung) measures its density and
    downshifts below the worst-case buffer once it has evidence —
    journaling a fresh geometry event — without perturbing the
    discovery set.  waves_per_call=1 gives the tuner per-wave quanta."""
    journal = str(tmp_path / "tuner.jsonl")
    # mf=2^11 puts 2pc(4) at the 16K buffer floor with ~9% peak density
    # — a measured at-least-halving downshift exists (mf=2^9's ~13%
    # density correctly does NOT downshift; the tuner must be able to
    # say "leave it alone" too, pinned in the explicit-rung test).
    ck = _model().checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 11, device=_cpu(),
        waves_per_call=1, journal=journal,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    full_u = ck._wl_cand_lanes()
    assert ck.metrics()["sort_lanes"] < full_u, (
        "the density tuner never downshifted a few-percent-density run"
    )
    geoms = [
        e for e in read_journal(journal) if e["event"] == "geometry"
    ]
    assert len(geoms) >= 2  # loop start + at least one retune
    assert geoms[-1]["sort_lanes"] < geoms[0]["sort_lanes"]
    assert np.array_equal(ck.discovered_fingerprints(), reference_fps)


def test_explicit_rung_disarms_tuner(tmp_path):
    """An explicit sort_lanes is a warm start: the tuner must not move
    it (the knob-cache contract — a warm run reproduces the cached
    program keys instead of re-adapting)."""
    rung = 1 << 11
    ck = _model().checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
        waves_per_call=1, sort_lanes=rung,
    ).join()
    assert ck.unique_state_count() == GOLDEN
    assert ck.metrics()["sort_lanes"] == min(rung, ck._wl_cand_lanes())


def test_traced_bytes_dedup_reflect_rung(tmp_path):
    """The modeled ``bytes.dedup`` of the tiered engine's phase-timed
    trace must drop with the rung.  Byte totals are deterministic modulo
    probe rounds, so strict inequality is safe."""
    def spawn(sort_lanes):
        return _model().checker().spawn_tpu_tiered(
            capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
            trace=True, sort_lanes=sort_lanes,
        ).join()

    full = spawn(FULL)
    slim = spawn(1 << 10)
    assert full.unique_state_count() == slim.unique_state_count() == GOLDEN
    b_full = full.trace_summary()["bytes"]["dedup"]
    b_slim = slim.trace_summary()["bytes"]["dedup"]
    assert b_slim < b_full, (b_slim, b_full)
    assert np.array_equal(
        full.discovered_fingerprints(), slim.discovered_fingerprints()
    )


def test_sharded_snapshot_persists_rung(tmp_path):
    """The discovered rung rides sharded snapshots like bucket_slack:
    a resume adopts it instead of re-paying the ramp."""
    snap = str(tmp_path / "rung.npz")
    bounded = _model().checker().target_state_count(400).spawn_tpu_sharded(
        mesh=_mesh(4), capacity=1 << 14, chunk_size=1 << 6,
        sort_lanes=SORT_RUNG_MIN,
    ).join()
    bounded.save_snapshot(snap)
    rung_at_save = bounded.metrics()["sort_lanes"]
    resumed = _model().checker().spawn_tpu_sharded(
        mesh=_mesh(4), capacity=1 << 14, chunk_size=1 << 6,
        resume_from=snap,
    ).join()
    assert resumed.unique_state_count() == GOLDEN
    assert resumed.metrics()["sort_lanes"] >= rung_at_save
