"""Observability subsystem: trace-off invariance, traced-run parity,
the device visitor stream, roofline units, and engine metrics.

The two contracts that matter most (ISSUE 4 acceptance):

- trace=False is the UNCHANGED fused path — golden counts and discovery
  sets identical to pre-change, and no additional per-wave device syncs
  (pinned via the journal: each host-loop iteration writes exactly one
  ``wave`` event, so an untraced run of a ≤256-wave model has exactly
  one);
- trace=True produces identical results (same kernels, same commit
  order) plus per-wave phase breakdowns whose seconds partition the
  measured wave time.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.models.fixtures import TrapCounter  # noqa: E402
from stateright_tpu.models.twophase import TwoPhaseSys  # noqa: E402
from stateright_tpu.obs.roofline import (  # noqa: E402
    hbm_util_frac,
    peaks_for_device,
    probe_bytes,
    sort_bytes,
    sort_passes,
)
from stateright_tpu.obs.trace import WaveTracer  # noqa: E402
from stateright_tpu.runtime.journal import read_journal  # noqa: E402


def _cpu():
    return jax.devices("cpu")[0]


# --- roofline units -----------------------------------------------------------


def test_peaks_for_device_known_cpu_and_unknown():
    class FakeV5e:
        device_kind = "TPU v5 lite"
        platform = "tpu"

    class FakeUnknownTpu:
        device_kind = "TPU v99"
        platform = "tpu"

    p = peaks_for_device(FakeV5e())
    assert p["hbm_bytes_per_sec"] == 8.19e11
    assert p["estimated"] is False

    p = peaks_for_device(_cpu())
    assert p["estimated"] is True  # the CPU row never masquerades
    assert p["hbm_bytes_per_sec"] > 0

    # A device missing from the table is an error, not a default.
    with pytest.raises(KeyError, match="TPU v99"):
        peaks_for_device(FakeUnknownTpu())


def test_byte_model_sanity():
    assert sort_passes(1) == 0
    assert sort_passes(2) == 1
    # 2^14 lanes: k=14 -> 105 passes; monotone in lanes.
    assert sort_passes(1 << 14) == 14 * 15 // 2
    assert sort_bytes(1 << 14, 3) == 2 * 105 * 3 * (1 << 14) * 4
    assert probe_bytes(100, 0) == 0
    assert probe_bytes(100, 2) == 6 * 2 * 100 * 4
    assert hbm_util_frac(0, 1.0, 1e9) == 0.0
    assert hbm_util_frac(1e9, 0.0, 1e9) == 0.0  # degenerate -> 0, not inf
    assert hbm_util_frac(5e8, 1.0, 1e9) == 0.5


def test_wave_tracer_totals_and_journal_enrichment():
    tracer = WaveTracer(_cpu(), "test-engine")
    rec = tracer.record_wave(
        {"step": 0.25, "dedup": 0.5, "readback": 0.25},
        {"step": 100_000_000, "dedup": 900_000_000},
        probe_rounds=3,
    )
    assert rec["wave_breakdown"] == {
        "step": 0.25, "dedup": 0.5, "readback": 0.25,
    }
    assert rec["bytes"] == {"step": 100_000_000, "dedup": 900_000_000}
    # 1 GB over 0.75 device seconds (readback excluded).
    peak = tracer.peaks["hbm_bytes_per_sec"]
    assert rec["hbm_util_frac"] == pytest.approx(
        1e9 / (0.75 * peak), rel=1e-3
    )
    tracer.record_wave({"step": 0.75}, {"step": 900_000_000})
    s = tracer.summary()
    assert s["traced_waves"] == 2
    assert s["wave_breakdown"]["step"] == pytest.approx(1.0)
    assert s["bytes"]["step"] == 1_000_000_000
    assert s["probe_rounds"] == 3
    # Fractions sum to ~1 over the recorded phases.
    assert sum(s["wave_breakdown_frac"].values()) == pytest.approx(
        1.0, abs=0.01
    )


# --- trace-off invariance -----------------------------------------------------


def test_trace_off_golden_and_no_per_wave_syncs(tmp_path):
    """trace=False: golden count unchanged AND exactly one host sync per
    waves_per_call quantum (2pc(3) finishes inside one 256-wave call, so
    the journal must hold exactly ONE wave event — a per-wave sync would
    write eleven)."""
    journal = str(tmp_path / "journal.jsonl")
    tpu = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu(
            capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
            journal=journal,
        )
        .join()
    )
    assert tpu.unique_state_count() == 288
    waves = [e for e in read_journal(journal) if e["event"] == "wave"]
    assert len(waves) == 1
    assert "wave_breakdown" not in waves[0]  # untraced records stay lean
    m = tpu.metrics()
    assert m["trace"] is False
    assert m["unique_state_count"] == 288
    assert m["device_calls"] == 1


# --- traced single-chip parity ------------------------------------------------


def test_traced_run_matches_host_and_breakdown_covers_wave_time(tmp_path):
    model = TwoPhaseSys(rm_count=3)
    host = model.checker().spawn_bfs().join()
    journal = str(tmp_path / "journal.jsonl")
    tpu = (
        model.checker()
        .spawn_tpu(
            capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
            trace=True, journal=journal,
        )
        .join()
    )
    assert tpu.unique_state_count() == host.unique_state_count() == 288
    assert tpu.state_count() == host.state_count()
    assert tpu.max_depth() == host.max_depth()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())

    s = tpu.trace_summary()
    assert s["traced_waves"] >= tpu.max_depth()  # >= one wave per level
    assert set(s["wave_breakdown"]) == {
        "step", "canon", "dedup", "append", "readback",
    }
    assert s["hbm_util_frac"] > 0
    assert s["bytes"]["dedup"] > 0

    # Per-wave records: the phase seconds partition call_sec (>= 90% is
    # the acceptance bar; the timers partition it exactly).
    waves = [e for e in read_journal(journal) if e["event"] == "wave"]
    assert len(waves) == s["traced_waves"]
    for w in waves:
        assert sum(w["wave_breakdown"].values()) >= 0.9 * w["call_sec"]
        assert 0 <= w["hbm_util_frac"]
    assert [e for e in read_journal(journal)
            if e["event"] == "trace_summary"]

    # The metrics surface carries the summary.
    assert tpu.metrics()["trace_summary"]["traced_waves"] == len(waves)


def test_traced_two_phase_model_matches_host():
    """paxos is the two-phase (step_valid/step_lane) compiled model: the
    traced step phase constructs successors on the compacted valid lanes
    — parity with the host oracle on the 265-state c=1 space."""
    from tests.test_paxos_compiled import paxos_model

    model = paxos_model(client_count=1)
    host = model.checker().spawn_bfs().join()
    tpu = (
        model.checker()
        .spawn_tpu(
            capacity=1 << 12, max_frontier=1 << 6, device=_cpu(),
            trace=True,
        )
        .join()
    )
    assert tpu.unique_state_count() == host.unique_state_count()
    assert tpu.state_count() == host.state_count()
    assert tpu.max_depth() == host.max_depth()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())
    assert tpu.trace_summary()["traced_waves"] >= 1


def test_traced_eventually_discoveries_match_host():
    model = TrapCounter()
    host = model.checker().spawn_bfs().join()
    tpu = (
        model.checker()
        .spawn_tpu(
            capacity=1 << 10, max_frontier=1 << 4, device=_cpu(),
            trace=True,
        )
        .join()
    )
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())
    assert tpu.discoveries()["reaches limit"].last_state() == model.trap_state


def test_traced_run_auto_grows_like_fused(tmp_path):
    """A traced run with a far-undersized table (and a visitor — the
    path that forces tracing on default-knob runs) grows in place and
    completes, exactly like the fused loop; a grow event lands in the
    journal; auto_tune=False still fails loudly."""
    from stateright_tpu.core.visitor import StateRecorder

    model = TwoPhaseSys(rm_count=3)
    journal = str(tmp_path / "journal.jsonl")
    recorder, accessor = StateRecorder.new_with_accessor()
    tpu = (
        model.checker()
        .visitor(recorder)
        .spawn_tpu(
            capacity=1 << 8, max_frontier=1 << 9, device=_cpu(),
            journal=journal,
        )
        .join()
    )
    assert tpu.unique_state_count() == 288
    assert len(accessor()) == 288  # re-run chunks never double-visit
    evs = read_journal(journal)
    assert any(e["event"] == "grow" for e in evs)

    with pytest.raises(RuntimeError, match="table overfull"):
        model.checker().spawn_tpu(
            capacity=1 << 8, max_frontier=1 << 9, device=_cpu(),
            trace=True, auto_tune=False,
        ).join()


def test_trace_rejects_resume(tmp_path):
    with pytest.raises(ValueError, match="resume_from"):
        TwoPhaseSys(rm_count=3).checker().spawn_tpu(
            trace=True, resume_from=str(tmp_path / "x.npz")
        )


# --- the device visitor stream ------------------------------------------------


def test_visitor_stream_coarse_wave_granularity():
    """The spawn_tpu visitor contract (docs/OBSERVABILITY.md): every
    unique state visited exactly once, at expansion, in BFS level order
    across waves (within a level the order is fingerprint-sorted, not
    insertion order — the coarse part of the contract)."""
    from stateright_tpu.core.visitor import StateRecorder

    model = TrapCounter()
    recorder, accessor = StateRecorder.new_with_accessor()
    tpu = (
        model.checker()
        .visitor(recorder)  # forces trace on — no rejection anymore
        .spawn_tpu(capacity=1 << 10, max_frontier=1 << 4, device=_cpu())
        .join()
    )
    assert tpu.metrics()["trace"] is True

    host_rec, host_acc = StateRecorder.new_with_accessor()
    host = model.checker().visitor(host_rec).spawn_bfs().join()

    got, want = accessor(), host_acc()
    assert len(got) == len(set(got))  # each unique state exactly once
    assert set(got) == set(want)
    assert len(got) == host.unique_state_count()

    # BFS level order: group the host's visit order into depth levels,
    # then check the device order equals the host order up to in-level
    # permutation.
    depth_of = {0: 0}
    for s in want:
        if s == 0:
            continue
        preds = [
            p for p in want
            if s in {
                p + 1 if p < model.limit else None,
                model.trap_state if p == model.trap_at else None,
            }
        ]
        depth_of[s] = min(depth_of[p] for p in preds) + 1
    got_depths = [depth_of[s] for s in got]
    assert got_depths == sorted(got_depths)  # level-monotone stream
    for d in set(got_depths):
        assert {s for s in got if depth_of[s] == d} == {
            s for s in want if depth_of[s] == d
        }


def test_visitor_single_state_paths():
    """Visited paths are single-state (no action prefix) — the documented
    coarse contract; last_state() is the visited state."""
    seen = []
    (
        TrapCounter()
        .checker()
        .visitor(lambda path: seen.append((len(path), path.last_state())))
        .spawn_tpu(capacity=1 << 10, max_frontier=1 << 4, device=_cpu())
        .join()
    )
    assert seen and all(n == 1 for n, _s in seen)


# --- traced sharded engine ----------------------------------------------------


def _mesh(n):
    devices = jax.devices("cpu")
    assert len(devices) >= n
    return jax.sharding.Mesh(np.array(devices[:n]), ("shards",))


def test_traced_sharded_parity_and_measured_exchange(tmp_path):
    model = TwoPhaseSys(rm_count=3)
    host = model.checker().spawn_bfs().join()
    journal = str(tmp_path / "journal.jsonl")
    sh = (
        model.checker()
        .spawn_tpu_sharded(
            mesh=_mesh(4), capacity=1 << 14, chunk_size=1 << 8,
            trace=True, journal=journal,
        )
        .join()
    )
    assert sh.unique_state_count() == host.unique_state_count() == 288
    assert sh.state_count() == host.state_count()
    assert sorted(sh.discoveries()) == sorted(host.discoveries())

    s = sh.trace_summary()
    assert set(s["wave_breakdown"]) == {
        "step", "canon", "dedup", "exchange", "append", "readback",
    }
    # Measured per-wave exchange instrumentation in the journal.
    waves = [e for e in read_journal(journal) if e["event"] == "wave"]
    assert waves
    for w in waves:
        assert "exchange_payload_bytes" in w
        assert 0.0 <= w["exchange_occupancy"] <= 1.0
    assert sum(w["exchange_payload_bytes"] for w in waves) == (
        s["exchange_payload_bytes"]
    )
    # Totals agree with the run accounting (same counters).
    acc = sh.accounting()
    assert acc["exchange_payload_bytes_total"] == s["exchange_payload_bytes"]
    assert 0.0 < acc["exchange_occupancy"] <= 1.0


def test_traced_sharded_one_shard_elides_exchange():
    model = TwoPhaseSys(rm_count=3)
    sh = (
        model.checker()
        .spawn_tpu_sharded(
            mesh=_mesh(1), capacity=1 << 14, chunk_size=1 << 8, trace=True,
        )
        .join()
    )
    assert sh.unique_state_count() == 288
    s = sh.trace_summary()
    assert s["bytes"]["exchange"] == 0
    assert s["exchange_payload_bytes"] == 0
    assert sh.accounting()["exchange_elided"] is True


# --- metrics surface ----------------------------------------------------------


def test_host_engine_base_metrics():
    m = TwoPhaseSys(rm_count=3).checker().spawn_bfs().join().metrics()
    assert m["unique_state_count"] == 288
    assert m["done"] is True


def test_sharded_metrics_include_accounting():
    sh = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu_sharded(
            mesh=_mesh(2), capacity=1 << 14, chunk_size=1 << 8,
        )
        .join()
    )
    m = sh.metrics()
    assert m["engine"] == "tpu-sharded"
    assert m["shards"] == 2
    assert m["accounting"]["waves"] >= 1
    assert "exchange_occupancy" in m["accounting"]


# --- density telemetry + compile observability (ISSUE 11) ---------------------


def test_untraced_run_reports_density_and_geometry_event(tmp_path):
    """trace=False (fused program pinned, no extra syncs — the golden
    test above stays green): metrics() still carries the density EMA +
    histogram and the load-factor trajectory, and the journal gains one
    ``geometry`` event plus a per-quantum ``density`` field."""
    journal = str(tmp_path / "journal.jsonl")
    tpu = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu(
            capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
            journal=journal,
        )
        .join()
    )
    assert tpu.unique_state_count() == 288
    m = tpu.metrics()
    assert 0 < m["valid_density_ema"] <= 1.0
    dh = m["histograms"]["valid_density"]
    assert dh["count"] > 0
    lf = m["histograms"]["load_factor"]
    assert lf["count"] > 0
    evs = read_journal(journal)
    geo = [e for e in evs if e["event"] == "geometry"]
    assert len(geo) == 1
    assert geo[0]["engine"] == "tpu-wavefront"
    assert geo[0]["u_lanes"] > 0 and geo[0]["dedup_factor"] == 8
    waves = [e for e in evs if e["event"] == "wave"]
    assert len(waves) == 1  # the no-extra-syncs pin, restated
    assert 0 < waves[0]["density"] <= 1.0


def test_traced_run_journals_density_per_wave(tmp_path):
    journal = str(tmp_path / "journal.jsonl")
    tpu = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu(
            capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
            trace=True, journal=journal,
        )
        .join()
    )
    assert tpu.unique_state_count() == 288
    waves = [e for e in read_journal(journal) if e["event"] == "wave"]
    assert waves and all(0 <= w["density"] <= 1.0 for w in waves)
    assert any(e["event"] == "geometry"
               for e in read_journal(journal))


def test_sharded_per_shard_gauges_and_skew(tmp_path):
    """The fused sharded loop exports per-shard frontier/insert/
    exchange gauges with a max/mean skew — derived from the stats
    readback it already holds (no extra syncs) — and the Prometheus
    exposition renders them as labeled families that validate."""
    from stateright_tpu.obs.prometheus import (
        parse_prometheus, render_prometheus,
    )

    journal = str(tmp_path / "journal.jsonl")
    sh = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu_sharded(
            mesh=_mesh(4), capacity=1 << 14, chunk_size=1 << 8,
            journal=journal,
        )
        .join()
    )
    assert sh.unique_state_count() == 288
    m = sh.metrics()
    for fam in ("shard_frontier", "shard_unique", "shard_exchange_bytes"):
        assert set(m[fam]) == {"0", "1", "2", "3"}, fam
    assert sum(m["shard_unique"].values()) == 288
    for skew in ("frontier_skew_max_over_mean", "unique_skew_max_over_mean",
                 "exchange_skew_max_over_mean"):
        assert m[skew] >= 1.0, skew
    # Hash ownership balances statically: skew stays near 1 on a
    # non-adversarial model.
    assert m["unique_skew_max_over_mean"] < 2.0
    assert 0 < m["valid_density_ema"] <= 1.0
    fams = parse_prometheus(render_prometheus(m))
    per_shard = fams["stateright_shard_unique"]
    assert {labels["key"] for _n, labels, _v in per_shard["samples"]} == {
        "0", "1", "2", "3",
    }
    geo = [e for e in read_journal(journal) if e["event"] == "geometry"]
    assert geo and geo[0]["shards"] == 4 and geo[0]["bucket_slack"] == 50


def test_compile_events_carry_label_provenance_and_timing(tmp_path):
    """A program-cache miss journals a ``compile`` event per built XLA
    program (first-call timed) with the key provenance, and the
    process-global compile metrics move."""
    from stateright_tpu.obs.metrics import GLOBAL

    journal = str(tmp_path / "journal.jsonl")
    before = float(GLOBAL.get("compile_sec_total", 0.0))
    tpu = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu(
            capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
            journal=journal, waves_per_call=5,  # unusual key: forced miss
        )
        .join()
    )
    assert tpu.unique_state_count() == 288
    compiles = [
        e for e in read_journal(journal) if e["event"] == "compile"
    ]
    assert len(compiles) >= 2  # the (seed, run) pair at least
    labels = {c["label"] for c in compiles}
    assert any(lb.startswith("TpuChecker.fused") for lb in labels)
    for c in compiles:
        assert c["sec"] >= 0
        assert c["provenance"]["waves_per_call"] == 5
        assert c["provenance"]["capacity"] == 1 << 14
    m = tpu.metrics()
    assert m["compile_sec_total"] >= before
    assert isinstance(m["recompile_storms"], int)


def test_recompile_storm_detector_rising_edge():
    """The storm detector fires once at the quiet->storm edge, not per
    compile, and resets when the window drains."""
    from stateright_tpu.parallel import wave_common as wc

    saved = (list(wc._COMPILE_TIMES), wc._STORM_ACTIVE[0])
    wc._COMPILE_TIMES.clear()
    wc._STORM_ACTIVE[0] = False
    try:
        t0 = 1000.0
        edges = [
            wc._note_compile(t0 + i) for i in range(wc.COMPILE_STORM_THRESHOLD)
        ]
        assert edges.count(True) == 1 and edges[-1] is True
        assert wc._note_compile(t0 + 10) is False  # still in storm: no edge
        # Window drains -> quiet -> a new burst fires a new edge.
        far = t0 + wc.COMPILE_STORM_WINDOW_SEC + 100
        assert wc._note_compile(far) is False
        for i in range(wc.COMPILE_STORM_THRESHOLD - 2):
            assert wc._note_compile(far + 1 + i) is False
        assert wc._note_compile(far + 50) is True
    finally:
        wc._COMPILE_TIMES.clear()
        wc._COMPILE_TIMES.extend(saved[0])
        wc._STORM_ACTIVE[0] = saved[1]
