"""Observability subsystem: the fused loop's no-extra-sync pin, the
tiered engine's phase-timed trace, the device visitor stream, roofline
units, and engine metrics.

The contracts that matter most:

- the fused path issues no additional per-wave device syncs (pinned via
  the journal: each host-loop iteration writes exactly one ``wave``
  event, so a run of a ≤256-wave model has exactly one);
- ``spawn_tpu_tiered(trace=True)`` produces identical results plus
  per-wave phase breakdowns whose seconds partition the measured wave
  time;
- a visitor on ``spawn_tpu`` sees every unique state exactly once, at
  expansion, in BFS level order, whatever the call granularity.
"""

import functools

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


# --- the fused loop's sync cadence ---------------------------------------------


def test_trace_off_golden_and_no_per_wave_syncs(tmp_path):
    """Golden count unchanged AND exactly one host sync per
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
    assert "wave_breakdown" not in waves[0]  # fused records stay lean
    m = tpu.metrics()
    assert m["unique_state_count"] == 288
    assert m["device_calls"] == 1


# --- host parity; the tiered engine's phase-timed trace -----------------------


def test_traced_run_matches_host_and_breakdown_covers_wave_time(tmp_path):
    """The tiered engine's phase-timed trace (its waves ARE separately
    dispatched phases): results match the host, and the per-wave phase
    seconds partition the measured wave time."""
    model = TwoPhaseSys(rm_count=3)
    host = model.checker().spawn_bfs().join()
    journal = str(tmp_path / "journal.jsonl")
    tpu = (
        model.checker()
        .spawn_tpu_tiered(
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
        "step", "canon", "dedup", "cold_probe", "append", "readback",
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
    assert tpu.metrics()["trace"] is True
    assert tpu.metrics()["trace_summary"]["traced_waves"] == len(waves)


def test_traced_two_phase_model_matches_host():
    """paxos is the two-phase (step_valid/step_lane) compiled model: the
    fused step constructs successors on the compacted valid lanes —
    parity with the host oracle on the 265-state c=1 space."""
    from tests.test_paxos_compiled import paxos_model

    model = paxos_model(client_count=1)
    host = model.checker().spawn_bfs().join()
    tpu = (
        model.checker()
        .spawn_tpu(
            capacity=1 << 12, max_frontier=1 << 6, device=_cpu(),
        )
        .join()
    )
    assert tpu.unique_state_count() == host.unique_state_count()
    assert tpu.state_count() == host.state_count()
    assert tpu.max_depth() == host.max_depth()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


def test_traced_eventually_discoveries_match_host():
    model = TrapCounter()
    host = model.checker().spawn_bfs().join()
    tpu = (
        model.checker()
        .spawn_tpu(
            capacity=1 << 10, max_frontier=1 << 4, device=_cpu(),
        )
        .join()
    )
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())
    assert tpu.discoveries()["reaches limit"].last_state() == model.trap_state


def test_traced_run_auto_grows_like_fused(tmp_path):
    """A visitor on the fused loop across a table growth AND a row-log
    growth (both non-committing re-runs of the flagged wave) visits the
    288 states exactly once; auto_tune=False still fails loudly."""
    from stateright_tpu.core.visitor import StateRecorder

    model = TwoPhaseSys(rm_count=3)
    journal = str(tmp_path / "journal.jsonl")
    recorder, accessor = StateRecorder.new_with_accessor()
    tpu = (
        model.checker()
        .visitor(recorder)
        .spawn_tpu(
            capacity=1 << 8, log_capacity=1 << 6, max_frontier=1 << 5,
            device=_cpu(), journal=journal,
        )
        .join()
    )
    assert tpu.unique_state_count() == 288
    got = accessor()
    assert len(got) == 288 and len(set(got)) == 288  # no double visits
    grows = [e for e in read_journal(journal) if e["event"] == "grow"]
    assert any(e["flags"] & 1 for e in grows), grows
    assert any(e["flags"] & 2 for e in grows), grows

    with pytest.raises(RuntimeError, match="table overfull"):
        model.checker().visitor(recorder).spawn_tpu(
            capacity=1 << 8, max_frontier=1 << 9, device=_cpu(),
            auto_tune=False,
        ).join()


# --- the device visitor stream ------------------------------------------------


def test_visitor_stream_coarse_wave_granularity():
    """The spawn_tpu visitor contract (docs/OBSERVABILITY.md): every
    unique state visited exactly once, at expansion, in BFS level order
    (within a level the order is the row log's, not the host's — the
    coarse part of the contract)."""
    from stateright_tpu.core.visitor import StateRecorder

    model = TrapCounter()
    recorder, accessor = StateRecorder.new_with_accessor()
    tpu = (
        model.checker()
        .visitor(recorder)
        .spawn_tpu(capacity=1 << 10, max_frontier=1 << 4, device=_cpu())
        .join()
    )

    host_rec, host_acc = StateRecorder.new_with_accessor()
    host = model.checker().visitor(host_rec).spawn_bfs().join()

    got, want = accessor(), host_acc()
    assert len(got) == len(set(got))  # each unique state exactly once
    assert set(got) == set(want)
    assert len(got) == host.unique_state_count() == tpu.unique_state_count()

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


def _fp(state):
    from stateright_tpu.ops.fingerprint import fingerprint

    return fingerprint(state)


def _host_levels(model):
    """Plain host BFS: the reachable states as one fingerprint set per
    depth level."""
    frontier = [s for s in model.init_states() if model.within_boundary(s)]
    seen = {_fp(s) for s in frontier}
    levels = []
    while frontier:
        levels.append({_fp(s) for s in frontier})
        nxt = []
        for s in frontier:
            for t in model.next_states(s):
                fp = _fp(t)
                if model.within_boundary(t) and fp not in seen:
                    seen.add(fp)
                    nxt.append(t)
        frontier = nxt
    return levels


@functools.lru_cache(maxsize=None)
def _twophase_levels(rm_count):
    return _host_levels(TwoPhaseSys(rm_count=rm_count))


def _visit(builder, **kwargs):
    """Spawn ``builder`` on the fused loop with a recording visitor;
    returns the checker and the visited states in visit order."""
    seen = []
    ck = builder.visitor(
        lambda path: seen.append(path.last_state())
    ).spawn_tpu(device=_cpu(), **kwargs).join()
    return ck, seen


def _depth_of(levels):
    return {fp: d for d, level in enumerate(levels) for fp in level}


def _assert_level_prefix(visited, levels, complete=None):
    """``visited`` is a BFS prefix: each state once, depths never
    decreasing, the first ``complete`` levels (default: all) exactly,
    and any further visits a part of the next level only."""
    complete = len(levels) if complete is None else complete
    fps = [_fp(s) for s in visited]
    assert len(fps) == len(set(fps))
    depth_of = _depth_of(levels)
    depths = [depth_of[fp] for fp in fps]
    assert depths == sorted(depths)
    for d in range(complete):
        assert {fp for fp in fps if depth_of[fp] == d} == levels[d], d
    assert all(d <= complete for d in depths)


def _case_twophase5_levels(tmp_path):
    # 2pc 5 against the host's per-level sets: every level, exactly.
    model = TwoPhaseSys(rm_count=5)
    ck, visited = _visit(model.checker())
    levels = _twophase_levels(5)
    assert ck.unique_state_count() == len(visited) == 8832
    _assert_level_prefix(visited, levels)


def _case_paxos2_log_order(tmp_path):
    # paxos with 2 clients (16,668 states): every logged row visited
    # once, in the row log's order, levels never interleaved (depths
    # from the log's own parent chain).
    from tests.test_paxos_compiled import paxos_model

    ck, visited = _visit(paxos_model(client_count=2).checker())
    assert ck.unique_state_count() == len(visited) == 16_668
    cm = ck._compiled
    tail = int(ck._carry_dev["tail"])
    rows = np.asarray(ck._carry_dev["rows"])[: tail * cm.state_width]
    assert [cm.decode(r) for r in rows.reshape(tail, -1)] == visited
    parent = np.asarray(ck._carry_dev["parent"])[:tail]
    depth = np.zeros(tail, np.int64)
    for pos in range(tail):
        if parent[pos] != 0xFFFFFFFF:
            depth[pos] = depth[parent[pos]] + 1
    assert (np.diff(depth) >= 0).all()


def _case_finish_when_mid_level(tmp_path):
    # finish_when stops inside a level: the expanded prefix is visited,
    # the discovery's state among it, and nothing past the stop.
    from stateright_tpu.core.has_discoveries import HasDiscoveries

    model = TwoPhaseSys(rm_count=4)
    ck, visited = _visit(
        model.checker().finish_when(HasDiscoveries.ANY),
        max_frontier=1 << 4, waves_per_call=1,
    )
    levels = _twophase_levels(4)
    found = ck.discoveries()
    assert found
    assert all(p.last_state() in visited for p in found.values())
    assert len(visited) < ck.unique_state_count() < 1568
    last = _depth_of(levels)[_fp(visited[-1])]
    _assert_level_prefix(visited, levels, complete=last)


def _case_target_max_depth(tmp_path):
    # target_max_depth(6) counts level 5 but expands (visits) levels
    # 0..4 only, like the host reference.
    model = TwoPhaseSys(rm_count=5)
    ck, visited = _visit(model.checker().target_max_depth(6))
    levels = _twophase_levels(5)
    assert len(visited) == sum(len(level) for level in levels[:5])
    _assert_level_prefix(visited, levels, complete=5)


def _case_waves_per_call_1_vs_default(tmp_path):
    # The call granularity changes when visits arrive, never which
    # states or in what order.
    model = TwoPhaseSys(rm_count=4)
    _ck1, one = _visit(model.checker(), max_frontier=1 << 5,
                       waves_per_call=1)
    _ckd, default = _visit(model.checker(), max_frontier=1 << 5)
    assert len(one) == 1568
    assert one == default
    _assert_level_prefix(one, _twophase_levels(4))


def _case_symmetry(tmp_path):
    # Symmetry: one visit per canonical class (the 166 orbits of 2pc 4),
    # each the logged original, in level order.
    from stateright_tpu.parallel.wave_loop import fingerprints_of_rows

    model = TwoPhaseSys(rm_count=4)
    ck, visited = _visit(model.checker().symmetry())
    cm = ck._compiled
    assert ck.unique_state_count() == len(visited) == 166
    rows = np.stack([cm.encode(s) for s in visited]).astype(np.uint32)
    assert np.array_equal(
        fingerprints_of_rows(cm, rows, ck._canon),
        ck.discovered_fingerprints(),
    )
    depth_of = _depth_of(_twophase_levels(4))
    depths = [depth_of[_fp(s)] for s in visited]
    assert depths == sorted(depths)


def _case_resume(tmp_path):
    # A bounded run and its resume visit the space once between them.
    model = TwoPhaseSys(rm_count=4)
    snap = str(tmp_path / "snap.npz")
    first, head = _visit(
        model.checker().target_state_count(2000), max_frontier=1 << 5,
        waves_per_call=1,
    )
    first.save_snapshot(snap)
    _rest, tail = _visit(
        model.checker(), max_frontier=1 << 5, resume_from=snap,
    )
    assert 0 < len(head) < 1568
    assert len(head) + len(tail) == 1568
    _assert_level_prefix(head + tail, _twophase_levels(4))


@pytest.mark.parametrize("case", [
    _case_twophase5_levels, _case_paxos2_log_order,
    _case_finish_when_mid_level, _case_target_max_depth,
    _case_waves_per_call_1_vs_default, _case_symmetry, _case_resume,
], ids=lambda case: case.__name__[len("_case_"):])
def test_fused_visitor_stream(case, tmp_path):
    """The visitor runs on the fused loop: every unique state exactly
    once, at expansion, in BFS level order — across call granularities,
    early stops, depth bounds, symmetry, and a resume."""
    case(tmp_path)


# --- sharded engine -------------------------------------------------------------


def _mesh(n):
    devices = jax.devices("cpu")
    assert len(devices) >= n
    return jax.sharding.Mesh(np.array(devices[:n]), ("shards",))


def test_traced_sharded_one_shard_elides_exchange():
    """A 1-shard mesh compiles the exchange out: the accounting reports
    it elided, with no payload."""
    model = TwoPhaseSys(rm_count=3)
    sh = (
        model.checker()
        .spawn_tpu_sharded(
            mesh=_mesh(1), capacity=1 << 14, chunk_size=1 << 8,
        )
        .join()
    )
    assert sh.unique_state_count() == 288
    acc = sh.accounting()
    assert acc["exchange_elided"] is True
    assert acc["exchange_payload_bytes_total"] == 0
    assert acc["all_to_all_bytes_total"] == 0


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
