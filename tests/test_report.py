"""Histograms, the Prometheus exposition, and journal-derived reports
(obs/metrics.py, obs/prometheus.py, obs/report.py;
docs/OBSERVABILITY.md "Run reports").
"""

import json

import pytest

from stateright_tpu.obs.metrics import (
    COUNT_BUCKETS, Histogram, MetricsRegistry,
)
from stateright_tpu.obs.prometheus import (
    ExpositionError, parse_prometheus, render_prometheus,
)
from stateright_tpu.obs.report import (
    analyze_journal, bench_trajectory, render_markdown,
    render_trajectory_markdown, report_main,
)

# --- histograms --------------------------------------------------------------


def test_histogram_buckets_sum_count_and_quantiles():
    h = Histogram((1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count == 5
    assert h.counts == [1, 2, 1, 1]  # (..1], (1..2], (2..4], +Inf
    assert h.sum == pytest.approx(106.5)
    # p50 falls in the (1..2] bucket; p99 in the +Inf tail (reported at
    # its lower bound — never an invented upper bound).
    assert 1.0 <= h.quantile(0.5) <= 2.0
    assert h.quantile(0.99) == 4.0
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["p50"] <= snap["p95"] <= snap["p99"]


def test_histogram_weighted_observation_and_bad_boundaries():
    h = Histogram(COUNT_BUCKETS)
    h.observe(3, count=16)  # one fused quantum = 16 equal waves
    assert h.count == 16 and h.counts[2] == 16
    with pytest.raises(ValueError):
        Histogram((2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram(())


def test_registry_observe_creates_and_snapshots():
    reg = MetricsRegistry()
    reg.observe("lat", 0.1, boundaries=(0.05, 0.5))
    reg.observe("lat", 0.01)  # boundaries fixed at first use
    snap = reg.snapshot_histograms()
    assert snap["lat"]["count"] == 2
    assert snap["lat"]["boundaries"] == [0.05, 0.5]
    assert reg.snapshot() == {}  # histograms never leak into the flat view


# --- prometheus exposition ---------------------------------------------------


def test_render_prometheus_types_and_parse_roundtrip():
    metrics = {
        "engine": "tpu-wavefront",
        "done": True,
        "unique_state_count": 288,
        "table_load_factor": 0.017,
        "device_call_sec_total": 1.25,
        "jobs": {"queued": 0, "done": 2},
        "histograms": {
            "wave_latency_sec": Histogram((0.01, 0.1)).snapshot(),
        },
        "trace_summary": {"nested": {"too": "deep"}},  # skipped
    }
    metrics["histograms"]["wave_latency_sec"]["counts"] = [3, 1, 1]
    metrics["histograms"]["wave_latency_sec"]["count"] = 5
    metrics["histograms"]["wave_latency_sec"]["sum"] = 0.5
    text = render_prometheus(metrics)
    fams = parse_prometheus(text)
    assert fams["stateright_unique_state_count"]["type"] == "counter"
    assert fams["stateright_device_call_sec_total"]["type"] == "counter"
    assert fams["stateright_table_load_factor"]["type"] == "gauge"
    assert fams["stateright_done"]["samples"][0][2] == 1
    # dict-of-numbers -> one labeled gauge family
    jobs = {
        labels["key"]: v
        for _, labels, v in fams["stateright_jobs"]["samples"]
    }
    assert jobs == {"queued": 0, "done": 2}
    # histogram: cumulative buckets, +Inf == count
    lat = fams["stateright_wave_latency_sec"]
    buckets = [
        (labels["le"], v)
        for n, labels, v in lat["samples"] if n.endswith("_bucket")
    ]
    assert buckets[-1] == ("+Inf", 5)
    assert [v for _, v in buckets] == [3, 4, 5]
    # strings land as labels on the info metric, not as samples
    info = fams["stateright_info"]["samples"][0]
    assert info[1]["engine"] == "tpu-wavefront"
    assert "stateright_trace_summary" not in fams


def test_wants_prometheus_respects_accept_preference_order():
    from stateright_tpu.obs.prometheus import wants_prometheus

    # Explicit query param always wins.
    assert wants_prometheus({"format": "prometheus"}, "application/json")
    assert not wants_prometheus({"format": "json"}, "text/plain")
    # A scraper's Accept (text exposition first) selects Prometheus ...
    assert wants_prometheus(
        {}, "application/openmetrics-text;version=1.0.0,"
            "text/plain;version=0.0.4;q=0.5,*/*;q=0.1")
    assert wants_prometheus({}, "text/plain")
    # ... but a JSON client listing text/plain as a FALLBACK keeps JSON
    # (axios et al. send exactly this default).
    assert not wants_prometheus({}, "application/json, text/plain, */*")
    assert not wants_prometheus({}, "*/*")
    assert not wants_prometheus({}, None)


def test_parse_prometheus_rejects_malformed_expositions():
    with pytest.raises(ExpositionError):
        parse_prometheus("this is not a sample\n")
    with pytest.raises(ExpositionError):
        parse_prometheus("# TYPE x wibble\nx 1\n")
    with pytest.raises(ExpositionError):
        parse_prometheus("x notanumber\n")
    # histogram with non-cumulative buckets
    bad = (
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
        "h_sum 1\nh_count 3\n"
    )
    with pytest.raises(ExpositionError):
        parse_prometheus(bad)


# --- journal run reports -----------------------------------------------------


def _wave(t, waves, unique, depth, call_sec, **extra):
    return {
        "t": t, "event": "wave", "waves": waves, "unique": unique,
        "states": unique * 2, "depth": depth, "flags": 0,
        "call_sec": call_sec, "occupancy": unique / 4096,
        "remaining": 0, **extra,
    }


def test_run_report_untraced_supervised_journal():
    """A supervisor-shaped journal (waves + crash/restart/resume) yields
    phase breakdown, bottleneck_phase, a throughput curve, and the
    restart timeline."""
    events = [
        {"t": 100.0, "event": "supervisor_start", "run_dir": "x"},
        {"t": 100.1, "event": "run_start"},
        _wave(101.0, 8, 1000, 3, 0.8),
        _wave(102.0, 16, 2500, 5, 0.7),
        {"t": 102.5, "event": "crash", "rc": 137},
        {"t": 102.6, "event": "restart", "restarts": 1},
        {"t": 102.7, "event": "resume"},
        _wave(104.0, 24, 5000, 8, 0.9),
        {"t": 104.1, "event": "checkpoint", "path": "ck.npz"},
        {"t": 104.2, "event": "grow", "flags": 1, "grown": "capacity"},
        _wave(106.0, 32, 9000, 11, 1.1),
        {"t": 106.1, "event": "engine_done", "unique": 9000},
        {"t": 106.2, "event": "supervisor_done"},
    ]
    rep = analyze_journal(events)
    assert rep["kind"] == "run"
    assert rep["unique"] == 9000 and rep["waves"] == 4
    assert rep["grows"] == 1 and rep["checkpoints"] == 1
    assert rep["restarts"] == 1 and rep["faults"] == 1
    assert rep["phase_source"] == "untraced-device/host-split"
    assert set(rep["phase_breakdown"]) == {"device", "host"}
    assert rep["bottleneck_phase"] in ("device", "host")
    curve = rep["throughput_curve"]
    assert curve[-1]["unique"] == 9000
    assert all(pt["uniq_per_sec"] >= 0 for pt in curve)
    assert [e["event"] for e in rep["timeline"]].count("crash") == 1
    md = render_markdown(rep)
    assert "bottleneck" in md.lower() and "crash" in md
    json.dumps(rep)  # the --json form must serialize


def test_run_report_traced_journal_names_device_phase(tmp_path):
    """The report over a real traced journal (the tiered engine's
    phase-timed waves): the phase split is the traced one, the
    bottleneck a device phase (host phases excluded), and the summary
    the run's own."""
    import jax

    from stateright_tpu.models.twophase import TwoPhaseSys
    from stateright_tpu.obs.trace import HOST_PHASES
    from stateright_tpu.runtime.journal import read_journal

    journal = str(tmp_path / "journal.jsonl")
    ck = TwoPhaseSys(rm_count=3).checker().spawn_tpu_tiered(
        capacity=1 << 14, max_frontier=1 << 9,
        device=jax.devices("cpu")[0], trace=True, journal=journal,
    ).join()
    assert ck.unique_state_count() == 288
    rep = analyze_journal(read_journal(journal))
    assert rep["phase_source"] == "traced"
    assert rep["bottleneck_phase"] not in HOST_PHASES
    assert rep["bottleneck_phase"] in {"step", "canon", "dedup", "append"}
    assert rep["trace_summary"]["hbm_util_frac"] == (
        ck.trace_summary()["hbm_util_frac"]
    )


def test_service_journal_report_collects_job_spans():
    events = [
        {"t": 10.0, "event": "service_start", "workers": 1},
        {"t": 10.1, "event": "job_submitted", "job": "job-000001",
         "workload": "twophase", "engine": "tpu"},
        {"t": 10.2, "event": "job_running", "job": "job-000001"},
        {"t": 10.2, "event": "job_span", "job": "job-000001",
         "span": "queue_wait", "sec": 0.1},
        {"t": 12.0, "event": "job_done", "job": "job-000001"},
        {"t": 12.0, "event": "job_span", "job": "job-000001",
         "span": "run", "sec": 1.8},
        {"t": 12.0, "event": "job_span", "job": "job-000001",
         "span": "total", "sec": 1.9},
        {"t": 12.1, "event": "job_submitted", "job": "job-000002",
         "workload": "fixtures", "engine": "tpu"},
        {"t": 12.2, "event": "job_cancelled", "job": "job-000002"},
        {"t": 12.2, "event": "job_span", "job": "job-000002",
         "span": "total", "sec": 0.1},
    ]
    rep = analyze_journal(events)
    assert rep["kind"] == "service"
    jobs = rep["jobs"]
    assert jobs["count"] == 2
    assert jobs["by_state"] == {"done": 1, "cancelled": 1}
    assert jobs["detail"]["job-000001"]["spans"]["queue_wait"] == 0.1
    assert "queue_wait_p95_sec" in jobs
    md = render_markdown(rep)
    assert "job-000001" in md and "queue_wait" in md


# --- bench trajectory + regression flagging ----------------------------------


def _round(tmp_path, name, value, metric="paxos3_unique_states_per_sec",
           rc=0, **extra):
    parsed = (
        {"metric": metric, "value": value, "unit": "u/s",
         "vs_baseline": 1.0, **extra}
        if value is not None else {}
    )
    p = tmp_path / f"{name}.json"
    p.write_text(json.dumps({"rc": rc, "parsed": parsed}))
    return str(p)


def test_trajectory_flags_synthetic_degraded_round(tmp_path):
    paths = [
        _round(tmp_path, "BENCH_r01", 100_000.0),
        _round(tmp_path, "BENCH_r02", 250_000.0),
        _round(tmp_path, "BENCH_r03", 120_000.0),  # < 0.8 * best -> flag
        _round(tmp_path, "BENCH_r04", None, rc=1),  # partial: never flagged
        _round(tmp_path, "BENCH_r05", 260_000.0),
    ]
    traj = bench_trajectory(paths)
    assert [r["round"] for r in traj["rounds"]] == [
        "BENCH_r01", "BENCH_r02", "BENCH_r03", "BENCH_r04", "BENCH_r05",
    ]
    assert len(traj["regressions"]) == 1
    flag = traj["regressions"][0]
    assert flag["round"] == "BENCH_r03"
    assert flag["best_round"] == "BENCH_r02"
    assert flag["ratio"] == pytest.approx(0.48)
    md = render_trajectory_markdown(traj)
    assert "⚠" in md and "BENCH_r03" in md
    # A metric change (new headline workload) never cross-flags.
    paths.append(
        _round(tmp_path, "BENCH_r06", 10.0, metric="other_metric")
    )
    assert len(bench_trajectory(paths)["regressions"]) == 1


def test_trajectory_on_committed_rounds_is_clean():
    """The repo's real BENCH_r*.json history renders without error and
    carries no regression (the trajectory is monotone so far)."""
    import glob
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = sorted(glob.glob(os.path.join(repo, "BENCH_r*.json")))
    if not paths:
        pytest.skip("no BENCH rounds committed")
    traj = bench_trajectory(paths)
    assert len(traj["rounds"]) == len(paths)
    assert traj["regressions"] == []
    md = render_trajectory_markdown(traj)
    assert all(os.path.basename(p)[:-5] in md for p in paths)


# --- the report CLI verb -----------------------------------------------------


def test_report_main_on_journal_and_bench_glob(tmp_path, capsys):
    from stateright_tpu.runtime.journal import Journal

    jpath = str(tmp_path / "journal.jsonl")
    with Journal(jpath) as j:
        j.append("wave", waves=1, unique=10, depth=1, call_sec=0.1,
                 occupancy=0.01, remaining=0, states=20, flags=0)
        j.append("engine_done", unique=10)
    assert report_main([jpath]) == 0
    out = capsys.readouterr().out
    assert "Run report" in out and "bottleneck" in out.lower()

    _round(tmp_path, "BENCH_r01", 100.0)
    _round(tmp_path, "BENCH_r02", 10.0)
    md_out = tmp_path / "traj.md"
    assert report_main(
        [str(tmp_path / "BENCH_r*.json"), "--out", str(md_out)]
    ) == 0
    text = md_out.read_text()
    assert "BENCH_r02" in text and "⚠" in text

    # --json emits the dict; mixing journals and rounds is refused.
    capsys.readouterr()
    assert report_main([jpath, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["kind"] == "run" and rep["unique"] == 10
    assert report_main([jpath, str(tmp_path / "BENCH_r01.json")]) == 2
    assert report_main(["/nonexistent/path.jsonl"]) == 2
    assert report_main([]) == 2


def test_report_cli_verb_through_example_main(tmp_path, capsys):
    """`python -m stateright_tpu.models.<any> report <journal>` — the
    verb rides on every model CLI."""
    from stateright_tpu.cli import example_main
    from stateright_tpu.models.twophase import cli_spec

    jpath = str(tmp_path / "journal.jsonl")
    from stateright_tpu.runtime.journal import Journal

    with Journal(jpath) as j:
        j.append("wave", waves=1, unique=5, depth=1, call_sec=0.1,
                 occupancy=0.01, remaining=0, states=5, flags=0)
    rc = example_main(cli_spec(), ["report", jpath])
    assert rc == 0
    assert "Run report" in capsys.readouterr().out


# --- histogram edge cases (obs/metrics.Histogram) -----------------------------


def test_histogram_empty_percentile_readback():
    """An empty histogram reads back 0.0 quantiles (never a div-by-zero
    or an invented value) and a well-formed snapshot."""
    h = Histogram((1.0, 2.0))
    assert h.quantile(0.5) == 0.0 and h.quantile(0.99) == 0.0
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["sum"] == 0.0
    assert snap["p50"] == snap["p95"] == snap["p99"] == 0.0
    assert snap["counts"] == [0, 0, 0]


def test_histogram_single_boundary_ladder():
    """A one-boundary ladder: two buckets ((-inf..b], +Inf); quantiles
    interpolate from 0 inside the finite bucket and report the boundary
    for the +Inf tail."""
    h = Histogram((10.0,))
    h.observe(4.0, count=2)
    assert h.counts == [2, 0]
    assert 0.0 <= h.quantile(0.5) <= 10.0
    h.observe(100.0)  # lands in +Inf
    assert h.counts == [2, 1]
    assert h.quantile(0.99) == 10.0  # +Inf reports its lower bound


def test_histogram_weighted_observations_straddling_inf():
    """Weighted observations split across the last finite bucket and
    the +Inf tail: counts, sum, and quantiles stay consistent."""
    h = Histogram((1.0,))
    h.observe(0.5, count=3)
    h.observe(5.0, count=7)  # +Inf bucket, weighted
    assert h.count == 10
    assert h.counts == [3, 7]
    assert h.sum == pytest.approx(0.5 * 3 + 5.0 * 7)
    # rank(0.5)=5 falls inside +Inf -> its lower bound, the last
    # finite boundary.
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.2) == pytest.approx(1.0 * (2 / 3), abs=1e-9)
    snap = h.snapshot()
    assert snap["p50"] == 1.0 and snap["p99"] == 1.0


# --- labeled gauge families (per-shard series) --------------------------------


def test_render_and_parse_labeled_gauge_families():
    """Flat numeric dicts (the sharded engine's per-shard gauges) render
    as ONE labeled family and validate."""
    text = render_prometheus({
        "shard_unique": {"0": 10, "1": 12},
        "unique_skew_max_over_mean": 1.2,
    })
    fams = parse_prometheus(text)
    fam = fams["stateright_shard_unique"]
    assert fam["type"] == "gauge"
    assert sorted(
        (labels["key"], v) for _n, labels, v in fam["samples"]
    ) == [("0", 10.0), ("1", 12.0)]


def test_parse_prometheus_rejects_inconsistent_labeled_families():
    # Mixed label-name sets within one family.
    with pytest.raises(ExpositionError, match="mixes label sets"):
        parse_prometheus(
            "# TYPE g gauge\n"
            'g{key="0"} 1\n'
            'g{shard="1"} 2\n'
        )
    # Duplicate series (same name + label set twice).
    with pytest.raises(ExpositionError, match="repeats series"):
        parse_prometheus(
            "# TYPE g gauge\n"
            'g{key="0"} 1\n'
            'g{key="0"} 2\n'
        )


# --- torn journal tails -------------------------------------------------------


def test_report_tolerates_torn_final_journal_line(tmp_path):
    """A crashed writer's torn tail — both an undecodable fragment and
    a truncation that still parses as a bare JSON scalar — is skipped
    with a report warning, never an exception (and never an
    AttributeError on a non-dict event)."""
    jpath = tmp_path / "journal.jsonl"
    jpath.write_text(
        json.dumps(_wave(1.0, 1, 100, 1, 0.5)) + "\n"
        + json.dumps(_wave(2.0, 2, 250, 2, 0.5)) + "\n"
        + '{"t": 3.0, "event": "wa'  # killed mid-os.write
    )
    rep = analyze_journal(str(jpath))
    assert rep["waves"] == 2 and rep["unique"] == 250
    assert any("torn" in w for w in rep["warnings"])
    md = render_markdown(rep)
    assert "⚠" in md and "torn" in md

    # Truncation that still decodes — as a scalar, not an object.
    with open(jpath, "a") as fh:
        fh.write("\n17\n")
    rep = analyze_journal(str(jpath))
    assert rep["waves"] == 2
    assert any("2 torn" in w for w in rep["warnings"])


# --- geometry advisor ---------------------------------------------------------


def test_advisor_recommends_dedup_rung_from_measured_density():
    from stateright_tpu.obs.report import advise_geometry

    events = [
        {"t": 0.0, "event": "geometry", "engine": "tpu-wavefront",
         "capacity": 1 << 20, "log_capacity": 1 << 20,
         "max_frontier": 1 << 15, "dedup_factor": 8, "u_lanes": 425_984,
         "waves_per_call": 256},
    ]
    for i in range(8):
        events.append(_wave(
            float(i + 1), i + 1, 10_000 * (i + 1), i, 0.5,
            density=0.01 + 0.002 * i,  # peak 0.024
        ))
    adv = advise_geometry(events)
    assert adv["measured"]["peak_density"] == pytest.approx(0.024)
    # 1/(0.024*4) ~ 10.4x shrink -> dedup rung 8 -> 64 capped by the
    # doubling-within-shrink rule: 8*2=16 <= 8*10.4, 16*2=32 <= 83,
    # 32*2=64 <= 83 -> 64.
    assert adv["recommended"]["dedup_factor"] == 64
    assert adv["recommended"]["unique_buffer_lanes"] <= 425_984
    assert adv["recommended"]["max_frontier"] == 1 << 15
    assert adv["recommended"]["capacity"] >= 2 * 80_000

    # An observed dedup overflow overrides: recommend the proven rung.
    events.append({"t": 9.0, "event": "grow", "flags": 4,
                   "grown": "dedup_factor=1"})
    adv = advise_geometry(events)
    assert adv["recommended"]["dedup_factor"] == 1
    assert adv["notes"]


def test_advisor_bucket_slack_consistent_with_bench_r06_rung():
    """Acceptance pin: fed the measured paxos c=2 virtual-8 exchange
    occupancies (BENCH_r06.json, the PR-8 bucketed-exchange round), the
    advisor's recommended bucket_slack must equal the knob-cache rung
    that round measured and persisted."""
    import os

    from stateright_tpu.obs.report import advise_geometry

    r06_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_r06.json",
    )
    with open(r06_path) as fh:
        r06 = json.load(fh)["parsed"]["sharded_virtual8"]
    events = [
        {"t": 0.0, "event": "geometry", "engine": "tpu-sharded",
         "shards": 8, "capacity_per_shard": 1 << 14,
         "chunk_size": 1 << 11, "dedup_factor": 4,
         "bucket_slack": r06["bucket_slack"],
         "exchange_bucket_lanes": r06["exchange_bucket_lanes"],
         "u_lanes": 8 * 16384, "waves_per_call": 1},
    ]
    for i in range(int(r06["waves"])):
        # Per-wave occupancies around the round's measured mean, with a
        # 2x peak wave — the shape the traced journal actually has.
        occ = r06["exchange_occupancy"] * (2.0 if i == 3 else 1.0)
        events.append(_wave(
            float(i + 1), i + 1, 40_000 * (i + 1), i, 0.5,
            density=0.005, exchange_occupancy=occ,
        ))
    adv = advise_geometry(events)
    assert adv["recommended"]["bucket_slack"] == r06["bucket_slack"]
    assert adv["measured"]["peak_exchange_occupancy"] == pytest.approx(
        2 * r06["exchange_occupancy"]
    )


def test_advisor_bucket_slack_after_observed_overflow_ramp():
    from stateright_tpu.obs.report import advise_geometry

    events = [
        {"t": 0.0, "event": "geometry", "engine": "tpu-sharded",
         "shards": 4, "bucket_slack": 50, "dedup_factor": 4,
         "chunk_size": 2048, "u_lanes": 4 * 16384},
        {"t": 0.5, "event": "grow", "flags": 32,
         "grown": "bucket_slack=100"},
        {"t": 0.6, "event": "grow", "flags": 32,
         "grown": "bucket_slack=200"},
        _wave(1.0, 1, 1000, 1, 0.5, density=0.01,
              exchange_occupancy=0.4),
    ]
    adv = advise_geometry(events)
    assert adv["recommended"]["bucket_slack"] == 200
    assert any("climbed" in n for n in adv["notes"])


def test_advisor_lands_in_report_and_markdown():
    events = [
        {"t": 0.0, "event": "geometry", "engine": "tpu-wavefront",
         "capacity": 4096, "max_frontier": 512, "dedup_factor": 8,
         "u_lanes": 4096, "waves_per_call": 4},
        _wave(1.0, 4, 500, 2, 0.5, density=0.05),
        _wave(2.0, 8, 900, 4, 0.5, density=0.08),
    ]
    rep = analyze_journal(events)
    assert "advisor" in rep
    md = render_markdown(rep)
    assert "Geometry advisor" in md and "dedup_factor" in md
    json.dumps(rep)


# --- the watch verb -----------------------------------------------------------


def test_watch_summarize_run_journal():
    from stateright_tpu.obs.watch import render_line, summarize_events

    events = [
        {"t": 0.0, "event": "geometry", "engine": "tpu-wavefront",
         "u_lanes": 4096, "dedup_factor": 8},
        _wave(1.0, 4, 500, 2, 0.5, density=0.03),
        _wave(2.0, 8, 900, 4, 0.5, density=0.05),
        {"t": 2.1, "event": "engine_done", "unique": 900},
    ]
    s = summarize_events(events)
    assert s["unique"] == 900 and s["depth"] == 4
    assert s["density"] == 0.05
    assert s["uniq_per_sec"] == pytest.approx(400.0)
    assert s["done"] is True
    line = render_line(s)
    assert "density=0.05" in line and "bottleneck=" in line
    assert "done" in line


def test_watch_flags_recompile_storm_and_torn_lines():
    from stateright_tpu.obs.watch import render_line, summarize_events

    events = [_wave(1.0, 1, 100, 1, 0.5)]
    events += [
        {"t": 1.0 + i * 0.1, "event": "compile", "label": f"p{i}",
         "sec": 0.2}
        for i in range(6)  # >= COMPILE_STORM_THRESHOLD inside the window
    ]
    s = summarize_events(events, skipped=1)
    assert s["recompile_storm"] is True
    line = render_line(s)
    assert "recompile-storm" in line and "torn-lines=1" in line


def test_watch_surfaces_sort_rung_and_thrash_badge():
    """ISSUE-12 satellite: the current sort rung renders next to density
    (latest geometry event, advanced by later rung-climb grow notes),
    and ≥3 flag-4 rung retries inside the window raise the ⚠ badge."""
    from stateright_tpu.obs.watch import render_line, summarize_events

    events = [
        {"t": 0.0, "event": "geometry", "engine": "tpu-wavefront",
         "u_lanes": 16384, "sort_lanes": 16384},
        _wave(1.0, 2, 200, 1, 0.4, density=0.01),
        {"t": 1.5, "event": "geometry", "engine": "tpu-wavefront",
         "u_lanes": 16384, "sort_lanes": 2048},  # tuner downshift
        _wave(2.0, 4, 500, 2, 0.4, density=0.02),
    ]
    s = summarize_events(events)
    assert s["sort_rung"] == 2048
    assert "rung_thrash" not in s
    line = render_line(s)
    assert "sort_rung=2048" in line

    # Three rung-climb retries in the trailing window: the climbed rung
    # wins (it is LATER than the geometry event) and the badge fires.
    events += [
        {"t": 2.0 + i, "event": "grow", "flags": 4,
         "grown": f"sort_lanes={4096 << i}", "unique": 500, "depth": 2}
        for i in range(3)
    ]
    s = summarize_events(events)
    assert s["sort_rung"] == 16384  # 4096 -> 8192 -> 16384
    assert s["sort_rung_retries"] == 3
    assert s["rung_thrash"] is True
    assert "rung-thrash" in render_line(s)


def test_advisor_recommends_sort_rung():
    """The geometry advisor sizes the sort rung from measured peak
    density (4× headroom, pow2), and a mid-run rung climb overrides the
    derivation with the proven rung — the bucket_slack rules, applied
    to the second ladder."""
    geometry = {
        "t": 0.0, "event": "geometry", "engine": "tpu-wavefront",
        "capacity": 1 << 15, "max_frontier": 1 << 11, "dedup_factor": 8,
        "sort_lanes": 16384, "u_lanes": 16384, "waves_per_call": 4,
    }
    events = [
        geometry,
        _wave(1.0, 4, 500, 2, 0.5, density=0.02),
        _wave(2.0, 8, 900, 4, 0.5, density=0.05),
    ]
    rec = analyze_journal(events)["advisor"]["recommended"]
    # peak 0.05 * 16384 * 4x headroom = 3276.8 -> pow2 4096.
    assert rec["sort_lanes"] == 4096

    climbed = events + [
        {"t": 3.0, "event": "grow", "flags": 4, "grown": "sort_lanes=8192",
         "unique": 900, "depth": 4},
    ]
    adv = analyze_journal(climbed)["advisor"]
    assert adv["recommended"]["sort_lanes"] == 8192
    assert any("sort-rung overflow" in n for n in adv["notes"])


def test_watch_summarize_service_journal():
    from stateright_tpu.obs.watch import render_line, summarize_events

    events = [
        {"t": 0.0, "event": "service_start", "workers": 1},
        {"t": 0.1, "event": "job_submitted", "job": "job-1"},
        {"t": 0.2, "event": "job_running", "job": "job-1"},
        {"t": 0.3, "event": "job_submitted", "job": "job-2"},
        {"t": 1.0, "event": "job_done", "job": "job-1"},
    ]
    s = summarize_events(events)
    assert s["jobs"] == {"done": 1, "queued": 1}
    assert "jobs" in render_line(s)


def test_watch_once_cli_smoke(tmp_path, capsys):
    """`watch <journal> --once` through the model CLI: one greppable
    line with the density and bottleneck fields, rc 0; a missing
    journal is rc 2."""
    from stateright_tpu.cli import example_main
    from stateright_tpu.models.twophase import cli_spec
    from stateright_tpu.runtime.journal import Journal

    jpath = str(tmp_path / "journal.jsonl")
    with Journal(jpath) as j:
        j.append("geometry", engine="tpu-wavefront", u_lanes=4096)
        j.append("wave", waves=1, unique=5, depth=1, call_sec=0.1,
                 occupancy=0.01, remaining=0, states=10, flags=0,
                 density=0.002)
        j.append("engine_done", unique=5)
    rc = example_main(cli_spec(), ["watch", jpath, "--once"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "density=0.002" in out and "bottleneck=" in out
    assert example_main(
        cli_spec(), ["watch", str(tmp_path / "nope.jsonl"), "--once"]
    ) == 2
    assert example_main(cli_spec(), ["watch", "--once"]) == 2


# --- actor/chaos journals (ISSUE 15) -----------------------------------------


def _actor_journal(consistent=True):
    """A synthetic chaos-run journal: injections, ops, stats, spans,
    give-up, summary, audit."""
    return [
        {"t": 0.0, "event": "chaos_start", "seed": 7, "spec": {}},
        {"t": 0.1, "event": "actor_op", "kind": "invoke", "client": 3,
         "request_id": 1},
        {"t": 0.15, "event": "actor_span", "trace": "ab" * 8, "hop": 0,
         "src": 3, "dst": 0, "latency_sec": 0.001},
        {"t": 0.2, "event": "chaos_drop", "src": 0, "dst": 1, "n": 0},
        {"t": 0.3, "event": "chaos_duplicate", "src": 0, "dst": 1, "n": 1},
        {"t": 0.35, "event": "actor_span", "trace": "ab" * 8, "hop": 1,
         "src": 0, "dst": 3, "latency_sec": 0.002},
        {"t": 0.4, "event": "actor_op", "kind": "return", "client": 3,
         "request_id": 1},
        {"t": 0.5, "event": "orl_give_up", "actor": 1, "dropped": 1,
         "seqs": [4]},
        {"t": 0.6, "event": "actor_stats", "datagrams": 40, "invoked": 1,
         "returned": 1, "retransmits": 6, "give_ups": 1,
         "partition_active": False},
        # A fault after the op window: attribution must exclude it.
        {"t": 2.0, "event": "chaos_drop", "src": 1, "dst": 0, "n": 2},
        {"t": 2.1, "event": "chaos_summary", "seed": 7, "total": 3,
         "by_kind": {"chaos_drop": 2, "chaos_duplicate": 1},
         "links": {"0->1": {"chaos_drop": 1, "chaos_duplicate": 1},
                   "1->0": {"chaos_drop": 1}}},
        {"t": 2.2, "event": "audit", "consistent": consistent,
         "invoked": 1, "returned": 1, "in_flight": 0, "violations": [],
         "completed": True, "expected": 2, "seed": 7},
    ]


def test_actor_only_journal_degrades_without_bottleneck_phase():
    """ISSUE-15 satellite regression: an actor-only journal (no engine
    wave events) must not crash analyze_journal and must NOT emit a
    bogus bottleneck_phase — it degrades to the actor section with a
    warning."""
    report = analyze_journal(_actor_journal())
    assert report["kind"] == "actor"
    assert "bottleneck_phase" not in report
    assert "phase_breakdown" not in report
    assert any("actor-only" in w for w in report["warnings"])
    actor = report["actor"]
    assert actor["fault_total"] == 3
    assert actor["faults_by_kind"] == {"chaos_drop": 2, "chaos_duplicate": 1}
    assert actor["faults_by_link"] == {
        "0->1": {"chaos_drop": 1, "chaos_duplicate": 1},
        "1->0": {"chaos_drop": 1},
    }
    # ...and it equals the transport's own journaled summary.
    assert actor["faults_by_link"] == actor["chaos_summary"]["links"]
    assert actor["orl_give_ups"] == 1
    assert actor["spans"] == 2 and actor["max_hop"] == 1
    assert actor["audit"]["consistent"] is True
    assert "fault_attribution" not in actor  # consistent: no window
    md = render_markdown(report)
    assert "## Actor runtime" in md and "consistent" in md
    json.dumps(report, default=str)


def test_rejected_audit_attribution_windows_on_ops():
    """A rejected history: the attribution table counts only faults
    inside the audited operation window."""
    report = analyze_journal(_actor_journal(consistent=False))
    attribution = report["actor"]["fault_attribution"]
    # The t=2.0 drop falls outside the [0.1, 0.4] op window.
    assert attribution["fault_total"] == 2
    assert attribution["faults_by_link"] == {
        "0->1": {"chaos_drop": 1, "chaos_duplicate": 1},
    }
    assert attribution["window"]["ops"] == 2
    md = render_markdown(report)
    assert "Fault attribution" in md and "REJECTED" in md


def test_engine_journal_with_actor_events_keeps_run_kind():
    """A run journal that ALSO carries chaos events (a supervised run
    under a chaos-wrapped transport) keeps its run analysis — the actor
    section rides alongside, no degrade warning."""
    events = [
        {"t": 0.5, "event": "wave", "waves": 1, "unique": 100, "depth": 2,
         "call_sec": 0.1, "occupancy": 0.1, "remaining": 0},
        {"t": 0.6, "event": "chaos_drop", "src": 0, "dst": 1, "n": 0},
        {"t": 0.9, "event": "engine_done", "unique": 100},
    ]
    report = analyze_journal(events)
    assert report["kind"] == "run"
    assert "bottleneck_phase" in report
    assert report["actor"]["fault_total"] == 1
    assert not any(
        "actor-only" in w for w in report.get("warnings", [])
    )


def test_watch_renders_actor_journal_fields_and_badges():
    from stateright_tpu.obs.watch import render_line, summarize_events

    events = _actor_journal()
    s = summarize_events(events)
    assert s["datagrams"] == 40 and s["retransmits"] == 6
    assert s["chaos_faults"] == 3 and s["orl_give_ups"] == 1
    assert s["done"] is True  # the audit verdict ends a chaos run
    line = render_line(s)
    assert "retransmits=6" in line and "faults=3" in line
    assert "audit=ok" in line
    assert "orl-give-ups=1" in line

    # msgs/s EMA over consecutive actor_stats events.
    events2 = [e for e in events if e["event"] != "actor_stats"] + [
        {"t": 1.0, "event": "actor_stats", "datagrams": 0, "invoked": 0,
         "returned": 0, "retransmits": 0, "give_ups": 0,
         "partition_active": False},
        {"t": 2.0, "event": "actor_stats", "datagrams": 50, "invoked": 1,
         "returned": 1, "retransmits": 2, "give_ups": 0,
         "partition_active": True},
    ]
    s2 = summarize_events(events2)
    assert s2["msgs_per_sec"] == pytest.approx(50.0)
    assert s2["partition_active"] is True
    assert "partition-active" in render_line(s2)

    # An inconsistent audit raises the badge.
    s3 = summarize_events(_actor_journal(consistent=False))
    assert "audit-inconsistent" in s3["warnings"]
    assert "audit=INCONSISTENT" in render_line(s3)


# --- chaos-ensemble journals (ensemble/engine.py) ----------------------------


def _ensemble_journal():
    """A synthetic ensemble journal: sweep -> failing -> shrink ->
    replay (rejected) -> repro, with the replay's audit event riding
    along (as run_ensemble journals it)."""
    return [
        {"t": 0.0, "event": "ensemble_start", "members": 256, "seed": 3,
         "steps": 48, "workload": "abd", "fault": "skip_ack",
         "spec": {"default": {"drop": 0.1}}},
        {"t": 1.0, "event": "ensemble_failing", "member": 6, "seed": 999,
         "property": "linearizable", "step": 4},
        {"t": 1.1, "event": "ensemble_sweep", "members": 256, "failing": 1,
         "states": 2000, "elapsed_sec": 1.0, "schedules_per_sec": 256.0,
         "ttff_sec": 1.0},
        {"t": 1.5, "event": "ensemble_shrink", "member": 6,
         "candidate": "prefix", "steps": 5, "accepted": True},
        {"t": 1.6, "event": "ensemble_shrink", "member": 6,
         "candidate": "drop", "accepted": False},
        {"t": 2.0, "event": "audit", "consistent": False, "invoked": 4,
         "returned": 4, "in_flight": 0, "violations": [], "seed": 999,
         "fault_links": {"0->1": {"chaos_drop": 1}}},
        {"t": 2.1, "event": "ensemble_replay", "member": 6, "seed": 999,
         "consistent": False, "violations": 0},
        {"t": 2.2, "event": "ensemble_repro", "member": 6, "seed": 999,
         "spec": {"default": {"drop": 0.0}}, "steps": 5,
         "partition_at": -1, "partition_heal": -1, "workload": "abd",
         "fault": "skip_ack", "client_count": 2, "put_count": 1,
         "server_count": 2, "property": "linearizable", "base_seed": 3},
    ]


def test_report_renders_ensemble_journal_as_first_class_kind():
    report = analyze_journal(_ensemble_journal())
    assert report["kind"] == "ensemble"
    ens = report["ensemble"]
    assert ens["members"] == 256 and ens["failing"] == 1
    assert ens["schedules_per_sec"] == 256.0
    assert ens["shrink_accepted"] == 1 and ens["shrink_candidates"] == 2
    assert ens["replay"]["rejected"] is True
    assert ens["repro"]["seed"] == 999 and ens["repro"]["steps"] == 5
    assert ens["failing_seeds"][0]["member"] == 6
    # No actor-only degrade warning: the replay's events ride under the
    # ensemble kind.
    assert not any("actor-only" in w for w in report.get("warnings", []))
    md = render_markdown(report)
    assert "## Chaos ensemble" in md
    assert "failing seeds: **1**" in md
    assert "REJECTED" in md and "repro journaled" in md
    json.dumps(report, default=str)


def test_watch_renders_ensemble_journal_without_audit_warning():
    from stateright_tpu.obs.watch import render_line, summarize_events

    s = summarize_events(_ensemble_journal())
    assert s["ensemble_members"] == 256 and s["ensemble_failing"] == 1
    assert s["ensemble_shrinks"] == 2
    assert s["ensemble_shrinks_accepted"] == 1
    assert s["ensemble_repro"] is True and s["done"] is True
    # The rejected replay audit is the ensemble's SUCCESS, not a warning.
    assert "audit-inconsistent" not in s["warnings"]
    line = render_line(s)
    assert "members=256" in line and "failing=1" in line
    assert "shrinks=1/2" in line and "repro=journaled" in line
    assert "audit=INCONSISTENT" not in line
