"""Journal-derived run reports and the cross-round bench trajectory.

The JSONL journal (runtime/journal.py) is already the durable record of
every run and of the checking service; this module turns it into
answers instead of raw lines:

- :func:`analyze_journal` reduces a run (or service) journal into one
  report dict: phase breakdown + ``bottleneck_phase``, the throughput
  curve (unique states over time), the restart/fault timeline the
  supervisor wrote, grow/spill/checkpoint counts, and — for service
  journals — per-job lifecycles with their span durations
  (``job_span`` events, serve/scheduler.py);
- :func:`bench_trajectory` reads the ``BENCH_r*.json`` round artifacts
  into a per-round table (uniq/s, vs_baseline, ttfv, occupancy) with
  **threshold-based regression flagging** against the best earlier
  round of the same metric — the cross-round story that previously
  lived in seven disconnected files;
- :func:`render_markdown` / :func:`render_trajectory_markdown` emit the
  human-readable form of a journal report and of the ``BENCH_r*.json``
  trajectory;
- :func:`report_main` backs the ``report`` CLI verb (cli.py):
  ``python -m stateright_tpu.models.<any> report <journal|bench-glob>``.

Phase semantics: a traced journal's ``wave_breakdown`` gives the real
per-phase split (obs/trace.py names); an untraced journal still splits
**device** (sum of ``call_sec``) from **host** (wall span between wave
events minus device time), so ``bottleneck_phase`` is always present —
coarse on untraced runs, exact on traced ones.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence

from .trace import HOST_PHASES, PHASE_ORDER

# A later round whose headline rate drops below this fraction of the
# best earlier round (same metric) is flagged as a regression.
REGRESSION_THRESHOLD = 0.8

# Events that mark supervisor/fault activity, in the timeline.
_TIMELINE_EVENTS = (
    "supervisor_start", "run_start", "resume", "crash", "hang", "relax",
    "restart", "wall_timeout", "give_up", "child_error", "run_end",
    "supervisor_done", "chaos_partition", "orl_give_up",
)

_CURVE_POINTS = 24  # throughput-curve downsample budget


def _read_events(path: str):
    from ..runtime.journal import read_journal_stats

    return read_journal_stats(path)


def analyze_journal(events_or_path) -> dict:
    """Reduce a journal (path or pre-parsed event list) to a report
    dict; see the module docstring for the shape.  A torn/partially-
    written trailing line (a crashed writer) is skipped and surfaced as
    a ``warnings`` entry, never an exception."""
    skipped = 0
    if isinstance(events_or_path, (str, os.PathLike)):
        events, skipped = _read_events(events_or_path)
    else:
        events = list(events_or_path)
    report: dict = {
        "events": len(events),
        "kind": "empty",
    }
    if skipped:
        report["warnings"] = [
            f"skipped {skipped} torn/garbled journal line(s) — "
            "a writer likely died mid-append; counts below cover the "
            "intact prefix"
        ]
    if not events:
        return report
    times = [e["t"] for e in events if isinstance(e.get("t"), (int, float))]
    t0, t1 = (min(times), max(times)) if times else (0.0, 0.0)
    report["duration_sec"] = round(t1 - t0, 3)

    waves = [e for e in events if e.get("event") == "wave"]
    jobs = {
        e.get("job") for e in events
        if str(e.get("event", "")).startswith("job_") and e.get("job")
    }
    report["kind"] = (
        "service" if jobs and not waves
        else "service+run" if jobs
        else "run" if waves
        else "other"
    )

    if waves:
        report.update(_run_section(events, waves, t0))
        advisor = advise_geometry(events)
        if advisor is not None:
            report["advisor"] = advisor
    if jobs:
        report["jobs"] = _jobs_section(events)
    fleet = _fleet_section(events)
    if fleet is not None:
        report["fleet"] = fleet
        if not waves and not jobs:
            # A fleet journal is its own run kind: job lifecycle lives
            # in fleet_* events, engine waves in the workers' own logs.
            report["kind"] = "fleet"
    incr = _incremental_section(events)
    if incr is not None:
        report["incremental"] = incr
    ensemble = _ensemble_section(events)
    if ensemble is not None:
        report["ensemble"] = ensemble
    actor = _actor_section(events, t0)
    if actor is not None:
        report["actor"] = actor
        if not waves and not jobs and ensemble is None:
            # An actor/chaos journal carries no engine wave events:
            # degrade to the actor section — no phase breakdown and,
            # critically, no fabricated ``bottleneck_phase``.
            report["kind"] = "actor"
            report.setdefault("warnings", []).append(
                "actor-only journal (no engine wave events): phase/"
                "bottleneck analysis skipped; see the Actor runtime "
                "section"
            )
    if ensemble is not None and not waves and not jobs:
        # An ensemble journal is a first-class run kind — the replay's
        # actor events ride under it, they don't degrade it to a warning.
        report["kind"] = "ensemble"
    timeline = [
        {"t": round(e["t"] - t0, 3), "event": e["event"],
         **{k: e[k] for k in ("reason", "restarts", "overrides", "rc")
            if k in e}}
        for e in events
        if e.get("event") in _TIMELINE_EVENTS
        and isinstance(e.get("t"), (int, float))
    ]
    report["timeline"] = timeline
    report["restarts"] = sum(
        1 for e in timeline if e["event"] == "restart"
    )
    report["faults"] = sum(
        1 for e in timeline if e["event"] in ("crash", "hang")
    )
    return report


def _run_section(events: List[dict], waves: List[dict], t0: float) -> dict:
    out: dict = {"waves": len(waves)}
    last = waves[-1]
    out["unique"] = last.get("unique")
    out["depth"] = last.get("depth")
    out["max_occupancy"] = max(
        (w.get("occupancy", 0.0) for w in waves), default=0.0
    )
    out["grows"] = sum(1 for e in events if e.get("event") == "grow")
    out["spills"] = sum(1 for e in events if e.get("event") == "spill")
    out["checkpoints"] = sum(
        1 for e in events if e.get("event") == "checkpoint"
    )

    # Phase breakdown: exact from traced wave_breakdown records when
    # present, else the coarse device/host split every journal affords.
    phases: Dict[str, float] = {}
    traced = [w for w in waves if isinstance(w.get("wave_breakdown"), dict)]
    if traced:
        for w in traced:
            for name, sec in w["wave_breakdown"].items():
                phases[name] = phases.get(name, 0.0) + float(sec)
        out["phase_source"] = "traced"
    else:
        device = sum(float(w.get("call_sec", 0.0)) for w in waves)
        wall = (
            waves[-1]["t"] - waves[0]["t"]
            if len(waves) > 1 and all("t" in w for w in (waves[0], waves[-1]))
            else device
        )
        phases = {"device": device, "host": max(0.0, wall - device)}
        out["phase_source"] = "untraced-device/host-split"
    out["phase_breakdown"] = {
        k: round(v, 4)
        for k, v in sorted(phases.items(), key=lambda kv: -kv[1])
    }
    candidates = {
        k: v for k, v in phases.items() if k not in HOST_PHASES
    } or phases
    out["bottleneck_phase"] = max(candidates, key=candidates.get)

    # Host-tail decomposition (obs/timeline.py host_span events): the
    # named sub-phases of the fused loop's per-quantum host work, and
    # host_share = host / (host + device) over the whole run.  Traced
    # journals carry no host_span records but their wave_breakdown is
    # the same split, so host_share is derivable either way.
    from .timeline import host_tail_sums

    tail = host_tail_sums(events)
    if tail:
        out["host_tail_breakdown"] = {
            k: round(v, 4)
            for k, v in sorted(tail.items(), key=lambda kv: -kv[1])
        }
        host = sum(tail.values())
        device = sum(float(w.get("call_sec", 0.0)) for w in waves)
        if host + device > 0:
            out["host_share"] = round(host / (host + device), 4)
    elif traced and sum(phases.values()) > 0:
        host = sum(v for k, v in phases.items() if k in HOST_PHASES)
        out["host_share"] = round(host / sum(phases.values()), 4)

    # Throughput curve: (t, unique) at each wave event, downsampled;
    # the uniq/s column is the rate over the preceding segment.
    pts = [
        (w["t"] - t0, int(w["unique"]))
        for w in waves
        if isinstance(w.get("t"), (int, float)) and "unique" in w
    ]
    if pts:
        stride = max(1, len(pts) // _CURVE_POINTS)
        sampled = pts[::stride]
        if sampled[-1] != pts[-1]:
            sampled.append(pts[-1])
        curve = []
        prev_t, prev_u = 0.0, 0
        for t, u in sampled:
            dt = t - prev_t
            curve.append({
                "t": round(t, 3),
                "unique": u,
                "uniq_per_sec": round((u - prev_u) / dt, 1) if dt > 0 else 0.0,
            })
            prev_t, prev_u = t, u
        out["throughput_curve"] = curve
    summaries = [e for e in events if e.get("event") == "trace_summary"]
    if summaries:
        out["trace_summary"] = {
            k: v for k, v in summaries[-1].items()
            if k not in ("t", "event")
        }
    return out


def _jobs_section(events: List[dict]) -> dict:
    jobs: Dict[str, dict] = {}
    for e in events:
        ev = str(e.get("event", ""))
        jid = e.get("job")
        if not jid or not ev.startswith("job_"):
            continue
        j = jobs.setdefault(jid, {"spans": {}})
        if e.get("worker"):
            # Every lifecycle event is worker-stamped (serve/jobs.py
            # ``_log``); keep the latest — it names who RAN the job,
            # not just who accepted it.
            j["worker"] = e.get("worker")
        if ev == "job_span":
            j["spans"][e.get("span")] = e.get("sec")
        elif ev == "job_submitted":
            j["workload"] = e.get("workload")
            j["engine"] = e.get("engine")
        elif ev in ("job_done", "job_failed", "job_cancelled"):
            j["state"] = ev[len("job_"):]
            if e.get("violation") is not None:
                j["violation"] = e.get("violation")
    states: Dict[str, int] = {}
    for j in jobs.values():
        states[j.get("state", "running")] = (
            states.get(j.get("state", "running"), 0) + 1
        )
    queue_waits = sorted(
        j["spans"]["queue_wait"] for j in jobs.values()
        if isinstance(j["spans"].get("queue_wait"), (int, float))
    )
    out = {"count": len(jobs), "by_state": states, "detail": jobs}
    if queue_waits:
        out["queue_wait_p95_sec"] = round(
            queue_waits[min(len(queue_waits) - 1,
                            int(0.95 * len(queue_waits)))], 6
        )
    return out


def _fleet_section(events: List[dict]) -> Optional[dict]:
    """Reduce the ``fleet_*``/``gang_*`` event family (fleet/,
    docs/SERVING.md "Fleet mode") to the multi-worker story: per-state
    job counts, the durability counters (claims won/lost, lease and
    orphan requeues, preemptions), gang batching occupancy, and the
    workers the journal saw — the journal alone reconstructs the
    fleet's history, so this section IS that reconstruction."""
    if not any(
        str(e.get("event", "")).startswith(("fleet_", "gang_"))
        for e in events
    ):
        return None
    from ..fleet.store import FleetStore

    view = FleetStore.fold_events(events)
    out: dict = {
        "jobs": view.counts(),
        "counters": {k: v for k, v in view.counters.items() if v},
        "torn": view.torn,
    }
    dispatches = view.counters.get("gang_dispatches", 0)
    if dispatches:
        out["gang_occupancy"] = round(
            view.counters.get("gang_jobs_batched", 0) / dispatches, 3
        )
    if view.workers:
        out["workers"] = {
            wid: {
                "platform": (w.get("desc") or {}).get("platform"),
                "stopped": bool(w.get("stopped")),
                "vitals": w.get("vitals") or {},
            }
            for wid, w in view.workers.items()
        }
    violations = {
        jid: j["violation"] for jid, j in view.jobs.items()
        if j.get("violation")
    }
    if violations:
        out["violations"] = violations
    return out


def _incremental_section(events: List[dict]) -> Optional[dict]:
    """Reduce the ``incr_*`` event family (incr/, docs/INCREMENTAL.md)
    to the re-check story: how many submissions took each mode, the
    verdict-cache hit count, what was stored, and the loud cold-run
    reasons — the journal's answer to "is re-checking actually cheap
    here"."""
    classified = [e for e in events if e.get("event") == "incr_classified"]
    stored = [e for e in events if e.get("event") == "incr_stored"]
    skipped = [
        e for e in events if e.get("event") == "incr_store_skipped"
    ]
    if not classified and not stored and not skipped:
        return None
    modes: Dict[str, int] = {}
    for e in classified:
        m = str(e.get("mode", "?"))
        modes[m] = modes.get(m, 0) + 1
    out: dict = {
        "classified": len(classified),
        "modes": modes,
        "verdict_hits": sum(
            1 for e in events if e.get("event") == "incr_verdict_hit"
        ),
        "stored": len(stored),
        "store_skipped": len(skipped),
    }
    cold_reasons = [
        str(e.get("reason", ""))[:160]
        for e in classified if e.get("mode") == "cold" and e.get("reason")
    ]
    if cold_reasons:
        out["cold_reasons"] = cold_reasons[-5:]
    rechecks = [
        e for e in events if e.get("event") == "incr_property_recheck"
    ]
    if rechecks:
        secs = sorted(
            float(e["sec"]) for e in rechecks
            if isinstance(e.get("sec"), (int, float))
        )
        if secs:
            out["property_recheck_median_sec"] = round(
                secs[len(secs) // 2], 4
            )
    return out


# --- chaos-ensemble section ---------------------------------------------------


def _ensemble_section(events: List[dict]) -> Optional[dict]:
    """Reduce the chaos-ensemble event family (``ensemble_start`` /
    ``ensemble_sweep`` / ``ensemble_failing`` / ``ensemble_shrink`` /
    ``ensemble_replay`` / ``ensemble_repro``; ensemble/engine.py,
    docs/CHAOS_ENSEMBLES.md) to the sweep's story: members dispatched,
    failing seeds found, shrink progress, and the journaled repro."""
    starts = [e for e in events if e.get("event") == "ensemble_start"]
    if not starts:
        return None
    start = starts[-1]
    out: dict = {
        "members": start.get("members"),
        "seed": start.get("seed"),
        "steps": start.get("steps"),
        "workload": start.get("workload"),
        "fault": start.get("fault"),
    }
    sweeps = [e for e in events if e.get("event") == "ensemble_sweep"]
    if sweeps:
        s = sweeps[-1]
        out["failing"] = s.get("failing")
        out["states"] = s.get("states")
        out["schedules_per_sec"] = s.get("schedules_per_sec")
        out["ttff_sec"] = s.get("ttff_sec")
    failing = [e for e in events if e.get("event") == "ensemble_failing"]
    if failing:
        out["failing_seeds"] = [
            {k: e.get(k) for k in ("member", "seed", "property", "step")}
            for e in failing
        ]
    shrinks = [e for e in events if e.get("event") == "ensemble_shrink"]
    if shrinks:
        out["shrink_candidates"] = len(shrinks)
        out["shrink_accepted"] = sum(1 for e in shrinks if e.get("accepted"))
        out["shrinks"] = [
            {
                "candidate": e.get("candidate"),
                "accepted": bool(e.get("accepted")),
            }
            for e in shrinks
        ]
    replays = [e for e in events if e.get("event") == "ensemble_replay"]
    if replays:
        r = replays[-1]
        out["replay"] = {
            "member": r.get("member"),
            "seed": r.get("seed"),
            "consistent": r.get("consistent"),
            "rejected": not r.get("consistent", True),
        }
    repros = [e for e in events if e.get("event") == "ensemble_repro"]
    if repros:
        r = repros[-1]
        out["repro"] = {
            k: r.get(k)
            for k in ("member", "seed", "steps", "property", "fault")
        }
    return out


# --- actor-runtime / chaos section --------------------------------------------

_CHAOS_FAULT_EVENTS = (
    "chaos_drop", "chaos_duplicate", "chaos_reorder", "chaos_delay",
    "chaos_partition",
)


def _actor_section(events: List[dict], t0: float) -> Optional[dict]:
    """Reduce the actor/chaos event families (``chaos_*``,
    ``actor_span``/``actor_op``/``actor_stats``, ``orl_give_up``,
    ``audit``; docs/ACTORS.md) to the run's fault-attribution story.

    The per-link/per-kind fault table aggregates the journaled
    injection events themselves, so it equals the transport's own
    counters (the ``chaos_summary`` event) to the count — pinned in
    tests/test_actor_obs.py.  When the live audit REJECTED the history,
    the section adds ``fault_attribution``: the injected faults whose
    timestamps fall inside the audited operation window (the
    ``actor_op`` invoke/return span) — the repro evidence a failing
    chaos seed needs (ROADMAP #4)."""
    inj = [e for e in events if e.get("event") in _CHAOS_FAULT_EVENTS]
    ops = [e for e in events if e.get("event") == "actor_op"]
    spans = [e for e in events if e.get("event") == "actor_span"]
    give_ups = [e for e in events if e.get("event") == "orl_give_up"]
    stats = [e for e in events if e.get("event") == "actor_stats"]
    audits = [e for e in events if e.get("event") == "audit"]
    summaries = [e for e in events if e.get("event") == "chaos_summary"]
    if not (inj or ops or spans or give_ups or stats or audits):
        return None

    def tally(source: List[dict]):
        by_kind: Dict[str, int] = {}
        by_link: Dict[str, Dict[str, int]] = {}
        for e in source:
            kind = e["event"]
            by_kind[kind] = by_kind.get(kind, 0) + 1
            link = f"{e.get('src')}->{e.get('dst')}"
            row = by_link.setdefault(link, {})
            row[kind] = row.get(kind, 0) + 1
        return by_kind, by_link

    by_kind, by_link = tally(inj)
    out: dict = {
        "fault_total": len(inj),
        "faults_by_kind": dict(sorted(by_kind.items())),
        "faults_by_link": {
            k: dict(sorted(v.items())) for k, v in sorted(by_link.items())
        },
        "orl_give_ups": len(give_ups),
        "spans": len(spans),
    }
    if spans:
        hops = [
            int(e["hop"]) for e in spans
            if isinstance(e.get("hop"), int)
        ]
        if hops:
            out["max_hop"] = max(hops)
        out["traces"] = len({e.get("trace") for e in spans})
    if ops:
        out["ops"] = {
            "invoked": sum(1 for e in ops if e.get("kind") == "invoke"),
            "returned": sum(1 for e in ops if e.get("kind") == "return"),
        }
    if stats:
        last = stats[-1]
        out["stats"] = {
            k: last[k]
            for k in ("datagrams", "retransmits", "give_ups",
                      "partition_active")
            if k in last
        }
    if summaries:
        out["chaos_summary"] = {
            k: v for k, v in summaries[-1].items()
            if k not in ("t", "event")
        }
    if audits:
        a = audits[-1]
        out["audit"] = {
            k: a[k]
            for k in ("consistent", "completed", "invoked", "returned",
                      "in_flight", "violations", "expected", "seed")
            if k in a
        }
        if a.get("consistent") is False:
            op_times = [
                e["t"] for e in ops
                if isinstance(e.get("t"), (int, float))
            ]
            if op_times:
                w0, w1 = min(op_times), max(op_times)
                in_window = [
                    e for e in inj
                    if isinstance(e.get("t"), (int, float))
                    and w0 <= e["t"] <= w1
                ]
                note = (
                    "faults injected inside the audited operation "
                    "window (the precise violating operation is not "
                    "identifiable from a failed serialization; the "
                    "window spans every audited op)"
                )
            else:
                in_window = inj
                note = (
                    "no actor_op events journaled: attributing every "
                    "injected fault"
                )
            wk, wl = tally(in_window)
            out["fault_attribution"] = {
                "window": {
                    "t0": round(min(op_times) - t0, 3) if op_times else None,
                    "t1": round(max(op_times) - t0, 3) if op_times else None,
                    "ops": len(ops),
                },
                "fault_total": len(in_window),
                "faults_by_kind": dict(sorted(wk.items())),
                "faults_by_link": {
                    k: dict(sorted(v.items())) for k, v in sorted(wl.items())
                },
                "note": note,
            }
    return out


# --- geometry advisor ---------------------------------------------------------

# Sizing headroom over the measured peak: the overflow-flag + retry
# contract makes an undersized buffer a recompile, not a wrong answer,
# so 4x over the observed peak balances "rarely retries" against "stops
# paying full-buffer bandwidth for dead lanes".
ADVISOR_HEADROOM = 4.0

# Engine limits mirrored here so advice can never recommend past them.
_ADVISOR_MAX_DEDUP = 64


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def advise_geometry(events) -> Optional[dict]:
    """The geometry advisor (docs/OBSERVABILITY.md "Geometry advisor"):
    read a run journal's measured density/occupancy telemetry back into
    recommended knobs — the dedup rung, ``bucket_slack``, and frontier
    geometry a re-run of this workload should use.  Advice-only: the
    ROADMAP #1 autotune ladder is the consumer that will act on it
    mid-run; until then the numbers justify a hand-tuned warm start.

    Inputs, all optional (None when the journal has none of them): the
    per-wave ``density`` field (valid candidates / worst-case ``U``
    buffer, wave_loop.LoopVitals — quantum-averaged on fused runs, so a
    partially-used final quantum UNDERestimates; peak-over-quanta plus
    the headroom absorbs that), per-wave ``exchange_occupancy`` (traced
    sharded runs), the run's ``geometry`` event, and ``grow`` events
    (an observed overflow overrides shrink advice in that direction).
    """
    waves = [e for e in events if e.get("event") == "wave"]
    densities = [
        float(w["density"]) for w in waves
        if isinstance(w.get("density"), (int, float))
    ]
    occs = [
        float(w["exchange_occupancy"]) for w in waves
        if isinstance(w.get("exchange_occupancy"), (int, float))
    ]
    if not densities and not occs:
        return None
    geoms = [e for e in events if e.get("event") == "geometry"]
    grows = [e for e in events if e.get("event") == "grow"]
    geom = {
        k: v for k, v in (geoms[-1] if geoms else {}).items()
        if k not in ("t", "event")
    }
    out: dict = {
        "headroom": ADVISOR_HEADROOM,
        "geometry": geom,
        "measured": {},
        "recommended": {},
        "notes": [],
    }
    meas, rec, notes = out["measured"], out["recommended"], out["notes"]

    if densities:
        peak = max(densities)
        meas["peak_density"] = round(peak, 6)
        meas["mean_density"] = round(sum(densities) / len(densities), 6)
        u = geom.get("u_lanes")
        if u and peak > 0:
            rec["unique_buffer_lanes"] = min(
                int(u),
                _next_pow2(max(128, int(peak * ADVISOR_HEADROOM * u))),
            )
            # The dedup-sort rung (wave_loop.py's sort-geometry ladder):
            # the power-of-two sort_lanes a re-run should START at.  A
            # mid-run rung climb overrides the density derivation — the
            # run itself proved the rung (same rule as bucket_slack).
            from ..parallel.wave_loop import SORT_RUNG_MIN

            # Per-engine-instance worst case: the sharded u_lanes is
            # mesh-global (n × per-shard U) while the rung is per shard.
            shards = max(1, int(geom.get("shards", 0) or 1))
            u_one = max(1, int(u) // shards)
            climbed = None
            for g in grows:
                m = re.search(r"sort_lanes=(\d+)", str(g.get("grown", "")))
                if m:
                    climbed = max(climbed or 0, int(m.group(1)))
            if climbed is not None:
                rec["sort_lanes"] = min(climbed, u_one)
                notes.append(
                    "sort-rung overflow observed mid-run (grow "
                    "sort_lanes=…): recommending the final climbed rung"
                )
            else:
                rec["sort_lanes"] = min(
                    u_one,
                    max(
                        SORT_RUNG_MIN,
                        _next_pow2(
                            max(128, int(peak * ADVISOR_HEADROOM * u_one))
                        ),
                    ),
                )
        dd = geom.get("dedup_factor")
        if dd and peak > 0:
            if any(int(g.get("flags", 0)) & 4 for g in grows):
                # The run itself hit a compaction overflow and relaxed
                # straight to the always-safe rung — measured densities
                # straddle two geometries, so recommend what the run
                # proved, not a re-derivation.
                rec["dedup_factor"] = 1
                notes.append(
                    "dedup overflow observed mid-run (grow flags&4): "
                    "the engine relaxed to dedup_factor=1; keep it"
                )
            else:
                # U scales as 1/dedup_factor (above the buffer floor):
                # the buffer can shrink by 1/(peak*headroom) before the
                # measured peak wave would overflow it.
                shrink = 1.0 / (peak * ADVISOR_HEADROOM)
                rec_dd = int(dd)
                while (
                    rec_dd * 2 <= dd * shrink
                    and rec_dd * 2 <= _ADVISOR_MAX_DEDUP
                ):
                    rec_dd *= 2
                rec["dedup_factor"] = rec_dd
        if geom.get("max_frontier"):
            # Frontier advice: the chunk itself was never the binding
            # buffer unless a dedup overflow halved it (covered above);
            # keep the proven size so the U-lanes recommendation is the
            # only mover.
            rec["max_frontier"] = int(geom["max_frontier"])

    # The step rung (wave_loop.py's frontier-sized chunk ladder): size
    # it to the measured live-frontier peak (the wave events' remaining
    # backlog) at the ladder's own headroom, capped at the worst-case
    # chunk; a mid-run flag-128 climb overrides the derivation (the run
    # itself proved the rung — the sort-rung rule).
    backlogs = [
        int(w["remaining"]) for w in waves
        if isinstance(w.get("remaining"), int) and w["remaining"] > 0
    ]
    full_chunk = geom.get("max_frontier") or geom.get("chunk_size")
    if backlogs and full_chunk:
        from ..parallel.wave_loop import (
            STEP_RUNG_HEADROOM, STEP_RUNG_MIN,
        )

        meas["peak_frontier"] = max(backlogs)
        # The journaled backlog is mesh-GLOBAL while step_lanes is the
        # per-shard chunk rung — same per-engine-instance correction as
        # the sort rung above.
        shards = max(1, int(geom.get("shards", 0) or 1))
        peak_one = max(1, max(backlogs) // shards)
        climbed_step = None
        for g in grows:
            m = re.search(r"step_lanes=(\d+)", str(g.get("grown", "")))
            if m:
                climbed_step = max(climbed_step or 0, int(m.group(1)))
        if climbed_step is not None:
            rec["step_lanes"] = min(climbed_step, int(full_chunk))
            notes.append(
                "step-rung clamp observed mid-run (grow step_lanes=…): "
                "recommending the final climbed rung"
            )
        else:
            rec["step_lanes"] = min(
                int(full_chunk),
                max(
                    STEP_RUNG_MIN,
                    _next_pow2(
                        int(peak_one * STEP_RUNG_HEADROOM) + 1
                    ),
                ),
            )

    # The sortless-vs-sort verdict: the claim election is the default
    # and density-insensitive; the one measured reason to stay on the
    # sort-rung path is a journaled fallback (the run itself proved the
    # claim path's compaction buffer insufficient for this workload).
    geoms_sortless = [
        g.get("sortless") for g in geoms if g.get("sortless") is not None
    ]
    fell_back = any(
        "sortless=0" in str(g.get("grown", "")) for g in grows
    )
    if geoms_sortless or fell_back:
        if fell_back or (geoms_sortless and not geoms_sortless[-1]):
            rec["dedup"] = "sort"
            if fell_back:
                notes.append(
                    "sortless fallback observed mid-run (grow "
                    "sortless=0): the claim election's compaction "
                    "buffer overflowed on this workload; warm-start on "
                    "the sort-rung path"
                )
        else:
            rec["dedup"] = "sortless"

    if occs and int(geom.get("shards", 0) or 0) > 1:
        from ..parallel.wave_loop import BUCKET_SLACK_DEFAULT

        peak_occ = max(occs)
        meas["peak_exchange_occupancy"] = round(peak_occ, 6)
        meas["mean_exchange_occupancy"] = round(
            sum(occs) / len(occs), 6
        )
        slack = int(geom.get("bucket_slack") or BUCKET_SLACK_DEFAULT)
        bucket_grows = [
            g for g in grows if int(g.get("flags", 0)) & 32
        ]
        if bucket_grows:
            # The ladder already climbed mid-run: the final rung is the
            # measured answer (parsed from the grow notes).
            final = slack
            for g in bucket_grows:
                m = re.search(r"bucket_slack=(\d+)", str(g.get("grown", "")))
                if m:
                    final = max(final, int(m.group(1)))
            rec["bucket_slack"] = final
            notes.append(
                "exchange bucket overflow observed mid-run (grow "
                "flags&32): recommending the final climbed rung"
            )
        else:
            # Occupancy is the measured fill fraction of a bucket AT
            # the journaled slack; the rung ladder (wave_loop) starts
            # at the default and doubles.
            want = slack * peak_occ * ADVISOR_HEADROOM
            rung = BUCKET_SLACK_DEFAULT
            while rung < want:
                rung *= 2
            rec["bucket_slack"] = rung

    # Table-capacity advice from the load trajectory: the run's final
    # unique count right-sizes the table at <=50% load (the engines'
    # tuned_kwargs rule); an observed table grow overrides shrinking.
    unique = next(
        (w.get("unique") for w in reversed(waves)
         if isinstance(w.get("unique"), int)), None,
    )
    if unique:
        if int(geom.get("shards", 0) or 0) > 1:
            per_shard = -(-unique // int(geom["shards"]))
            rec["capacity_per_shard"] = 1 << max(
                10, (2 * per_shard).bit_length()
            )
        else:
            rec["capacity"] = 1 << max(10, (2 * unique).bit_length())
    if any(int(g.get("flags", 0)) & 1 for g in grows):
        notes.append(
            "table overflow observed mid-run (grow flags&1): capacity "
            "advice reflects the final unique count, not the spawn size"
        )
    return out


def render_markdown(report: dict, title: str = "Run report") -> str:
    """The human-readable form of :func:`analyze_journal`'s dict."""
    lines = [f"# {title}", ""]
    for w in report.get("warnings", ()):
        lines.append(f"> ⚠ {w}")
    if report.get("warnings"):
        lines.append("")
    lines.append(
        f"- kind: **{report.get('kind')}** · events: "
        f"{report.get('events')} · duration: "
        f"{report.get('duration_sec', 0)} s"
    )
    if "unique" in report:
        lines.append(
            f"- unique states: **{report.get('unique')}** · waves: "
            f"{report.get('waves')} · depth: {report.get('depth')} · "
            f"max occupancy: {report.get('max_occupancy')}"
        )
        lines.append(
            f"- grows: {report.get('grows')} · spills: "
            f"{report.get('spills')} · checkpoints: "
            f"{report.get('checkpoints')} · restarts: "
            f"{report.get('restarts')} · faults: {report.get('faults')}"
        )
    if report.get("phase_breakdown"):
        lines += ["", "## Phase breakdown", ""]
        lines.append(
            f"Bottleneck phase: **{report.get('bottleneck_phase')}** "
            f"(source: {report.get('phase_source')})"
        )
        lines += ["", "| phase | seconds |", "|---|---|"]
        order = [p for p in PHASE_ORDER if p in report["phase_breakdown"]]
        order += [
            p for p in report["phase_breakdown"] if p not in order
        ]
        for p in order:
            lines.append(f"| {p} | {report['phase_breakdown'][p]} |")
    advisor = report.get("advisor")
    if advisor:
        lines += ["", "## Geometry advisor", ""]
        lines.append(
            f"Measured-density sizing with {advisor['headroom']}× "
            "headroom (docs/OBSERVABILITY.md \"Geometry advisor\"; "
            "advice-only — an undersized knob costs a retry, never a "
            "wrong answer)."
        )
        meas = advisor.get("measured") or {}
        if meas:
            lines += ["", "| measured | value |", "|---|---|"]
            for k in sorted(meas):
                lines.append(f"| {k} | {meas[k]} |")
        rec = advisor.get("recommended") or {}
        if rec:
            lines += ["", "| recommended knob | value | current |",
                      "|---|---|---|"]
            geom = advisor.get("geometry") or {}
            for k in sorted(rec):
                cur = geom.get(k, "—")
                lines.append(f"| {k} | {rec[k]} | {cur} |")
        for note in advisor.get("notes", ()):
            lines.append(f"- {note}")
    incr = report.get("incremental")
    if incr:
        lines += ["", "## Incremental re-checking", ""]
        lines.append(
            f"- classified: {incr.get('classified')} · modes: "
            + (", ".join(
                f"{k}={v}" for k, v in sorted(incr.get("modes", {}).items())
            ) or "—")
            + f" · verdict hits: {incr.get('verdict_hits')} · stored: "
            f"{incr.get('stored')}"
        )
        if "property_recheck_median_sec" in incr:
            lines.append(
                "- property re-eval median: "
                f"{incr['property_recheck_median_sec']} s"
            )
        for reason in incr.get("cold_reasons", ()):
            lines.append(f"- cold: {reason}")
    ensemble = report.get("ensemble")
    if ensemble:
        lines += ["", "## Chaos ensemble", ""]
        lines.append(
            f"- members: **{ensemble.get('members')}** · base seed: "
            f"{ensemble.get('seed')} · steps: {ensemble.get('steps')} · "
            f"workload: {ensemble.get('workload')}"
            + (f" (fault: {ensemble['fault']})" if ensemble.get("fault")
               else "")
        )
        if "failing" in ensemble:
            lines.append(
                f"- failing seeds: **{ensemble.get('failing')}** · states "
                f"walked: {ensemble.get('states')} · schedules/s: "
                f"{ensemble.get('schedules_per_sec')} · time to first "
                f"failure: {ensemble.get('ttff_sec')} s"
            )
        if "shrink_candidates" in ensemble:
            lines.append(
                f"- shrink: {ensemble.get('shrink_accepted')}/"
                f"{ensemble.get('shrink_candidates')} candidates accepted"
                + (" (" + ", ".join(
                    f"{s['candidate']}={'✓' if s['accepted'] else '✗'}"
                    for s in ensemble.get("shrinks", ())
                ) + ")" if ensemble.get("shrinks") else "")
            )
        replay = ensemble.get("replay")
        if replay is not None:
            lines.append(
                "- host replay: "
                + ("**REJECTED** (failing seed confirmed)"
                   if replay.get("rejected") else "consistent (unconfirmed)")
                + f" · seed: {replay.get('seed')}"
            )
        repro = ensemble.get("repro")
        if repro is not None:
            lines.append(
                f"- repro journaled: member {repro.get('member')}, seed "
                f"{repro.get('seed')}, {repro.get('steps')} steps, "
                f"property {repro.get('property')} — replay with "
                "`ensemble.replay_repro` from the `ensemble_repro` "
                "event alone"
            )
        if ensemble.get("failing_seeds"):
            lines += ["", "| member | seed | property | latch step |",
                      "|---|---|---|---|"]
            for f in ensemble["failing_seeds"]:
                lines.append(
                    f"| {f.get('member')} | {f.get('seed')} | "
                    f"{f.get('property')} | {f.get('step')} |"
                )
    actor = report.get("actor")
    if actor:
        lines += ["", "## Actor runtime", ""]
        audit = actor.get("audit")
        if audit is not None:
            verdict = (
                "**consistent**" if audit.get("consistent")
                else "**REJECTED**"
            )
            lines.append(
                f"- live audit: {verdict} · ops returned: "
                f"{audit.get('returned')}/{audit.get('invoked')} invoked "
                f"· in flight: {audit.get('in_flight')} · seed: "
                f"{audit.get('seed')}"
            )
        lines.append(
            f"- injected faults: {actor.get('fault_total')} · ORL "
            f"give-ups: {actor.get('orl_give_ups')} · trace spans: "
            f"{actor.get('spans')}"
            + (f" (max hop {actor['max_hop']})" if "max_hop" in actor
               else "")
        )
        if actor.get("faults_by_link"):
            lines += ["", "| link | fault | count |", "|---|---|---|"]
            for link, kinds in actor["faults_by_link"].items():
                for kind, n in kinds.items():
                    lines.append(f"| {link} | {kind} | {n} |")
        attribution = actor.get("fault_attribution")
        if attribution:
            w = attribution.get("window") or {}
            lines += ["", "### Fault attribution (rejected history)", ""]
            lines.append(
                f"- operation window: t+{w.get('t0')}s … t+{w.get('t1')}s "
                f"({w.get('ops')} ops) · faults in window: "
                f"{attribution.get('fault_total')}"
            )
            lines.append(f"- {attribution.get('note')}")
            if attribution.get("faults_by_link"):
                lines += ["", "| link | fault | count in window |",
                          "|---|---|---|"]
                for link, kinds in attribution["faults_by_link"].items():
                    for kind, n in kinds.items():
                        lines.append(f"| {link} | {kind} | {n} |")
    curve = report.get("throughput_curve")
    if curve:
        lines += ["", "## Throughput curve", "",
                  "| t (s) | unique | uniq/s (segment) |", "|---|---|---|"]
        for pt in curve:
            lines.append(
                f"| {pt['t']} | {pt['unique']} | {pt['uniq_per_sec']} |"
            )
    jobs = report.get("jobs")
    if jobs:
        lines += ["", "## Jobs", ""]
        lines.append(
            f"- jobs: {jobs['count']} · by state: "
            + ", ".join(
                f"{k}={v}" for k, v in sorted(jobs["by_state"].items())
            )
        )
        if "queue_wait_p95_sec" in jobs:
            lines.append(
                f"- queue wait p95: {jobs['queue_wait_p95_sec']} s"
            )
        lines += ["", "| job | workload | engine | state | worker | "
                  "queue_wait | run | total |", "|---|---|---|---|---|---|---|---|"]
        for jid in sorted(jobs["detail"]):
            j = jobs["detail"][jid]
            s = j.get("spans", {})
            lines.append(
                f"| {jid} | {j.get('workload', '?')} | "
                f"{j.get('engine', '?')} | {j.get('state', 'running')} | "
                f"{j.get('worker', '—')} | "
                f"{s.get('queue_wait', '—')} | {s.get('run', '—')} | "
                f"{s.get('total', '—')} |"
            )
    fleet = report.get("fleet")
    if fleet:
        lines += ["", "## Fleet", ""]
        lines.append(
            "- jobs: " + ", ".join(
                f"{k}={v}" for k, v in sorted(fleet["jobs"].items())
            )
        )
        counters = fleet.get("counters") or {}
        if counters:
            lines.append(
                "- counters: " + ", ".join(
                    f"{k}={v}" for k, v in sorted(counters.items())
                )
            )
        if "gang_occupancy" in fleet:
            lines.append(
                f"- gang occupancy: **{fleet['gang_occupancy']}** jobs "
                "per device dispatch"
            )
        for wid, w in sorted((fleet.get("workers") or {}).items()):
            vit = w.get("vitals") or {}
            lines.append(
                f"- worker {wid} ({w.get('platform')}, "
                + ("stopped" if w.get("stopped") else "alive")
                + (f", jobs_done={vit['jobs_done']}"
                   if "jobs_done" in vit else "")
                + ")"
            )
        for jid, v in sorted((fleet.get("violations") or {}).items()):
            lines.append(f"- violation: {jid} → **{v!r}**")
        if fleet.get("torn"):
            lines.append(
                f"- ⚠ {fleet['torn']} torn journal line(s) — a writer "
                "died mid-append; the fold covers the intact prefix"
            )
    timeline = report.get("timeline") or []
    if timeline:
        lines += ["", "## Restart / fault timeline", ""]
        for e in timeline:
            extra = ", ".join(
                f"{k}={v}" for k, v in e.items() if k not in ("t", "event")
            )
            lines.append(
                f"- t+{e['t']}s **{e['event']}**"
                + (f" ({extra})" if extra else "")
            )
    return "\n".join(lines) + "\n"


# --- cross-round bench trajectory --------------------------------------------


def _round_label(path: str) -> str:
    m = re.search(r"(BENCH_r\d+)", os.path.basename(path))
    return m.group(1) if m else os.path.basename(path)


def bench_trajectory(
    paths: Sequence[str],
    threshold: float = REGRESSION_THRESHOLD,
) -> dict:
    """Cross-round trajectory over ``BENCH_r*.json`` artifacts: one row
    per round (headline uniq/s, vs_baseline, ttfv, measured exchange
    occupancy, rc, note) plus threshold-based regression flags — a
    round whose headline rate fell below ``threshold`` × the best
    earlier round of the SAME metric is flagged (partial CPU-only
    rounds carry no headline and are never flagged)."""
    rounds = []
    for path in sorted(paths, key=_round_label):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                data = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            rounds.append({"round": _round_label(path), "error": str(e)})
            continue
        parsed = data.get("parsed") or {}
        if not isinstance(parsed, dict):
            parsed = {}
        row = {
            "round": _round_label(path),
            "rc": data.get("rc"),
            "metric": parsed.get("metric"),
            "uniq_per_sec": parsed.get("value"),
            "vs_baseline": parsed.get("vs_baseline"),
            "ttfv_tpu_sec": parsed.get("ttfv_tpu_sec"),
            "exchange_occupancy": parsed.get("exchange_occupancy"),
            # The dedup regression gauge (bench.py's dedup phase, since
            # r08): the traced dedup share of wave time — at the
            # knob-cached sort rung through r09, on the sortless
            # claim-plane election since r10.
            "dedup_share": parsed.get("dedup_share"),
            # The step regression gauge (bench.py's step phase, since
            # r10): the traced step share of wave time and modeled
            # bytes.step at the knob-cached frontier-sized rung.
            "step_share": parsed.get("step_share"),
            "bytes_step": parsed.get("bytes_step"),
            # The incremental re-check gauge (bench.py's recheck phase,
            # since r09): median re-check latency on a one-line model
            # edit — the warm-vs-cold story for NEAR-identical traffic.
            "recheck_median_sec": parsed.get("recheck_median_sec"),
            # The chaos-ensemble gauge (bench.py's ensemble phase, since
            # r16): schedules swept per second in one device dispatch,
            # golden-gated on the known-violating skip_ack workload.
            "ensemble_schedules_per_sec": parsed.get(
                "ensemble_schedules_per_sec"
            ),
            # The fleet gang-batching gauge (bench.py's fleet phase,
            # since r18): gang-batched jobs/sec over the serialized
            # solo baseline, golden-gated on per-job verdict equality.
            "gang_speedup": parsed.get("gang_speedup"),
            # The host-tail gauge (since r19): host / (host + device)
            # share of the headline run, from the fused loop's host_span
            # decomposition (obs/timeline.py) — a creeping host tail is
            # a regression even when uniq/s holds.
            "host_share": parsed.get("host_share"),
        }
        if parsed.get("note"):
            row["note"] = str(parsed["note"])[:200]
        if parsed.get("bottleneck_phase"):
            row["bottleneck_phase"] = parsed["bottleneck_phase"]
        rounds.append(row)

    regressions = []
    best: Dict[str, dict] = {}  # metric -> best earlier row
    for row in rounds:
        metric, value = row.get("metric"), row.get("uniq_per_sec")
        if not metric or not isinstance(value, (int, float)):
            continue
        prev = best.get(metric)
        if prev is not None and value < threshold * prev["uniq_per_sec"]:
            flag = {
                "round": row["round"],
                "metric": metric,
                "uniq_per_sec": value,
                "best_round": prev["round"],
                "best_uniq_per_sec": prev["uniq_per_sec"],
                "ratio": round(value / prev["uniq_per_sec"], 3),
                "threshold": threshold,
            }
            row["regression"] = True
            regressions.append(flag)
        if prev is None or value > prev["uniq_per_sec"]:
            best[metric] = row
    return {
        "rounds": rounds,
        "regressions": regressions,
        "threshold": threshold,
    }


def render_trajectory_markdown(traj: dict) -> str:
    lines = [
        "# Bench trajectory (BENCH_r*.json)",
        "",
        "Generated by `stateright_tpu.obs.report.bench_trajectory` "
        "(`bench.py` regenerates this file each round; "
        "`cli.py report 'BENCH_r*.json'` prints it).  A ⚠ row fell "
        f"below {traj.get('threshold')}× the best earlier round of the "
        "same metric.",
        "",
        "| round | metric | uniq/s | vs_baseline | ttfv (s) | "
        "exchange occ. | dedup share | step share | recheck (s) | "
        "ensemble sched/s | gang ×| host share | rc | |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]

    def cell(v):
        return "—" if v is None else v

    for row in traj["rounds"]:
        if "error" in row:
            lines.append(
                f"| {row['round']} | unreadable: {row['error']} "
                "| | | | | | | | | | | | |"
            )
            continue
        note = "⚠ regression" if row.get("regression") else (
            "partial" if row.get("uniq_per_sec") is None else ""
        )
        lines.append(
            f"| {row['round']} | {cell(row.get('metric'))} | "
            f"{cell(row.get('uniq_per_sec'))} | "
            f"{cell(row.get('vs_baseline'))} | "
            f"{cell(row.get('ttfv_tpu_sec'))} | "
            f"{cell(row.get('exchange_occupancy'))} | "
            f"{cell(row.get('dedup_share'))} | "
            f"{cell(row.get('step_share'))} | "
            f"{cell(row.get('recheck_median_sec'))} | "
            f"{cell(row.get('ensemble_schedules_per_sec'))} | "
            f"{cell(row.get('gang_speedup'))} | "
            f"{cell(row.get('host_share'))} | "
            f"{cell(row.get('rc'))} | {note} |"
        )
    if traj["regressions"]:
        lines += ["", "## Regressions", ""]
        for r in traj["regressions"]:
            lines.append(
                f"- **{r['round']}** {r['metric']}: {r['uniq_per_sec']} "
                f"uniq/s is {r['ratio']}× the best earlier round "
                f"({r['best_round']}: {r['best_uniq_per_sec']})"
            )
    notes = [
        (row["round"], row["note"])
        for row in traj["rounds"] if row.get("note")
    ]
    if notes:
        lines += ["", "## Round notes", ""]
        for rnd, note in notes:
            lines.append(f"- **{rnd}**: {note}")
    return "\n".join(lines) + "\n"


# --- CLI verb ----------------------------------------------------------------


def _expand(target: str) -> List[str]:
    if os.path.isdir(target):
        return sorted(glob.glob(os.path.join(target, "BENCH_r*.json")))
    matches = sorted(glob.glob(target))
    return matches if matches else [target]


def report_main(args: List[str], out=None) -> int:
    """``report <journal.jsonl | BENCH-glob | dir>`` (cli.py): journal
    files get a run/service report, JSON round artifacts get the
    trajectory table.  ``--json`` emits the dict instead of markdown;
    ``--out FILE`` writes instead of printing; ``--timeline-out FILE``
    additionally exports the journal as a Chrome trace
    (obs/timeline.py — load it in Perfetto or chrome://tracing)."""
    import sys

    out = out or sys.stdout
    as_json = False
    out_path: Optional[str] = None
    timeline_out: Optional[str] = None
    threshold = REGRESSION_THRESHOLD
    targets: List[str] = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--json":
            as_json = True
        elif a == "--out":
            i += 1
            if i >= len(args):
                print("--out requires a path", file=sys.stderr)
                return 2
            out_path = args[i]
        elif a == "--timeline-out":
            i += 1
            if i >= len(args):
                print("--timeline-out requires a path", file=sys.stderr)
                return 2
            timeline_out = args[i]
        elif a == "--threshold":
            i += 1
            try:
                threshold = float(args[i])
            except (IndexError, ValueError):
                print("--threshold requires a fraction", file=sys.stderr)
                return 2
        else:
            targets.append(a)
        i += 1
    if not targets:
        print(
            "report requires a journal path, a BENCH_r*.json glob, or a "
            "directory containing rounds",
            file=sys.stderr,
        )
        return 2
    paths: List[str] = []
    for t in targets:
        paths.extend(_expand(t))
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"no such file: {', '.join(missing)}", file=sys.stderr)
        return 2
    jsonl = [p for p in paths if p.endswith(".jsonl")]
    if jsonl and len(paths) > len(jsonl):
        print(
            "report takes EITHER journals (.jsonl) or bench rounds "
            "(.json), not a mix",
            file=sys.stderr,
        )
        return 2
    if timeline_out and not jsonl:
        print(
            "--timeline-out needs a journal (.jsonl) target",
            file=sys.stderr,
        )
        return 2
    if jsonl:
        if len(jsonl) > 1:
            print("report one journal at a time", file=sys.stderr)
            return 2
        report = analyze_journal(jsonl[0])
        if timeline_out:
            from .timeline import export_timeline

            trace = export_timeline([jsonl[0]], out=timeline_out)
            print(
                f"wrote {timeline_out} "
                f"({len(trace['traceEvents'])} trace events)",
                file=out,
            )
        text = (
            json.dumps(report, indent=2, sort_keys=True, default=str)
            if as_json
            else render_markdown(
                report, title=f"Run report: {os.path.basename(jsonl[0])}"
            )
        )
    else:
        traj = bench_trajectory(paths, threshold=threshold)
        text = (
            json.dumps(traj, indent=2, sort_keys=True, default=str)
            if as_json else render_trajectory_markdown(traj)
        )
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            fh.write(text)
        print(f"wrote {out_path}", file=out)
    else:
        print(text, file=out)
    return 0
