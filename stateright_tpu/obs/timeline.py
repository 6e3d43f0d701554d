"""Unified timeline: host-tail span decomposition + Perfetto export.

Three pieces, one clock discipline (docs/OBSERVABILITY.md "Timeline
export and profiling"):

- :class:`SpanRecorder` — the zero-new-readback span layer the fused
  host loop (parallel/wave_loop.py) threads through its per-quantum
  tail: every named sub-phase (``journal`` append, ``checkpoint``
  write, the ``after_commit`` hook — a tiered spill or the visitor
  stream —, sort/step ``retune``, overflow ``grow``, the previous
  record's own ``flush`` write) is timed with two
  ``time.monotonic()`` calls and journaled as ONE ``host_span``
  event per quantum plus per-phase ``host_<phase>_sec`` histograms —
  so ``host_sec_total`` decomposes into named parts.  Engines report
  in-call host work (the ``readback`` decode, the tiered engine's
  ``cold_probe`` windowing) through the same record under
  ``call_spans``.  No device traffic anywhere: the fused program stays
  byte-for-byte pinned.

- :func:`export_timeline` — fold any run / serve / fleet journal
  (or several) into Chrome trace-event JSON loadable in Perfetto /
  ``chrome://tracing``: one process track per ``pid@host`` worker
  stamp (aligned via the journal's ``clock_sync`` wall+monotonic
  epoch, runtime/journal.py), device-call and host-tail slices, job
  spans, and job/gang flow arrows submit -> claim -> dispatch ->
  result.  :func:`validate_trace` is the CI/test gate (well-nested
  ``X`` slices per track, balanced ``B``/``E``, resolving flow ids).

- xprof hooks — ``check-tpu --xprof-dir`` flips :func:`set_xprof`;
  the loops then wrap each quantum in
  ``jax.profiler.StepTraceAnnotation`` and the recorder mirrors every
  host span as a ``jax.profiler.TraceAnnotation`` named exactly like
  the journal phase, so a hardware profile aligns with the journal
  timeline for free.
"""

from __future__ import annotations

import json
import os
import socket
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..runtime.journal import (
    CLOCK_SYNC_EVENT, read_journal_stats,
)

# The journal event one fused-loop quantum's host tail folds into.
SPAN_EVENT = "host_span"

# Host-tail sub-phases (between device calls; their durations sum — with
# the residual ``other`` and the previous record's own ``flush`` write —
# to the quantum's ``host_sec`` gap, the same gap LoopVitals accounts
# into ``host_sec_total``).
TAIL_PHASES = (
    "journal", "after_commit", "retune", "checkpoint", "grow", "other",
)
# Spans measured INSIDE the device-call window (host-observed, but part
# of ``device_call_sec_total``, not the host tail): the stats readback
# decode and the tiered engine's host-side cold windowing.
CALL_PHASES = ("readback", "cold_probe")
# Run-scoped one-shot spans (outside the wave loop): knob-cache writes.
ONESHOT_PHASES = ("knob_cache",)

_US = 1_000_000.0


def default_worker() -> str:
    """The ``pid@host`` worker stamp (same shape as fleet/store.py)."""
    return f"{os.getpid()}@{socket.gethostname()}"


# --- hardware profiler hooks -----------------------------------------------

_xprof_on = False


def set_xprof(enabled: bool) -> None:
    """Process-wide xprof toggle (``check-tpu --xprof-dir``): loops
    started after this wrap quanta in ``StepTraceAnnotation`` and
    mirror host spans as ``TraceAnnotation``s.  Process-wide because
    ``jax.profiler.start_trace`` is."""
    global _xprof_on
    _xprof_on = bool(enabled)


def xprof_enabled() -> bool:
    return _xprof_on


def step_annotation(step: int, name: str = "wave_quantum"):
    """A ``jax.profiler.StepTraceAnnotation`` for one loop quantum when
    xprof is on; a no-op context otherwise (or when jax's profiler is
    unavailable) — the loops call this unconditionally."""
    if not _xprof_on:
        return nullcontext()
    try:
        from jax.profiler import StepTraceAnnotation
    except Exception:
        return nullcontext()
    return StepTraceAnnotation(name, step_num=int(step))


def phase_annotation(name: str):
    """A named ``jax.profiler.TraceAnnotation`` when xprof is on —
    host spans carry the SAME names into the hardware profile as into
    the journal, so the two timelines align by string."""
    if not _xprof_on:
        return nullcontext()
    try:
        from jax.profiler import TraceAnnotation
    except Exception:
        return nullcontext()
    return TraceAnnotation(str(name))


# --- the span layer ---------------------------------------------------------


class SpanRecorder:
    """Per-quantum host-tail accounting for the fused loop.

    The loop marks the tail start (:meth:`tail_start`, right after the
    device call returns), wraps each named tail section in
    :meth:`span`, and closes the quantum at the top of the next
    iteration (:meth:`quantum_start`) — the SAME boundary
    ``LoopVitals.call_started`` accounts into ``host_sec_total``, so
    the journaled decomposition and the counter agree by construction.
    The flush write itself (one journal line) lands in the NEXT
    record as the ``flush`` span, positioned at its true (earlier)
    monotonic time, so no tail microsecond goes unattributed.

    Every timestamp is host ``time.monotonic()``; there is no device
    traffic and no new readback.
    """

    def __init__(self, journal=None, metrics=None,
                 worker: Optional[str] = None):
        self._journal = journal
        self._metrics = metrics
        self._worker = worker or default_worker()
        self._tail_mark: Optional[float] = None
        self._spans: List[Tuple[str, float, float]] = []
        self._call_spans: List[Tuple[str, float, float]] = []
        self._quantum = 0
        self._xprof = xprof_enabled()

    @contextmanager
    def span(self, phase: str):
        """Time one named section; in-call phases (:data:`CALL_PHASES`)
        are kept apart from the tail decomposition."""
        ann = phase_annotation(f"host/{phase}") if self._xprof else None
        if ann is not None:
            ann.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            dur = time.monotonic() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            dest = (
                self._call_spans if phase in CALL_PHASES else self._spans
            )
            dest.append((phase, t0, dur))

    def step(self):
        """The per-quantum ``StepTraceAnnotation`` wrapper (no-op
        unless xprof is on)."""
        self._quantum += 1
        if not self._xprof:
            return nullcontext()
        return step_annotation(self._quantum)

    def collect(self, eng) -> None:
        """Fold in-call host spans the engine measured itself (the
        optional ``_wl_host_spans()`` hook: e.g. the tiered engine's
        cold-run windowing inside ``_wl_call``)."""
        hook = getattr(eng, "_wl_host_spans", None)
        if hook is None:
            return
        for phase, t0, dur in hook() or ():
            dest = (
                self._call_spans if phase in CALL_PHASES else self._spans
            )
            dest.append((str(phase), float(t0), float(dur)))

    def tail_start(self, now: float) -> None:
        self._tail_mark = now

    def quantum_start(self, now: float) -> None:
        """Close the previous quantum's tail ``[tail_start, now)`` —
        called at the top of each loop iteration with the same
        timestamp handed to ``vitals.call_started``."""
        if self._tail_mark is not None:
            self._flush(now)

    def finish(self, now: float) -> float:
        """Close the final tail at loop exit; returns its seconds so
        the loop can fold them into ``host_sec_total``
        (``LoopVitals.record_host``) — the last tail has no next call
        to account it otherwise."""
        if self._tail_mark is None:
            return 0.0
        return self._flush(now)

    def _flush(self, now: float) -> float:
        tail = max(0.0, now - self._tail_mark)
        spans: Dict[str, List[float]] = {}
        for phase, t0, dur in self._spans:
            rel = t0 - self._tail_mark
            cur = spans.get(phase)
            if cur is None:
                spans[phase] = [rel, dur]
            else:
                cur[0] = min(cur[0], rel)
                cur[1] += dur
        in_tail = sum(v[1] for k, v in spans.items() if v[0] >= 0.0)
        other = max(0.0, tail - in_tail)
        spans["other"] = [max(0.0, tail - other), other]
        call_spans: Dict[str, List[float]] = {}
        for phase, t0, dur in self._call_spans:
            rel = t0 - self._tail_mark
            cur = call_spans.get(phase)
            if cur is None:
                call_spans[phase] = [rel, dur]
            else:
                cur[0] = min(cur[0], rel)
                cur[1] += dur
        t_flush0 = time.monotonic()
        if self._metrics is not None:
            from .metrics import LATENCY_BUCKETS

            for phase, (_rel, dur) in spans.items():
                self._metrics.observe(
                    f"host_{phase}_sec", dur, boundaries=LATENCY_BUCKETS
                )
            for phase, (_rel, dur) in call_spans.items():
                self._metrics.observe(
                    f"host_{phase}_sec", dur, boundaries=LATENCY_BUCKETS
                )
        if self._journal is not None:
            self._journal.append(
                SPAN_EVENT,
                quantum=self._quantum,
                worker=self._worker,
                mono=round(self._tail_mark, 6),
                host_sec=round(tail, 6),
                spans={
                    k: [round(v[0], 6), round(v[1], 6)]
                    for k, v in spans.items()
                },
                **(
                    {"call_spans": {
                        k: [round(v[0], 6), round(v[1], 6)]
                        for k, v in call_spans.items()
                    }} if call_spans else {}
                ),
            )
        flush_dur = time.monotonic() - t_flush0
        self._spans = [("flush", t_flush0, flush_dur)]
        self._call_spans = []
        self._tail_mark = None
        return tail


def record_oneshot_span(journal, metrics, phase: str, sec: float,
                        **fields) -> None:
    """A run-scoped host span outside the wave loop (knob-cache
    writes): one ``host_span`` event with ``scope="run"`` — excluded
    from the per-quantum tail reconciliation — plus the same
    ``host_<phase>_sec`` histogram."""
    sec = max(0.0, float(sec))
    if metrics is not None:
        from .metrics import LATENCY_BUCKETS

        metrics.observe(f"host_{phase}_sec", sec,
                        boundaries=LATENCY_BUCKETS)
    if journal is not None:
        journal.append(
            SPAN_EVENT, scope="run", worker=default_worker(),
            host_sec=round(sec, 6),
            spans={phase: [0.0, round(sec, 6)]}, **fields,
        )


def host_share_of(metrics: Dict) -> Optional[float]:
    """``host_sec_total / (host_sec_total + device_call_sec_total)`` —
    the ROADMAP #2 regression gauge; None when the metrics cannot say."""
    try:
        h = float(metrics.get("host_sec_total"))
        d = float(metrics.get("device_call_sec_total"))
    except (TypeError, ValueError):
        return None
    if h < 0 or d <= 0:
        return None
    return h / (h + d)


def host_tail_sums(events: Iterable[Dict]) -> Dict[str, float]:
    """Per-phase summed seconds over a journal's per-quantum
    ``host_span`` events (run-scoped one-shots excluded) — the
    reconciliation side of the ``host_sec_total`` counter."""
    sums: Dict[str, float] = {}
    for e in events:
        if e.get("event") != SPAN_EVENT or e.get("scope") == "run":
            continue
        for phase, rel_dur in (e.get("spans") or {}).items():
            try:
                sums[phase] = sums.get(phase, 0.0) + float(rel_dur[1])
            except (TypeError, IndexError, ValueError):
                continue
    return sums


# --- the exporter -----------------------------------------------------------

_SUBMIT_EVENTS = frozenset({"fleet_submitted", "job_submitted"})
_STEP_EVENTS = frozenset({
    "fleet_claimed", "fleet_requeued", "fleet_lease", "gang_dispatch",
    "job_running", "fleet_preempted",
})
_FINISH_EVENTS = frozenset({
    "fleet_done", "fleet_failed", "fleet_cancelled",
    "job_done", "job_failed", "job_cancelled",
})
_FLOW_EVENTS = _SUBMIT_EVENTS | _STEP_EVENTS | _FINISH_EVENTS

_TID_DEVICE = 1
_TID_HOST = 2
_TID_JOBS = 3


def resolve_journal(path: str) -> str:
    """Accept a journal file, a run directory, or a fleet directory."""
    if os.path.isdir(path):
        for cand in (
            os.path.join(path, "journal.jsonl"),
            os.path.join(path, "fleet", "journal.jsonl"),
        ):
            if os.path.exists(cand):
                return cand
        raise FileNotFoundError(
            f"no journal.jsonl under directory {path!r}"
        )
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    return path


class _Clock:
    """Per-worker monotonic -> wall mapping from ``clock_sync`` epochs
    (the journal header event, runtime/journal.py): sound on stepping
    wall clocks because each process's offset is measured once against
    its OWN monotonic clock."""

    def __init__(self, syncs: Sequence[Dict]):
        self._by_worker: Dict[str, Tuple[float, float]] = {}
        for s in syncs:
            w = s.get("worker")
            if w and w not in self._by_worker:
                try:
                    self._by_worker[w] = (float(s["t"]), float(s["mono"]))
                except (KeyError, TypeError, ValueError):
                    continue
        self.primary: Optional[str] = (
            min(self._by_worker) if self._by_worker else None
        )

    def wall(self, worker: Optional[str], mono: Optional[float],
             fallback: float) -> float:
        if mono is not None:
            ref = self._by_worker.get(worker) or (
                self._by_worker.get(self.primary)
                if worker is None else None
            )
            if ref is not None:
                t0, m0 = ref
                return t0 + (float(mono) - m0)
        return fallback


def _pid_of(worker: str, fallback: int) -> int:
    head = str(worker).split("@", 1)[0]
    try:
        return int(head)
    except ValueError:
        return fallback


def build_trace(events: Sequence[Dict]) -> Dict:
    """Fold merged journal events into a Chrome trace-event object.

    Deterministic: tracks are keyed and sorted by worker stamp, flow
    ids are assigned in sorted-job-id order, and the final event list
    is fully sorted — exporting the same event set in any input order
    yields byte-identical JSON."""
    syncs = [e for e in events if e.get("event") == CLOCK_SYNC_EVENT]
    clock = _Clock(syncs)
    workers: Dict[str, int] = {}

    def track(worker: Optional[str]) -> Tuple[str, int]:
        w = worker or clock.primary or "run"
        if w not in workers:
            workers[w] = _pid_of(w, 100_000 + len(workers))
        return w, workers[w]

    # slices: (pid, tid, start_wall, dur_sec, name, args, children)
    slices: List[Dict] = []
    flows: List[Dict] = []
    has_spans = any(
        e.get("event") == SPAN_EVENT and e.get("scope") != "run"
        for e in events
    )
    job_points: Dict[str, List[Tuple[float, str, int, int]]] = {}

    try:
        from .trace import PHASE_ORDER
    except Exception:  # pragma: no cover - trace module is sibling
        PHASE_ORDER = ()

    for e in events:
        kind = e.get("event")
        t = float(e.get("t", 0.0))
        if kind == "wave":
            w, pid = track(e.get("worker"))
            call_sec = max(0.0, float(e.get("call_sec", 0.0)))
            start = clock.wall(w, e.get("mono"), t - call_sec)
            parent = {
                "pid": pid, "tid": _TID_DEVICE, "name": "wave",
                "start": start, "dur": call_sec,
                "args": {
                    k: e[k] for k in (
                        "waves", "depth", "unique", "flags", "occupancy",
                        "remaining",
                    ) if k in e
                },
                "children": [],
            }
            breakdown = e.get("wave_breakdown")
            if isinstance(breakdown, dict):
                order = [p for p in PHASE_ORDER if p in breakdown]
                order += sorted(k for k in breakdown if k not in order)
                at = start
                for ph in order:
                    try:
                        d = max(0.0, float(breakdown[ph]))
                    except (TypeError, ValueError):
                        continue
                    parent["children"].append({
                        "pid": pid, "tid": _TID_DEVICE, "name": ph,
                        "start": at, "dur": d, "args": {},
                    })
                    at += d
            slices.append(parent)
        elif kind == SPAN_EVENT:
            w, pid = track(e.get("worker"))
            host_sec = max(0.0, float(e.get("host_sec", 0.0)))
            start = clock.wall(w, e.get("mono"), t - host_sec)
            oneshot = e.get("scope") == "run"
            parent = {
                "pid": pid, "tid": _TID_HOST,
                "name": "knob_cache" if oneshot else "host",
                "start": start, "dur": host_sec,
                "args": {
                    k: e[k] for k in ("quantum", "job") if k in e
                },
                "children": [],
            }
            if not oneshot:
                for ph, rel_dur in sorted(
                    (e.get("spans") or {}).items()
                ):
                    try:
                        rel, dur = float(rel_dur[0]), float(rel_dur[1])
                    except (TypeError, IndexError, ValueError):
                        continue
                    if dur <= 0.0:
                        continue
                    if rel < 0.0:
                        # The previous record's flush write: a sibling
                        # slice at its true (earlier) position.
                        slices.append({
                            "pid": pid, "tid": _TID_HOST, "name": ph,
                            "start": start + rel, "dur": min(dur, -rel),
                            "args": {},
                        })
                    else:
                        parent["children"].append({
                            "pid": pid, "tid": _TID_HOST, "name": ph,
                            "start": start + rel, "dur": dur, "args": {},
                        })
                for ph, rel_dur in sorted(
                    (e.get("call_spans") or {}).items()
                ):
                    try:
                        rel, dur = float(rel_dur[0]), float(rel_dur[1])
                    except (TypeError, IndexError, ValueError):
                        continue
                    if dur <= 0.0 or rel >= 0.0:
                        continue
                    # In-call host work: before the tail, clamped so it
                    # cannot lap into the host slice.
                    slices.append({
                        "pid": pid, "tid": _TID_HOST, "name": ph,
                        "start": start + rel, "dur": min(dur, -rel),
                        "args": {},
                    })
            slices.append(parent)
        elif kind == "checkpoint" and not has_spans:
            w, pid = track(e.get("worker"))
            dur = max(0.0, float(e.get("write_sec", 0.0)))
            if dur > 0.0:
                slices.append({
                    "pid": pid, "tid": _TID_HOST, "name": "checkpoint",
                    "start": t - dur, "dur": dur, "args": {},
                })
        elif kind == "job_span":
            w, pid = track(e.get("worker"))
            dur = max(0.0, float(e.get("sec", 0.0)))
            slices.append({
                "pid": pid, "tid": _TID_JOBS,
                "name": str(e.get("span", "span")),
                "start": t - dur, "dur": dur,
                "args": {"job": e.get("job")},
            })
        if kind in _FLOW_EVENTS:
            w, pid = track(e.get("worker"))
            jids = e.get("jobs") if kind == "gang_dispatch" else None
            if jids is None:
                jids = [e.get("job")] if e.get("job") else []
            phase = (
                0 if kind in _SUBMIT_EVENTS
                else (2 if kind in _FINISH_EVENTS else 1)
            )
            for jid in jids:
                if jid is None:
                    continue
                job_points.setdefault(str(jid), []).append(
                    (t, kind, pid, phase)
                )

    # Job lifecycle anchors + flow arrows: s at the first point, t at
    # the middles, f at the last — every started flow resolves.
    flow_ids = {
        jid: i + 1 for i, jid in enumerate(sorted(job_points))
    }
    for jid, points in sorted(job_points.items()):
        points.sort()
        if len(points) < 2:
            continue
        for i, (t, kind, pid, _phase) in enumerate(points):
            slices.append({
                "pid": pid, "tid": _TID_JOBS, "name": kind,
                "start": t, "dur": 0.0, "args": {"job": jid},
            })
            ph = "s" if i == 0 else ("f" if i == len(points) - 1 else "t")
            flow = {
                "ph": ph, "id": flow_ids[jid], "pid": pid,
                "tid": _TID_JOBS, "name": "job", "cat": "job",
                "start": t,
            }
            if ph == "f":
                flow["bp"] = "e"
            flows.append(flow)

    if not slices and not flows:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    t0 = min(
        [s["start"] for s in slices]
        + [s["start"] for sl in slices for s in sl.get("children", ())]
        + [f["start"] for f in flows]
    )

    def us(x: float) -> int:
        return max(0, int(round((x - t0) * _US)))

    out: List[Dict] = []
    for w, pid in sorted(workers.items()):
        out.append({
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": w}, "ts": 0,
        })
        for tid, label in (
            (_TID_DEVICE, "device"), (_TID_HOST, "host"),
            (_TID_JOBS, "jobs"),
        ):
            out.append({
                "ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                "args": {"name": label}, "ts": 0,
            })
    for s in slices:
        ts, dur = us(s["start"]), max(0, int(round(s["dur"] * _US)))
        ev = {
            "ph": "X", "pid": s["pid"], "tid": s["tid"],
            "name": s["name"], "ts": ts, "dur": dur, "args": s["args"],
        }
        out.append(ev)
        end = ts + dur
        for c in s.get("children", ()):
            cts = min(max(us(c["start"]), ts), end)
            cdur = max(0, min(int(round(c["dur"] * _US)), end - cts))
            out.append({
                "ph": "X", "pid": c["pid"], "tid": c["tid"],
                "name": c["name"], "ts": cts, "dur": cdur,
                "args": c["args"],
            })
    for f in flows:
        ev = dict(f)
        ev["ts"] = us(ev.pop("start"))
        out.append(ev)

    _sanitize_nesting(out)
    out.sort(key=_sort_key)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def _sort_key(ev: Dict) -> Tuple:
    return (
        0 if ev.get("ph") == "M" else 1,
        ev.get("ts", 0), ev.get("pid", 0), ev.get("tid", 0),
        -ev.get("dur", 0), str(ev.get("ph")), str(ev.get("name")),
        ev.get("id", 0),
    )


def _sanitize_nesting(events: List[Dict]) -> None:
    """Clamp microsecond rounding so every ``X`` slice either nests in
    or is disjoint from its track neighbours (the validator's rule)."""
    by_track: Dict[Tuple, List[Dict]] = {}
    for ev in events:
        if ev.get("ph") == "X":
            by_track.setdefault(
                (ev.get("pid"), ev.get("tid")), []
            ).append(ev)
    for track in by_track.values():
        track.sort(key=lambda e: (e["ts"], -e.get("dur", 0)))
        stack: List[Dict] = []
        for ev in track:
            while stack and ev["ts"] >= (
                stack[-1]["ts"] + stack[-1]["dur"]
            ):
                stack.pop()
            if stack:
                top_end = stack[-1]["ts"] + stack[-1]["dur"]
                if ev["ts"] + ev["dur"] > top_end:
                    ev["dur"] = max(0, top_end - ev["ts"])
            stack.append(ev)


def validate_trace(trace: Dict) -> List[str]:
    """Structural validation of a Chrome trace-event object; returns a
    list of problems (empty = valid).  Checks the invariants Perfetto
    and ``chrome://tracing`` rely on: every event carries ``ph``;
    ``X`` slices have nonnegative integer ``ts``/``dur`` and are
    well-nested per (pid, tid) track; ``B``/``E`` pairs balance per
    track; every flow ``s`` resolves to an ``f`` and every flow event
    lands on a slice."""
    problems: List[str] = []
    if not isinstance(trace, dict) or not isinstance(
        trace.get("traceEvents"), list
    ):
        return ["trace must be a dict with a traceEvents list"]
    try:
        json.dumps(trace)
    except (TypeError, ValueError) as exc:
        return [f"trace is not JSON-serializable: {exc}"]
    xs: Dict[Tuple, List[Dict]] = {}
    bes: Dict[Tuple, List[Dict]] = {}
    flow_phases: Dict = {}
    flow_events: List[Dict] = []
    for i, ev in enumerate(trace["traceEvents"]):
        if not isinstance(ev, dict) or "ph" not in ev:
            problems.append(f"event {i}: missing ph")
            continue
        ph = ev["ph"]
        if ph == "M":
            continue
        if not isinstance(ev.get("ts"), (int, float)):
            problems.append(f"event {i} ({ph}): missing ts")
            continue
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "X":
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                problems.append(
                    f"event {i}: X slice needs dur >= 0, got "
                    f"{ev.get('dur')!r}"
                )
                continue
            xs.setdefault(key, []).append(ev)
        elif ph in ("B", "E"):
            bes.setdefault(key, []).append(ev)
        elif ph in ("s", "t", "f"):
            if "id" not in ev:
                problems.append(f"event {i}: flow {ph} without id")
                continue
            flow_phases.setdefault(ev["id"], set()).add(ph)
            flow_events.append(ev)
    for key, track in xs.items():
        track.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: List[Dict] = []
        for ev in track:
            while stack and ev["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
                stack.pop()
            if stack:
                top_end = stack[-1]["ts"] + stack[-1]["dur"]
                if ev["ts"] + ev["dur"] > top_end:
                    problems.append(
                        f"track {key}: slice {ev.get('name')!r} at "
                        f"ts={ev['ts']} overlaps {stack[-1].get('name')!r} "
                        "without nesting"
                    )
            stack.append(ev)
    for key, track in bes.items():
        track.sort(key=lambda e: (e["ts"], 0 if e["ph"] == "E" else 1))
        depth: List[str] = []
        for ev in sorted(track, key=lambda e: e["ts"]):
            if ev["ph"] == "B":
                depth.append(str(ev.get("name")))
            elif not depth:
                problems.append(
                    f"track {key}: E without matching B at ts={ev['ts']}"
                )
            else:
                depth.pop()
        if depth:
            problems.append(
                f"track {key}: {len(depth)} unclosed B event(s)"
            )
    for fid, phases in sorted(flow_phases.items(), key=str):
        if "s" in phases and "f" not in phases:
            problems.append(f"flow id {fid!r}: started but never finishes")
        if "f" in phases and "s" not in phases:
            problems.append(f"flow id {fid!r}: finishes but never starts")
    for ev in flow_events:
        key = (ev.get("pid"), ev.get("tid"))
        ts = ev["ts"]
        if not any(
            s["ts"] <= ts <= s["ts"] + s["dur"] for s in xs.get(key, ())
        ):
            problems.append(
                f"flow {ev['ph']} id={ev.get('id')!r} at ts={ts} binds "
                f"to no slice on track {key}"
            )
    return problems


def export_timeline(paths, out: Optional[str] = None) -> Dict:
    """Export one or more journals (files, run dirs, or fleet dirs)
    into a single aligned Chrome trace-event object; write it to
    ``out`` when given.  Multi-journal merges are deterministic:
    input order never changes the output."""
    if isinstance(paths, (str, os.PathLike)):
        paths = [paths]
    events: List[Dict] = []
    for p in paths:
        evs, _skipped = read_journal_stats(
            resolve_journal(str(p)), include_sync=True
        )
        events.extend(evs)
    trace = build_trace(events)
    if out:
        parent = os.path.dirname(out)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(out, "w", encoding="utf-8") as fh:
            json.dump(trace, fh, sort_keys=True)
            fh.write("\n")
    return trace


def timeline_main(args: List[str]) -> int:
    """The ``timeline`` CLI verb: ``timeline export <journal|dir>...
    [--out FILE]`` — export, validate, and report one summary line."""
    args = list(args)
    if args and args[0] == "export":
        args = args[1:]
    out = None
    paths: List[str] = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--out":
            if i + 1 >= len(args):
                print("timeline: --out needs a path")
                return 2
            out = args[i + 1]
            i += 2
        elif a.startswith("--"):
            print(f"timeline: unknown flag {a!r}")
            return 2
        else:
            paths.append(a)
            i += 1
    if not paths:
        print(
            "usage: timeline export <journal.jsonl|run-dir|fleet-dir>... "
            "[--out FILE]"
        )
        return 2
    try:
        resolved = [resolve_journal(p) for p in paths]
    except FileNotFoundError as exc:
        print(f"timeline: {exc}")
        return 2
    if out is None:
        out = resolved[0] + ".trace.json"
    trace = export_timeline(paths, out=out)
    problems = validate_trace(trace)
    n_slices = sum(
        1 for e in trace["traceEvents"] if e.get("ph") == "X"
    )
    n_flows = sum(
        1 for e in trace["traceEvents"] if e.get("ph") in ("s", "t", "f")
    )
    n_tracks = len({
        e.get("pid") for e in trace["traceEvents"] if e.get("ph") != "M"
    })
    print(
        f"timeline: journals={len(resolved)} slices={n_slices} "
        f"flows={n_flows} workers={n_tracks} "
        f"valid={'yes' if not problems else 'NO'} out={out}"
    )
    for p in problems[:10]:
        print(f"timeline: problem: {p}")
    return 0 if not problems else 1
