"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

- busy: the union of the intervals in which an operation ran on a
  device, clipped to the traced window and averaged over the devices;
- the device operations that took the most time, by the names the trace
  gives them;
- the idle gaps of each device, each named by the host span
  (``TraceAnnotation``) that was open during it, summed by name.

The traced window is the host span named ``window`` (the harness opens
it around the measured work); without one, the first to the last event
of the devices.  Times are seconds.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

# The line of a device plane that holds one event per executed op.
OP_LINES = ("XLA Ops",)
# The line whose events are whole programs.
MODULE_LINE = "XLA Modules"
# Host spans that name an idle gap: the harness's own and the program's
# (obs/timeline.py mirrors its host-tail phases as ``host/<phase>`` and
# wraps each loop quantum in a ``wave_quantum`` step annotation).
SPAN_PREFIXES = ("bench/", "host/", "wave_quantum")
NO_SPAN = "(no host span)"

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals, sorted by start."""
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) around merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host span that best explains an idle gap: the shortest span
    that covers at least half of it, else the one that covers most."""
    gs, ge = gap
    length = ge - gs
    best, best_cover, covering = None, 0.0, []
    for name, s, e in spans:
        cover = min(e, ge) - max(s, gs)
        if cover <= 0:
            continue
        if cover >= 0.5 * length:
            covering.append((e - s, name))
        if cover > best_cover:
            best, best_cover = name, cover
    if covering:
        return min(covering)[1]
    return best or NO_SPAN


def reduce_events(
    devices: Dict[str, Dict[str, List[Interval]]],
    spans: Sequence[Tuple[str, float, float]],
    window: Optional[Interval] = None,
    top: int = 10,
) -> Dict:
    """The reduction itself, on plain data so that tests can feed it.

    ``devices`` maps a device name to its op events (op name -> list of
    intervals); ``spans`` are host spans ``(name, start, end)``."""
    if not devices or not any(devices.values()):
        return {}
    if window is None:
        starts = [s for ops in devices.values() for iv in ops.values()
                  for s, _ in iv]
        ends = [e for ops in devices.values() for iv in ops.values()
                for _, e in iv]
        window = (min(starts), max(ends))
    lo, hi = window
    span_len = hi - lo
    if span_len <= 0:
        return {}
    named = [sp for sp in spans if sp[0].startswith(SPAN_PREFIXES)]
    busy_total, op_time, gap_time = 0.0, {}, {}
    for ops in devices.values():
        all_iv = []
        for name, iv in ops.items():
            iv = clip(iv, lo, hi)
            all_iv.extend(iv)
            op_time[name] = op_time.get(name, 0.0) + sum(e - s for s, e in iv)
        busy = union(all_iv)
        busy_total += sum(e - s for s, e in busy)
        for g in gaps(busy, lo, hi):
            key = name_gap(g, named)
            gap_time[key] = gap_time.get(key, 0.0) + (g[1] - g[0])
    n = len(devices)

    def ranked(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_total / n,
        "window_s": span_len,
        "device_ops": ranked(op_time),
        "idle_gaps": ranked(gap_time),
        "devices": sorted(devices),
    }


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, window_span: str = "bench/window"):
    """Read an xplane file into ``reduce_events``' arguments."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Interval]]] = {}
    spans: List[Tuple[str, float, float]] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            # Op names repeat across programs: prefix each op with the
            # program (``XLA Modules`` event) it ran in.
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 ev.name.split("(")[0])
                for ln in plane.lines if ln.name == MODULE_LINE
                for ev in ln.events)
            starts = [m[0] for m in modules]
            ops: Dict[str, List[Interval]] = {}
            for ln in plane.lines:
                if ln.name not in OP_LINES:
                    continue
                for ev in ln.events:
                    i = bisect.bisect_right(starts, ev.start_ns) - 1
                    mod = (modules[i][2] + "/" if i >= 0
                           and ev.start_ns < modules[i][1] else "")
                    # "%fusion.12 = u32[...] fusion(...)": the op's name
                    name = mod + ev.name.split(" = ")[0]
                    s = ev.start_ns * 1e-9
                    ops.setdefault(name, []).append(
                        (s, s + ev.duration_ns * 1e-9))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if ev.name == window_span:
                        window = (s, e)
                    spans.append((ev.name, s, e))
    return devices, spans, window


def reduce_trace(trace_dir: str) -> Dict:
    """busy_s, window_s, device_ops, idle_gaps of the newest trace under
    ``trace_dir``; ``{}`` when there is none or no device op in it."""
    path = find_xplane(trace_dir)
    if path is None:
        return {}
    devices, spans, window = load(path)
    return reduce_events(devices, spans, window)

