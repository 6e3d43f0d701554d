"""The trace reduction: busy time as a union of op intervals, idle gaps
named by the host span open during them, and the same on a small trace
recorded on a v5e (data/v5e_small.xplane.pb)."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_busy_is_the_union_not_the_sum():
    # Two ops overlap on one device: busy is 3 s of a 10 s window, not 4.
    devices = {"/device:TPU:0": {"a": [(0.0, 2.0)], "b": [(1.0, 3.0)]}}
    out = tr.reduce_events(devices, [], window=(0.0, 10.0))
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["device_ops"] == [["a", 2.0], ["b", 2.0]]


def test_busy_is_clipped_to_the_window_and_averaged_over_devices():
    devices = {"/device:TPU:0": {"a": [(-1.0, 1.0)]},
               "/device:TPU:1": {"a": [(0.0, 3.0)]}}
    out = tr.reduce_events(devices, [], window=(0.0, 4.0))
    assert out["busy_s"] == pytest.approx(2.0)  # (1 + 3) / 2


def test_gap_is_named_by_the_innermost_span_covering_it():
    devices = {"/device:TPU:0": {"op": [(0.0, 1.0), (5.0, 6.0)]}}
    spans = [
        ("bench/window", 0.0, 6.0),
        ("bench/request", 0.5, 6.0),
        ("bench/spawn", 1.0, 4.5),  # covers 3.5 of the 4 s gap
        ("PjitFunction(run)", 4.5, 5.0),  # not a named span: ignored
    ]
    out = tr.reduce_events(devices, spans, window=(0.0, 6.0))
    assert out["idle_gaps"] == [["bench/spawn", 4.0]]


def test_gap_with_no_span_and_empty_trace():
    devices = {"/device:TPU:0": {"op": [(0.0, 1.0)]}}
    out = tr.reduce_events(devices, [], window=(0.0, 3.0))
    assert out["idle_gaps"] == [[tr.NO_SPAN, 2.0]]
    assert tr.reduce_events({}, [], window=(0.0, 1.0)) == {}


def test_recorded_v5e_trace():
    """Three sort programs under ``host/readback`` with 20 ms sleeps under
    ``bench/spawn`` between them, traced on one v5e (PR 22)."""
    path = DATA / "v5e_small.xplane.pb"
    devices, spans, window = tr.load(str(path))
    assert list(devices) == ["/device:TPU:0"]
    out = tr.reduce_events(devices, spans, window)
    assert 0 < out["busy_s"] < out["window_s"]
    names = [n for n, _ in out["idle_gaps"]]
    assert names[0] == "bench/spawn"
    spawn = dict(out["idle_gaps"])["bench/spawn"]
    assert 0.06 <= spawn <= out["window_s"]  # three 20 ms sleeps
    # ops are named by their program and their HLO name
    assert out["device_ops"][0][0] == "jit__lambda/%sort.6"
