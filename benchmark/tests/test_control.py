"""The control and each planted fault come out ``correct: false``; the
sound run beside them comes out true.  Small sizes on the CPU; the chip
readings at the cells' own sizes are in PERF.md."""

import pytest

from conftest import run_small


def test_sound_runs_are_correct():
    assert run_small("paxos-3.exhaustive")["correct"] is True


@pytest.mark.parametrize("key,plant,number", [
    ("twophase-8.exhaustive@7", "fp32", "unique_gap"),
    ("twophase-8.exhaustive", "half", "unique_gap"),
    ("twophase-8.exhaustive", "answer_count", "unique_gap"),
    ("paxos-3.hunt", "dfs", "path_gap"),
    ("paxos-3.hunt", "answer_path", "holds_at_end"),
    ("paxos-3.hunt", "answer_drop", "not_found"),
    ("paxos-3.exhaustive", "half", "depth_gap"),
    ("twophase-8.exhaustive", "answer_drop", "discovery_names_wrong"),
])
def test_plant_fails_its_number(key, plant, number):
    out = run_small(key, plant=plant, seconds=0.5)
    assert out["correct"] is False
    c = out["compared"]
    assert c[number]["value"] > c[number]["limit"]
