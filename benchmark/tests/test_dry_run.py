"""A CPU dry run of every traffic file: the harness runs end to end and
decides ``correct``, and refuses to report a device metric off the
chip.  The command itself refuses to run at all without a TPU."""

import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, run_small


@pytest.mark.parametrize("key", ["twophase-8.exhaustive", "paxos-3.hunt",
                                 "sharded4.bounded"])
@pytest.mark.parametrize("trace", [False, True])
def test_dry_run_is_correct_and_reports_no_metric(key, trace):
    out = run_small(key, seconds=1.0, trace=trace)
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "compared"


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "paxos-3.hunt",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout == ""
