"""Helpers for the benchmark's own tests.  They run on the CPU at small
sizes (``JAX_PLATFORMS=cpu``); a run that needs JAX goes to a child
process, so that a planted fault never meets a program compiled sound
in the same process."""

import json
import os
import pathlib
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# The cells at a size a test run holds: the same configuration files,
# traffic files and cell files, with a smaller n.  2pc at 7 (296,448
# states) is the smallest at which the 32-bit control is sure to lose
# states (about ten expected collisions).
SMALL = {
    "twophase-8.exhaustive": {"n": 3, "cell_file": None},
    "twophase-8.exhaustive@7": {"n": 7, "cell_file": None},
    "paxos-3.exhaustive": {"n": 2, "cell_file": {"expect": {
        "unique": 16668, "max_depth": 21, "discoveries": ["value chosen"]}}},
    "paxos-3.hunt": {"n": 2, "cell_file": None},
}

CHILD = r"""
import json, sys
sys.path.insert(0, {bench!r})
import run, control
bench = run.load_json(run.ROOT / "BENCHMARK.json")
cell = next(w for w in bench["workloads"] if w["name"] == {cell!r})
cfgs = {{c["name"]: c for c in bench["configs"]}}
config = dict(run.load_json(run.ROOT / cfgs[cell["config"]]["file"]), n={n})
cell_file = {cell_file!r} or run.load_json(run.BENCH / "cells" / ({cell!r} + ".json"))
spec = {{"bench": bench, "cell": cell, "config": config, "cell_file": cell_file,
        "traffic": run.load_json(run.BENCH / "traffic" / (cell["traffic"] + ".json"))}}
if {plant!r}:
    out = control.run_planted({cell!r}, {plant!r}, [11], {seconds}, False, spec)[0]
else:
    out = run.run({cell!r}, 11, {seconds}, {trace}, False, spec)
print(json.dumps(out))
"""


def run_small(key, plant=None, seconds=1.0, trace=False):
    """One small run of a cell in a child process on the CPU; its result."""
    cell = key.split("@")[0]
    small = SMALL[key]
    code = CHILD.format(bench=str(BENCH), cell=cell, n=small["n"],
                        cell_file=small["cell_file"], plant=plant,
                        seconds=seconds, trace=trace)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])
