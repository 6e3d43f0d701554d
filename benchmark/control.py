#!/usr/bin/env python3
"""The control and the planted faults: a run of a cell exactly as
``run.py`` makes it, with one guarantee of the timed path broken
underneath.  Each must come out ``correct: false``.  The benchmark's
own runs never load this file.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> --plant <name>

Plants:

- ``fp32``, the control of the exhaustive cells: the device keeps 32 of
  its 64 fingerprint bits, the step that would tempt a later PR (half
  the table's key bytes).  Colliding states are lost.
- ``dfs``, the control of the hunt: the program's own depth-first
  engine in place of the breadth-first one; its counterexample is a
  path but not a shortest one.
- ``half``: half of the new states left out (all states whose low
  fingerprint bit is set collide into one).
- ``no_exchange``: the sharded engine's exchange between chips left out:
  ``all_to_all`` hands each shard back its own send buffers, so a state
  is inserted where it was generated and not by its owner.
- ``answer_count``, ``answer_path`` and ``answer_drop``: an answer
  altered where it is produced: the unique count one too high, the
  counterexample path one state short, the discoveries dropped.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import run  # noqa: E402


def _patch_fp(fn):
    from stateright_tpu.ops import device_fp

    orig = device_fp.device_fp64
    device_fp.device_fp64 = lambda words: fn(*orig(words))
    return lambda: setattr(device_fp, "device_fp64", orig)


def fp32():
    import jax.numpy as jnp

    return _patch_fp(lambda hi, lo: (jnp.ones_like(hi), lo))


def half():
    import jax.numpy as jnp

    def merge(hi, lo):
        drop = (lo & jnp.uint32(1)) == jnp.uint32(1)
        seven = jnp.uint32(7)
        return jnp.where(drop, seven, hi), jnp.where(drop, seven, lo)

    return _patch_fp(merge)


def no_exchange():
    import jax

    orig = jax.lax.all_to_all
    jax.lax.all_to_all = lambda x, *_a, **_kw: x  # each shard keeps its sends
    return lambda: setattr(jax.lax, "all_to_all", orig)


def dfs():
    from stateright_tpu.core.checker import CheckerBuilder

    orig = CheckerBuilder.spawn_tpu
    CheckerBuilder.spawn_tpu = lambda self, **_kw: self.spawn_dfs()
    return lambda: setattr(CheckerBuilder, "spawn_tpu", orig)


def _patch_answer(method: str, wrap):
    """Replace ``method`` of both device engines by ``wrap(original)``."""
    from stateright_tpu.parallel.sharded import ShardedTpuChecker
    from stateright_tpu.parallel.wavefront import TpuChecker

    origs = {cls: getattr(cls, method) for cls in (TpuChecker,
                                                   ShardedTpuChecker)}
    for cls, orig in origs.items():
        setattr(cls, method, wrap(orig))

    def undo():
        for cls, orig in origs.items():
            setattr(cls, method, orig)

    return undo


def answer_count():
    return _patch_answer("unique_state_count",
                         lambda orig: lambda self: orig(self) + 1)


def answer_path():
    from stateright_tpu.core.path import Path

    def short(orig):
        return lambda self: {k: Path(p._steps[:-1])
                             for k, p in orig(self).items()}

    return _patch_answer("discoveries", short)


def answer_drop():
    return _patch_answer("discoveries", lambda orig: lambda self: {})


PLANTS = {f.__name__: f for f in (fp32, half, no_exchange, dfs, answer_count,
                                  answer_path, answer_drop)}


def run_planted(name: str, plant: str, seeds, seconds: float,
                require_chip: bool = True, spec: dict = None) -> list:
    """One planted run per seed, in this one process; the results."""
    undo = PLANTS[plant]()
    try:
        return [run.run(name, seed, seconds, False, require_chip, spec)
                for seed in seeds]
    finally:
        undo()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plant", required=True, choices=sorted(PLANTS))
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        results = run_planted(args.workload, args.plant, seeds, args.seconds)
    except run.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 3
    for seed, r in zip(seeds, results):
        print(json.dumps({"plant": args.plant, "seed": seed,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "errors": r["errors"], "compared": r["compared"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
