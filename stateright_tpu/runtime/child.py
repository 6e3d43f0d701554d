"""Child-process entry for supervised runs:
``python -m stateright_tpu.runtime.child RUN_DIR``.

Rebuilds the pickled :class:`~stateright_tpu.runtime.supervisor.CheckSpec`
in a fresh process, spawns the checker with the journal/checkpoint hooks
pointed into the run directory, resumes from the latest checkpoint when
one exists, and writes ``result.json`` on completion.  A Python-level
failure is written to ``error.txt`` and exits with rc=3 so the supervisor
can separate deterministic errors (no retry) from runtime kills (retry +
geometry backoff).

Fault injection (used by the crash-resilience tests, harmless otherwise):
``STATERIGHT_RUNTIME_FAULT_EXIT_AFTER_CHECKPOINT=<rc>`` makes a
NON-resumed child die with ``os._exit(rc)`` as soon as its first
checkpoint lands — a deterministic stand-in for the mid-run worker kill.
"""

from __future__ import annotations

import json
import os
import pickle
import sys
import time
import traceback

from .compile_cache import use_compile_cache
from .journal import Journal
from .supervisor import (
    CHECKPOINT_FILE,
    CHILD_CONFIG_FILE,
    CHILD_ERROR_RC,
    ERROR_FILE,
    JOURNAL_FILE,
    RELAX_FILE,
    RESULT_FILE,
    SPEC_FILE,
    load_json_or_default,
)

FAULT_ENV = "STATERIGHT_RUNTIME_FAULT_EXIT_AFTER_CHECKPOINT"




def run_child(run_dir: str) -> int:
    run_dir = os.path.abspath(run_dir)
    # Restarted children recompile the same programs; the fixed cache
    # directory lets every resume hit them.
    use_compile_cache()

    journal = Journal(os.path.join(run_dir, JOURNAL_FILE))
    try:
        with open(os.path.join(run_dir, SPEC_FILE), "rb") as fh:
            spec = pickle.load(fh)
        cfg = load_json_or_default(
            os.path.join(run_dir, CHILD_CONFIG_FILE), {}
        )
        relax = load_json_or_default(os.path.join(run_dir, RELAX_FILE), {})

        checkpoint = os.path.join(run_dir, CHECKPOINT_FILE)
        engine_kwargs = dict(spec.engine_kwargs)
        engine_kwargs.update(relax)
        # Traced children never resume: the tiered engine refuses
        # trace=True + resume_from (tracing is a diagnostic mode), and a
        # restart that passed both would die in __init__ on every
        # attempt — burning the supervisor's restarts in seconds.  A
        # traced child restarts from scratch instead; its journal still
        # carries every completed wave's trace records.
        resumed = (
            bool(cfg.get("resume", True))
            and os.path.exists(checkpoint)
            and not bool(engine_kwargs.get("trace"))
        )
        engine_kwargs.update(
            journal=journal,
            checkpoint_path=checkpoint,
            checkpoint_every_waves=cfg.get("checkpoint_every_waves"),
            checkpoint_every_sec=cfg.get("checkpoint_every_sec"),
        )
        if resumed:
            engine_kwargs["resume_from"] = checkpoint

        journal.append(
            "run_start", pid=os.getpid(), resumed=resumed,
            engine=spec.engine, engine_kwargs={
                k: v for k, v in engine_kwargs.items()
                if isinstance(v, (int, float, str, bool, type(None)))
            },
        )

        model = spec.build_model()
        builder = model.checker()
        if spec.target_state_count is not None:
            builder = builder.target_state_count(spec.target_state_count)
        if spec.target_max_depth is not None:
            builder = builder.target_max_depth(spec.target_max_depth)
        if spec.timeout is not None:
            builder = builder.timeout(spec.timeout)
        if spec.engine == "sharded":
            checker = builder.spawn_tpu_sharded(**engine_kwargs)
        elif spec.engine == "tiered":
            checker = builder.spawn_tpu_tiered(**engine_kwargs)
        elif spec.engine == "tiered-sharded":
            checker = builder.spawn_tpu_tiered_sharded(**engine_kwargs)
        else:
            checker = builder.spawn_tpu(**engine_kwargs)

        fault_rc = os.environ.get(FAULT_ENV)
        if fault_rc is not None and not resumed:
            # Die mid-run, deterministically, once durable progress
            # exists — the test stand-in for a TPU worker kill.  Only a
            # non-resumed child dies, so the restarted attempt completes.
            while not checker.is_done():
                if os.path.exists(checkpoint):
                    journal.append("fault_injected", rc=int(fault_rc))
                    os._exit(int(fault_rc))
                time.sleep(0.005)

        checker.join()
        discoveries = checker.discoveries()
        result = {
            "completed": True,
            "unique_state_count": checker.unique_state_count(),
            "state_count": checker.state_count(),
            "max_depth": checker.max_depth(),
            "discoveries": sorted(discoveries),
            "discovery_classifications": {
                name: checker.discovery_classification(name)
                for name in discoveries
            },
            # The observability snapshot rides the durable result, so a
            # supervised run's wave cadence / occupancy / trace summary
            # survive the child process (docs/OBSERVABILITY.md).
            "metrics": checker.metrics(),
        }
        tmp = os.path.join(run_dir, RESULT_FILE + ".tmp")
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(result, fh)
        os.replace(tmp, os.path.join(run_dir, RESULT_FILE))
        journal.append("run_end", **result)
        return 0
    except Exception:
        err = traceback.format_exc()
        with open(
            os.path.join(run_dir, ERROR_FILE), "w", encoding="utf-8"
        ) as fh:
            fh.write(err)
        journal.append("child_error", error=err[-2000:])
        sys.stderr.write(err)
        return CHILD_ERROR_RC
    finally:
        journal.close()


if __name__ == "__main__":
    if len(sys.argv) != 2:
        print("usage: python -m stateright_tpu.runtime.child RUN_DIR",
              file=sys.stderr)
        sys.exit(2)
    sys.exit(run_child(sys.argv[1]))
