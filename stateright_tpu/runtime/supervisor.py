"""Run supervisor: crash/hang detection for a check in a child process,
and checkpointed auto-resume with an adaptive geometry backoff.

The failure modes this subsystem absorbs: a TPU runtime that
hard-crashes on a device call (round 5 saw it past ~80 s per call), and
a driver that kills the whole process at a wall deadline (rc=124).  The
parent never touches JAX, so the child alone holds the chip.  The
engines already persist full run state (``save_snapshot`` /
``resume_from``); the supervisor turns those primitives into resilience:

- the check runs in an isolated CHILD process, so a poisoned TPU runtime
  (a crashed worker fails every later device call in that process, retries
  included) costs one attempt, never the parent;
- the child checkpoints every N waves / T seconds through the engine's
  journal/checkpoint hooks, atomically (write + rename);
- the parent watches the child's journal for liveness: death and hangs are
  both detected, and the next attempt resumes from the latest checkpoint;
- each crash restart applies :func:`relax_geometry` — straight to
  ``dedup_factor=1``, never stepwise, because the intermediate stop was
  itself measured as a NEW worker-crash geometry (commit history: the
  dd=2-at-doubled-frontier stop crashed where dd=1 completes).

This is the swarm-verification / TLC-checkpointing recipe (PAPERS.md):
restartable workers plus durable progress state.

Observability: the child's ``result.json`` carries the checker's full
``metrics()`` snapshot, and a tiered child spawned with ``trace=True``
in its engine kwargs streams enriched per-wave trace records (and a
final ``trace_summary`` event) into the run dir's ``journal.jsonl`` —
the wave-trace artifact (docs/OBSERVABILITY.md).  ``relax_geometry``
never touches ``trace``: backoff changes tuning knobs only, and whether
a run is traced is a user decision, not a geometry.  Traced children
never RESUME (the tiered engine refuses trace+resume); a restarted
traced child starts from scratch, keeping its journaled trace records.
"""

from __future__ import annotations

import os
import pickle
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from .compile_cache import ENV_VAR, compile_cache_dir
from .journal import Journal, last_event, read_journal

# File names inside a supervised run directory.
JOURNAL_FILE = "journal.jsonl"
CHECKPOINT_FILE = "checkpoint.npz"
SPEC_FILE = "spec.pkl"
CHILD_CONFIG_FILE = "child_config.json"
RELAX_FILE = "relax.json"
RESULT_FILE = "result.json"
ERROR_FILE = "error.txt"
CHILD_LOG_FILE = "child.log"

# Child exit code for a clean Python-level failure (written to ERROR_FILE),
# as opposed to a runtime kill (signal) or an interpreter abort.
CHILD_ERROR_RC = 3

# Exit code for a COMPLETED check that discovered a property violation
# (cli.py check-tpu / submit): nonzero so CI and service callers can
# gate on the verdict, distinct from crash (1) / usage (2) / error (3).
# The supervisor treats a CLI child exiting with this code as done —
# a found counterexample is a result, not a failure to retry.
VIOLATION_RC = 4


def load_json_or_default(path: str, default: dict) -> dict:
    """Tolerant run-dir artifact read, shared by every relax.json /
    child_config.json consumer: a missing OR torn file (killed writer)
    degrades to the default instead of bricking the run dir."""
    import json

    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except (FileNotFoundError, json.JSONDecodeError):
        return default


# --- geometry backoff --------------------------------------------------------

# Engine defaults the policy assumes when the caller left a knob unset.
_DEFAULTS = {
    "tpu": {"dedup_factor": 8, "frontier_key": "max_frontier",
            "frontier": 1 << 15},
    "sharded": {"dedup_factor": 4, "frontier_key": "chunk_size",
                "frontier": 1 << 11},
}
# The tiered engine is the single-chip engine plus a cold tier — same
# knob names, same crash-relevant geometry axes.
_DEFAULTS["tiered"] = _DEFAULTS["tpu"]
# The composed engine is the sharded engine plus per-shard cold tiers:
# sharded knob names (chunk_size), sharded defaults.
_DEFAULTS["tiered-sharded"] = _DEFAULTS["sharded"]
FRONTIER_FLOOR = 2048
WAVES_PER_CALL_FLOOR = 8


def relax_geometry(engine_kwargs: dict, engine: str = "tpu") -> Optional[dict]:
    """One backoff step for a crashed run's engine geometry; None when
    nothing is left to relax.

    Ordered by what the crash evidence supports:

    1. ``dedup_factor`` goes STRAIGHT to the always-safe 1, never
       stepwise: the intermediate stop (dd=2 at a doubled frontier) was
       measured as a NEW worker-crash geometry on the 61.5M-state 2pc run,
       while dd=1 — same unique-buffer lanes — completes.
    2. The frontier/chunk halves (floor 2048): smaller chunks shorten the
       per-wave device time behind the round-5 worker crashes past ~80 s.
    3. ``waves_per_call`` halves (floor 8): per-call device time is
       waves_per_call x per-wave cost, the common thread across every
       observed hard crash.

    The returned dict is a NEW kwargs mapping (the input is not mutated);
    resumed runs adopt the snapshot's table/log geometry, so relaxing
    these tuning-only knobs never changes results, only overflow/crash
    behavior.

    Only ``dedup_factor`` is ever relaxed from an engine DEFAULT; the
    frontier and waves_per_call steps require the knob to be present in
    the kwargs.  Writing a frontier derived from the assumed default
    would OVERRIDE a smaller model-specific setting the caller never
    exposed here (e.g. a CLI spec's tuned ``tpu_kwargs``) with a much
    larger one — lengthening per-call device time, the very axis the
    backoff exists to shrink.
    """
    d = _DEFAULTS[engine]
    kwargs = dict(engine_kwargs)
    dd = int(kwargs.get("dedup_factor", d["dedup_factor"]))
    if dd > 1:
        kwargs["dedup_factor"] = 1
        return kwargs
    fkey = d["frontier_key"]
    frontier = kwargs.get(fkey)
    if frontier is not None and int(frontier) > FRONTIER_FLOOR:
        kwargs[fkey] = max(FRONTIER_FLOOR, int(frontier) // 2)
        return kwargs
    wpc = kwargs.get("waves_per_call")
    if wpc is not None and int(wpc) > WAVES_PER_CALL_FLOOR:
        kwargs["waves_per_call"] = max(WAVES_PER_CALL_FLOOR, int(wpc) // 2)
        return kwargs
    return None


# --- checkpointed run supervision --------------------------------------------


@dataclass
class CheckSpec:
    """A supervised check, in picklable form (the child rebuilds it in a
    fresh process).  ``model_factory`` must be a module-level callable —
    e.g. a model class, ``functools.partial`` over one, or a helper like
    ``serve.workloads.build_model`` — because lambdas do not pickle."""

    model_factory: Callable
    factory_args: tuple = ()
    factory_kwargs: dict = field(default_factory=dict)
    engine: str = "tpu"  # "tpu" | "sharded" | "tiered" | "tiered-sharded"
    engine_kwargs: dict = field(default_factory=dict)
    target_state_count: Optional[int] = None
    target_max_depth: Optional[int] = None
    timeout: Optional[float] = None

    def build_model(self):
        return self.model_factory(*self.factory_args, **self.factory_kwargs)


@dataclass
class SupervisorConfig:
    run_dir: str
    # Checkpoint cadence, forwarded to the engine's checkpoint hooks.
    checkpoint_every_waves: Optional[int] = None
    checkpoint_every_sec: Optional[float] = 30.0
    # Wall deadline for the WHOLE supervised run (all attempts); on expiry
    # the child is killed and a partial result (from the journal) returned.
    wall_deadline_sec: Optional[float] = None
    # Liveness: a child whose journal stops moving for this long is hung
    # (the observed TPU hang mode leaves the process alive but stuck in a
    # device call) and is killed + restarted from the last checkpoint.
    call_deadline_sec: float = 300.0
    max_restarts: int = 3
    poll_interval_sec: float = 0.25
    resume: bool = True  # resume from an existing checkpoint in run_dir
    # Apply relax_geometry() on crash restarts (tuning-only; results are
    # unaffected because resumes adopt the snapshot's geometry).
    geometry_backoff: bool = True
    # Which engine's geometry defaults the backoff assumes when
    # supervising a child_argv (spec mode reads the spec's engine).
    engine: str = "tpu"
    # CLI mode streams the child's report lines to the parent's stdout;
    # library mode captures them to run_dir/child.log.
    inherit_output: bool = False


class SupervisorError(RuntimeError):
    pass


class RunSupervisor:
    """Supervises one checkpointed check to completion across child
    crashes, hangs, and restarts.

    Two child modes share the monitor loop: a :class:`CheckSpec` (pickled
    into the run dir; the child is ``python -m
    stateright_tpu.runtime.child RUN_DIR``) or an explicit ``child_argv``
    (the CLI re-invokes the model module's own CLI with
    ``--checkpoint-dir/--resume``).
    """

    def __init__(
        self,
        config: SupervisorConfig,
        spec: Optional[CheckSpec] = None,
        child_argv: Optional[List[str]] = None,
        engine_kwargs: Optional[dict] = None,
    ):
        """``engine_kwargs`` seeds the geometry-backoff state in
        child_argv mode, where the supervisor cannot see the child's
        actual engine settings (the CLI passes its spec's ``tpu_kwargs``
        here so the frontier relax steps can fire — the policy only
        relaxes knobs it can see).  Ignored in spec mode, which reads
        the spec's own engine_kwargs."""
        if (spec is None) == (child_argv is None):
            raise ValueError("provide exactly one of spec or child_argv")
        self.config = config
        self.spec = spec
        self._child_argv = child_argv
        self._proc: Optional[subprocess.Popen] = None
        self.run_dir = os.path.abspath(config.run_dir)
        self.journal_path = os.path.join(self.run_dir, JOURNAL_FILE)
        self.checkpoint_path = os.path.join(self.run_dir, CHECKPOINT_FILE)
        self.result_path = os.path.join(self.run_dir, RESULT_FILE)
        self._engine_kwargs = dict(
            spec.engine_kwargs if spec is not None else (engine_kwargs or {})
        )
        # The completing child's exit code ("done" outcomes only): lets
        # the CLI propagate a VIOLATION_RC verdict through supervision.
        self.last_child_rc: Optional[int] = None

    # -- setup ----------------------------------------------------------------

    def _prepare(self) -> Journal:
        import json

        os.makedirs(self.run_dir, exist_ok=True)
        if not self.config.resume:
            # A fresh (non-resume) session must not inherit ANY state
            # from a previous one — including the journal, whose stale
            # last wave event would otherwise surface as this run's
            # "partial progress" on an early wall-deadline.
            for name in (CHECKPOINT_FILE, RELAX_FILE, RESULT_FILE,
                         ERROR_FILE, JOURNAL_FILE, CHILD_LOG_FILE):
                try:
                    os.remove(os.path.join(self.run_dir, name))
                except FileNotFoundError:
                    pass
        else:
            # A resumed session inherits the previous session's proven
            # relaxation: re-seeding the backoff from the unrelaxed spec
            # kwargs would, on the next crash, overwrite relax.json with
            # a geometry already known to crash.
            self._engine_kwargs.update(
                load_json_or_default(
                    os.path.join(self.run_dir, RELAX_FILE), {}
                )
            )
        # A stale result from a previous completed run must never be
        # mistaken for this run's outcome.
        try:
            os.remove(self.result_path)
        except FileNotFoundError:
            pass
        if self.spec is not None:
            with open(os.path.join(self.run_dir, SPEC_FILE), "wb") as fh:
                pickle.dump(self.spec, fh)
            with open(
                os.path.join(self.run_dir, CHILD_CONFIG_FILE), "w",
                encoding="utf-8",
            ) as fh:
                json.dump(
                    {
                        "checkpoint_every_waves":
                            self.config.checkpoint_every_waves,
                        "checkpoint_every_sec":
                            self.config.checkpoint_every_sec,
                        # Always true for the CHILD: config.resume only
                        # governs pre-existing checkpoints, which the
                        # non-resume branch above already deleted.
                        # Within-session crash restarts must resume from
                        # their own fresh checkpoint or every restart
                        # would start from scratch.
                        "resume": True,
                    },
                    fh,
                )
        return Journal(self.journal_path)

    def _child_command(self) -> List[str]:
        if self._child_argv is not None:
            return list(self._child_argv)
        return [sys.executable, "-m", "stateright_tpu.runtime.child",
                self.run_dir]

    def _child_env(self) -> dict:
        env = dict(os.environ)
        # The child must be able to import this package even when it is
        # not installed (the repo-checkout workflow).
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        parts = [pkg_root] + (
            env.get("PYTHONPATH", "").split(os.pathsep)
            if env.get("PYTHONPATH")
            else []
        )
        env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
        # Every restart of every child mode compiles the same programs:
        # one fixed cache directory lets the restarts hit it.
        env[ENV_VAR] = compile_cache_dir()
        return env

    # -- monitoring -----------------------------------------------------------

    @property
    def child_pid(self) -> Optional[int]:
        return self._proc.pid if self._proc is not None else None

    def _journal_activity(self) -> float:
        """Monotonic-comparable timestamp of the journal's last growth
        (file size is the signal: mtime granularity is filesystem-
        dependent)."""
        try:
            return os.stat(self.journal_path).st_size
        except FileNotFoundError:
            return -1.0

    def _kill_child(self) -> None:
        if self._proc is None or self._proc.poll() is not None:
            return
        try:
            self._proc.send_signal(signal.SIGKILL)
        except (ProcessLookupError, OSError):
            pass
        try:
            self._proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass

    def _partial_from_journal(self) -> Dict:
        wave = last_event(self.journal_path, "wave") or {}
        return {
            "completed": False,
            "unique_state_count": wave.get("unique", 0),
            "state_count": wave.get("states", 0),
            "max_depth": wave.get("depth", 0),
            "checkpoint": (
                self.checkpoint_path
                if os.path.exists(self.checkpoint_path)
                else None
            ),
        }

    def _read_error(self) -> str:
        try:
            with open(
                os.path.join(self.run_dir, ERROR_FILE), encoding="utf-8"
            ) as fh:
                return fh.read()
        except FileNotFoundError:
            return ""

    def _log_tail(self, n: int = 2000) -> str:
        try:
            with open(
                os.path.join(self.run_dir, CHILD_LOG_FILE),
                encoding="utf-8", errors="replace",
            ) as fh:
                return fh.read()[-n:]
        except FileNotFoundError:
            return ""

    # -- main loop ------------------------------------------------------------

    def run(self) -> Dict:
        """Supervise to completion; returns the child's result dict (or a
        partial one with ``completed: False`` on wall-deadline expiry).
        Raises :class:`SupervisorError` when restarts are exhausted or the
        child reports a Python-level error."""
        import json

        cfg = self.config
        journal = self._prepare()
        deadline = (
            time.monotonic() + cfg.wall_deadline_sec
            if cfg.wall_deadline_sec is not None
            else None
        )
        journal.append(
            "supervisor_start",
            run_dir=self.run_dir,
            engine_kwargs=self._engine_kwargs,
            max_restarts=cfg.max_restarts,
        )
        attempts = cfg.max_restarts + 1
        try:
            for attempt in range(1, attempts + 1):
                outcome = self._run_attempt(journal, attempt, deadline)
                if outcome == "done":
                    result = self._load_result()
                    journal.append("supervisor_done", attempt=attempt,
                                   result=result)
                    return result
                if outcome == "wall_timeout":
                    partial = self._partial_from_journal()
                    journal.append("wall_timeout", attempt=attempt,
                                   partial=partial)
                    return partial
                if outcome == "fatal":
                    msg = self._read_error() or self._log_tail()
                    journal.append("give_up", attempt=attempt,
                                   reason="deterministic child error")
                    raise SupervisorError(
                        f"child failed deterministically: {msg[:2000]}"
                    )
                # outcome == "crash": maybe relax geometry, then restart.
                if attempt == attempts:
                    journal.append("give_up", attempt=attempt,
                                   reason="restart budget exhausted")
                    raise SupervisorError(
                        f"supervised run crashed {attempts} times; "
                        f"last child log tail:\n{self._log_tail()}"
                    )
                if cfg.geometry_backoff:
                    engine = (
                        self.spec.engine if self.spec is not None
                        else cfg.engine
                    )
                    relaxed = relax_geometry(self._engine_kwargs, engine)
                    if relaxed is not None and relaxed != self._engine_kwargs:
                        self._engine_kwargs = relaxed
                        # Atomic like every other run artifact: a torn
                        # relax.json would fail every later child's JSON
                        # parse and brick the run dir.
                        relax_path = os.path.join(self.run_dir, RELAX_FILE)
                        with open(
                            relax_path + ".tmp", "w", encoding="utf-8"
                        ) as fh:
                            json.dump(relaxed, fh)
                        os.replace(relax_path + ".tmp", relax_path)
                        journal.append("relax", engine_kwargs=relaxed)
                journal.append(
                    "restart",
                    attempt=attempt + 1,
                    from_checkpoint=os.path.exists(self.checkpoint_path),
                )
            raise AssertionError("unreachable")  # loop always returns/raises
        finally:
            self._kill_child()
            journal.close()

    def _run_attempt(self, journal: Journal, attempt: int,
                     deadline: Optional[float]) -> str:
        """One child lifetime; returns "done" | "crash" | "fatal" |
        "wall_timeout"."""
        cfg = self.config
        cmd = self._child_command()
        if cfg.inherit_output:
            stdout = stderr = None
        else:
            logfh = open(
                os.path.join(self.run_dir, CHILD_LOG_FILE), "ab"
            )
            stdout = stderr = logfh
        try:
            self._proc = subprocess.Popen(
                cmd, stdout=stdout, stderr=stderr, env=self._child_env(),
                cwd=self.run_dir,
            )
        finally:
            if not cfg.inherit_output:
                logfh.close()  # the child holds its own descriptor

        last_size = self._journal_activity()
        last_change = time.monotonic()
        while True:
            rc = self._proc.poll()
            if rc is not None:
                break
            now = time.monotonic()
            if deadline is not None and now >= deadline:
                self._kill_child()
                return "wall_timeout"
            size = self._journal_activity()
            if size != last_size:
                last_size = size
                last_change = now
            elif now - last_change > cfg.call_deadline_sec:
                journal.append(
                    "hang", attempt=attempt,
                    stalled_sec=round(now - last_change, 1),
                )
                self._kill_child()
                return "crash"
            time.sleep(cfg.poll_interval_sec)

        if (
            rc == 0
            or (rc == VIOLATION_RC and self._child_argv is not None)
        ) and (
            self._child_argv is not None
            or os.path.exists(self.result_path)
        ):
            # rc=VIOLATION_RC from a CLI child is a COMPLETED check whose
            # verdict was a violation — done, never a crash to retry.
            self.last_child_rc = rc
            return "done"
        if rc == CHILD_ERROR_RC:
            # A clean Python-level failure is deterministic: the same
            # spec in a fresh process raises it again.
            journal.append("crash", attempt=attempt, returncode=rc,
                           deterministic=True,
                           error=self._read_error()[:500])
            return "fatal"
        if self._child_argv is not None and rc == 2:
            # CLI children exit 2 on usage errors — deterministic by
            # construction; retrying the identical argv cannot succeed.
            journal.append("crash", attempt=attempt, returncode=rc,
                           deterministic=True)
            return "fatal"
        journal.append("crash", attempt=attempt, returncode=rc)
        return "crash"

    def _load_result(self) -> Dict:
        import json

        if os.path.exists(self.result_path):
            with open(self.result_path, encoding="utf-8") as fh:
                return json.load(fh)
        # CLI mode: the child printed its own report; synthesize counts
        # from the journal for the caller.
        done = last_event(self.journal_path, "engine_done") or {}
        return {
            "completed": True,
            "unique_state_count": done.get("unique", 0),
            "state_count": done.get("states", 0),
            "max_depth": done.get("depth", 0),
        }


def journal_events(run_dir: str) -> List[Dict]:
    """All events of a supervised run directory's journal."""
    return read_journal(os.path.join(run_dir, JOURNAL_FILE))
