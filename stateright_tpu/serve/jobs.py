"""Job store for the persistent checking service.

A *job* is one check request against the service's mesh: a registered
workload (serve/workloads.py) plus engine/config overrides, optionally
fanned into a diversified portfolio (serve/portfolio.py).  Jobs move
through a fixed lifecycle::

    queued -> running -> done | failed | cancelled

``cancelled`` is reachable from both ``queued`` (the job never starts)
and ``running`` (the scheduler forwards the cancel to the engine's
cooperative ``request_stop``, core/checker.py).  Every transition is
appended to the service journal (runtime/journal.py) as a ``job_*``
event, so the journal is the durable record of what the service did —
the swarm-verification requirement that restartable work leave an
auditable trail (PAPERS.md, Holzmann-Joshi-Groce).

The store itself is deliberately in-memory: the service owns one
process-lifetime mesh, and a job's expensive artifacts (compiled
programs, tuned knobs) persist in the program cache and knob cache, not
here.  docs/SERVING.md documents the lifecycle and the JSON shapes.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from typing import Callable, Dict, List, Optional

# Lifecycle states.
QUEUED = "queued"
RUNNING = "running"
DONE = "done"
FAILED = "failed"
CANCELLED = "cancelled"


def worker_id() -> str:
    """This process's worker identity (``pid@host``) — stamped on every
    ``job_*`` / ``fleet_*`` journal event so a multi-worker journal can
    attribute each lifecycle step to the process that performed it
    (without it, a failed job in a merged fleet journal names no
    culprit).  Computed per call: a forked worker must not inherit its
    parent's pid."""
    return f"{os.getpid()}@{socket.gethostname()}"

_ENGINES = (
    "tpu", "tiered", "sharded", "tiered-sharded", "bfs", "dfs",
    "simulation", "tpu_simulation",
)
_FINISH_WHEN = ("all", "any", "any_failures", "all_failures")


class JobCancelled(Exception):
    """Raised inside a job runner when its cancel event fired; carries
    the partial counts collected before the engine wound down."""

    def __init__(self, partial: Optional[dict] = None):
        super().__init__("job cancelled")
        self.partial = partial or {}


class JobSpec:
    """A validated check request (the ``POST /jobs`` body).

    Validation is loud and total: an unknown field, engine, or
    finish_when is a ``ValueError`` at submit time, never a dead job
    discovered minutes later on the worker thread.
    """

    FIELDS = (
        "workload", "n", "network", "engine", "engine_kwargs", "symmetry",
        "target_max_depth", "target_state_count", "timeout", "finish_when",
        "seed", "threads", "priority", "portfolio", "use_knob_cache",
        "store",
    )

    def __init__(
        self,
        workload: str,
        n: Optional[int] = None,
        network: Optional[str] = None,
        engine: str = "tpu",
        engine_kwargs: Optional[dict] = None,
        symmetry: bool = False,
        target_max_depth: Optional[int] = None,
        target_state_count: Optional[int] = None,
        timeout: Optional[float] = None,
        finish_when: Optional[str] = None,
        seed: int = 0,
        threads: Optional[int] = None,
        priority: int = 0,
        portfolio: Optional[dict] = None,
        use_knob_cache: bool = True,
        store: bool = False,
    ):
        if not workload or not isinstance(workload, str):
            raise ValueError("workload must be a nonempty string")
        if engine not in _ENGINES:
            raise ValueError(
                f"unknown engine {engine!r} (one of {', '.join(_ENGINES)})"
            )
        if finish_when is not None and finish_when not in _FINISH_WHEN:
            raise ValueError(
                f"unknown finish_when {finish_when!r} "
                f"(one of {', '.join(_FINISH_WHEN)})"
            )
        if portfolio is not None:
            if not isinstance(portfolio, dict):
                raise ValueError("portfolio must be an object")
            unknown = set(portfolio) - {
                "size", "seed", "parallelism", "simulation",
            }
            if unknown:
                raise ValueError(
                    f"unknown portfolio field(s): {', '.join(sorted(unknown))}"
                )
            if int(portfolio.get("size", 0)) < 2:
                raise ValueError("portfolio.size must be >= 2")
        if engine_kwargs is not None and not isinstance(engine_kwargs, dict):
            raise ValueError("engine_kwargs must be an object")
        if engine_kwargs and engine in ("bfs", "dfs", "simulation"):
            # The host engines take no spawn kwargs; silently dropping
            # them would let a misplaced knob pass unreported.
            raise ValueError(
                f"engine {engine!r} takes no engine_kwargs "
                "(host-engine tuning is the threads field)"
            )
        if store:
            # The verification store journals single-chip wavefront
            # runs (docs/INCREMENTAL.md): a portfolio's diversified
            # members explore property-dependently, and other engines
            # don't produce the store's snapshot format — silently
            # running them un-stored would make `store: true` a lie.
            if engine != "tpu":
                raise ValueError(
                    "store requires engine 'tpu' (the verification "
                    "store journals single-chip wavefront runs)"
                )
            if portfolio is not None:
                raise ValueError(
                    "store does not combine with portfolio jobs"
                )
        self.workload = workload
        self.n = None if n is None else int(n)
        self.network = network
        self.engine = engine
        self.engine_kwargs = dict(engine_kwargs or {})
        self.symmetry = bool(symmetry)
        self.target_max_depth = (
            None if target_max_depth is None else int(target_max_depth)
        )
        self.target_state_count = (
            None if target_state_count is None else int(target_state_count)
        )
        self.timeout = None if timeout is None else float(timeout)
        self.finish_when = finish_when
        self.seed = int(seed)
        self.threads = None if threads is None else int(threads)
        self.priority = int(priority)
        self.portfolio = None if portfolio is None else dict(portfolio)
        self.use_knob_cache = bool(use_knob_cache)
        self.store = bool(store)

    @classmethod
    def from_dict(cls, data: dict) -> "JobSpec":
        if not isinstance(data, dict):
            raise ValueError("job spec must be a JSON object")
        unknown = set(data) - set(cls.FIELDS)
        if unknown:
            raise ValueError(
                f"unknown job field(s): {', '.join(sorted(unknown))}"
            )
        if "workload" not in data:
            raise ValueError("job spec requires a workload")
        return cls(**data)

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.FIELDS}

    def finish_when_policy(self):
        from ..core.has_discoveries import HasDiscoveries

        return {
            None: None,
            "all": HasDiscoveries.ALL,
            "any": HasDiscoveries.ANY,
            "any_failures": HasDiscoveries.ANY_FAILURES,
            "all_failures": HasDiscoveries.ALL_FAILURES,
        }[self.finish_when]


class Job:
    """One submitted check and its lifecycle state.  The completed
    checker object is retained (``job.checker``) so the Explorer can be
    attached to it afterwards (serve/server.py ``/jobs/<id>/explore``)."""

    def __init__(self, job_id: str, spec: JobSpec):
        self.id = job_id
        self.spec = spec
        self.state = QUEUED
        self.submitted_at = time.time()
        self.started_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.result: Optional[dict] = None
        self.error: Optional[str] = None
        self.checker = None  # retained after completion for the Explorer
        self.explorer_address = None
        self.cancel = threading.Event()
        self._finished = threading.Event()

    def snapshot(self) -> dict:
        """JSON view served by ``GET /jobs/<id>``.  While the job is
        RUNNING and its checker is attached, a ``vitals`` key carries
        the engine's live counters (``Checker.metrics()`` is documented
        mid-run-safe — it reads already-synced scalars, never the
        device), so a client watching one job no longer needs the
        aggregated ``/.metrics`` to see whether ITS check is moving."""
        out = {
            "id": self.id,
            "state": self.state,
            "spec": self.spec.to_dict(),
            "submitted_at": self.submitted_at,
            "started_at": self.started_at,
            "finished_at": self.finished_at,
            "result": self.result,
            "error": self.error,
        }
        if self.state == RUNNING and self.checker is not None:
            from ..obs.metrics import vitals_view

            vitals = vitals_view(self.checker)
            if vitals is not None:
                out["vitals"] = vitals
        if self.explorer_address is not None:
            out["explorer_address"] = list(self.explorer_address)
        return out

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the job reaches a terminal state."""
        return self._finished.wait(timeout)

    @property
    def terminal(self) -> bool:
        return self.state in (DONE, FAILED, CANCELLED)


class JobStore:
    """Thread-safe id -> Job map with journaled state transitions."""

    def __init__(self, journal=None):
        self._lock = threading.Lock()
        self._jobs: Dict[str, Job] = {}
        self._seq = 0
        self._journal = journal
        # Called with each job that reaches a terminal state, after the
        # transition is journaled and before its waiters wake, so that a
        # waiter sees everything the scheduler writes at the end.
        self.on_terminal: Optional[Callable[[Job], None]] = None

    def create(self, spec: JobSpec) -> Job:
        with self._lock:
            self._seq += 1
            job = Job(f"job-{self._seq:06d}", spec)
            self._jobs[job.id] = job
        self._log("job_submitted", job, workload=spec.workload,
                  engine=spec.engine, priority=spec.priority,
                  portfolio=bool(spec.portfolio))
        return job

    def get(self, job_id: str) -> Optional[Job]:
        with self._lock:
            return self._jobs.get(job_id)

    def list(self) -> List[Job]:
        with self._lock:
            return sorted(self._jobs.values(), key=lambda j: j.id)

    def counts(self) -> Dict[str, int]:
        out = {s: 0 for s in (QUEUED, RUNNING, DONE, FAILED, CANCELLED)}
        with self._lock:
            for job in self._jobs.values():
                out[job.state] += 1
        return out

    def try_start(self, job: Job) -> bool:
        """Atomically move a queued job to running; False when a cancel
        (or anything else) got there first — the worker must drop it.
        Without this compare-and-set, a cancel landing between the
        worker's pop and its RUNNING transition would be silently
        overwritten and the job would run cancelled."""
        with self._lock:
            if job.state != QUEUED or job.cancel.is_set():
                return False
            job.state = RUNNING
            job.started_at = time.time()
        self._log("job_running", job)
        return True

    def try_cancel_queued(self, job: Job) -> bool:
        """The cancel-side compare-and-set paired with :meth:`try_start`:
        atomically move a still-queued job to cancelled.  False when the
        job already left QUEUED — the caller then relies on the cancel
        EVENT, which the runner's poll loop forwards to the engine (one
        terminal transition either way, never two)."""
        with self._lock:
            if job.state != QUEUED:
                return False
            job.state = CANCELLED
            job.finished_at = time.time()
        self._log("job_cancelled", job, reason="while queued")
        self._release(job)
        return True

    def transition(self, job: Job, state: str, **fields) -> None:
        """Move ``job`` to ``state``, journal it, and release waiters on
        terminal states.  Transitions are scheduler-serialized per job;
        the lock here only guards the map's consistency view."""
        with self._lock:
            job.state = state
            if state == RUNNING:
                job.started_at = time.time()
            if state in (DONE, FAILED, CANCELLED):
                job.finished_at = time.time()
        self._log(f"job_{state}", job, **fields)
        if job.terminal:
            self._release(job)

    def _release(self, job: Job) -> None:
        try:
            if self.on_terminal is not None:
                self.on_terminal(job)
        finally:
            job._finished.set()

    def _log(self, event: str, job: Job, **fields) -> None:
        if self._journal is not None:
            self._journal.append(
                event, job=job.id, worker=worker_id(), **fields
            )
