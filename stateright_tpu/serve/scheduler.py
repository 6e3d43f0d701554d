"""Job scheduler: priority queue + worker pool over the engines.

One scheduler owns one mesh.  Jobs run IN-PROCESS on worker threads
(default one — a single device runs one wavefront at a time), which is
what makes the warm-start story real: the engines' compiled-program
cache (parallel/wave_common.cached_program) and the persisted knob
cache (runtime/knob_cache.py) are process-level, so the second
submission of a workload skips both the auto-tune discovery and the
compile that made the first one slow — the 126 s -> ~0 warmup
collapse the ROADMAP names, asserted by the ``knob_cache_hits`` /
``program_cache_hits`` counters in the aggregated metrics
(docs/SERVING.md).

Cancellation is cooperative end to end: a queued job is simply marked
cancelled; a running job's cancel event is forwarded to the engine's
``request_stop`` (core/checker.py), which winds the run down like a
deadline — partial counts stand and are reported with the cancelled
job.
"""

from __future__ import annotations

import heapq
import os
import threading
import time
from typing import List, Optional

from ..obs.metrics import GLOBAL, MetricsRegistry
from ..runtime.knob_cache import (
    drop_knobs, knob_key, load_knobs, store_knobs,
)
from .jobs import (
    CANCELLED, DONE, FAILED, QUEUED, RUNNING,
    Job, JobCancelled, JobSpec, JobStore, worker_id,
)
from .portfolio import checker_summary, diversify, run_portfolio
from .workloads import build_model, workload_label

_SIM_ENGINES = ("simulation", "tpu_simulation")
# A simulation job with no stopping condition would walk forever; the
# service bounds it like the CLI's check-simulation does.
_SIM_DEFAULT_TARGET = 1_000_000


# -- builder assembly (module-level: shared by the in-process scheduler
# and the fleet worker, fleet/worker.py — one definition of how a
# JobSpec maps onto the CheckerBuilder, so the two run paths cannot
# drift) -----------------------------------------------------------------------


def make_builder(spec: JobSpec, engine: str, symmetry: bool):
    """(model, cli_spec, builder, resolved_n) for one run — the one
    place job fields map onto the CheckerBuilder, shared by single
    runs, every portfolio member, and fleet workers."""
    model, cli, n = build_model(spec.workload, spec.n, spec.network)
    builder = model.checker().threads(
        spec.threads or (os.cpu_count() or 1)
    )
    device = engine in (
        "tpu", "tiered", "sharded", "tiered-sharded", "tpu_simulation",
    )
    depth = spec.target_max_depth
    if depth is None:
        depth = (
            cli.tpu_target_max_depth
            if device and cli.tpu_target_max_depth is not None
            else cli.target_max_depth
        )
    if depth is not None:
        builder = builder.target_max_depth(depth)
    if spec.target_state_count is not None:
        builder = builder.target_state_count(spec.target_state_count)
    if spec.timeout is not None:
        builder = builder.timeout(spec.timeout)
    policy = spec.finish_when_policy()
    if policy is not None:
        builder = builder.finish_when(policy)
    if symmetry:
        builder = builder.symmetry()
    return model, cli, builder, n


def spawn_engine(builder, spec: JobSpec, engine: str,
                 engine_kwargs: dict, seed: int):
    if engine == "tpu":
        return builder.spawn_tpu(**engine_kwargs)
    if engine == "tiered":
        return builder.spawn_tpu_tiered(**engine_kwargs)
    if engine == "sharded":
        return builder.spawn_tpu_sharded(**engine_kwargs)
    if engine == "tiered-sharded":
        return builder.spawn_tpu_tiered_sharded(**engine_kwargs)
    if engine == "bfs":
        return builder.spawn_bfs()
    if engine == "dfs":
        return builder.spawn_dfs()
    if engine == "tpu_simulation":
        return builder.spawn_tpu_simulation(seed, **engine_kwargs)
    if engine == "simulation":
        return builder.spawn_simulation(seed)
    raise ValueError(engine)


def bound_simulation(builder, spec: JobSpec) -> None:
    """Simulation engines only stop on a policy/target/timeout; give
    unbounded specs the service default instead of an immortal job."""
    from ..core.has_discoveries import HasDiscoveries

    if spec.finish_when is None:
        builder.finish_when(HasDiscoveries.ANY_FAILURES)
    if spec.target_state_count is None and spec.timeout is None:
        builder.target_state_count(_SIM_DEFAULT_TARGET)


def knob_engine_tag(engine: str) -> str:
    """The knob_key engine tag for a job's engine: sharded and
    tiered entries live under their own tags (their knob sets and
    sizing rules differ from the single-chip engine's); everything
    else uses the single-chip default (simulation winners only ever
    land under the portfolio-only label, so the tag is inert for
    them)."""
    from ..runtime.knob_cache import (
        SHARDED_ENGINE, SINGLE_CHIP_ENGINE, TIERED_ENGINE,
        TIERED_SHARDED_ENGINE,
    )

    if engine == "sharded":
        return SHARDED_ENGINE
    if engine == "tiered":
        return TIERED_ENGINE
    if engine == "tiered-sharded":
        return TIERED_SHARDED_ENGINE
    return SINGLE_CHIP_ENGINE


def final_geometry(checker) -> dict:
    # The keys are exactly the engines' spawn kwargs: single-chip
    # (and tiered, whose budget-derived capacity lands here as the
    # capacity it pinned) exposes capacity/log_capacity/
    # max_frontier/dedup_factor/sort_lanes, the sharded engine
    # capacity/chunk_size/dedup_factor/bucket_slack/sort_lanes (the
    # discovered exchange-bucket and sort-geometry rungs —
    # persisting them is what lets a warm repeat skip the
    # overflow-retry ramps, not just the auto-tune growth).  Each
    # engine's metrics() emits its own subset; the `in m` filter
    # picks the right one.
    m = checker.metrics()
    out = {
        k: int(m[k])
        for k in ("capacity", "log_capacity", "max_frontier",
                  "chunk_size", "dedup_factor", "bucket_slack")
        if k in m
    }
    # The rungs persist ONLY when the run actually pinned one
    # (sort_lanes_rung/step_lanes_rung; 0 = full buffer, tuner
    # armed): storing the live full width from a too-short-to-tune
    # run would spawn every warm repeat with an explicit rung and
    # disarm its tuner.  The dedup PATH persists always — a
    # sortless→sort fallback is a per-workload selection a warm
    # repeat must not re-discover with another aborted wave.
    # ...and the sort rung NEVER persists off a sortless run: there
    # it is the claim compaction buffer's tuner detail, and an
    # explicit sort_lanes under sortless is the fallback-forcing
    # budget cap — a warm repeat must re-arm the tuner instead.
    rung = int(m.get("sort_lanes_rung", 0) or 0)
    if rung and not m.get("sortless"):
        out["sort_lanes"] = rung
    step_rung = int(m.get("step_lanes_rung", 0) or 0)
    if step_rung:
        out["step_lanes"] = step_rung
    if "sortless" in m:
        out["sortless"] = int(bool(m["sortless"]))
    # The tiered-sharded engine's PER-SHARD budget is part of its
    # geometry identity (it derives cap_s, which the snapshot and
    # the warm start must agree on); a float, so it bypasses the
    # int() cast above.  The budget-keyed cache label already
    # separates budgets — storing it here makes the warm-started
    # spawn self-describing even without the label.
    if m.get("engine") == "tpu-tiered-sharded" and \
            m.get("memory_budget_mb") is not None:
        out["memory_budget_mb"] = float(m["memory_budget_mb"])
    return out


class Scheduler:
    def __init__(
        self,
        store: JobStore,
        journal=None,
        knob_cache_dir: Optional[str] = None,
        workers: int = 1,
        poll_interval: float = 0.02,
        retain_checkers: int = 4,
        store_dir: Optional[str] = None,
    ):
        """``retain_checkers`` caps how many completed jobs keep their
        checker alive for Explorer attach: a finished wavefront checker
        pins its whole device table + row log, so a long-lived daemon
        retaining every job's checker is an unbounded memory leak.  The
        oldest unexplored checkers past the cap are released (their job
        results remain; only ``/jobs/{id}/explore`` stops working)."""
        self.store = store
        store.on_terminal = self._finish_spans
        self.journal = journal
        self.knob_cache_dir = knob_cache_dir
        # The persistent verification store (incr/, docs/INCREMENTAL.md)
        # jobs opt into with ``store: true``: identical resubmissions
        # short-circuit to the journaled verdict, near-identical ones
        # take the cheapest sound re-check path.  The recheck-mode
        # counters below are the /.metrics evidence.
        self.store_dir = store_dir
        self._retain = max(0, retain_checkers)
        self._retained: List[Job] = []  # oldest first
        self._retain_lock = threading.Lock()
        self.metrics = MetricsRegistry(
            jobs_submitted=0, jobs_completed=0, jobs_failed=0,
            jobs_cancelled=0, knob_cache_hits=0, knob_cache_misses=0,
            portfolio_wins=0, violations_found=0, unique_states_total=0,
            verdict_cache_hits=0, recheck_property_only=0,
            recheck_constant_widening=0, recheck_cold=0,
        )
        self._poll = poll_interval
        self._cond = threading.Condition()
        self._heap: List[tuple] = []  # (-priority, seq, job_id)
        self._seq = 0
        self._shutdown = threading.Event()
        self._workers = [
            threading.Thread(
                target=self._worker, daemon=True, name=f"serve-worker-{i}"
            )
            for i in range(max(1, workers))
        ]
        for t in self._workers:
            t.start()

    # -- submission surface ---------------------------------------------------

    def submit(self, spec: JobSpec) -> Job:
        job = self.store.create(spec)
        self.metrics.inc("jobs_submitted")
        with self._cond:
            self._seq += 1
            heapq.heappush(
                self._heap, (-spec.priority, self._seq, job.id)
            )
            self._cond.notify()
        return job

    def cancel(self, job_id: str) -> bool:
        """Cancel a job: False when unknown or already terminal.  Queued
        jobs die immediately; running jobs get a cooperative stop and
        finish as ``cancelled`` with their partial counts."""
        job = self.store.get(job_id)
        if job is None or job.terminal:
            return False
        job.cancel.set()
        # Atomic vs the worker's try_start: exactly one side wins, so a
        # job is either cancelled-while-queued here or runs and gets the
        # cooperative stop — never both terminal transitions.
        if self.store.try_cancel_queued(job):
            self.metrics.inc("jobs_cancelled")
        return True

    # -- per-job spans / SLO aggregation --------------------------------------

    def _span(self, job: Job, span: str, sec: float) -> None:
        """One lifecycle span: a ``job_span`` journal event (the durable
        per-job trace, docs/SERVING.md) plus the matching SLO histogram
        (``job_<span>_sec``) the aggregated ``/.metrics`` serves —
        queue p95 and end-to-end latency distributions come from
        exactly these."""
        from ..obs.metrics import LATENCY_BUCKETS

        sec = max(0.0, sec)
        self.metrics.observe(
            f"job_{span}_sec", sec, boundaries=LATENCY_BUCKETS
        )
        if self.journal is not None:
            self.journal.append(
                "job_span", job=job.id, span=span,
                sec=round(sec, 6), state=job.state,
                worker=worker_id(),
            )

    def _finish_spans(self, job: Job) -> None:
        """Terminal-state spans: ``run`` (running -> terminal; absent
        for a job cancelled while still queued) and ``total``
        (submit -> terminal, the end-to-end latency a client saw)."""
        if job.finished_at is None:
            return
        if job.started_at is not None:
            self._span(job, "run", job.finished_at - job.started_at)
        self._span(job, "total", job.finished_at - job.submitted_at)

    def shutdown(self, wait: bool = True) -> None:
        """Stop the workers: cancel any RUNNING job (the poll loop
        forwards the cancel to the engine's cooperative stop, so
        workers actually come home) and, with ``wait`` (default), join
        them.  The join matters beyond politeness: worker frames hold
        references into engine state (the running job's checker), and
        tearing the scheduler down while a worker is mid-exit lets the
        GC free device buffers in an order the XLA runtime's teardown
        aborts on (observed as ``terminate called without an active
        exception`` at interpreter exit)."""
        self._shutdown.set()
        for job in self.store.list():
            if job.state == RUNNING:
                job.cancel.set()
        with self._cond:
            self._cond.notify_all()
        if wait:
            for t in self._workers:
                if t is not threading.current_thread():
                    t.join(timeout=60.0)

    # -- worker loop ----------------------------------------------------------

    def _worker(self) -> None:
        while not self._shutdown.is_set():
            job = self._next_job()
            if job is None:
                continue
            self._run_job(job)

    def _next_job(self) -> Optional[Job]:
        with self._cond:
            while not self._heap and not self._shutdown.is_set():
                self._cond.wait(0.25)
            if self._shutdown.is_set() or not self._heap:
                return None
            _, _, job_id = heapq.heappop(self._heap)
        job = self.store.get(job_id)
        if job is None or job.state != QUEUED:
            return None  # cancelled while queued
        return job

    def _run_job(self, job: Job) -> None:
        if not self.store.try_start(job):
            return  # cancelled between pop and start
        self._span(job, "queue_wait", job.started_at - job.submitted_at)
        t0 = time.monotonic()
        prog_hits0 = GLOBAL.get("program_cache_hits", 0)
        try:
            if job.spec.portfolio is not None:
                result = self._run_portfolio(job)
            else:
                result = self._run_single(job)
        except JobCancelled as c:
            result = dict(c.partial)
            result["completed"] = False
            # Result lands BEFORE the terminal transition releases
            # waiters: a client woken by /result?wait= must see the
            # partial counts, not "cancelled" with result null.
            job.result = result
            job.checker = None  # explore() refuses non-DONE jobs; don't pin
            self.metrics.inc("jobs_cancelled")
            self.store.transition(
                job, CANCELLED,
                unique=result.get("unique_state_count"),
            )
            return
        except Exception as exc:
            import traceback

            job.error = f"{type(exc).__name__}: {exc}"
            job.result = {"completed": False, "error": job.error}
            job.checker = None
            self.metrics.inc("jobs_failed")
            if self.journal is not None:
                self.journal.append(
                    "job_error", job=job.id,
                    traceback=traceback.format_exc(limit=5)[-2000:],
                )
            self.store.transition(job, FAILED, error=job.error[:500])
            return
        result["completed"] = True
        result["elapsed_sec"] = round(time.monotonic() - t0, 3)
        # Per-job attribution of the process-global counter is only
        # meaningful when jobs run one at a time; with concurrent
        # workers another job's compiles/hits would land in this
        # window, so the per-job delta is withheld (the aggregated
        # /.metrics totals stay correct either way).
        result["program_cache_hits_delta"] = (
            GLOBAL.get("program_cache_hits", 0) - prog_hits0
            if len(self._workers) == 1 else None
        )
        job.result = result
        self.metrics.inc("jobs_completed")
        self.metrics.inc(
            "unique_states_total", result.get("unique_state_count", 0)
        )
        if result.get("violation"):
            self.metrics.inc("violations_found")
        self.store.transition(
            job, DONE,
            unique=result.get("unique_state_count"),
            violation=result.get("violation"),
        )
        self._enforce_checker_retention(job)

    def _enforce_checker_retention(self, job: Job) -> None:
        with self._retain_lock:
            if job.checker is not None:
                self._retained.append(job)
            excess = len(self._retained) - self._retain
            if excess <= 0:
                return
            keep = []
            for j in self._retained:
                # Explorer-attached checkers stay pinned: releasing one
                # would break a UI someone is looking at.
                if excess > 0 and j.explorer_address is None:
                    j.checker = None
                    excess -= 1
                else:
                    keep.append(j)
            self._retained = keep

    # -- builder assembly (delegates to the module-level helpers shared
    # with fleet/worker.py) ---------------------------------------------------

    def _make_builder(self, spec: JobSpec, engine: str,
                      symmetry: bool):
        return make_builder(spec, engine, symmetry)

    def _spawn(self, builder, spec: JobSpec, engine: str,
               engine_kwargs: dict, seed: int):
        return spawn_engine(builder, spec, engine, engine_kwargs, seed)

    def _bound_simulation(self, builder, spec: JobSpec) -> None:
        bound_simulation(builder, spec)

    # -- single-run jobs ------------------------------------------------------

    def _run_single(self, job: Job, _retry: bool = False) -> dict:
        spec = job.spec
        if spec.store:
            return self._run_stored(job)
        model, cli, builder, n = self._make_builder(
            spec, spec.engine, spec.symmetry
        )
        if spec.engine in _SIM_ENGINES:
            self._bound_simulation(builder, spec)

        # Engine kwargs: workload defaults < cached tuned knobs <
        # explicit request overrides.  The knob cache is the cross-job
        # warm start: the first job's auto-tune discovery is persisted,
        # so the second identical job spawns right-sized and skips the
        # growth pauses entirely (asserted by tests/test_serve.py).
        engine_kwargs = (
            dict(cli.tpu_kwargs)
            if spec.engine in ("tpu", "tiered")
            else {}
        )
        cache_key = None
        cache_hit = False
        # Every device engine warm-starts from the knob cache; sharded
        # and tiered entries live under their own engine tags (the
        # sharded knob set — chunk_size/bucket_slack — is disjoint from
        # the single-chip one, and tiered entries pin the budget-derived
        # capacity, which must never shadow the in-HBM right-sizing).
        device_engine = spec.engine in (
            "tpu", "tiered", "sharded", "tiered-sharded",
        )
        if (
            device_engine
            and spec.use_knob_cache
            and self.knob_cache_dir is not None
        ):
            label = workload_label(
                spec.workload, n, spec.network, spec.symmetry
            )
            if spec.engine in ("tiered", "tiered-sharded"):
                # Tiered entries pin a budget-DERIVED capacity (and a
                # possibly budget-shrunk frontier), so the budget is
                # part of the entry's identity: without it, one
                # budget's tiny pinned table would silently warm-start
                # the same workload at a different (or no) budget.
                # Tiered-sharded budgets are PER SHARD, but the engine
                # tag already separates the two entry families.
                label += ":mb={}".format(
                    spec.engine_kwargs.get("memory_budget_mb")
                )
            cache_key = knob_key(
                label, engine=self._knob_engine_tag(spec.engine),
            )
            cached = None if _retry else load_knobs(
                self.knob_cache_dir, cache_key
            )
            if cached is not None:
                engine_kwargs.update(cached)
                cache_hit = True
                self.metrics.inc("knob_cache_hits")
            elif not _retry:
                self.metrics.inc("knob_cache_misses")
        engine_kwargs.update(spec.engine_kwargs)

        try:
            checker = self._spawn(
                builder, spec, spec.engine, engine_kwargs, spec.seed
            )
            job.checker = checker
            self._poll_to_completion(job, checker)
        except JobCancelled:
            raise
        except Exception:
            if cache_hit and cache_key is not None:
                # Stale cached geometry (engine defaults moved under
                # it): drop the entry and rerun once from a fresh
                # discovery — the knob-cache staleness contract
                # (runtime/knob_cache.py).
                drop_knobs(self.knob_cache_dir, cache_key)
                if self.journal is not None:
                    self.journal.append(
                        "knobs_dropped", job=job.id, key=cache_key
                    )
                return self._run_single(job, _retry=True)
            raise

        summary = checker_summary(checker)
        summary["engine"] = spec.engine
        summary["n"] = n
        summary["knob_cache_hit"] = cache_hit
        # Explicit knobs aren't "tuned" and are never persisted — EXCEPT
        # memory_budget_mb, which is a budget, not a geometry: it is the
        # normal way a tiered job arrives, the engine re-derives capacity
        # from it deterministically, and withholding the store would make
        # the TIERED_ENGINE warm start unreachable for exactly the jobs
        # it exists for (the discovered log_capacity/max_frontier are
        # what the repeat would otherwise re-pay auto-tune for).
        hand_tuned = set(spec.engine_kwargs) - {"memory_budget_mb"}
        if (
            cache_key is not None
            and not cache_hit
            and device_engine
            and not hand_tuned
        ):
            # Persist the run's FINAL geometry (post any auto-tune
            # growth), not the shrunk tuned_kwargs: an identical repeat
            # then reproduces the exact compiled-program cache keys, so
            # the second job skips both the growth pauses AND the
            # compiles — the full warmup collapse the serving bench
            # phase measures.
            knobs = self._final_geometry(checker)
            if knobs:
                t_kc = time.monotonic()
                store_knobs(
                    self.knob_cache_dir, cache_key, knobs,
                    unique=summary["unique_state_count"],
                    depth=summary["max_depth"], source=f"serve:{job.id}",
                )
                # The knob-cache write is part of the job's host tail:
                # journaled like every other lifecycle span so the
                # timeline exporter can place it.
                self._span(job, "knob_cache", time.monotonic() - t_kc)
        return summary

    # -- verification-store jobs (incr/, docs/INCREMENTAL.md) -----------------

    def _run_stored(self, job: Job) -> dict:
        """One ``store: true`` job: classify the spec against the
        persistent verification store and take the cheapest sound path.
        An identical resubmission is the SCHEDULER SHORT-CIRCUIT — the
        journaled verdict + counterexample paths come back with zero
        device dispatches (the content-addressed verdict cache, ROADMAP
        #3c); property-only edits re-evaluate over the stored row log;
        declared constant widenings explore only the new region;
        anything else runs cold with the reason journaled AND surfaced
        in the job result (``recheck_mode`` / ``recheck_reason``)."""
        from ..incr.recheck import StoredVerdictChecker, incremental_check

        spec = job.spec
        if self.store_dir is None:
            raise ValueError(
                "job requested the verification store (store: true), "
                "but this service was started without one (serve "
                "--store-dir DIR)"
            )
        _model, cli, builder, n = self._make_builder(
            spec, spec.engine, spec.symmetry
        )
        # Same kwargs layering as _run_single: workload defaults <
        # cached tuned knobs < explicit request overrides.  Engine
        # geometry is excluded from spec matching (incr/spec_hash.py),
        # so the knob cache's warm start composes freely with the
        # store: a cold-classified repeat of a once-seen workload still
        # skips the auto-tune growth pauses.
        engine_kwargs = dict(cli.tpu_kwargs)
        cache_key = None
        cache_hit = False
        if spec.use_knob_cache and self.knob_cache_dir is not None:
            cache_key = knob_key(workload_label(
                spec.workload, n, spec.network, spec.symmetry
            ))
            cached = load_knobs(self.knob_cache_dir, cache_key)
            if cached is not None:
                engine_kwargs.update(cached)
                cache_hit = True
                self.metrics.inc("knob_cache_hits")
            else:
                self.metrics.inc("knob_cache_misses")
        engine_kwargs.update(spec.engine_kwargs)

        def attach(ck):
            # Live vitals for RUNNING store jobs, same as _run_single's
            # at-spawn attach (jobs.py reads checker.metrics() for the
            # /jobs/{id} vitals key).
            job.checker = ck

        checker, info = incremental_check(
            builder,
            self.store_dir,
            engine_kwargs=engine_kwargs,
            journal=self.journal,
            reuse=True,
            cancel=job.cancel,
            on_spawn=attach,
        )
        if job.cancel.is_set():
            # Same contract as every other job path: a cancelled run
            # reports its partial counts as CANCELLED (the store's
            # completeness gate already refused the partial verdict).
            raise JobCancelled(partial=checker_summary(checker))
        counter = {
            "identical": "verdict_cache_hits",
            "property_only": "recheck_property_only",
            "constant_widening": "recheck_constant_widening",
            "cold": "recheck_cold",
        }.get(info["mode"])
        if counter:
            self.metrics.inc(counter)
        # Cache-served checkers hold no device state worth exploring;
        # retaining them would only shadow the retention cap.
        if not isinstance(checker, StoredVerdictChecker):
            job.checker = checker
        summary = checker_summary(checker)
        # Persist a cold run's FINAL geometry on a knob-cache miss,
        # exactly like _run_single: the next cold-classified job of
        # this workload then spawns right-sized AND reproduces the
        # compiled-program cache keys.
        if (
            info["mode"] == "cold"
            and cache_key is not None
            and not cache_hit
            and not spec.engine_kwargs
            and job.checker is not None
        ):
            knobs = self._final_geometry(job.checker)
            if knobs:
                t_kc = time.monotonic()
                store_knobs(
                    self.knob_cache_dir, cache_key, knobs,
                    unique=summary["unique_state_count"],
                    depth=summary["max_depth"],
                    source=f"serve:{job.id}:store",
                )
                self._span(job, "knob_cache", time.monotonic() - t_kc)
        summary["engine"] = spec.engine
        summary["n"] = n
        summary["knob_cache_hit"] = cache_hit
        summary["recheck_mode"] = info["mode"]
        summary["recheck_reason"] = info["reason"]
        if "seeded_states" in info:
            summary["recheck_seeded_states"] = info["seeded_states"]
        return summary

    @staticmethod
    def _knob_engine_tag(engine: str) -> str:
        return knob_engine_tag(engine)

    @staticmethod
    def _final_geometry(checker) -> dict:
        return final_geometry(checker)

    def _poll_to_completion(self, job: Job, checker) -> None:
        while not checker.is_done():
            if job.cancel.is_set():
                checker.request_stop()
            time.sleep(self._poll)
        checker.join()
        if job.cancel.is_set():
            raise JobCancelled(partial=checker_summary(checker))

    # -- portfolio jobs -------------------------------------------------------

    def _run_portfolio(self, job: Job) -> dict:
        from ..core.has_discoveries import HasDiscoveries

        spec = job.spec
        pf = spec.portfolio
        _, cli, n = build_model(spec.workload, spec.n, spec.network)
        base_kwargs = dict(cli.tpu_kwargs) if spec.engine == "tpu" else {}
        base_kwargs.update(spec.engine_kwargs)
        members = diversify(
            size=int(pf["size"]),
            seed=int(pf.get("seed", 0)),
            base_engine=spec.engine,
            base_kwargs=base_kwargs,
            symmetry_capable=self._symmetry_capable(spec),
            include_simulation=bool(pf.get("simulation", True)),
        )
        if self.journal is not None:
            self.journal.append(
                "portfolio_start", job=job.id, size=len(members),
                seed=int(pf.get("seed", 0)),
                parallelism=int(pf.get("parallelism", 1)),
            )

        def spawn_member(member):
            _, _, builder, _ = self._make_builder(
                spec, member.engine, member.symmetry
            )
            # Swarm semantics: every member stops at the first
            # failure-classified discovery; clean exhaustive members run
            # out their full space (the completeness anchor).
            builder.finish_when(HasDiscoveries.ANY_FAILURES)
            if member.kind == "simulation":
                target = (
                    spec.target_state_count or member.target_state_count
                )
                builder.target_state_count(target)
            return self._spawn(
                builder, spec, member.engine, member.engine_kwargs,
                member.seed or spec.seed,
            )

        res = run_portfolio(
            members, spawn_member, job.cancel, journal=self.journal,
            parallelism=int(pf.get("parallelism", 1)),
            poll_interval=self._poll,
        )
        if job.cancel.is_set():
            raise JobCancelled(partial={"portfolio": res["portfolio"]})

        winner_idx = res["winner_index"]
        entries = res["entries"]
        # The authoritative counts: the winner's run, else the
        # exhaustive anchor (member 0), else the first member that
        # completed at all.
        authoritative = None
        if winner_idx is not None:
            authoritative = entries[winner_idx]
            self.metrics.inc("portfolio_wins")
        else:
            for e in entries:
                if e and e.get("summary") is not None:
                    authoritative = e
                    break
        if authoritative is None or authoritative.get("summary") is None:
            raise RuntimeError(
                "every portfolio member failed; see the service journal"
            )
        job.checker = authoritative.get("checker")
        summary = dict(authoritative["summary"])
        # Label the counts with the engine that PRODUCED them: a
        # simulation-member winner's counts are a sampled walk, and
        # reporting them under the requested exhaustive engine would
        # misrepresent a Monte-Carlo number as a full search.
        summary["engine"] = authoritative.get("engine", spec.engine)
        summary["sampled"] = authoritative.get("kind") == "simulation"
        summary["authoritative_member"] = authoritative.get("index")
        summary["n"] = n
        summary["portfolio"] = res["portfolio"]
        self._fold_winner_knobs(job, spec, n, members, winner_idx, entries)
        return summary

    def _symmetry_capable(self, spec: JobSpec) -> bool:
        """May portfolio members toggle symmetry on?  Device members
        need a compiled canonicalization (parallel/canon.py); host DFS
        members need the model's representative()."""
        try:
            model, cli, _ = build_model(spec.workload, spec.n, spec.network)
        except Exception:
            return False
        if not cli.symmetry:
            return False
        if spec.engine in ("tpu", "sharded"):
            try:
                from ..parallel.canon import make_canon
                from ..parallel.compiled import compiled_model_for

                return make_canon(compiled_model_for(model)) is not None
            except Exception:
                return False
        return spec.engine == "dfs"

    def _fold_winner_knobs(self, job, spec, n, members, winner_idx,
                           entries) -> None:
        """Swarm feedback loop: the config that found the counterexample
        becomes the workload's warm-start entry, so the next job on this
        model starts from the geometry that actually worked."""
        if winner_idx is None or self.knob_cache_dir is None:
            return
        member = members[winner_idx]
        checker = entries[winner_idx].get("checker")
        label = workload_label(
            spec.workload, n, spec.network, member.symmetry
        )
        if member.engine in ("tpu", "sharded") and checker is not None:
            knobs = self._final_geometry(checker) or member.engine_kwargs
        else:
            # A simulation winner's "config" is its seed/bounds, which
            # are not spawn_tpu knobs: record it under a portfolio-only
            # label so plain jobs never load it as engine geometry.
            label += ":portfolio-winner"
            knobs = member.engine_kwargs or {"seed": member.seed}
        key = knob_key(label, engine=self._knob_engine_tag(member.engine))
        t_kc = time.monotonic()
        store_knobs(
            self.knob_cache_dir, key, knobs,
            portfolio_winner=True, member=member.index,
            member_engine=member.engine, job=job.id,
            violation=entries[winner_idx].get("violation"),
        )
        self._span(job, "knob_cache", time.monotonic() - t_kc)
