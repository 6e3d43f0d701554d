"""Checker surface and fluent builder.

Reference: src/checker.rs — ``CheckerBuilder`` (fluent config + spawn_bfs /
spawn_dfs / spawn_on_demand / spawn_simulation / serve) and the ``Checker``
trait (counts, discoveries, join/report, assertion helpers).  This module
adds ``spawn_tpu`` — the TPU wavefront engine that is the point of this
framework — as a first-class sibling of the reference spawn methods.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from .has_discoveries import HasDiscoveries
from .model import Expectation, Model
from .path import Path
from .report import ReportData, ReportDiscovery, Reporter
from .visitor import as_visitor


class CheckerBuilder:
    def __init__(self, model: Model):
        self.model = model
        self._symmetry = None
        self._target_state_count: Optional[int] = None
        self._target_max_depth: Optional[int] = None
        self._thread_count = 1
        self._visitor = None
        self._finish_when: HasDiscoveries = HasDiscoveries.ALL
        self._timeout: Optional[float] = None

    def symmetry(self) -> "CheckerBuilder":
        """Enable symmetry reduction via the state's ``representative()``
        method.  Reference: src/checker.rs:222-227.

        Engine support mirrors the reference plus the device path:
        ``spawn_dfs`` dedups on the representative's fingerprint host-side
        (src/checker/dfs.rs:309-334); ``spawn_bfs`` ignores the option
        (reference parity, SURVEY §2.1); ``spawn_tpu`` /
        ``spawn_tpu_sharded`` honor it when the compiled model declares a
        device canonicalization (``canon_spec()``/``canon_rows``,
        parallel/canon.py) and raise loudly otherwise — never a silent
        fall-through to unreduced exploration (docs/SYMMETRY.md)."""
        return self.symmetry_fn(lambda s: s.representative())

    def symmetry_fn(self, representative) -> "CheckerBuilder":
        self._symmetry = representative
        return self

    def finish_when(self, has_discoveries: HasDiscoveries) -> "CheckerBuilder":
        self._finish_when = has_discoveries
        return self

    def target_state_count(self, count: int) -> "CheckerBuilder":
        self._target_state_count = count if count > 0 else None
        return self

    def target_max_depth(self, depth: int) -> "CheckerBuilder":
        self._target_max_depth = depth if depth > 0 else None
        return self

    def threads(self, thread_count: int) -> "CheckerBuilder":
        if thread_count < 1:
            raise ValueError("thread_count must be >= 1")
        self._thread_count = thread_count
        return self

    def visitor(self, visitor) -> "CheckerBuilder":
        self._visitor = as_visitor(visitor)
        return self

    def timeout(self, seconds: float) -> "CheckerBuilder":
        self._timeout = seconds
        return self

    def spawn_bfs(self) -> "Checker":
        from .engine import GraphChecker

        return GraphChecker(self, dfs=False)

    def spawn_dfs(self) -> "Checker":
        from .engine import GraphChecker

        return GraphChecker(self, dfs=True)

    @staticmethod
    def _require(module: str, what: str) -> None:
        """Distinguish "engine not written yet" from a genuinely broken
        transitive import inside an existing engine module."""
        import importlib.util

        if importlib.util.find_spec(module) is None:
            raise NotImplementedError(f"{what} not yet implemented in this build")

    def spawn_simulation(self, seed: int, chooser=None) -> "Checker":
        self._require("stateright_tpu.core.simulation", "simulation checker")
        from .simulation import SimulationChecker, UniformChooser

        return SimulationChecker(self, seed, chooser or UniformChooser())

    def spawn_on_demand(self) -> "Checker":
        self._require("stateright_tpu.core.on_demand", "on-demand checker")
        from .on_demand import OnDemandChecker

        return OnDemandChecker(self)

    def spawn_tpu(self, **kwargs) -> "Checker":
        """Spawn the TPU wavefront checker: successor expansion, frontier
        dedup, and property evaluation run on-device as a vmapped wavefront
        BFS (the replacement for the reference's thread-pool hot loop,
        src/checker/bfs.rs:177-335).  With ``symmetry()``, dedup keys on
        the canonical row's fingerprint via the compiled model's canon
        spec (parallel/canon.py) while logging original rows; models
        without a canon spec fail the spawn loudly.

        A ``visitor()`` runs on the fused wave loop at the granularity
        of one fused call: after each committed call the row log's
        newly expanded states are read back, and the visitor receives
        every unique state once, at expansion, as a single-state path —
        BFS level order, log order within a level
        (docs/OBSERVABILITY.md)."""
        self._require("stateright_tpu.parallel.wavefront", "TPU wavefront checker")
        from ..parallel.wavefront import TpuChecker

        return TpuChecker(self, **kwargs)

    def spawn_tpu_simulation(self, seed: int, **kwargs) -> "Checker":
        """Spawn the device Monte-carlo checker: a batch of random trace
        walks per program call, one walker per vmap lane (the stochastic
        sibling of ``spawn_tpu``; host engine: core/simulation.py).  Runs
        until ``finish_when`` / ``target_state_count`` / ``timeout``
        stops it, like the host simulation engine."""
        self._require(
            "stateright_tpu.parallel.simulation_tpu", "TPU simulation checker"
        )
        from ..parallel.simulation_tpu import TpuSimulationChecker

        return TpuSimulationChecker(self, seed, **kwargs)

    def spawn_tpu_tiered(self, **kwargs) -> "Checker":
        """Spawn the tiered out-of-core wavefront checker: the same
        wavefront BFS as ``spawn_tpu`` under a fixed HBM budget
        (``memory_budget_mb``) — the device hash set is the hot tier,
        evicted fingerprint partitions live in host RAM (optionally
        disk, ``cold_dir=``) as sorted immutable runs, and candidate
        waves are merge-joined against the cold runs on device before
        commit, so the discovery set is bit-identical to an
        unconstrained run (docs/TIERED.md).  Use for state spaces whose
        fingerprint set exceeds one chip's HBM, or whenever the table
        footprint must be capped; resumable mid-run like ``spawn_tpu``."""
        self._require(
            "stateright_tpu.tiered.engine", "tiered TPU checker"
        )
        from ..tiered.engine import TieredTpuChecker

        return TieredTpuChecker(self, **kwargs)

    def spawn_tpu_tiered_sharded(self, **kwargs) -> "Checker":
        """Spawn the composed pod-scale engine: the sharded wavefront
        BFS of ``spawn_tpu_sharded`` with the tiered engine's hard
        memory cap applied PER SHARD (``memory_budget_mb`` bounds each
        shard's fingerprint table; evicted partitions live in shard-
        local cold stores — owner-sharded fingerprints mean the
        pre-commit cold merge-join never crosses shards).  Snapshots
        embed mesh size × cold tiers and can be re-keyed onto a larger
        or smaller mesh with ``stateright_tpu.tiered.reshard`` (the
        ``reshard`` CLI verb); discovery sets stay bit-identical to an
        unconstrained single-chip run (docs/TIERED.md)."""
        self._require(
            "stateright_tpu.tiered.sharded_engine",
            "tiered sharded TPU checker",
        )
        from ..tiered.sharded_engine import TieredShardedTpuChecker

        return TieredShardedTpuChecker(self, **kwargs)

    def spawn_tpu_sharded(self, **kwargs) -> "Checker":
        """Spawn the multi-chip wavefront checker: frontier and visited set
        sharded over a ``jax.sharding.Mesh`` by fingerprint ownership, with
        an all_to_all successor exchange per wave and psum termination —
        the ICI-collective replacement for the reference's job market
        (src/job_market.rs; SURVEY §2.7)."""
        self._require(
            "stateright_tpu.parallel.sharded", "sharded TPU wavefront checker"
        )
        from ..parallel.sharded import ShardedTpuChecker

        return ShardedTpuChecker(self, **kwargs)

    def serve(self, address, **kwargs) -> "Checker":
        """Serve the interactive Explorer on ``address`` backed by an
        on-demand checker (reference: src/checker.rs:144-151).  Blocks by
        default like the reference; pass ``block=False`` to serve in the
        background and get the checker back immediately."""
        self._require("stateright_tpu.explorer.server", "explorer server")
        from ..explorer.server import serve

        return serve(self, address, **kwargs)


def property_verdicts(checker):
    """Per-property verdict rows for a finished checker, plus the first
    failure-classified discovery name (in the model's property order —
    the deterministic ``violation`` the serving layer and the
    incremental verification store both report).  ONE definition so a
    job result (serve/portfolio.checker_summary) and a stored verdict
    record (incr/store._summarize) can never disagree about the same
    run."""
    model = checker.model()
    discoveries = checker.discoveries()
    props = []
    violation = None
    for p in model.properties():
        found = p.name in discoveries
        classification = (
            checker.discovery_classification(p.name) if found else None
        )
        if found and classification == "counterexample" and violation is None:
            violation = p.name
        props.append({
            "name": p.name,
            "expectation": p.expectation.name,
            "discovered": found,
            "classification": classification,
        })
    return props, violation


class Checker:
    """Base checker surface.  Reference: the ``Checker`` trait,
    src/checker.rs:294-578."""

    def __init__(self, model: Model):
        self._model = model
        # Cooperative cancellation (the serving layer's job-cancel path,
        # serve/scheduler.py): request_stop() asks the engine to wind
        # down at its next host-side check, exactly like a wall-clock
        # timeout — partial counts stand, is_done() becomes true, join()
        # returns.  Engines poll stop_requested() at the same points they
        # poll their deadline.
        self._stop_requested = threading.Event()

    # --- interface implemented by engines -----------------------------------

    def model(self) -> Model:
        return self._model

    def state_count(self) -> int:
        raise NotImplementedError

    def unique_state_count(self) -> int:
        raise NotImplementedError

    def max_depth(self) -> int:
        raise NotImplementedError

    def discoveries(self) -> Dict[str, Path]:
        raise NotImplementedError

    def handles(self) -> list:
        return []

    def is_done(self) -> bool:
        raise NotImplementedError

    def join(self) -> "Checker":
        for h in self.handles():
            h.join()
        return self

    def check_fingerprint(self, fingerprint: int) -> None:
        pass  # only meaningful for on-demand checking

    def run_to_completion(self) -> None:
        pass  # only meaningful for on-demand checking

    def request_stop(self) -> None:
        """Ask a running check to stop early (cooperative, never blocks):
        the engine finishes its current block/device call, keeps every
        committed count and discovery, and completes like a timed-out
        run.  Idempotent; a no-op on an already-finished checker.
        Engines with extra wakeup machinery extend this (the host graph
        engine closes its job market so idle workers drain)."""
        self._stop_requested.set()

    def stop_requested(self) -> bool:
        return self._stop_requested.is_set()

    def metrics(self) -> dict:
        """Live observability snapshot — counts every engine has; the
        device engines extend it with their registry (wave cadence,
        table occupancy, device-call time, always-on vitals histograms)
        and, for ``spawn_tpu_tiered(trace=True)``, the roofline trace
        summary.  Served
        by the Explorer's ``GET /.metrics`` (docs/OBSERVABILITY.md names
        the fields); never blocks on a still-running checker.

        The keys emitted HERE are the guaranteed cross-engine schema
        (pinned by tests/test_metrics_schema.py): every engine — host
        graph, simulation, and all device engines — reports them with
        these types.  ``table_load_factor`` is 0.0 for engines with no
        device fingerprint table; the program-cache counters are the
        process-global compiled-program cache
        (parallel/wave_common.cached_program), included everywhere so
        one scrape answers "is this process reusing compiles"."""
        from ..obs.metrics import GLOBAL

        return {
            "engine": type(self).__name__,
            "done": self.is_done(),
            "state_count": self.state_count(),
            "unique_state_count": self.unique_state_count(),
            "max_depth": self.max_depth(),
            "table_load_factor": 0.0,
            "program_cache_hits": int(GLOBAL.get("program_cache_hits", 0)),
            "program_cache_misses": int(
                GLOBAL.get("program_cache_misses", 0)
            ),
            # Compile observability (wave_common.cached_program, docs/
            # OBSERVABILITY.md "Compile events"): accumulated first-call
            # compile wall time and the storm counter — included on
            # every engine so one scrape answers "is this process
            # recompiling, and is it thrashing".
            "compile_sec_total": round(
                float(GLOBAL.get("compile_sec_total", 0.0)), 4
            ),
            "recompile_storms": int(GLOBAL.get("recompile_storms", 0)),
        }

    # --- shared functionality -----------------------------------------------

    def discovery(self, name: str) -> Optional[Path]:
        return self.discoveries().get(name)

    def try_discovery(self, name: str) -> Optional[Path]:
        """Like :meth:`discovery`, but never blocks on a still-running
        checker (device engines override this; the Explorer's status view
        polls it mid-run)."""
        return self.discovery(name)

    def discovery_classification(self, name: str) -> str:
        prop = self._model.get_property(name)
        return "example" if prop.expectation is Expectation.SOMETIMES else "counterexample"

    def _report_data(self, start: float, done: bool) -> ReportData:
        return ReportData(
            total_states=self.state_count(),
            unique_states=self.unique_state_count(),
            max_depth=self.max_depth(),
            duration=time.monotonic() - start,
            done=done,
        )

    def _report_final(self, reporter: Reporter, start: float) -> None:
        reporter.report_checking(self._report_data(start, done=True))
        discoveries = {
            name: ReportDiscovery(path, self.discovery_classification(name))
            for name, path in self.discoveries().items()
        }
        reporter.report_discoveries(self._model, discoveries)

    def report(self, reporter: Reporter) -> "Checker":
        """Reference: src/checker.rs:412-452."""
        start = time.monotonic()
        while not self.is_done():
            reporter.report_checking(self._report_data(start, done=False))
            time.sleep(reporter.delay())
        self._report_final(reporter, start)
        return self

    def join_and_report(self, reporter: Reporter) -> "Checker":
        """Join while reporting; final timing is accurate rather than rounded
        to the polling interval.  Reference: src/checker.rs:351-409."""
        import threading

        start = time.monotonic()
        stop = threading.Event()

        def poll():
            while not stop.is_set() and not self.is_done():
                reporter.report_checking(self._report_data(start, done=False))
                stop.wait(reporter.delay())

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        self.join()
        stop.set()
        poller.join()
        self._report_final(reporter, start)
        return self

    # --- assertion helpers (src/checker.rs:468-577) -------------------------

    def assert_properties(self) -> None:
        for p in self._model.properties():
            if p.expectation is Expectation.SOMETIMES:
                self.assert_any_discovery(p.name)
            else:
                self.assert_no_discovery(p.name)

    def assert_any_discovery(self, name: str) -> Path:
        found = self.discovery(name)
        if found is not None:
            return found
        assert self.is_done(), (
            f'Discovery for "{name}" not found, but model checking is incomplete.'
        )
        raise AssertionError(f'Discovery for "{name}" not found.')

    def assert_no_discovery(self, name: str) -> None:
        found = self.discovery(name)
        if found is not None:
            raise AssertionError(
                f'Unexpected "{name}" {self.discovery_classification(name)} '
                f"{found}Last state: {found.last_state()!r}\n"
            )
        assert self.is_done(), (
            f'Discovery for "{name}" not found, but model checking is incomplete.'
        )

    def assert_discovery(self, name: str, actions: List[Any]) -> None:
        """Re-execute ``actions`` and validate they constitute a genuine
        discovery per the property's semantics.  Reference:
        src/checker.rs:521-577."""
        additional_info: List[str] = []
        found = self.assert_any_discovery(name)
        model = self._model
        for init_state in model.init_states():
            path = Path.from_actions(model, init_state, actions)
            if path is None:
                continue
            prop = model.get_property(name)
            if prop.expectation is Expectation.ALWAYS:
                if not prop.condition(model, path.last_state()):
                    return
            elif prop.expectation is Expectation.EVENTUALLY:
                states = path.into_states()
                is_liveness_satisfied = any(
                    prop.condition(model, s) for s in states
                )
                acts: List[Any] = []
                model.actions(states[-1], acts)
                is_path_terminal = not acts
                if not is_liveness_satisfied and is_path_terminal:
                    return
                if is_liveness_satisfied:
                    additional_info.append(
                        "incorrect counterexample satisfies eventually property"
                    )
                if not is_path_terminal:
                    additional_info.append("incorrect counterexample is nonterminal")
            else:  # SOMETIMES
                if prop.condition(model, path.last_state()):
                    return
        extra = f" ({'; '.join(additional_info)})" if additional_info else ""
        raise AssertionError(
            f'Invalid discovery for "{name}"{extra}, but a valid one was found. '
            f"found={found.into_actions()!r}"
        )
