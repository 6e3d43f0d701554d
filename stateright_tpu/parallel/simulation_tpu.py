"""Stochastic checking on device: vmapped random trace walks.

The host simulation engine (core/simulation.py, reference
src/checker/simulation.rs) walks one random trace at a time per OS thread.
The TPU form walks a whole *batch* of traces in lockstep — one walker per
vmap lane, each carrying its own PRNG key, fingerprint history (for the
per-trace cycle check), eventually-bits, and discovery latches — with the
entire bounded walk unrolled into a single jitted program per batch.

Semantics mirrored from the host engine:

- properties are evaluated at every counted state; an always-violation or
  sometimes-satisfaction latches the walker's first hit;
- a trace ends at a cycle (the repeated fingerprint joins the path but is
  not counted), a boundary exit, or a terminal state (no action yields a
  successor — uniform choice among valid lanes is exactly the host's
  swap_remove retry loop, which never selects an invalid action);
- leftover eventually-bits at a trace that ended for any of those reasons
  are counterexamples; traces truncated by the depth bound skip that check
  (the host's ``ended_by_depth``, src/checker/simulation.rs:263-272);
- there is no global dedup: ``unique_state_count == state_count``.

Discovery paths are rebuilt host-side from the walker's fingerprint
history via ``Path.from_fingerprints`` — the same host-re-execution
mechanism the wavefront engines use.

Unlike the host engine, walkers within a batch do not see each other's
discoveries mid-trace, so they keep walking where a host thread would
early-exit its trace; that only affects how much work a batch does, never
which discoveries are valid.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..core.checker import Checker
from ..core.model import Expectation
from ..core.path import Path
from .compiled import CompiledModel, compiled_model_for

NO_STEP = 0xFFFFFFFF


def build_walk(compiled, properties, t_max: int, fault_hook=None):
    """One bounded random-trace walk as a pure device function — the
    loop body shared between the Monte-Carlo checker (below, no hook)
    and the chaos-ensemble engine (``ensemble/engine.py``), which
    supplies a ``fault_hook`` masking deliverable lanes by each
    member's fault schedule.

    Hook contract (both methods traced inside the jitted walk):

    - ``fault_hook.init(params)`` -> a carry pytree (per-walk arrays,
      e.g. per-link datagram counters);
    - ``fault_hook.apply(t, state, valid, carry, params)`` ->
      ``(valid, carry)`` — runs after the step kernel's valid mask and
      before the uniform lane choice, so masked lanes are never
      selected (a fully-masked step ends the trace as terminal).

    With ``fault_hook=None`` the emitted program is the checker's
    original walk, unchanged, and the returned callable takes ``key``
    alone; with a hook it takes ``(key, params)`` and both are vmapped
    by the caller.

    Returns ``walk -> (trace, disc, counted, appended, flag)`` where
    ``disc[p]`` is the trace index of property ``p``'s first discovery
    (``NO_STEP`` if none), ``counted`` the states this walk counted,
    ``appended`` the trace length, and ``flag`` the step kernel's
    encoding-overflow alarm.
    """
    import jax
    import jax.numpy as jnp

    from ..core.model import Expectation
    from ..ops.device_fp import device_fp64

    cm = compiled
    props = properties
    n_props = len(props)
    ev_indices = [
        i
        for i, p in enumerate(props)
        if p.expectation is Expectation.EVENTUALLY
    ]
    always_idx = {
        i for i, p in enumerate(props) if p.expectation is Expectation.ALWAYS
    }
    sometimes_idx = {
        i
        for i, p in enumerate(props)
        if p.expectation is Expectation.SOMETIMES
    }
    eb0 = (1 << len(ev_indices)) - 1
    has_flags = getattr(cm, "step_flags", False)

    init = cm.init_packed()
    n_init = init.shape[0]
    init_dev = jnp.asarray(init)
    has_boundary = cm.boundary(init_dev[0]) is not None

    def walk(key, params=None):
        u = jnp.uint32
        key, sub = jax.random.split(key)
        state0 = init_dev[jax.random.randint(sub, (), 0, n_init)]
        hook_carry = fault_hook.init(params) if fault_hook is not None else ()

        def body(t, carry):
            (
                state,
                fps_hi,
                fps_lo,
                trace,
                ebits,
                disc,
                done,
                counted,
                appended,
                flag,
                hook_carry,
                key,
            ) = carry
            active = ~done
            if has_boundary:
                in_bound = cm.boundary(state)
            else:
                in_bound = jnp.ones((), jnp.bool_)
            end_boundary = active & ~in_bound

            hi, lo = device_fp64(state[: cm.fp_words or cm.state_width])
            seen = jnp.any(
                (fps_hi == hi)
                & (fps_lo == lo)
                & (jnp.arange(t_max, dtype=u) < appended)
            )
            do_append = active & ~end_boundary
            idx = jnp.where(do_append, appended, u(t_max))
            fps_hi = fps_hi.at[idx].set(hi, mode="drop")
            fps_lo = fps_lo.at[idx].set(lo, mode="drop")
            trace = trace.at[idx].set(state, mode="drop")
            appended = appended + do_append.astype(u)
            end_cycle = do_append & seen
            count_this = do_append & ~seen
            counted = counted + count_this.astype(u)

            conds = cm.property_conds(state)
            here = appended - u(1)  # index of this state's fp
            for p in range(n_props):
                if p in always_idx:
                    hit = count_this & ~conds[p]
                elif p in sometimes_idx:
                    hit = count_this & conds[p]
                else:
                    continue
                cand = jnp.where(hit, here, u(NO_STEP))
                disc = disc.at[p].set(
                    jnp.where(disc[p] == u(NO_STEP), cand, disc[p])
                )
            for bit, p in enumerate(ev_indices):
                ebits = ebits & ~(
                    (count_this & conds[p]).astype(u) << bit
                )

            if has_flags:
                nexts, valid, sf = cm.step(state)
                flag = flag | (sf & count_this)
            else:
                nexts, valid = cm.step(state)
            valid = valid & count_this
            if fault_hook is not None:
                valid, hook_carry = fault_hook.apply(
                    t, state, valid, hook_carry, params
                )
            v = jnp.sum(valid, dtype=u)
            terminal = count_this & (v == u(0))
            key, sub = jax.random.split(key)
            j = jax.random.randint(sub, (), 0, jnp.maximum(v, u(1)))
            lane = jnp.argmax(jnp.cumsum(valid.astype(u)) == j + u(1))
            advance = count_this & (v > u(0))
            state = jnp.where(advance, nexts[lane], state)
            done = done | end_boundary | end_cycle | terminal
            return (
                state,
                fps_hi,
                fps_lo,
                trace,
                ebits,
                disc,
                done,
                counted,
                appended,
                flag,
                hook_carry,
                key,
            )

        carry = (
            state0,
            jnp.zeros((t_max,), jnp.uint32),
            jnp.zeros((t_max,), jnp.uint32),
            jnp.zeros((t_max, cm.state_width), jnp.uint32),
            jnp.uint32(eb0),
            jnp.full((n_props,), NO_STEP, jnp.uint32),
            jnp.zeros((), jnp.bool_),
            jnp.uint32(0),
            jnp.uint32(0),
            jnp.zeros((), jnp.bool_),
            hook_carry,
            key,
        )
        (
            _state,
            fps_hi,
            fps_lo,
            trace,
            ebits,
            disc,
            done,
            counted,
            appended,
            flag,
            _hook_carry,
            _key,
        ) = jax.lax.fori_loop(0, t_max, body, carry)

        # Trace truncated by the depth bound (never ended): skip the
        # leftover-eventually check, like the host's ended_by_depth.
        u = jnp.uint32
        for bit, p in enumerate(ev_indices):
            left = done & (((ebits >> bit) & u(1)) == u(1))
            cand = jnp.where(left, appended - u(1), u(NO_STEP))
            disc = disc.at[p].set(
                jnp.where(disc[p] == u(NO_STEP), cand, disc[p])
            )
        return trace, disc, counted, appended, flag

    if fault_hook is None:
        return lambda key: walk(key)
    return walk


class TpuSimulationChecker(Checker):
    """Monte-carlo checker running ``walkers`` traces per device batch."""

    def __init__(
        self,
        options,
        seed: int,
        walkers: int = 1024,
        max_trace_len: Optional[int] = None,
        device=None,
        compiled: Optional[CompiledModel] = None,
    ):
        super().__init__(options.model)
        import jax

        if options._visitor is not None:
            raise ValueError(
                "spawn_tpu_simulation() does not support visitors"
            )
        if options._symmetry is not None:
            raise ValueError(
                "spawn_tpu_simulation() does not support symmetry reduction"
            )
        self._options = options
        self._seed = seed
        self._walkers = walkers
        # The device walk is bounded; target_max_depth (if set) is exactly
        # the host's depth bound, otherwise a generous default.
        self._t = max_trace_len or options._target_max_depth or 256
        self._device = device or jax.devices()[0]
        self._compiled = compiled or compiled_model_for(options.model)
        self._properties = self._model.properties()
        self._ev_indices = [
            i
            for i, p in enumerate(self._properties)
            if p.expectation is Expectation.EVENTUALLY
        ]
        self._state_count = 0
        self._max_depth = 0
        self._discovery_fps: Dict[str, List[int]] = {}
        self._discoveries_cache: Optional[Dict[str, Path]] = None
        self._shutdown = threading.Event()
        self._done = threading.Event()
        self._errors: List[BaseException] = []
        self._lock = threading.Lock()

        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    # --- device program ------------------------------------------------------

    def _build_batch(self):
        import jax

        walk = build_walk(self._compiled, self._properties, self._t)
        return jax.jit(jax.vmap(walk))

    # --- host loop -----------------------------------------------------------

    def _run(self) -> None:
        try:
            self._check()
        except BaseException as e:
            self._errors.append(e)
        finally:
            self._done.set()

    def _check(self) -> None:
        import jax

        opts = self._options
        props = self._properties
        deadline = (
            time.monotonic() + opts._timeout if opts._timeout is not None else None
        )

        with jax.default_device(self._device):
            batch = self._build_batch()
            base = jax.random.PRNGKey(self._seed)
            round_idx = 0
            while not self._shutdown.is_set():
                keys = jax.vmap(
                    lambda w: jax.random.fold_in(
                        jax.random.fold_in(base, round_idx), w
                    )
                )(np.arange(self._walkers))
                trace_dev, disc_dev, counted_dev, appended_dev, flag_dev = (
                    batch(keys)
                )
                disc = np.asarray(disc_dev)
                counted = np.asarray(counted_dev)
                appended = np.asarray(appended_dev)
                if bool(np.asarray(flag_dev).any()):
                    raise RuntimeError(
                        "the model step kernel flagged an encoding-capacity "
                        "overflow during a simulated trace"
                    )
                # Packed-state traces are pulled per discovered walker only
                # (one [T, W] row, not the whole batch).
                with self._lock:
                    self._state_count += int(counted.sum())
                    self._max_depth = max(
                        self._max_depth, int(appended.max(initial=0))
                    )
                    for p, prop in enumerate(props):
                        if prop.name in self._discovery_fps:
                            continue
                        hits = np.flatnonzero(disc[:, p] != NO_STEP)
                        if len(hits):
                            wkr = int(hits[0])
                            end = int(disc[wkr, p]) + 1
                            row = np.asarray(trace_dev[wkr, :end])
                            fps = [
                                self._model.fingerprint(
                                    self._compiled.decode(row[i])
                                )
                                for i in range(end)
                            ]
                            self._discovery_fps[prop.name] = fps
                round_idx += 1
                if opts._finish_when.matches(
                    frozenset(self._discovery_fps), props
                ):
                    return
                if (
                    opts._target_state_count is not None
                    and opts._target_state_count <= self._state_count
                ):
                    return
                if deadline is not None and time.monotonic() >= deadline:
                    return

    # --- Checker surface -----------------------------------------------------

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        # No global visited set, matching the host simulation engine.
        return self._state_count

    def max_depth(self) -> int:
        return self._max_depth

    def discoveries(self) -> Dict[str, Path]:
        self.join()
        if self._discoveries_cache is None:
            with self._lock:
                items = list(self._discovery_fps.items())
            self._discoveries_cache = {
                name: Path.from_fingerprints(self._model, fps)
                for name, fps in items
            }
        return dict(self._discoveries_cache)

    def try_discovery(self, name: str) -> Optional[Path]:
        # Non-blocking while the run is live; a failed run surfaces its
        # error through join(), not here.
        if not self._done.is_set() or self._errors:
            return None
        return self.discoveries().get(name)

    def handles(self) -> List[threading.Thread]:
        return [self._thread]

    def is_done(self) -> bool:
        return self._done.is_set()

    def shutdown(self) -> None:
        """Stop after the in-flight batch: without this, a run whose
        ``finish_when`` never matches and that has neither ``timeout`` nor
        ``target_state_count`` would walk forever (the host engine's
        ``_shutdown`` event, core/simulation.py)."""
        self._shutdown.set()

    def request_stop(self) -> None:
        super().request_stop()
        self._shutdown.set()

    def join(self) -> "TpuSimulationChecker":
        self._thread.join()
        if self._errors:
            raise self._errors[0]
        return self
