"""Wave-evaluation logic shared by the single-device and sharded engines.

Both engines evaluate the frontier the same way — property conditions at
expansion time (the pop-time analog of src/checker/bfs.rs:230-281),
eventually-bit clearing, successor expansion, and terminal
eventually-counterexample detection — differing only in how a state is
identified (a table slot on one device, a shard<<bits|slot global id across
a mesh).  Keeping it in one place keeps the two engines' discovery
semantics from diverging.
"""

from __future__ import annotations

from typing import NamedTuple

from ..core.model import Expectation

NO_ID = 0xFFFFFFFF


class WaveEval(NamedTuple):
    disc_cand: object  # uint32[P] candidate state-id per property (NO_ID none)
    eb: object  # uint32[F] eventually-bits after this state's own clears
    nexts: object  # uint32[F, A, W] successor candidates
    valid: object  # bool[F, A]
    generated: object  # uint32 scalar: local boundary-passing successors
    step_flag: object  # bool scalar: a successor overflowed the encoding


def finish_when_trivially_true(fw, props) -> bool:
    """Policies that match with zero discoveries (e.g. ALL with no
    properties); only the host-side ``matches()`` check stops those,
    preserving the at-least-one-block-first behavior of the reference."""
    fail_props = [p for p in props if p.expectation.discovery_is_failure]
    return (
        (fw._kind == "all" and not props)
        or (fw._kind == "all_failures" and not fail_props)
        or (fw._kind == "all_of" and not fw._names)
    )


def default_waves_per_call(options) -> int:
    """How many chunks each fused run() call may execute before a host
    sync.  Fidelity knobs that only the host can check (wall-clock timeout,
    target_state_count) and trivially-true finish_when policies force
    one-chunk granularity; everything else — including finish_when, which
    is mirrored on device — runs 256 chunks per sync.  Shared so the
    single-chip and sharded engines cannot drift apart."""
    fine_grained = (
        options._timeout is not None
        or options._target_state_count is not None
        or finish_when_trivially_true(
            options._finish_when, options.model.properties()
        )
    )
    return 1 if fine_grained else 256


def make_finish_when_device(fw, props):
    """Device mirror of ``HasDiscoveries.matches()`` (has_discoveries.py):
    returns ``fn(found: bool[P]) -> bool scalar`` deciding whether the
    policy is satisfied.  Constant-TRUE policies return False here — see
    :func:`finish_when_trivially_true`."""
    n_props = len(props)
    fail_idx = [
        i for i, p in enumerate(props) if p.expectation.discovery_is_failure
    ]
    name_idx = {p.name: i for i, p in enumerate(props)}
    named = [name_idx[n] for n in sorted(fw._names) if n in name_idx]
    names_all_known = all(n in name_idx for n in fw._names)
    kind = fw._kind

    def matched(found):
        import jax.numpy as jnp

        false = jnp.zeros((), jnp.bool_)
        if kind == "all":
            return jnp.all(found) if n_props else false
        if kind == "any":
            return jnp.any(found) if n_props else false
        if kind == "any_failures":
            return jnp.any(found[jnp.asarray(fail_idx)]) if fail_idx else false
        if kind == "all_failures":
            return jnp.all(found[jnp.asarray(fail_idx)]) if fail_idx else false
        if kind == "all_of":
            if not names_all_known or not named:
                return false
            return jnp.all(found[jnp.asarray(named)])
        if kind == "any_of":
            return jnp.any(found[jnp.asarray(named)]) if named else false
        raise ValueError(kind)

    return matched


def two_phase_capable(cm) -> bool:
    """Host-side mirror of :func:`wave_eval`'s two-phase gate: the model
    exposes ``step_valid`` + ``step_lane`` AND is unbounded (``boundary``
    None).  The tiered engine's ``trace=True`` uses it to pick the
    matching roofline byte model; keeping it beside
    the trace-time gate keeps the two from drifting."""
    import numpy as np

    if not (hasattr(cm, "step_valid") and hasattr(cm, "step_lane")):
        return False
    return cm.boundary(np.zeros((cm.state_width,), np.uint32)) is None


# --- compile observability (docs/OBSERVABILITY.md "Compile events") ----------
#
# A program-cache MISS is the recompile event the serving layer's
# warm-start story hinges on; these knobs turn misses into attributable
# evidence: each compiled program's FIRST invocation is timed (JAX
# compiles lazily at first call, so that wall time is compile + first
# execution — an upper bound on compile cost, documented as such), the
# knobs that formed the cache key travel as ``provenance`` on the
# journaled ``compile`` event, and a burst of misses inside the storm
# window raises a ``recompile_storms`` counter + a storm-flagged journal
# event (the `watch` verb and CI smoke alert on it).  A storm means the
# key is churning — knob defaults moving under a warm cache, or a
# geometry ladder thrashing — exactly the condition that silently eats a
# "warm" daemon's latency budget.
COMPILE_STORM_WINDOW_SEC = 120.0
COMPILE_STORM_THRESHOLD = 6
_COMPILE_TIMES: list = []  # monotonic stamps of recent first-call compiles
_STORM_ACTIVE = [False]


def _note_compile(now: float) -> bool:
    """Fold one compile stamp into the storm window; True exactly at the
    rising edge (quiet -> storm), so the counter counts storms, not
    compiles."""
    _COMPILE_TIMES.append(now)
    cutoff = now - COMPILE_STORM_WINDOW_SEC
    while _COMPILE_TIMES and _COMPILE_TIMES[0] < cutoff:
        _COMPILE_TIMES.pop(0)
    in_storm = len(_COMPILE_TIMES) >= COMPILE_STORM_THRESHOLD
    rising = in_storm and not _STORM_ACTIVE[0]
    _STORM_ACTIVE[0] = in_storm
    return rising


# Per-cache-entry instrumentation context, REFRESHED on every
# cached_program access (hit or miss) so a wrapper's deferred first
# call attributes the compile to the engine that actually invoked it —
# the builder's journal is never captured permanently (an engine that
# builds but dies before invoking must not receive a later caller's
# compile event into its finished run's record).  Keyed by
# (id(cache), key); entries evicted in lockstep with the cache.
_PROGRAM_CTX: dict = {}


def _record_compile(ctx, sublabel, sec) -> None:
    import logging
    import time

    from ..obs.metrics import GLOBAL, LATENCY_BUCKETS

    label = f"{ctx.get('label', 'program')}{sublabel}"
    GLOBAL.inc("compile_sec_total", sec)
    GLOBAL.set("last_compile_sec", round(sec, 4))
    GLOBAL.observe("compile_sec", sec, boundaries=LATENCY_BUCKETS)
    storm = _note_compile(time.monotonic())
    if storm:
        GLOBAL.inc("recompile_storms")
        logging.getLogger(__name__).warning(
            "recompile storm: %d compiles within %.0fs (latest: %s) — "
            "a program-cache key is churning",
            len(_COMPILE_TIMES), COMPILE_STORM_WINDOW_SEC, label,
        )
    journal = ctx.get("journal")
    if journal is not None:
        fields = {"label": label, "sec": round(sec, 4),
                  "cache_size": ctx.get("cache_size", 0)}
        if ctx.get("provenance"):
            fields["provenance"] = ctx["provenance"]
        if storm:
            fields["storm"] = True
            fields["storm_compiles"] = len(_COMPILE_TIMES)
        journal.append("compile", **fields)


def _timed_first_call(fn, sublabel, ctx):
    """Wrap one compiled callable so its FIRST invocation — where JAX
    actually traces + lowers + compiles — is timed and recorded; later
    calls pay one flag check.  ``ctx`` is the live per-cache-entry
    context (journal/label/provenance), read at FIRE time."""
    import time
    from functools import wraps

    state = [True]

    @wraps(fn)
    def wrapper(*args, **kwargs):
        if state[0]:
            state[0] = False
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            _record_compile(ctx, sublabel, time.perf_counter() - t0)
            return out
        return fn(*args, **kwargs)

    return wrapper


def _instrument_programs(prog, ctx):
    """Wrap every callable in a program (a bare callable, a tuple like
    the single-chip ``(seed, run)`` pair, or a phase-program dict) —
    each is a distinct XLA program with its own compile."""
    if callable(prog):
        return _timed_first_call(prog, "", ctx)
    if isinstance(prog, tuple):
        return tuple(
            _timed_first_call(p, f"[{i}]", ctx) if callable(p) else p
            for i, p in enumerate(prog)
        )
    if isinstance(prog, dict):
        return {
            k: _timed_first_call(p, f".{k}", ctx) if callable(p) else p
            for k, p in prog.items()
        }
    return prog


def cached_program(cache: dict, max_size: int, key, build,
                   label: str = "program", journal=None, provenance=None):
    """Bounded-FIFO memo for compiled engine programs, shared by the
    single-chip and sharded engines so the key-tuple + eviction idiom
    exists once.  The KEY must cover everything the built closure traces
    over — a stale hit is a silent wrong-program bug.

    Hits and misses count into the process-global metrics registry
    (``program_cache_hits`` / ``program_cache_misses``): the observable
    evidence that a warm repeat of a workload skipped its compiles —
    the checking service's warmup-reuse counter (docs/SERVING.md).

    A miss additionally records COMPILE observability (the helpers
    above): each built callable's first invocation is timed, journaled
    as a ``compile`` event carrying ``label`` and ``provenance`` (the
    human-readable knobs behind the cache key), folded into the
    process-global ``compile_sec_total``/``compile_sec`` metrics, and
    watched by the recompile-storm detector.  ``journal``/``label``/
    ``provenance`` refresh the entry's live context on EVERY access —
    hits included — so a deferred first call journals into the engine
    that actually invoked (and paid for) the compile, never a dead
    builder's record; hits journal nothing themselves (a hit is the
    warm path the evidence exists to prove)."""
    from ..obs.metrics import GLOBAL

    ctx = _PROGRAM_CTX.setdefault((id(cache), key), {})
    ctx.update(label=label, journal=journal, provenance=provenance)
    prog = cache.get(key)
    if prog is None:
        GLOBAL.inc("program_cache_misses")
        ctx["cache_size"] = len(cache) + 1
        prog = _instrument_programs(build(), ctx)
        while len(cache) >= max_size:
            evicted = next(iter(cache))
            cache.pop(evicted)
            _PROGRAM_CTX.pop((id(cache), evicted), None)
        cache[key] = prog
    else:
        GLOBAL.inc("program_cache_hits")
    return prog


def compact(mask, values, size: int):
    """Stream-compact ``values[mask]`` into a ``size``-wide buffer (excess
    dropped; caller checks counts).  One shared definition of the
    cumsum/where/scatter idiom both engines and the hash set rely on."""
    import jax.numpy as jnp

    pos = jnp.cumsum(mask.astype(jnp.uint32)) - 1
    idx = jnp.where(mask, pos, jnp.uint32(size))
    if values.ndim == 1:
        buf = jnp.zeros((size,), values.dtype)
    else:
        buf = jnp.zeros((size,) + values.shape[1:], values.dtype)
    return buf.at[idx].set(values, mode="drop")


def wave_eval(cm, props, ev_indices, states, active, ids, eb_in, disc,
              allow_two_phase: bool = False):
    """The shared wave step (minus dedup/insert, which differs per engine).

    Returns :class:`WaveEval` with ``disc`` already folded (first-writer-
    wins against the incoming ``disc`` vector).  With ``allow_two_phase``
    and a model exposing BOTH ``step_valid`` and ``step_lane``, ``nexts``
    comes back None — the caller constructs successors itself (via
    ``step_lane``) on the compacted valid lanes.
    """
    import jax
    import jax.numpy as jnp

    n_props = len(props)
    always_idx = {
        i for i, p in enumerate(props) if p.expectation is Expectation.ALWAYS
    }
    sometimes_idx = {
        i for i, p in enumerate(props) if p.expectation is Expectation.SOMETIMES
    }

    conds = jax.vmap(cm.property_conds)(states)  # [F, P]
    # "Awaiting discoveries": the reference stops expanding a state when
    # every property already has a discovery and this state contributes
    # none (src/checker/bfs.rs:231-281) — checked against the discoveries
    # as of wave start, the parallel analog of the reference's block-order
    # (and thread-racy) reads.
    discovered0 = [disc[p] != jnp.uint32(NO_ID) for p in range(n_props)]
    awaiting = jnp.zeros(active.shape, jnp.bool_)
    for p in range(n_props):
        if p in always_idx:
            awaiting = awaiting | (~discovered0[p] & conds[:, p])
        elif p in sometimes_idx:
            awaiting = awaiting | (~discovered0[p] & ~conds[:, p])
        else:  # EVENTUALLY: discovered only at trace ends — always awaited
            awaiting = awaiting | ~discovered0[p]
    for p in range(n_props):
        if p in always_idx:
            hit = active & ~conds[:, p]
        elif p in sometimes_idx:
            hit = active & conds[:, p]
        else:
            continue
        idx = jnp.argmax(hit)
        cand = jnp.where(jnp.any(hit), ids[idx], jnp.uint32(NO_ID))
        disc = disc.at[p].set(jnp.where(disc[p] == jnp.uint32(NO_ID), cand, disc[p]))

    # Clear this state's own satisfied eventually bits.
    eb = eb_in
    for bit, p in enumerate(ev_indices):
        eb = eb & ~(conds[:, p].astype(jnp.uint32) << bit)

    # Successor expansion.  Two-phase models answer lane VALIDITY without
    # constructing successors (construction then runs compacted, on the
    # ~5% surviving lanes — the engine's phase B); their per-lane
    # capacity flags surface in phase B instead.
    two_phase = (
        allow_two_phase
        and hasattr(cm, "step_valid")
        and hasattr(cm, "step_lane")
        and cm.boundary(states[0]) is None
    )
    if two_phase:
        nexts = None
        valid = jax.vmap(cm.step_valid)(states)  # [F, A]
        step_flag = jnp.zeros((), jnp.bool_)
    elif getattr(cm, "step_flags", False):
        nexts, valid, lane_flags = jax.vmap(cm.step)(states)
        step_flag = jnp.any(jnp.asarray(lane_flags) & active)
    else:
        nexts, valid = jax.vmap(cm.step)(states)  # [F, A, W], [F, A]
        step_flag = jnp.zeros((), jnp.bool_)
    valid = valid & active[:, None]
    # With zero properties nothing is ever awaited and the reference
    # expands nothing at all — the gate reproduces that too.
    valid = valid & awaiting[:, None]
    if cm.boundary(states[0]) is not None:
        valid = valid & jax.vmap(jax.vmap(cm.boundary))(nexts)
    generated = jnp.sum(valid, dtype=jnp.uint32)

    # Terminal frontier states with leftover ebits -> eventually
    # counterexamples (src/checker/bfs.rs:326-333).
    terminal = active & ~jnp.any(valid, axis=1)
    for bit, p in enumerate(ev_indices):
        hit = terminal & (((eb >> bit) & 1) == 1)
        idx = jnp.argmax(hit)
        cand = jnp.where(jnp.any(hit), ids[idx], jnp.uint32(NO_ID))
        disc = disc.at[p].set(jnp.where(disc[p] == jnp.uint32(NO_ID), cand, disc[p]))

    return WaveEval(disc, eb, nexts, valid, generated, step_flag)
