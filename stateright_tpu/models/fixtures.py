"""Fixture models for tests (and docs).

Reference: src/test_util.rs — binary_clock, dgraph, linear_equation_solver,
and panicker, reproduced with the same state spaces so the reference's
golden counts (e.g. 65,536 states for full LinearEquation enumeration) pin
this implementation too.  ``TrapCounter`` (+ its compiled form) is this
package's own fixture for the device engines: the smallest model
exercising the full eventually-property pipeline, and — via its identity
canonicalization — the symmetry plumbing on a model with no symmetric
structure.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

import numpy as np

from ..core.model import Model, Property
from ..parallel.compiled import CompiledModel


class BinaryClock(Model):
    """2-state cycle; the smallest possible model (src/test_util.rs:4-47)."""

    class Action(enum.Enum):
        GO_LOW = "GoLow"
        GO_HIGH = "GoHigh"

        def __repr__(self) -> str:
            return self.value

    def init_states(self):
        return [0, 1]

    def actions(self, state, actions):
        if state == 0:
            actions.append(BinaryClock.Action.GO_HIGH)
        else:
            actions.append(BinaryClock.Action.GO_LOW)

    def next_state(self, state, action):
        return 1 if action is BinaryClock.Action.GO_HIGH else 0

    def properties(self):
        return [Property.always("in [0, 1]", lambda _m, s: 0 <= s <= 1)]


@dataclass
class DGraph(Model):
    """A directed graph specified via paths from initial states; the harness
    for eventually-property semantics tests (src/test_util.rs:50-116)."""

    inits: Set[int] = field(default_factory=set)
    edges: Dict[int, Set[int]] = field(default_factory=dict)
    props: List[Property] = field(default_factory=list)

    @staticmethod
    def with_property(prop: Property) -> "DGraph":
        return DGraph(props=[prop])

    def with_path(self, path: List[int]) -> "DGraph":
        src = path[0]
        self.inits.add(src)
        for dst in path[1:]:
            self.edges.setdefault(src, set()).add(dst)
            src = dst
        return self

    def check(self):
        return self.checker().spawn_bfs().join()

    def init_states(self):
        return sorted(self.inits)

    def actions(self, state, actions):
        actions.extend(sorted(self.edges.get(state, ())))

    def next_state(self, state, action):
        return action

    def properties(self):
        return list(self.props)


@dataclass
class LinearEquation(Model):
    """Finds x, y with a*x + b*y = c (mod 256); the standard checker test —
    full enumeration is 65,536 states (src/test_util.rs:140-192)."""

    a: int
    b: int
    c: int

    class Guess(enum.Enum):
        INCREASE_X = "IncreaseX"
        INCREASE_Y = "IncreaseY"

        def __repr__(self) -> str:
            return self.value

    def init_states(self):
        return [(0, 0)]

    def actions(self, state, actions):
        actions.append(LinearEquation.Guess.INCREASE_X)
        actions.append(LinearEquation.Guess.INCREASE_Y)

    def next_state(self, state, action):
        x, y = state
        if action is LinearEquation.Guess.INCREASE_X:
            return ((x + 1) % 256, y)
        return (x, (y + 1) % 256)

    def properties(self):
        def solvable(model, solution):
            x, y = solution
            return (model.a * x + model.b * y) % 256 == model.c

        return [Property.sometimes("solvable", solvable)]


class Panicker(Model):
    """Raises mid-exploration to test clean thread shutdown
    (src/test_util.rs:195-228)."""

    def init_states(self):
        return [0]

    def actions(self, state, actions):
        actions.append(1)

    def next_state(self, last_state, action):
        if last_state == 5:
            raise RuntimeError("reached panic state")
        return last_state + action

    def properties(self):
        return [Property.always("true", lambda _m, _s: True)]


class TrapCounter(Model):
    """0 →inc→ 1 → … → limit, with a dead-end trap edge at ``trap_at``.

    Exercises the full eventually pipeline: "reaches one" is satisfied
    along every path (bit cleared mid-path, never reported); "reaches
    limit" has a genuine counterexample ending in the trap terminal state.
    States are plain ints with no symmetric structure, so the compiled
    form's canonicalization is the identity — the fixture for pinning
    that symmetry-on changes nothing when there is nothing to reduce
    (``checker().symmetry_fn(lambda s: s)`` on the host side).
    """

    def __init__(self, limit=5, trap_at=2):
        self.limit = limit
        self.trap_at = trap_at
        self.trap_state = limit + 1

    def init_states(self):
        return [0]

    def actions(self, state, actions):
        if state < self.limit:
            actions.append("inc")
        if state == self.trap_at:
            actions.append("trap")

    def next_state(self, state, action):
        return state + 1 if action == "inc" else self.trap_state

    def properties(self):
        return [
            Property.eventually("reaches one", lambda _m, s: s >= 1),
            Property.eventually(
                "reaches limit", lambda _m, s: s == self.limit
            ),
            Property.sometimes(
                "trapped", lambda _m, s: s == self.trap_state
            ),
        ]

    def compiled(self):
        return TrapCounterCompiled(self)


class TrapCounterCompiled(CompiledModel):
    state_width = 1
    max_actions = 2

    def __init__(self, model):
        self.model = model

    def encode(self, state):
        return np.array([state], np.uint32)

    def decode(self, words):
        return int(words[0])

    def step(self, state):
        import jax.numpy as jnp

        n = state[0]
        limit = jnp.uint32(self.model.limit)
        inc = jnp.stack([n + jnp.uint32(1)])
        trap = jnp.stack([jnp.uint32(self.model.trap_state)])
        nexts = jnp.stack([inc, trap])
        valid = jnp.stack(
            [n < limit, n == jnp.uint32(self.model.trap_at)]
        )
        return nexts, valid

    def property_conds(self, state):
        import jax.numpy as jnp

        n = state[0]
        return jnp.stack(
            [
                n >= jnp.uint32(1),
                n == jnp.uint32(self.model.limit),
                n == jnp.uint32(self.model.trap_state),
            ]
        )

    def canon_spec(self):
        """No symmetric records: the canonical form is the row itself —
        an empty spec, so symmetry-enabled runs must match plain runs
        bit-for-bit (pinned in tests/test_tpu_symmetry.py)."""
        from ..parallel.canon import CanonSpec

        return CanonSpec(n=0)

    def spec_constants(self):
        """TrapCounter is not a dataclass, so the incremental store's
        default constants derivation (parallel/compiled.py) cannot see
        ``limit``/``trap_at`` — declared explicitly so trap-counter
        entries participate in the store instead of degrading to
        "no stable constants"."""
        return {
            "limit": repr(self.model.limit),
            "trap_at": repr(self.model.trap_at),
        }


def cli_spec():
    """CLI/workload spec for :class:`TrapCounter` — the smallest
    KNOWN-VIOLATING workload with a compiled device form ("reaches
    limit" has a genuine counterexample ending in the trap terminal).
    Registered so the checking service (serve/workloads.py), its CI
    smoke, and the violation-exit-code CLI test all have a fast
    violating job to submit."""
    from ..cli import CliSpec

    return CliSpec(
        name="trap counter",
        # limit must clear trap_at=2 or the trap edge is unreachable
        # and the fixture stops violating.
        build=lambda n: TrapCounter(limit=max(n, 3)),
        default_n=5,
        n_meta="LIMIT",
        tpu=True,
        tpu_kwargs=dict(capacity=1 << 10, max_frontier=1 << 6),
    )


def main(argv=None) -> int:
    from ..cli import example_main

    return example_main(cli_spec(), argv)


@dataclass(frozen=True)
class GridWalk(Model):
    """Monotone walk on the integer grid ``[0, bound]²`` — the fixture
    for the incremental store's CONSTANT-WIDENING mode (incr/,
    docs/INCREMENTAL.md): the packed encoding is bound-independent
    (x and y each ride a 16-bit lane), the transition function emits
    the same candidate successors at every bound, and ``bound`` only
    prunes via the boundary — so raising it is a declared monotone
    reachable-set widening (``spec_widens``), exactly the "one constant
    bumped" re-check the store seeds from the prior reachable set.
    ``(bound+1)²`` unique states at depth ``2·bound``.  The always
    property never violates, so a completed run is exhaustive (every
    state stays awaited — the store's row-reuse witness)."""

    bound: int = 4

    def init_states(self):
        return [(0, 0)]

    def actions(self, state, actions):
        actions.append("right")
        actions.append("up")

    def next_state(self, state, action):
        x, y = state
        return (x + 1, y) if action == "right" else (x, y + 1)

    def within_boundary(self, state):
        x, y = state
        return x <= self.bound and y <= self.bound

    def properties(self):
        return [
            Property.always(
                "in bounds",
                lambda m, s: 0 <= s[0] <= m.bound and 0 <= s[1] <= m.bound,
            ),
            Property.sometimes(
                "reaches corner",
                lambda m, s: s[0] == m.bound and s[1] == m.bound,
            ),
        ]

    def compiled(self):
        return GridWalkCompiled(self)


class GridWalkCompiled(CompiledModel):
    state_width = 1
    max_actions = 2

    def __init__(self, model: GridWalk):
        if not 0 <= model.bound < (1 << 15):
            raise ValueError("GridWalk bound must fit a 16-bit lane")
        self.model = model

    def encode(self, state):
        x, y = state
        return np.array([x | (y << 16)], np.uint32)

    def decode(self, words):
        w = int(words[0])
        return (w & 0xFFFF, w >> 16)

    def step(self, state):
        import jax.numpy as jnp

        w = state[0]
        right = jnp.stack([w + jnp.uint32(1)])
        up = jnp.stack([w + jnp.uint32(1 << 16)])
        nexts = jnp.stack([right, up])
        valid = jnp.ones((2,), jnp.bool_)
        return nexts, valid

    def boundary(self, state):
        import jax.numpy as jnp

        w = state[0]
        b = jnp.uint32(self.model.bound)
        return ((w & jnp.uint32(0xFFFF)) <= b) & ((w >> jnp.uint32(16)) <= b)

    def property_conds(self, state):
        import jax.numpy as jnp

        w = state[0]
        b = jnp.uint32(self.model.bound)
        x = w & jnp.uint32(0xFFFF)
        y = w >> jnp.uint32(16)
        return jnp.stack([(x <= b) & (y <= b), (x == b) & (y == b)])

    def spec_widens(self, old_constants: dict) -> bool:
        """Raising ``bound`` only ever ADDS reachable states: every old
        state keeps its packed row, its candidate successors, and its
        in-old-bounds successors, and the boundary admits a superset —
        the store's constant-widening contract."""
        try:
            old_bound = int(str(old_constants["bound"]))
        except (KeyError, TypeError, ValueError):
            return False
        return set(old_constants) == {"bound"} and (
            old_bound <= self.model.bound
        )

    # --- gang batching (fleet/gang.py): the canonical gang family —
    # the codec is bound-independent, so differently-bounded walks
    # share one program with ``bound`` riding the consts lane.

    def gang_key(self):
        return ("GridWalk", self.state_width, self.max_actions, 2)

    def gang_constants(self):
        return np.array([self.model.bound], np.uint32)

    def gang_step(self, state, consts):
        del consts  # successors are bound-independent; boundary prunes
        return self.step(state)

    def gang_boundary(self, state, consts):
        import jax.numpy as jnp

        w = state[0]
        b = consts[0]
        return ((w & jnp.uint32(0xFFFF)) <= b) & ((w >> jnp.uint32(16)) <= b)

    def gang_property_conds(self, state, consts):
        import jax.numpy as jnp

        w = state[0]
        b = consts[0]
        x = w & jnp.uint32(0xFFFF)
        y = w >> jnp.uint32(16)
        return jnp.stack([(x <= b) & (y <= b), (x == b) & (y == b)])


@dataclass(frozen=True)
class CapCounter(Model):
    """Counter 0 → 1 → … → ``limit`` with an ALWAYS cap property — the
    gang-batch VIOLATION fixture (fleet/gang.py): "within cap" violates
    exactly when ``limit > cap``, so one gang can mix violating and
    clean members and each must report its own verdict (the per-job
    ``VIOLATION_RC`` parity gate).  The "counts up" ALWAYS property
    never violates, so — like GridWalk's "in bounds" — every state
    stays awaited and a completed run is EXHAUSTIVE whether or not the
    cap property discovered, which is what makes gang-vs-solo
    fingerprint parity independent of discovery timing."""

    limit: int = 6
    cap: int = 10

    def init_states(self):
        return [0]

    def actions(self, state, actions):
        if state < self.limit:
            actions.append("inc")

    def next_state(self, state, action):
        return state + 1

    def properties(self):
        return [
            Property.always("counts up", lambda _m, s: s >= 0),
            Property.always("within cap", lambda m, s: s <= m.cap),
            Property.sometimes("reaches limit", lambda m, s: s == m.limit),
        ]

    def compiled(self):
        return CapCounterCompiled(self)


class CapCounterCompiled(CompiledModel):
    state_width = 1
    max_actions = 1

    def __init__(self, model: CapCounter):
        self.model = model

    def encode(self, state):
        return np.array([state], np.uint32)

    def decode(self, words):
        return int(words[0])

    def step(self, state):
        import jax.numpy as jnp

        n = state[0]
        nexts = jnp.stack([jnp.stack([n + jnp.uint32(1)])])
        valid = jnp.stack([n < jnp.uint32(self.model.limit)])
        return nexts, valid

    def property_conds(self, state):
        import jax.numpy as jnp

        n = state[0]
        return jnp.stack([
            n >= jnp.uint32(0),
            n <= jnp.uint32(self.model.cap),
            n == jnp.uint32(self.model.limit),
        ])

    # consts = [limit, cap]: the step's enable mask and the cap
    # property both become data, so every CapCounter shares one traced
    # gang program regardless of parameters.

    def gang_key(self):
        return ("CapCounter", self.state_width, self.max_actions, 3)

    def gang_constants(self):
        return np.array([self.model.limit, self.model.cap], np.uint32)

    def gang_step(self, state, consts):
        import jax.numpy as jnp

        n = state[0]
        nexts = jnp.stack([jnp.stack([n + jnp.uint32(1)])])
        valid = jnp.stack([n < consts[0]])
        return nexts, valid

    def gang_property_conds(self, state, consts):
        import jax.numpy as jnp

        n = state[0]
        return jnp.stack([
            n >= jnp.uint32(0), n <= consts[1], n == consts[0],
        ])


class TwoPhaseEdited:
    """The "one-line model edit" fixture for the incremental store's
    PROPERTY-ONLY mode: two-phase commit with one property appended —
    codec, constants, and symmetry hash identical to the stock model
    (the subclasses below inherit ``encode``/``step`` unchanged, so the
    code digests match), only the property component differs.  Used by
    tests/test_incr.py and the CI incremental smoke as the canonical
    near-identical resubmission."""

    @staticmethod
    def build(rm_count: int) -> Model:
        from dataclasses import dataclass as _dc

        from .twophase import PREPARED, TwoPhaseSys
        from .twophase_compiled import TwoPhaseCompiled, _U32

        class _EditedCompiled(TwoPhaseCompiled):
            def property_conds(self, state):
                import jax.numpy as jnp

                base = TwoPhaseCompiled.property_conds(self, state)
                n = self.n
                w0 = state[0]
                some_prepared = jnp.zeros((), jnp.bool_)
                for rm in range(n):
                    rs = (w0 >> _U32(2 * rm)) & _U32(3)
                    some_prepared |= rs == _U32(PREPARED)
                return jnp.concatenate([base, some_prepared[None]])

        @_dc(frozen=True)
        class _Edited(TwoPhaseSys):
            def properties(self):
                return TwoPhaseSys.properties(self) + [
                    Property.sometimes(
                        "some rm prepared",
                        lambda _m, s: any(
                            r == PREPARED for r in s.rm_state
                        ),
                    ),
                ]

            def compiled(self):
                return _EditedCompiled(self)

        return _Edited(rm_count=rm_count)


class FnModel(Model):
    """A model defined by a function ``fn(prev_state_or_None, out_list)`` —
    the analog of the reference's blanket Model impl for functions
    (src/test_util.rs:119-137)."""

    def __init__(self, fn):
        self._fn = fn

    def init_states(self):
        out: list = []
        self._fn(None, out)
        return out

    def actions(self, state, actions):
        self._fn(state, actions)

    def next_state(self, state, action):
        return action


if __name__ == "__main__":
    import sys

    sys.exit(main())
