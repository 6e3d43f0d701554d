"""Plain reference for abstract two-phase commit (stateright
examples/2pc.rs), written from the model's description alone.

It imports nothing of the program.  A state packs into one int64:

    bits [0, 2n)        rm_state, 2 bits per RM (working, prepared,
                        committed, aborted)
    bits [2n, 2n+2)     tm_state (init, committed, aborted)
    bits [2n+2, 3n+2)   tm_prepared, one bit per RM
    bits [3n+2, 4n+2)   message prepared(rm), one bit per RM
    bit  4n+2           message commit
    bit  4n+3           message abort

The messages form a set, so a message sent twice is one message.  The
search is a level-by-level breadth-first search with numpy arrays:
every state of a level is expanded at once, and a successor is new when
it is in no earlier level.  Run it as ``python twophase.py N``.
"""

from __future__ import annotations

import json
import sys

import numpy as np

WORKING, PREPARED, COMMITTED, ABORTED = 0, 1, 2, 3
TM_INIT, TM_COMMITTED, TM_ABORTED = 0, 1, 2


def _layout(n: int):
    rm = lambda i: 2 * i  # noqa: E731
    tm = 2 * n
    tp = lambda i: 2 * n + 2 + i  # noqa: E731
    mp = lambda i: 3 * n + 2 + i  # noqa: E731
    return rm, tm, tp, mp, 4 * n + 2, 4 * n + 3


def _field(s, shift, width):
    return (s >> np.int64(shift)) & np.int64((1 << width) - 1)


def _set(s, shift, width, value):
    mask = np.int64(((1 << width) - 1) << shift)
    return (s & ~mask) | (np.int64(value) << np.int64(shift))


def successors(states: np.ndarray, n: int) -> np.ndarray:
    """Every successor of every state, as one flat int64 array."""
    rm, tm, tp, mp, commit, abort = _layout(n)
    tm_state = _field(states, tm, 2)
    tm_init = tm_state == TM_INIT
    has_commit = _field(states, commit, 1) == 1
    has_abort = _field(states, abort, 1) == 1
    all_prepared = np.ones(states.shape, bool)
    for i in range(n):
        all_prepared &= _field(states, tp(i), 1) == 1
    out = []
    # TmCommit and TmAbort.
    s = states[tm_init & all_prepared]
    out.append(_set(_set(s, tm, 2, TM_COMMITTED), commit, 1, 1))
    s = states[tm_init]
    out.append(_set(_set(s, tm, 2, TM_ABORTED), abort, 1, 1))
    for i in range(n):
        working = _field(states, rm(i), 2) == WORKING
        # TmRcvPrepared(i)
        s = states[tm_init & (_field(states, mp(i), 1) == 1)]
        out.append(_set(s, tp(i), 1, 1))
        # RmPrepare(i)
        s = states[working]
        out.append(_set(_set(s, rm(i), 2, PREPARED), mp(i), 1, 1))
        # RmChooseToAbort(i)
        out.append(_set(s, rm(i), 2, ABORTED))
        # RmRcvCommitMsg(i), RmRcvAbortMsg(i)
        out.append(_set(states[has_commit], rm(i), 2, COMMITTED))
        out.append(_set(states[has_abort], rm(i), 2, ABORTED))
    return np.concatenate(out)


def _all_rm(states, n, value):
    rm = _layout(n)[0]
    ok = np.ones(states.shape, bool)
    for i in range(n):
        ok &= _field(states, rm(i), 2) == value
    return ok


def _any_rm(states, n, value):
    rm = _layout(n)[0]
    hit = np.zeros(states.shape, bool)
    for i in range(n):
        hit |= _field(states, rm(i), 2) == value
    return hit


def properties(states: np.ndarray, n: int) -> dict:
    """Which of the model's three properties this level discovers: the
    two ``sometimes`` ones where some state satisfies them, ``consistent``
    (an ``always``) where some state breaks it."""
    aborted, committed = _any_rm(states, n, ABORTED), _any_rm(
        states, n, COMMITTED)
    return {
        "abort agreement": bool(_all_rm(states, n, ABORTED).any()),
        "commit agreement": bool(_all_rm(states, n, COMMITTED).any()),
        "consistent": bool((aborted & committed).any()),
    }


def check(n: int) -> dict:
    """Exhaustive BFS: unique states, generated successors, depth (the
    number of levels, the initial state's level being 1) and the names
    of the properties discovered."""
    level = np.zeros(1, np.int64)  # every RM working, TM init, no messages
    seen = level.copy()
    generated, depth = 0, 0
    found = set()
    while level.size:
        depth += 1
        found |= {k for k, v in properties(level, n).items() if v}
        succ = successors(level, n)
        generated += succ.size
        succ = np.unique(succ)
        new = succ[~np.isin(succ, seen, assume_unique=True)]
        seen = np.union1d(seen, new)
        level = new
    return {
        "unique": int(seen.size),
        "generated": int(generated),
        "max_depth": depth,
        "discoveries": sorted(found),
    }


if __name__ == "__main__":
    print(json.dumps(check(int(sys.argv[1]))))
