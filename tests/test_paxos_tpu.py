"""Device-kernel gates for the paxos flagship workload.

Three layers of defense, per docs/TPU_PAXOS_DESIGN.md:

1. step-kernel differential: device successor sets == host successor sets
   over the *entire* reachable space (C=1 exhaustively per-lane, C=2 as
   successor-set equality over all 16,668 states);
2. exact-linearizability differential: the on-device Wing&Gong-style
   subset-DP (`_device_linearizable`) agrees with the host
   ``LinearizabilityTester.serialized_history()`` on an exhaustive
   enumeration of consistent tester states — crucially including
   NON-linearizable ones, which the reachable paxos space never produces;
3. full-checker golden: ``spawn_tpu`` reproduces the reference's 16,668
   unique states (examples/paxos.rs:328) with a discovery set identical to
   the host oracle's.
"""

import numpy as np
import pytest

from stateright_tpu.actor import Id
from stateright_tpu.actor.model import Deliver
from stateright_tpu.models.paxos import PaxosModelCfg
from stateright_tpu.models.paxos_compiled import PaxosCompiled

from .test_paxos_compiled import enumerate_reachable, paxos_model


def lane_fn_for(cm):
    import jax
    import jax.numpy as jnp

    return jax.jit(
        jax.vmap(
            lambda st: jax.vmap(lambda k: cm._deliver_lane(st, k))(
                jnp.arange(cm.m, dtype=jnp.uint32)
            )
        )
    )


def test_step_differential_full_reachable_c1(reachable_c1):
    """Per-lane: validity, successor words, and flags for all 265 states."""
    import jax.numpy as jnp

    model = paxos_model(1)
    cm = PaxosCompiled(model)
    states = list(reachable_c1.values())
    enc = np.stack([cm.encode(s) for s in states]).astype(np.uint32)
    nexts, valid, flags = (
        np.asarray(x) for x in lane_fn_for(cm)(jnp.asarray(enc))
    )
    assert not flags.any()
    for bi, s in enumerate(states):
        host_map = {}
        for env in s.network.iter_deliverable():
            ns = model.next_state(s, Deliver(env.src, env.dst, env.msg))
            host_map[cm._env_code(env)] = None if ns is None else cm.encode(ns)
        for k in range(cm.m):
            code = int(enc[bi][cm._NET0 + k])
            if code == 0:
                assert not valid[bi, k]
                continue
            want = host_map[code]
            if want is None:
                assert not valid[bi, k], cm._env_of(code)
            else:
                assert valid[bi, k], cm._env_of(code)
                assert np.array_equal(nexts[bi, k], want), cm._env_of(code)


@pytest.mark.slow
def test_step_differential_full_reachable_c2(reachable_c2):
    """Successor-set equality over the full golden 16,668-state space."""
    import jax.numpy as jnp

    model = paxos_model(2)
    cm = PaxosCompiled(model)
    states = list(reachable_c2.values())
    enc = np.stack([cm.encode(s) for s in states]).astype(np.uint32)
    lane_fn = lane_fn_for(cm)
    bad = 0
    for off in range(0, len(states), 2048):
        chunk = enc[off : off + 2048]
        nexts, valid, flags = (
            np.asarray(x) for x in lane_fn(jnp.asarray(chunk))
        )
        assert not flags.any()
        for bi in range(len(chunk)):
            s = states[off + bi]
            host_succ = set()
            for env in s.network.iter_deliverable():
                ns = model.next_state(s, Deliver(env.src, env.dst, env.msg))
                if ns is not None:
                    host_succ.add(cm.encode(ns).tobytes())
            dev_succ = {
                nexts[bi, k].tobytes() for k in range(cm.m) if valid[bi, k]
            }
            bad += dev_succ != host_succ
    assert bad == 0


def test_step_differential_bounded_c3():
    """The c=3-only paths (32-slot network, 2-slot last-completed snapshots,
    third-client packing) differentially checked per-lane over a bounded
    host BFS prefix (every state to depth 7, ~4,700 states)."""
    import jax.numpy as jnp

    from stateright_tpu.ops.fingerprint import fingerprint

    model = paxos_model(3)
    cm = PaxosCompiled(model)
    seen = {}
    frontier = model.init_states()
    for s in frontier:
        seen[fingerprint(s)] = s
    for _ in range(7):
        nxt = []
        for s in frontier:
            acts = []
            model.actions(s, acts)
            for a in acts:
                ns = model.next_state(s, a)
                if ns is None:
                    continue
                fp = fingerprint(ns)
                if fp not in seen:
                    seen[fp] = ns
                    nxt.append(ns)
        frontier = nxt
    states = list(seen.values())
    enc = np.stack([cm.encode(s) for s in states]).astype(np.uint32)
    lane_fn = lane_fn_for(cm)
    for off in range(0, len(states), 2048):
        chunk = enc[off : off + 2048]
        nexts, valid, flags = (
            np.asarray(x) for x in lane_fn(jnp.asarray(chunk))
        )
        assert not flags.any()
        for bi in range(len(chunk)):
            s = states[off + bi]
            host_map = {}
            for env in s.network.iter_deliverable():
                ns = model.next_state(s, Deliver(env.src, env.dst, env.msg))
                host_map[cm._env_code(env)] = (
                    None if ns is None else cm.encode(ns)
                )
            for k in range(cm.m):
                code = int(chunk[bi][cm._NET0 + k])
                if code == 0:
                    assert not valid[bi, k]
                    continue
                want = host_map[code]
                if want is None:
                    assert not valid[bi, k], cm._env_of(code)
                else:
                    assert valid[bi, k], cm._env_of(code)
                    assert np.array_equal(nexts[bi, k], want), cm._env_of(code)


def _consistent_tester_words(cm, rng=None, limit=None):
    """Enumerate (or sample) consistent synthetic tester states as per-client
    packed words.  Consistency: a last-completed snapshot about thread j
    cannot claim more completed ops than j currently has (counts only grow,
    so any reachable state satisfies this)."""
    c = cm.c
    lcb = 2 * (c - 1)
    choices = []
    for phase in (0, 1, 2, 3, 4):
        lc_opts = [0]
        if phase >= 3:
            lc_opts = range(1 << lcb)
        v_opts = [0]
        if phase == 4:
            v_opts = range(c + 1)
        for lc in lc_opts:
            if any(((lc >> (2 * s)) & 3) == 3 for s in range(c - 1)):
                continue  # code 3 (index 2) does not exist: ops/thread <= 2
            for v in v_opts:
                choices.append((phase, lc, v))
    import itertools

    combos = itertools.product(choices, repeat=c)
    if limit is not None:
        combos = list(combos)
        rng.shuffle(combos)
        combos = combos[:limit]
    for combo in combos:
        phases = [x[0] for x in combo]
        ok = True
        words = []
        for i, (phase, lc, v) in enumerate(combo):
            slot = 0
            for j in range(c):
                if j == i:
                    continue
                code = (lc >> (2 * slot)) & 3
                cnt_j = (phases[j] >= 2) + (phases[j] == 4)
                if code > cnt_j:
                    ok = False
                slot += 1
            words.append(phase | (lc << (3 + lcb)) | (v << (3 + 2 * lcb)))
        if ok:
            yield words


def _lin_cases(c, rng=None, limit=None):
    from stateright_tpu.models.paxos import NULL_VALUE
    from stateright_tpu.semantics import LinearizabilityTester, Register

    model = paxos_model(c)
    cm = PaxosCompiled(model)
    cases = []
    for words in _consistent_tester_words(cm, rng=rng, limit=limit):
        tester = LinearizabilityTester(Register(NULL_VALUE))
        for i, w in enumerate(words):
            cm._decode_tester_into(tester, w, i)
        state = np.zeros(cm.state_width, np.uint32)
        tst0 = cm._NET0 + cm.m
        for i, w in enumerate(words):
            state[tst0 + i] = w
        cases.append((state, tester.serialized_history() is not None))
    return cm, cases


def _assert_lin_matches(cm, cases):
    import jax
    import jax.numpy as jnp

    lin = jax.jit(jax.vmap(cm._device_linearizable))
    enc = np.stack([s for s, _ in cases])
    got = np.asarray(lin(jnp.asarray(enc)))
    want = np.array([w for _, w in cases])
    mism = np.flatnonzero(got != want)
    assert len(mism) == 0, (
        f"{len(mism)} mismatches, first state={enc[mism[0]]}, "
        f"host={want[mism[0]]}, device={got[mism[0]]}"
    )
    # The enumeration must actually exercise violations.
    assert (~want).sum() > 0


def test_device_linearizability_exhaustive_c2():
    cm, cases = _lin_cases(2)
    _assert_lin_matches(cm, cases)


def test_device_linearizability_sampled_c3():
    import random

    cm, cases = _lin_cases(3, rng=random.Random(7), limit=2500)
    _assert_lin_matches(cm, cases)


@pytest.mark.slow
def test_spawn_tpu_paxos2_matches_host_oracle(reachable_c2):
    model = paxos_model(2)
    tpu = (
        model.checker()
        .spawn_tpu(capacity=1 << 18, max_frontier=1 << 13)
        .join()
    )
    assert tpu.unique_state_count() == 16_668  # examples/paxos.rs:328
    host = paxos_model(2).checker().spawn_bfs().join()
    assert tpu.unique_state_count() == host.unique_state_count()
    assert tpu.state_count() == host.state_count()
    assert tpu.max_depth() == host.max_depth()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())
    # The device discovery must replay as a genuine example trace.
    tpu.assert_properties()


@pytest.mark.slow
def test_violating_variant_found_on_device():
    """The bench's time-to-first-violation variant: an always-"never
    decided" property that paxos falsifies; the device discovery must
    replay as a genuine counterexample trace."""
    from stateright_tpu.actor import Network
    from stateright_tpu.core.has_discoveries import HasDiscoveries

    model = PaxosModelCfg(
        client_count=2,
        server_count=3,
        network=Network.new_unordered_nonduplicating(),
        never_decided=True,
    ).into_model()
    tpu = (
        model.checker()
        .finish_when(HasDiscoveries.ANY_FAILURES)
        .spawn_tpu(capacity=1 << 16, max_frontier=1 << 10)
        .join()
    )
    assert "never decided" in tpu.discoveries()
    final = tpu.discoveries()["never decided"].last_state()
    assert any(getattr(a, "is_decided", False) for a in final.actor_states)


def test_step_flag_overflow_is_loud():
    """A delivery whose sends exceed the slot budget must flag, not corrupt."""
    import jax
    import jax.numpy as jnp

    from stateright_tpu.actor import Envelope
    from stateright_tpu.actor.register import Internal, Put
    from stateright_tpu.models.paxos import Prepare

    model = paxos_model(2)
    cm = PaxosCompiled(model)
    state = np.zeros(cm.state_width, np.uint32)
    # Slot 0: client 0's Put to server 0 (delivery broadcasts 2 Prepares).
    codes = [cm._env_code(Envelope(Id(3), Id(0), Put(3, "A")))]
    # Fill the rest with distinct well-formed Prepare envelopes.
    for r in range(2, 10):
        for src in range(3):
            for dst in range(3):
                if src != dst and len(codes) < cm.m:
                    codes.append(
                        cm._env_code(
                            Envelope(Id(src), Id(dst), Internal(Prepare((r, Id(src)))))
                        )
                    )
    assert len(codes) == cm.m
    for k, code in enumerate(sorted(codes)):
        state[cm._NET0 + k] = code
    nexts, valid, flag = cm.step(jnp.asarray(state))
    assert bool(jnp.any(flag))


def test_engine_surfaces_step_flag():
    """The wavefront engine turns a step flag into a hard error."""
    import jax.numpy as jnp

    from stateright_tpu.models.twophase import TwoPhaseSys
    from stateright_tpu.models.twophase_compiled import TwoPhaseCompiled

    class Flagging(TwoPhaseCompiled):
        step_flags = True

        def step(self, state):
            nexts, valid = super().step(state)
            return nexts, valid, jnp.ones((), jnp.bool_)

        def cache_key(self):
            return (type(self).__qualname__, self.n)

    model = TwoPhaseSys(rm_count=3)
    with pytest.raises(RuntimeError, match="encoding-capacity overflow"):
        model.checker().spawn_tpu(
            capacity=1 << 12, compiled=Flagging(model)
        ).join()


@pytest.fixture(scope="module")
def reachable_c1():
    return enumerate_reachable(paxos_model(1))


@pytest.fixture(scope="module")
def reachable_c2():
    return enumerate_reachable(paxos_model(2))


@pytest.mark.slow
def test_spawn_tpu_paxos_c4_depth_bounded_differential():
    """4 clients — past the round-2 client cap, exercising the widened
    proposal/value fields and base-8 envelope addressing.  Depth-bounded:
    the full c=4 space exceeds suite runtime."""
    host = (
        paxos_model(4)
        .checker()
        .target_max_depth(9)
        .spawn_bfs()
        .join()
    )
    tpu = (
        paxos_model(4)
        .checker()
        .target_max_depth(9)
        .spawn_tpu(capacity=1 << 20, max_frontier=1 << 10)
        .join()
    )
    assert host.unique_state_count() == 8_352
    assert tpu.unique_state_count() == 8_352
    assert tpu.max_depth() == host.max_depth() == 9
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


def test_paxos_check6_codec_compiles():
    """`paxos check 6` (the reference bench workload, bench.sh:28) must at
    least construct, round-trip its init states, and lower the step kernel
    + property predicates to HLO.  (Full checking at c=6 is bounded by the
    linearizability DP's 2^(2C) subset space — see the cost-curve note in
    docs/TPU_PAXOS_DESIGN.md.)"""
    import jax
    import jax.numpy as jnp

    model = paxos_model(6)
    cm = PaxosCompiled(model)
    assert cm.c == 6 and cm.m == 64
    for s in model.init_states():
        enc = cm.encode(s)
        assert cm.decode(enc) == s
    enc0 = jnp.asarray(cm.encode(next(iter(model.init_states()))))
    jax.jit(cm.step).lower(enc0)
    jax.jit(cm.property_conds).lower(enc0)


@pytest.mark.slow
def test_spawn_tpu_paxos_c6_depth_bounded_differential():
    """`paxos check 6` — the biggest reference bench workload
    (bench.sh:28) — depth-bounded so the host oracle fits suite runtime.
    The bit-packed linearizability DP (128 subset words per value column
    at C=6) must agree exactly with the host tester; the full-scale
    anchors are the tpu-marked golden below and the benchmark's
    ``paxos-6.exhaustive`` cell (full c=6 on hardware: 9,357,525 unique,
    depth 28, differential vs host pinned at depth 12: 283,217)."""
    host = (
        paxos_model(6)
        .checker()
        .target_max_depth(9)
        .spawn_bfs()
        .join()
    )
    tpu = (
        paxos_model(6)
        .checker()
        .target_max_depth(9)
        .spawn_tpu(capacity=1 << 20, max_frontier=1 << 10)
        .join()
    )
    assert host.unique_state_count() == tpu.unique_state_count()
    assert host.state_count() == tpu.state_count()
    assert tpu.max_depth() == host.max_depth() == 9
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


@pytest.mark.tpu
def test_paxos_check5_full_golden_device():
    """Full `paxos check 5` on the real chip: this framework's pinned
    golden (no reference-pinned count exists past c=2); cross-validated
    by the depth-bounded host differentials and the c=6 depth-12
    differential (283,217 both engines, scratch run 2026-07-31)."""
    tpu = (
        paxos_model(5)
        .checker()
        .spawn_tpu(capacity=1 << 24, max_frontier=1 << 13, dedup_factor=8)
        .join()
    )
    assert tpu.unique_state_count() == 4_711_569
    assert tpu.max_depth() == 28
    assert sorted(tpu.discoveries()) == ["value chosen"]


@pytest.mark.tpu
def test_paxos_check6_full_golden_device():
    """Full `paxos check 6` (reference bench.sh:28) on the real chip:
    9,357,525 unique states at depth 28.  The decoupled table/row-log
    geometry (2^25 slots / 10.5M positions) is what fits the run on one
    16 GB chip."""
    tpu = (
        paxos_model(6)
        .checker()
        .spawn_tpu(
            capacity=1 << 25,
            log_capacity=10_500_000,
            max_frontier=1 << 13,
            dedup_factor=8,
        )
        .join()
    )
    assert tpu.unique_state_count() == 9_357_525
    assert tpu.max_depth() == 28
    assert sorted(tpu.discoveries()) == ["value chosen"]


@pytest.mark.slow
def test_step_valid_matches_full_kernel_c2(reachable_c2):
    """Two-phase contract: the phase-A ``step_valid`` plane must equal the
    full kernel's valid plane on every lane of every reachable state.

    This is the differential that would have caught the r4 regression
    class at trace time: it exercises the public two-phase surface
    (``step_valid`` + ``step_lane``) rather than the private kernel."""
    import jax
    import jax.numpy as jnp

    model = paxos_model(2)
    cm = PaxosCompiled(model)
    states = list(reachable_c2.values())
    enc = np.stack([cm.encode(s) for s in states]).astype(np.uint32)
    # Pad to a chunk multiple so every jit call sees one shape (the tail
    # would otherwise recompile both kernels); duplicates are harmless —
    # the assertion is elementwise va == vb.
    pad = (-len(enc)) % 2048
    enc = np.concatenate([enc, np.tile(enc[:1], (pad, 1))])
    valid_fn = jax.jit(jax.vmap(cm.step_valid))
    lane_fn = jax.jit(
        jax.vmap(
            lambda st: jax.vmap(lambda k: cm.step_lane(st, k))(
                jnp.arange(cm.m, dtype=jnp.uint32)
            )
        )
    )
    for off in range(0, len(enc), 2048):
        chunk = jnp.asarray(enc[off : off + 2048])
        va = np.asarray(valid_fn(chunk))
        nexts, vb, flags = (np.asarray(x) for x in lane_fn(chunk))
        assert not flags.any()
        assert np.array_equal(va, vb), (
            f"step_valid != step_lane valid plane in chunk at {off}"
        )


@pytest.mark.slow
def test_two_phase_matches_single_phase_full_run(monkeypatch):
    """Full-run golden: the two-phase engine path and the single-phase
    path must produce identical counts and discoveries on paxos c=2.

    Deleting ``step_valid`` forces the engine's single-phase branch
    (`parallel/wave_common.py` gates two-phase on hasattr).  The
    two-phase capability is part of the compiled-program cache key
    (`wavefront.py:_programs`), so the second run genuinely re-traces —
    asserted below via the cache keys."""
    from stateright_tpu.parallel import wavefront

    two = (
        paxos_model(2)
        .checker()
        .spawn_tpu(capacity=1 << 18, max_frontier=1 << 13)
        .join()
    )
    keys_before = set(wavefront._PROGRAM_CACHE)
    monkeypatch.delattr(PaxosCompiled, "step_valid")
    one = (
        paxos_model(2)
        .checker()
        .spawn_tpu(capacity=1 << 18, max_frontier=1 << 13)
        .join()
    )
    # A new program (single-phase) must have been compiled — if the
    # two-phase program had been served from cache this golden would be
    # comparing a run against itself.
    assert set(wavefront._PROGRAM_CACHE) - keys_before
    assert two.unique_state_count() == one.unique_state_count() == 16_668
    assert two.state_count() == one.state_count()
    assert two.max_depth() == one.max_depth()
    assert sorted(two.discoveries()) == sorted(one.discoveries())
