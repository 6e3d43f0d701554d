"""Shared device machinery for register-harness workloads.

Every model built on the register test harness (actor/register.py — paxos,
the ABD register, …) shares the same client-side structure: scripted
clients that Put once then Get (``RegisterClient(put_count=1)``), a
``LinearizabilityTester`` history recorded through the Get/Put ↔
GetOk/PutOk hooks, and therefore the same packed client/tester layout and
the same exact on-device linearizability decision.  This module carries
that shared half so each protocol's compiled model only implements its
server records and message kinds.

Layout owned here (C clients, S servers):

- one *client word* of 4-bit records: awaiting kind (0 none / 1 put /
  2 get) + op_count, per client;
- C *tester words*: phase (3b), write-invocation snapshot (2b per other
  client), read-invocation snapshot (same), read value (2b) — an injective
  encoding of the ``LinearizabilityTester`` state for this client
  (consistency.py:198-239; clients invoke their Put at ``on_start``, so
  the write snapshot is always empty in reachable states).

The linearizability decision is a Wing&Gong-style subset-reachability DP
over the ≤ 2C register operations — see ``device_linearizable`` and the
exhaustive differential (including violations) in tests/test_paxos_tpu.py.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ..actor.ids import Id
from ..actor.register import ClientState
from ..semantics.register import READ, ReadOk, WRITE_OK, WriteOp


def representative_slot_code(state, net0: int, m: int, k):
    """(code, occupied) for unordered-multiset Deliver lane ``k``.

    Slots hold SORTED envelope codes with duplicates as repeated codes
    (host multiset count > 1, like the raft codec).  The host enumerates
    one Deliver per DISTINCT envelope (network.iter_deliverable), so only
    the first slot of an equal-code run is the representative lane —
    later copies of a duplicated send stay in flight.  Shared by the
    paxos/ABD/single-copy codecs so the rule cannot drift."""
    import jax.numpy as jnp

    u = jnp.uint32
    sel = jnp.arange(m, dtype=u)
    slots = state[net0 : net0 + m]
    code = jnp.sum(jnp.where(sel == k, slots, u(0)))
    prev = jnp.sum(jnp.where(sel == k - u(1), slots, u(0)))
    occupied = (code != u(0)) & ((k == u(0)) | (prev != code))
    return code, occupied


def decode_slot_counts(words, net0: int, m: int, env_of):
    """Host decode of the slot section back to multiset (env, count)
    pairs, counting repeated codes.  Shared across the register codecs."""
    env_counts: dict = {}
    for k in range(m):
        code = int(words[net0 + k])
        if code:
            env = env_of(code)
            env_counts[env] = env_counts.get(env, 0) + 1
    return frozenset(env_counts.items())


class RegisterClientCodec:
    """Codec + device predicates for the harness's client/tester section.

    ``cli_word``: index of the packed client-record word; ``tst0``: index
    of the first tester word.  ``values[i]`` is client i's put value
    (actor/register.py:126).
    """

    def __init__(self, server_count: int, client_count: int, cli_word: int,
                 tst0: int):
        self.s = server_count
        self.c = client_count
        self.cli_word = cli_word
        self.tst0 = tst0
        self.lcb = 2 * (client_count - 1)
        # Value codes 0..C (0 = NULL): width derived, not hard-coded, so
        # bench-scale configs (paxos check 6, single-copy check 4 —
        # reference bench.sh:27-34) pack correctly.
        self.vb = max(2, client_count.bit_length())
        # Tester word: phase(3) + two snapshots(lcb each) + value(vb); the
        # client word holds 4 bits per client.  Both must fit one u32.
        if 3 + 2 * self.lcb + self.vb > 32 or 4 * client_count > 32:
            raise ValueError(
                f"register harness supports at most 7 clients "
                f"(got {client_count}: tester word needs "
                f"{3 + 2 * self.lcb + self.vb} bits)"
            )
        self.values = tuple(
            chr(ord("A") + i) for i in range(client_count)
        )

    # --- host side -----------------------------------------------------------

    def value_code(self, v, null_value) -> int:
        """0 = NULL, 1+i = client i's value."""
        if v == null_value:
            return 0
        return 1 + self.values.index(v)

    def value_of(self, code: int, null_value):
        return null_value if code == 0 else self.values[code - 1]

    def encode_clients(self, actor_states) -> int:
        bits = 0
        for i in range(self.c):
            cs: ClientState = actor_states[self.s + i]
            if cs.awaiting is None:
                kind = 0
            elif cs.awaiting == self.s + i:
                kind = 1  # awaiting the put
            else:
                assert cs.awaiting == 2 * (self.s + i)
                kind = 2  # awaiting the get
            assert cs.op_count <= 3
            bits |= (kind | (cs.op_count << 2)) << (4 * i)
        return bits

    def decode_clients(self, bits: int) -> List[ClientState]:
        out = []
        for i in range(self.c):
            nib = (bits >> (4 * i)) & 0xF
            kind, op_count = nib & 0x3, nib >> 2
            awaiting = {0: None, 1: self.s + i, 2: 2 * (self.s + i)}[kind]
            out.append(ClientState(awaiting=awaiting, op_count=op_count))
        return out

    def _lc_code(self, last_completed, me: int) -> int:
        """Snapshot tuple -> 2 bits per other client (0 absent, else idx+1)."""
        lc = dict(last_completed)
        bits = 0
        slot = 0
        for j in range(self.c):
            if j == me:
                continue
            v = lc.get(Id(self.s + j))
            bits |= (0 if v is None else v + 1) << (2 * slot)
            slot += 1
        return bits

    def _lc_of(self, bits: int, me: int):
        out = []
        slot = 0
        for j in range(self.c):
            if j == me:
                continue
            v = (bits >> (2 * slot)) & 0x3
            if v:
                out.append((Id(self.s + j), v - 1))
            slot += 1
        return tuple(sorted(out))

    def encode_tester(self, h, me: int, null_value) -> int:
        tid = Id(self.s + me)
        hist = h.history_by_thread.get(tid)
        inflight = h.in_flight_by_thread.get(tid)
        lcb = self.lcb
        if hist is None and inflight is None:
            return 0  # phase 0
        if inflight is not None and not hist:
            lc, op = inflight
            assert op == WriteOp(self.values[me])
            return 1 | (self._lc_code(lc, me) << 3)
        assert hist[0][1] == WriteOp(self.values[me]) and hist[0][2] == WRITE_OK
        lc_w = self._lc_code(hist[0][0], me)
        if len(hist) == 1 and inflight is None:
            return 2 | (lc_w << 3)
        if len(hist) == 1:
            lc, op = inflight
            assert op == READ
            return 3 | (lc_w << 3) | (self._lc_code(lc, me) << (3 + lcb))
        assert len(hist) == 2 and inflight is None and hist[1][1] == READ
        lc_r = self._lc_code(hist[1][0], me)
        vcode = self.value_code(hist[1][2].value, null_value)
        return 4 | (lc_w << 3) | (lc_r << (3 + lcb)) | (vcode << (3 + 2 * lcb))

    def decode_tester_into(self, h, bits: int, me: int, null_value) -> None:
        tid = Id(self.s + me)
        phase = bits & 0x7
        if phase == 0:
            return
        lcb = self.lcb
        lc_w = self._lc_of((bits >> 3) & ((1 << lcb) - 1), me)
        if phase == 1:
            h.in_flight_by_thread[tid] = (lc_w, WriteOp(self.values[me]))
            h.history_by_thread[tid] = ()
            return
        entry_w = (lc_w, WriteOp(self.values[me]), WRITE_OK)
        if phase == 2:
            h.history_by_thread[tid] = (entry_w,)
            return
        lc_r = self._lc_of((bits >> (3 + lcb)) & ((1 << lcb) - 1), me)
        if phase == 3:
            h.history_by_thread[tid] = (entry_w,)
            h.in_flight_by_thread[tid] = (lc_r, READ)
            return
        vcode = (bits >> (3 + 2 * lcb)) & ((1 << self.vb) - 1)
        h.history_by_thread[tid] = (
            entry_w,
            (lc_r, READ, ReadOk(self.value_of(vcode, null_value))),
        )

    # --- device side ----------------------------------------------------------

    def client_record(self, state, ci):
        """(kind, op_count) of (possibly clamped) client ``ci``; plus the
        clamped index usable for in-bounds tester-word selects."""
        import jax.numpy as jnp

        u = jnp.uint32
        ci = jnp.minimum(ci, u(self.c - 1))
        cli = state[self.cli_word]
        nib = (cli >> (u(4) * ci)) & u(0xF)
        return ci, cli, nib & u(3), nib >> u(2)

    def tester_word(self, state, ci):
        import jax.numpy as jnp

        u = jnp.uint32
        tw = u(0)
        for j in range(self.c):
            tw = jnp.where(ci == u(j), state[self.tst0 + j], tw)
        return tw

    def putok_transition(self, state, ci, cli, tw):
        """Client ``ci`` receives its PutOk: nibble -> (get, 2); tester
        phase 1 -> 3, snapshotting the other clients' completed counts at
        the Get invocation (consistency.py:215)."""
        import jax.numpy as jnp

        u = jnp.uint32
        cli_new = (cli & ~(u(0xF) << (u(4) * ci))) | (u(10) << (u(4) * ci))
        phases = [state[self.tst0 + j] & u(0x7) for j in range(self.c)]
        counts = [
            (phases[j] >= u(2)).astype(u) + (phases[j] == u(4)).astype(u)
            for j in range(self.c)
        ]
        lc_opts = []
        for me in range(self.c):
            bits = u(0)
            slot = 0
            for j in range(self.c):
                if j == me:
                    continue
                bits = bits | (counts[j] << u(2 * slot))
                slot += 1
            lc_opts.append(bits)
        lc_r = u(0)
        for me in range(self.c):
            lc_r = jnp.where(ci == u(me), lc_opts[me], lc_r)
        lc_w_old = (tw >> u(3)) & u((1 << self.lcb) - 1)
        tw_new = u(3) | (lc_w_old << u(3)) | (lc_r << u(3 + self.lcb))
        return cli_new, tw_new

    def getok_transition(self, ci, cli, tw, value_code):
        """Client ``ci`` receives its GetOk(value): nibble -> (done, 3);
        tester phase 3 -> 4 recording the read value."""
        import jax.numpy as jnp

        u = jnp.uint32
        cli_new = (cli & ~(u(0xF) << (u(4) * ci))) | (u(12) << (u(4) * ci))
        tw_new = (tw & ~u(7)) | u(4) | (value_code << u(3 + 2 * self.lcb))
        return cli_new, tw_new

    def device_linearizable(self, state):
        """Exact linearizability of the recorded register history.

        The host property runs ``LinearizabilityTester.serialized_history()``
        — an exponential interleaving search with real-time pruning
        (semantics/consistency.py:241-295).  On device the same decision is
        a reachability DP over Wing&Gong-style configurations: subsets of
        the ≤ 2C register operations crossed with the register value, where
        an op may be appended iff its real-time prerequisites (from the
        tester's last-completed snapshots) are already in the subset and,
        for a read, the register holds the value it returned.  The history
        is linearizable iff a configuration containing every *completed*
        op is reachable (in-flight writes are optional; in-flight reads are
        always droppable).  Exactness is pinned by tests/test_paxos_tpu.py
        against the host tester over both full reachable state spaces and
        an exhaustive synthetic tester-state enumeration (including
        violations).

        The subset dimension is BIT-PACKED into u32 lanes (bit k of word w
        = subset 32w+k), so the DP state is ``[nv, nsub/32]`` u32 instead
        of ``[nsub, nv]`` bool and every transition is word-parallel:

        - appending op o maps subset ``sub^bit`` -> ``sub``, which for
          o < 5 is an in-word shift by 2^o masked to lanes with bit o set,
          and for o >= 5 a static word-level butterfly (low half -> high
          half at stride 2^(o-5));
        - the real-time gate ``pm[o] ⊆ sub`` is a *superset indicator*,
          built by ANDing the static has-bit masks of pm's set bits — no
          per-subset arithmetic at all (pm never contains o itself: writes
          have empty masks and a read's mask holds only other ops, so the
          gate over ``sub`` equals the gate over ``sub^bit``).

        At C=6 this turns 28,672 bool cells/state into 896 u32 words/state
        (the difference between `paxos check 6` lowering and running —
        VERDICT r3 #1; cost table in docs/TPU_PAXOS_DESIGN.md).

        The DP's ops carry the name scope ``register/lin_dp`` in their
        metadata, so a device trace can tell its time from the rest of
        the step.
        """
        import jax

        with jax.named_scope("register/lin_dp"):
            return self._linearizability_dp(state)

    def _linearizability_dp(self, state):
        """The DP of :meth:`device_linearizable`."""
        import jax.numpy as jnp

        u = jnp.uint32
        c = self.c
        n_ops = 2 * c  # op i = W_i (client i's put), op c+i = R_i (its get)
        nsub = 1 << n_ops
        nv = c + 1  # register values: 0 = NULL, 1+i = client i's value
        nwords = max(1, nsub // 32)
        lcb = self.lcb
        tst0 = self.tst0

        tw = [state[tst0 + i] for i in range(c)]
        phase = [w & u(7) for w in tw]
        lc_r = [(w >> u(3 + lcb)) & u((1 << lcb) - 1) for w in tw]
        v_read = [
            (w >> u(3 + 2 * lcb)) & u((1 << self.vb) - 1) for w in tw
        ]

        w_completed = [phase[i] >= u(2) for i in range(c)]
        w_present = [phase[i] >= u(1) for i in range(c)]
        r_present = [phase[i] == u(4) for i in range(c)]  # completed reads

        # Real-time prerequisite masks.  A snapshot code about thread j
        # constrains only j's *completed* ops (consistency.py:252-261).
        pm = []
        for i in range(c):
            pm.append(u(0))  # writes invoke at init: empty snapshot
        for i in range(c):
            mask = u(1 << i)  # program order: W_i before R_i
            slot = 0
            for j in range(c):
                if j == i:
                    continue
                cj = (lc_r[i] >> u(2 * slot)) & u(3)
                mask = mask | jnp.where(
                    (cj >= u(1)) & w_completed[j], u(1 << j), u(0)
                )
                mask = mask | jnp.where(
                    (cj >= u(2)) & r_present[j], u(1 << (c + j)), u(0)
                )
                slot += 1
            pm.append(mask)
        present = w_present + r_present

        # Static has-bit masks: HAS[b] bit k of word w <=> subset 32w+k
        # contains op b.  Only real subsets get bits, so for nsub < 32 the
        # unused high lanes of the single word can never light up.
        sub_np = np.arange(nsub, dtype=np.uint64)
        weights = (np.uint64(1) << np.arange(32, dtype=np.uint64))
        has_np = np.empty((n_ops, nwords), np.uint32)
        for b in range(n_ops):
            bits = ((sub_np >> np.uint64(b)) & np.uint64(1)).astype(np.uint64)
            pad = np.zeros(nwords * 32 - nsub, np.uint64)
            bits = np.concatenate([bits, pad]).reshape(nwords, 32)
            has_np[b] = (bits * weights[None, :]).sum(axis=1).astype(np.uint32)
        HAS = jnp.asarray(has_np)  # [n_ops, nwords]
        ones = jnp.full((nwords,), 0xFFFFFFFF, u)

        def superset_indicator(mask_scalar):
            """Packed indicator of {sub : mask ⊆ sub} via AND of HAS rows."""
            out = ones
            for b in range(n_ops):
                bit_set = (mask_scalar >> u(b)) & u(1)
                out = out & jnp.where(bit_set == u(1), HAS[b], ones)
            return out

        # Per-op gates, hoisted out of the sweep (pm is sweep-invariant).
        gates = []
        for o in range(n_ops):
            g = superset_indicator(pm[o]) & HAS[o]
            gates.append(jnp.where(present[o], g, jnp.zeros((), u)))
        v_arange = jnp.arange(nv, dtype=u)
        # Read-op value-column mask, also sweep-invariant: [n_ops, nv].
        colmask = []
        for o in range(n_ops):
            if o < c:
                colmask.append((v_arange == u(1 + o)).astype(u) * u(0xFFFFFFFF))
            else:
                colmask.append(
                    (v_arange == v_read[o - c]).astype(u) * u(0xFFFFFFFF)
                )

        def shift_src(dp, o):
            """dp word-image of sub^bit(o) at lanes with bit o set."""
            if o < 5:
                return (dp << u(1 << o)) & HAS[o][None, :]
            stride = 1 << (o - 5)
            r = dp.reshape(nv, nwords // (2 * stride), 2, stride)
            lowhalf = r[:, :, 0:1, :]
            shifted = jnp.concatenate(
                [jnp.zeros_like(lowhalf), lowhalf], axis=2
            )
            return shifted.reshape(nv, nwords)

        def sweep(dp):
            for o in range(n_ops):
                shifted = shift_src(dp, o)
                if o < c:
                    # Write: any source value reaches; register becomes 1+o.
                    any_v = shifted[0]
                    for v in range(1, nv):
                        any_v = any_v | shifted[v]
                    add = any_v & gates[o]
                    dp = dp | (add[None, :] & colmask[o][:, None])
                else:
                    # Read: register must already equal the returned value.
                    dp = dp | (shifted & gates[o] & colmask[o][:, None])
            return dp

        # ``| (state[0] & 0)`` types the loop carry as varying so the DP
        # also traces under the sharded engine's shard_map (a constant
        # carry with a varying loop body fails scan type checking).
        dp0 = (
            jnp.zeros((nv, nwords), u) | (state[0] & u(0))
        ).at[0, 0].set(u(1))
        # n_ops rounds of relaxation reach any appendable-op order; the
        # round body is o-unrolled but round-invariant, so a fori_loop
        # keeps the trace 2C× smaller than full unrolling.
        import jax

        dp = jax.lax.fori_loop(
            0, n_ops, lambda _, d: sweep(d), dp0, unroll=False
        )

        req = u(0)
        for i in range(c):
            req = req | jnp.where(w_completed[i], u(1 << i), u(0))
            req = req | jnp.where(r_present[i], u(1 << (c + i)), u(0))
        covers = superset_indicator(req)
        return jnp.any((dp & covers[None, :]) != u(0))
