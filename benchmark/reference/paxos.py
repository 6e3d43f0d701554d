"""Plain reference for single-decree Paxos checked for linearizability
(stateright examples/paxos.rs with the register harness of
src/actor/register.rs), written from the example alone.

It imports nothing of the program.  Everything is a plain tuple:

- a message: ``("put", req, value)``, ``("get", req)``, ``("putok", req)``,
  ``("getok", req, value)``, ``("prepare", ballot)``,
  ``("prepared", ballot, last_accepted)``, ``("accept", ballot, proposal)``,
  ``("accepted", ballot)``, ``("decided", ballot, proposal)``; a ballot is
  ``(round, server)``, a proposal ``(req, client, value)``;
- a server: ``(ballot, proposal, prepares, accepts, accepted, decided)``;
- a client: ``(awaiting, op_count)``; clients follow the servers in id
  order, each Puts once to server ``id % servers`` and then Gets from
  server ``(id + 1) % servers``;
- the network: a multiset of ``(src, dst, msg)``, as a frozenset of
  ``(envelope, count)``; delivering takes one copy away;
- the history: per client its completed operations
  ``(last_completed, op, ret)`` and its operation in flight
  ``(last_completed, op)``, where ``last_completed`` holds the index of
  the last completed operation of every other client at invocation.

A delivery that changes no actor and sends nothing is no transition.
The search is breadth first, level by level; a state is new when its
hash is in no earlier level (64-bit, so a collision over a million
states is a chance of about 1 in 10^7).  Run it as
``python paxos.py CLIENTS [--hunt]``.
"""

from __future__ import annotations

import json
import sys
from functools import lru_cache

NULL = "\x00"
SERVERS = 3
MAJORITY = SERVERS // 2 + 1


def _peers(me):
    return [j for j in range(SERVERS) if j != me]


def _server_msg(me, st, src, msg):
    """(new state or None, [(dst, msg), ...])"""
    ballot, proposal, prepares, accepts, accepted, decided = st
    kind = msg[0]
    if decided:
        if kind == "get":
            return None, [(src, ("getok", msg[1], accepted[1][2]))]
        return None, []
    if kind == "put" and proposal is None:
        b = (ballot[0] + 1, me)
        return ((b, (msg[1], src, msg[2]), ((me, accepted),), frozenset(),
                 accepted, False),
                [(p, ("prepare", b)) for p in _peers(me)])
    if kind == "prepare":
        if ballot < msg[1]:
            return ((msg[1], proposal, prepares, accepts, accepted, decided),
                    [(src, ("prepared", msg[1], accepted))])
        return None, []
    if kind == "prepared":
        if msg[1] != ballot:
            return None, []
        got = dict(prepares)
        got[src] = msg[2]
        if len(got) == MAJORITY:
            best = max(got.values(),
                       key=lambda la: (0,) if la is None else (1, la))
            prop = best[1] if best is not None else proposal
            return ((ballot, prop, tuple(sorted(got.items())),
                     frozenset([me]), (ballot, prop), False),
                    [(p, ("accept", ballot, prop)) for p in _peers(me)])
        return ((ballot, proposal, tuple(sorted(got.items())), accepts,
                 accepted, decided), [])
    if kind == "accept":
        if ballot <= msg[1]:
            return ((msg[1], proposal, prepares, accepts, (msg[1], msg[2]),
                     decided), [(src, ("accepted", msg[1]))])
        return None, []
    if kind == "accepted":
        if msg[1] != ballot:
            return None, []
        acc = accepts | {src}
        if len(acc) == MAJORITY:
            sends = [(p, ("decided", msg[1], proposal)) for p in _peers(me)]
            sends.append((proposal[1], ("putok", proposal[0])))
            return (ballot, proposal, prepares, acc, accepted, True), sends
        return (ballot, proposal, prepares, acc, accepted, decided), []
    if kind == "decided":
        return (msg[1], proposal, prepares, accepts, (msg[1], msg[2]),
                True), []
    return None, []


def _client_msg(me, st, msg):
    awaiting, ops = st
    if awaiting is None:
        return None, []
    if msg[0] == "putok" and msg[1] == awaiting:
        req = (ops + 1) * me
        return (req, ops + 1), [((me + ops) % SERVERS, ("get", req))]
    if msg[0] == "getok" and msg[1] == awaiting:
        return (None, ops + 1), []
    return None, []


# --- the linearizability tester's history ----------------------------------


def _invoke(history, thread, op):
    done, flight, valid = history
    if not valid:
        return history
    flight = dict(flight)
    if thread in flight:
        return done, tuple(sorted(flight.items())), False
    done = dict(done)
    last = tuple(sorted((t, len(h) - 1) for t, h in done.items()
                        if t != thread and h))
    flight[thread] = (last, op)
    done.setdefault(thread, ())
    return tuple(sorted(done.items())), tuple(sorted(flight.items())), True


def _return(history, thread, ret):
    done, flight, valid = history
    if not valid:
        return history
    flight = dict(flight)
    if thread not in flight:
        return done, tuple(sorted(flight.items())), False
    last, op = flight.pop(thread)
    done = dict(done)
    done[thread] = done.get(thread, ()) + ((last, op, ret),)
    return tuple(sorted(done.items())), tuple(sorted(flight.items())), True


def _record_out(history, src, msg):
    if msg[0] == "put":
        return _invoke(history, src, ("write", msg[2]))
    if msg[0] == "get":
        return _invoke(history, src, ("read",))
    return history


def _record_in(history, dst, msg):
    if msg[0] == "getok":
        return _return(history, dst, ("read_ok", msg[2]))
    if msg[0] == "putok":
        return _return(history, dst, ("write_ok",))
    return history


@lru_cache(maxsize=None)
def linearizable(history) -> bool:
    """Is there an order of the operations, each completed one taking
    effect after every operation that completed before its invocation,
    in which a register gives every read its value?  Operations in
    flight may take effect or not."""
    done, flight, valid = history
    if not valid:
        return False
    done, flight = dict(done), dict(flight)
    threads = sorted(done)

    @lru_cache(maxsize=None)
    def go(pos, pending, value):
        if all(pos[i] == len(done[t]) for i, t in enumerate(threads)):
            return True
        at = dict(zip(threads, pos))
        for i, t in enumerate(threads):
            if pos[i] < len(done[t]):
                last, op, ret = done[t][pos[i]]
            elif t in pending:
                (last, op), ret = flight[t], None
            else:
                continue
            if any(at.get(p, 0) <= idx for p, idx in last):
                continue  # a real-time predecessor has not taken effect
            if op[0] == "write":
                nxt = op[1]
            elif ret is None or ret[1] == value:
                nxt = value
            else:
                continue
            if pos[i] < len(done[t]):
                npos = pos[:i] + (pos[i] + 1,) + pos[i + 1:]
                if go(npos, pending, nxt):
                    return True
            elif go(pos, pending - {t}, nxt):
                return True
        return False

    return go(tuple(0 for _ in threads), frozenset(flight), NULL)


# --- the actor system --------------------------------------------------------


def _send(net, history, src, sends):
    net = dict(net)
    for dst, msg in sends:
        history = _record_out(history, src, msg)
        env = (src, dst, msg)
        net[env] = net.get(env, 0) + 1
    return net, history


def init_state(clients: int):
    servers = tuple(((0, 0), None, (), frozenset(), None, False)
                    for _ in range(SERVERS))
    net, history = {}, ((), (), True)
    actors = list(servers)
    for me in range(SERVERS, SERVERS + clients):
        value = chr(ord("A") + me - SERVERS)
        net, history = _send(net, history, me,
                             [(me % SERVERS, ("put", me, value))])
        actors.append((me, 1))
    return tuple(actors), frozenset(net.items()), history


def successors(state):
    actors, net, history = state
    out = []
    for (src, dst, msg), count in net:
        if dst < SERVERS:
            nxt, sends = _server_msg(dst, actors[dst], src, msg)
        else:
            nxt, sends = _client_msg(dst, actors[dst], msg)
        if nxt is None and not sends:
            continue
        h = _record_in(history, dst, msg)
        n = dict(net)
        if count == 1:
            del n[(src, dst, msg)]
        else:
            n[(src, dst, msg)] = count - 1
        n, h = _send(n, h, dst, sends)
        a = actors if nxt is None else actors[:dst] + (nxt,) + actors[dst + 1:]
        out.append((a, frozenset(n.items()), h))
    return out


def properties(state) -> dict:
    """What this state discovers: ``linearizable`` and ``never decided``
    (``always``) where it breaks them, ``value chosen`` (``sometimes``)
    where it holds."""
    actors, net, history = state
    return {
        "linearizable": not linearizable(history),
        "value chosen": any(m[0] == "getok" and m[2] != NULL
                            for (_s, _d, m), _c in net),
        "never decided": any(a[5] for a in actors[:SERVERS]),
    }


def check(n: int, hunt: bool = False) -> dict:
    """Exhaustive BFS of ``n`` clients (``hunt``: stop at the first level with a state that
    is decided, and give that level as the counterexample's length in
    states).  Depth counts levels, the initial state's being 1."""
    level = [init_state(n)]
    seen = {hash(level[0])}
    found, depth, generated = {}, 0, 0
    wanted = ("linearizable", "value chosen") + (
        ("never decided",) if hunt else ())
    while level:
        depth += 1
        for s in level:
            for k, v in properties(s).items():
                if v and k in wanted:
                    found.setdefault(k, depth)
        if hunt and "never decided" in found:
            break
        nxt = []
        for s in level:
            for t in successors(s):
                generated += 1
                h = hash(t)
                if h not in seen:
                    seen.add(h)
                    nxt.append(t)
        level = nxt
    return {"unique": len(seen), "generated": generated, "max_depth": depth,
            "discoveries": sorted(found), "first_found_at": found}


if __name__ == "__main__":
    print(json.dumps(check(int(sys.argv[1]), "--hunt" in sys.argv)))
