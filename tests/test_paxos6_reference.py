"""`paxos check 6` on the single-chip engine against the benchmark's plain
reference (``benchmark/reference/paxos.py``), bounded in depth.

The reference imports nothing of the program; this test drives its
``init_state``, ``successors`` and ``properties`` level by level itself,
so the reference file stays as the benchmark runs it.  The bound is
applied as ``target_max_depth`` applies it (and as the 2pc reference's
``max_depth`` does): the last level's states are counted but neither
checked nor expanded.  The engine is built the way the benchmark's
``paxos-6`` configuration builds it, through the workload registry.
"""

import importlib.util
import pathlib

REFERENCE = (pathlib.Path(__file__).resolve().parents[1]
             / "benchmark" / "reference" / "paxos.py")
CLIENTS = 6
DEPTH = 9
WANTED = ("linearizable", "value chosen")


def load_reference():
    spec = importlib.util.spec_from_file_location("paxos_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_bounded(ref, clients: int, max_depth: int) -> dict:
    """The reference's breadth-first search, stopped at ``max_depth``."""
    level = [ref.init_state(clients)]
    seen = {hash(level[0])}
    found, depth, generated = set(), 0, 0
    while level:
        depth += 1
        if depth == max_depth:
            break
        for s in level:
            found |= {k for k, v in ref.properties(s).items()
                      if v and k in WANTED}
        nxt = []
        for s in level:
            for t in ref.successors(s):
                generated += 1
                h = hash(t)
                if h not in seen:
                    seen.add(h)
                    nxt.append(t)
        level = nxt
    return {"unique": len(seen), "generated": generated, "max_depth": depth,
            "discoveries": sorted(found)}


def test_spawn_tpu_paxos6_matches_plain_reference_at_depth_9():
    from stateright_tpu.serve.workloads import build_model

    want = reference_bounded(load_reference(), CLIENTS, DEPTH)
    model, _, _ = build_model("paxos", CLIENTS, "unordered_nonduplicating")
    ck = (
        model.checker()
        .target_max_depth(DEPTH)
        .spawn_tpu(capacity=1 << 16, max_frontier=1 << 10)
        .join()
    )
    assert ck.unique_state_count() == want["unique"] == 25_849
    # The engine counts the one initial state among those it generated.
    assert ck.state_count() == want["generated"] + 1
    assert ck.max_depth() == want["max_depth"] == DEPTH
    assert sorted(ck.discoveries()) == want["discoveries"]
