"""Cross-engine anchor for the flagship bench workload `paxos check 3`.

The full-scale golden (1,194,428 unique / depth 28) is held on the chip by
the benchmark's ``paxos-3.exhaustive`` cell, against the plain reference
``benchmark/reference/paxos.py``; this pins the same configuration
across all three engines — host BFS,
single-chip wavefront, and the sharded mesh engine — so a simultaneous
regression in the host and device encodings cannot go unnoticed.  Full
scale exceeds suite runtime on a CPU box (the host alone needs ~10 min),
so the pin is depth-bounded here, and the depth prefix below is exact for
every engine (target_max_depth semantics are level-accurate on all
three).
"""

import pytest

from stateright_tpu.actor import Network
from stateright_tpu.models.paxos import PaxosModelCfg

PINNED_D11_UNIQUE = 21_838  # paxos check 3, depth <= 11 (exact BFS prefix)


def paxos3():
    return PaxosModelCfg(
        client_count=3,
        server_count=3,
        network=Network.new_unordered_nonduplicating(),
    ).into_model()


@pytest.mark.slow
def test_paxos3_depth11_pinned_across_engines():
    host = paxos3().checker().target_max_depth(11).spawn_bfs().join()
    assert host.unique_state_count() == PINNED_D11_UNIQUE
    assert host.max_depth() == 11

    tpu = (
        paxos3()
        .checker()
        .target_max_depth(11)
        .spawn_tpu(capacity=1 << 20, max_frontier=1 << 10)
        .join()
    )
    assert tpu.unique_state_count() == PINNED_D11_UNIQUE
    assert tpu.max_depth() == 11
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())

    sharded = (
        paxos3()
        .checker()
        .target_max_depth(11)
        .spawn_tpu_sharded(capacity=1 << 20, chunk_size=1 << 9)
        .join()
    )
    assert sharded.unique_state_count() == PINNED_D11_UNIQUE
    assert sharded.max_depth() == 11
    assert sorted(sharded.discoveries()) == sorted(host.discoveries())
