"""Multi-chip sharded wavefront: golden-count and discovery-set parity with
the host oracle on the 8-device virtual CPU mesh (conftest sets
xla_force_host_platform_device_count=8)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.models.twophase import TwoPhaseSys  # noqa: E402
from tests.test_tpu_wavefront import TrapCounter  # noqa: E402


def _mesh(n):
    # The virtual CPU mesh (conftest forces 8 host devices), whatever the
    # default backend is.
    devices = jax.devices("cpu")
    assert len(devices) >= n, f"need {n} CPU devices, have {len(devices)}"
    return jax.sharding.Mesh(np.array(devices[:n]), ("shards",))


@pytest.mark.slow
def test_twophase3_sharded_parity_8_devices():
    model = TwoPhaseSys(rm_count=3)
    host = model.checker().spawn_bfs().join()
    sh = (
        model.checker()
        .spawn_tpu_sharded(mesh=_mesh(8), capacity=1 << 14, chunk_size=1 << 8)
        .join()
    )
    assert sh.unique_state_count() == host.unique_state_count() == 288
    assert sh.state_count() == host.state_count()
    assert sh.max_depth() == host.max_depth()
    assert sorted(sh.discoveries()) == sorted(host.discoveries())
    for _name, path in sh.discoveries().items():
        assert len(path) >= 1  # building a Path re-executes the host model


def test_eventually_sharded_parity():
    model = TrapCounter()
    host = model.checker().spawn_bfs().join()
    sh = (
        model.checker()
        .spawn_tpu_sharded(mesh=_mesh(4), capacity=1 << 13, chunk_size=1 << 4)
        .join()
    )
    assert sh.unique_state_count() == host.unique_state_count()
    assert sorted(sh.discoveries()) == sorted(host.discoveries())
    assert sh.discoveries()["reaches limit"].last_state() == model.trap_state


@pytest.mark.slow
def test_sharded_levels_span_multiple_chunks():
    """2pc(5): 8,832 states whose peak level (~2,000 wide globally) spans
    several 64-state chunks per shard — full parity with the host oracle
    through the fused sharded loop."""
    model = TwoPhaseSys(rm_count=5)
    tpu = (
        model.checker()
        .spawn_tpu_sharded(mesh=_mesh(8), capacity=1 << 16, chunk_size=1 << 6)
        .join()
    )
    host = model.checker().spawn_bfs().join()
    assert tpu.unique_state_count() == host.unique_state_count() == 8832
    assert tpu.state_count() == host.state_count()
    assert tpu.max_depth() == host.max_depth()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


@pytest.mark.slow
def test_sharded_extreme_skew_tiny_model():
    """11 states spread over 8 shards: most shards run empty chunks most
    levels (hash-random ownership skew at its worst); counts and
    discoveries still match the host."""
    from stateright_tpu.models.ping_pong import PingPongCfg
    from stateright_tpu.models.ping_pong_compiled import compiled_ping_pong

    model = PingPongCfg(maintains_history=False, max_nat=5).into_model()
    tpu = (
        model.checker()
        .spawn_tpu_sharded(
            mesh=_mesh(8),
            capacity=1 << 13,
            chunk_size=1 << 5,
            compiled=compiled_ping_pong(model),
        )
        .join()
    )
    host = (
        PingPongCfg(maintains_history=False, max_nat=5)
        .into_model()
        .checker()
        .spawn_bfs()
        .join()
    )
    assert tpu.unique_state_count() == host.unique_state_count() == 11
    assert tpu.state_count() == host.state_count()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


@pytest.mark.slow
def test_sharded_paxos_golden():
    """The flagship model through the multi-chip engine: paxos check 2 on
    an 8-device mesh reproduces the reference golden 16,668
    (examples/paxos.rs:328) with the host oracle's discovery set."""
    from stateright_tpu.actor import Network
    from stateright_tpu.models.paxos import PaxosModelCfg

    model = PaxosModelCfg(
        client_count=2,
        server_count=3,
        network=Network.new_unordered_nonduplicating(),
    ).into_model()
    tpu = (
        model.checker()
        .spawn_tpu_sharded(mesh=_mesh(8), capacity=1 << 16, chunk_size=1 << 8)
        .join()
    )
    assert tpu.unique_state_count() == 16_668
    host = (
        PaxosModelCfg(
            client_count=2,
            server_count=3,
            network=Network.new_unordered_nonduplicating(),
        )
        .into_model()
        .checker()
        .spawn_bfs()
        .join()
    )
    assert tpu.state_count() == host.state_count()
    assert tpu.max_depth() == host.max_depth()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


def test_one_shard_mesh_elides_exchange_and_matches_host():
    """The 1-shard mesh traces the exchange-elided branch (no bucket/
    sort/all_to_all) — it must still match the host oracle exactly and
    say so in the accounting."""
    import jax
    import numpy as np

    from stateright_tpu.models.twophase import TwoPhaseSys

    mesh = jax.sharding.Mesh(np.array(jax.devices("cpu")[:1]), ("shards",))
    model = TwoPhaseSys(rm_count=3)
    host = TwoPhaseSys(rm_count=3).checker().spawn_bfs().join()
    c = (
        model.checker()
        .spawn_tpu_sharded(mesh=mesh, capacity=1 << 13, chunk_size=1 << 6)
        .join()
    )
    assert c.unique_state_count() == host.unique_state_count() == 288
    assert c.state_count() == host.state_count()
    assert c.max_depth() == host.max_depth()
    assert sorted(c.discoveries()) == sorted(host.discoveries())
    acc = c.accounting()
    assert acc["exchange_elided"] is True
    assert acc["all_to_all_bytes_total"] == 0
    assert acc["exchange_occupancy"] == 0.0


def test_bucketed_exchange_fingerprint_pin_vs_single_chip():
    """The bucketed exchange must not change WHAT is discovered, only
    the buffers it rides in: the sharded discovery SET (sorted state
    fingerprints) is bit-identical to the fused single-chip engine's at
    2 and 4 virtual shards, and the accounting's byte totals derive from
    the actual bucket geometry (occupancy × transmitted = useful)."""
    model = TwoPhaseSys(rm_count=3)
    single = (
        model.checker()
        .spawn_tpu(capacity=1 << 13, max_frontier=1 << 6)
        .join()
    )
    fps = single.discovered_fingerprints()
    assert len(fps) == single.unique_state_count() == 288
    for n in (2, 4):
        sh = (
            TwoPhaseSys(rm_count=3).checker()
            .spawn_tpu_sharded(
                mesh=_mesh(n), capacity=1 << 13, chunk_size=1 << 6
            )
            .join()
        )
        assert np.array_equal(sh.discovered_fingerprints(), fps)
        acc = sh.accounting()
        from stateright_tpu.parallel.compiled import compiled_model_for

        w = compiled_model_for(model).state_width
        assert acc["all_to_all_bytes_per_wave_per_shard"] == (
            n * acc["exchange_bucket_lanes"] * (w + 3) * 4
        )
        assert acc["all_to_all_bytes_total"] == (
            acc["waves"] * n * acc["all_to_all_bytes_per_wave_per_shard"]
        )
        # occupancy × transmitted = useful bytes (the accounting's own
        # stated identity, now over the bucketed denominator).
        assert acc["exchange_occupancy"] * acc["all_to_all_bytes_total"] \
            == pytest.approx(acc["exchange_payload_bytes_total"], rel=1e-9)


def test_bucket_overflow_retry_path_forced(tmp_path):
    """A deliberately tiny bucket slack forces the overflow-flag +
    retry-at-next-rung path: the run journals a ``grow`` event with
    flag 32, climbs the slack ladder, and still lands the exact
    single-chip discovery set."""
    from stateright_tpu.runtime.journal import read_journal

    model = TwoPhaseSys(rm_count=4)
    single = (
        model.checker()
        .spawn_tpu(capacity=1 << 14, max_frontier=1 << 7)
        .join()
    )
    fps = single.discovered_fingerprints()
    journal = str(tmp_path / "bucket_retry.jsonl")
    sh = (
        TwoPhaseSys(rm_count=4).checker()
        .spawn_tpu_sharded(
            mesh=_mesh(4), capacity=1 << 14, chunk_size=1 << 7,
            bucket_slack=1, journal=journal,
        )
        .join()
    )
    assert sh.unique_state_count() == 1568
    assert np.array_equal(sh.discovered_fingerprints(), fps)
    acc = sh.accounting()
    assert acc["bucket_retries"] >= 1
    assert acc["bucket_slack"] > 1  # the ladder actually climbed
    grows = [e for e in read_journal(journal) if e["event"] == "grow"]
    assert grows and any(e["flags"] & 32 for e in grows)


@pytest.mark.slow
def test_bucketed_paxos_golden_all_mesh_sizes():
    """The ISSUE-8 acceptance pin: paxos c=2 (reference golden 16,668)
    through the bucketed sharded engine at 1/2/4/8 virtual shards is
    discovery-set bit-identical to the fused single-chip engine, and at
    8 shards the transmitted all_to_all total is ≤ 250 MB (vs 1,233 MB
    with the fixed [n, U] buffers) with measured lane occupancy ≥ 2%.
    An extra 8-shard run with a deliberately tiny slack factor forces
    the bucket-overflow retry path and must land the same set."""
    from stateright_tpu.actor import Network
    from stateright_tpu.models.paxos import PaxosModelCfg

    def paxos2():
        return PaxosModelCfg(
            client_count=2,
            server_count=3,
            network=Network.new_unordered_nonduplicating(),
        ).into_model()

    single = (
        paxos2().checker()
        .spawn_tpu(capacity=1 << 16, max_frontier=1 << 9)
        .join()
    )
    assert single.unique_state_count() == 16_668
    fps = single.discovered_fingerprints()
    for n in (1, 2, 4, 8):
        sh = (
            paxos2().checker()
            .spawn_tpu_sharded(
                mesh=_mesh(n), capacity=1 << 16, chunk_size=1 << 9
            )
            .join()
        )
        assert sh.unique_state_count() == 16_668
        assert np.array_equal(sh.discovered_fingerprints(), fps)
        if n == 8:
            acc = sh.accounting()
            assert acc["all_to_all_bytes_total"] <= 250_000_000
            assert acc["exchange_occupancy"] >= 0.02
    # Forced overflow-retry: same golden, same set.  The 2-shard mesh
    # is the forcing one — its per-destination candidate peaks (~450 per
    # wave) overflow the minimum 128-lane bucket, where the 8-shard
    # split (~80 per destination) fits even the tiny-slack bucket.
    forced = (
        paxos2().checker()
        .spawn_tpu_sharded(
            mesh=_mesh(2), capacity=1 << 16, chunk_size=1 << 9,
            bucket_slack=1,
        )
        .join()
    )
    assert forced.unique_state_count() == 16_668
    assert np.array_equal(forced.discovered_fingerprints(), fps)
    assert forced.accounting()["bucket_retries"] >= 1


def test_owner_mix_host_matches_device():
    """Seeding routes init states by the HOST owner mix while the run
    loop's exchange routes by the DEVICE mix — a divergence would seed
    states into the wrong shard's table and silently duplicate
    exploration, so the two are pinned bit-identical here."""
    import jax.numpy as jnp
    import numpy as np

    from stateright_tpu.parallel.sharded import _owner_mix, _owner_mix_host

    rng = np.random.default_rng(11)
    hi = rng.integers(0, 2**32, size=4096, dtype=np.uint32)
    lo = rng.integers(0, 2**32, size=4096, dtype=np.uint32)
    dev = np.asarray(_owner_mix(jnp.asarray(hi), jnp.asarray(lo)))
    host = np.array(
        [_owner_mix_host(int(h), int(l)) for h, l in zip(hi, lo)],
        np.uint32,
    )
    assert np.array_equal(dev, host)
