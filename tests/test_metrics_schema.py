"""The guaranteed cross-engine ``metrics()`` schema and the always-on
fused-loop vitals (docs/OBSERVABILITY.md).

Two pins: (1) every engine — host graph, simulation, single-chip,
sharded, tiered — reports the guaranteed key set with consistent types
(incl. ``table_load_factor`` and the process-global program-cache
counters); (2) a FUSED (non-traced) device run reports nonzero
wave-latency histogram counts, a uniq/s EMA, and ``table_load_factor``
through ``metrics()`` and the Explorer's ``GET /.metrics`` — in both
JSON and Prometheus form — while the trace=False device-program
invariance pin (tests/test_obs.py) stays green.
"""

import json
import urllib.request

import pytest

jax = pytest.importorskip("jax")
import numpy as np  # noqa: E402

from stateright_tpu.core.simulation import UniformChooser  # noqa: E402
from stateright_tpu.models.fixtures import (  # noqa: E402
    BinaryClock, LinearEquation,
)
from stateright_tpu.models.twophase import TwoPhaseSys  # noqa: E402
from stateright_tpu.obs.prometheus import parse_prometheus  # noqa: E402


def _cpu():
    return jax.devices("cpu")[0]


# name -> required type(s); bool is checked FIRST (it is an int subclass).
GUARANTEED = {
    "engine": str,
    "done": bool,
    "state_count": int,
    "unique_state_count": int,
    "max_depth": int,
    "table_load_factor": (int, float),
    "program_cache_hits": int,
    "program_cache_misses": int,
    # Compile observability (ISSUE 11): the process-global first-call
    # compile time + storm counter ride the guaranteed schema so one
    # scrape answers "is this process recompiling / thrashing".
    "compile_sec_total": (int, float),
    "recompile_storms": int,
}


def _assert_schema(m: dict, who: str) -> None:
    for key, want in GUARANTEED.items():
        assert key in m, f"{who}: metrics() missing guaranteed key {key!r}"
        value = m[key]
        if want is bool:
            assert isinstance(value, bool), (who, key, type(value))
        elif want is int:
            assert isinstance(value, int) and not isinstance(value, bool), (
                who, key, type(value),
            )
        else:
            assert isinstance(value, want) and not isinstance(value, bool), (
                who, key, type(value),
            )
    # The snapshot must stay JSON-serializable: every surface (Explorer,
    # serve, result.json) ships it as JSON.
    json.dumps(m)


def test_guaranteed_schema_actor_runtime():
    """The actor runtime (ISSUE 15) reports the same guaranteed key set
    as every checking engine — a spawned production system scrapes like
    a checker.  Actor semantics: state_count counts handled messages,
    unique_state_count the spawned actors, max_depth the deepest causal
    hop; no device table, so table_load_factor is 0.0."""
    from stateright_tpu.actor.base import Actor, Out
    from stateright_tpu.actor.ids import Id
    from stateright_tpu.actor.obs import ObservedTransport
    from stateright_tpu.actor.spawn import (
        json_deserialize, json_serialize, spawn,
    )
    from stateright_tpu.actor.transport import LoopbackTransport

    class _Quiet(Actor):
        def on_start(self, id, storage, o: Out):
            return ()

        def on_msg(self, id, state, src, msg, o: Out):
            return None

    transport = ObservedTransport(LoopbackTransport(), trace=True)
    runtime = spawn(
        json_serialize, json_deserialize, json_serialize, json_deserialize,
        [(Id(1), _Quiet())], storage_dir="/tmp", transport=transport,
        metrics=transport.registry,
    )
    probe = transport.bind(Id(9))
    try:
        probe.send(Id(1), json_serialize({"poke": 1}))
        deadline_metrics = runtime.metrics()
        _assert_schema(deadline_metrics, "ActorRuntime (running)")
        assert deadline_metrics["done"] is False
    finally:
        probe.close()
        runtime.stop()
    m = runtime.metrics()
    _assert_schema(m, "ActorRuntime")
    assert m["engine"] == "ActorRuntime"
    assert m["done"] is True
    assert m["unique_state_count"] == 1
    assert m["table_load_factor"] == 0.0  # no device table
    assert "histograms" in m


def test_guaranteed_schema_host_and_simulation_engines():
    bfs = BinaryClock().checker().spawn_bfs().join()
    _assert_schema(bfs.metrics(), "GraphChecker")
    sim = (
        LinearEquation(a=2, b=10, c=14)
        .checker()
        .spawn_simulation(0, UniformChooser())
        .join()
    )
    _assert_schema(sim.metrics(), "SimulationChecker")
    assert sim.metrics()["table_load_factor"] == 0.0  # no device table


def test_guaranteed_schema_device_engines():
    model = TwoPhaseSys(rm_count=3)
    tpu = model.checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
    ).join()
    _assert_schema(tpu.metrics(), "TpuChecker")

    mesh = jax.sharding.Mesh(np.array(jax.devices("cpu")[:2]), ("shards",))
    sharded = model.checker().spawn_tpu_sharded(
        mesh=mesh, capacity=1 << 12, chunk_size=1 << 6,
    ).join()
    _assert_schema(sharded.metrics(), "ShardedTpuChecker")

    tiered = model.checker().spawn_tpu_tiered(
        capacity=512, max_frontier=1 << 6,
    ).join()
    _assert_schema(tiered.metrics(), "TieredTpuChecker")

    for who, m in (("tpu", tpu.metrics()), ("sharded", sharded.metrics()),
                   ("tiered", tiered.metrics())):
        assert m["unique_state_count"] == 288, who
        assert m["table_load_factor"] > 0, who


# --- always-on fused-loop vitals ---------------------------------------------


def test_fused_untraced_run_reports_vitals():
    """The fused device program untouched (the sync-cadence pin in
    tests/test_obs.py) — and yet metrics() carries the vitals: nonzero wave-latency histogram counts, a
    uniq/s EMA, grow counters, and the host/device time split."""
    ck = TwoPhaseSys(rm_count=3).checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
    ).join()
    m = ck.metrics()
    h = m["histograms"]["wave_latency_sec"]
    assert h["count"] > 0
    assert sum(h["counts"]) == h["count"]
    assert h["p50"] <= h["p95"] <= h["p99"]
    assert m["uniq_per_sec_ema"] > 0
    assert m["waves_per_sec_ema"] > 0
    assert m["host_sec_total"] >= 0
    assert m["device_call_sec_total"] > 0
    assert m["table_load_factor"] > 0
    # Density telemetry (ISSUE 11): the valid-candidates-vs-U-buffer
    # fraction, as EMA gauge + histogram, and the load-factor
    # trajectory — all on the untouched fused path.
    assert 0 < m["valid_density_ema"] <= 1.0
    assert m["histograms"]["valid_density"]["count"] > 0
    assert m["histograms"]["load_factor"]["count"] > 0


def test_every_device_engine_reports_density_keys():
    """The acceptance bar: every engine's metrics() reports the new
    density keys — single-chip, sharded (with per-shard skew), and
    tiered."""
    model = TwoPhaseSys(rm_count=3)
    tpu = model.checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
    ).join()
    mesh = jax.sharding.Mesh(np.array(jax.devices("cpu")[:2]), ("shards",))
    sharded = model.checker().spawn_tpu_sharded(
        mesh=mesh, capacity=1 << 12, chunk_size=1 << 6,
    ).join()
    tiered = model.checker().spawn_tpu_tiered(
        capacity=512, max_frontier=1 << 6,
    ).join()
    for who, m in (("tpu", tpu.metrics()), ("sharded", sharded.metrics()),
                   ("tiered", tiered.metrics())):
        assert 0 < m["valid_density_ema"] <= 1.0, who
        assert m["histograms"]["valid_density"]["count"] > 0, who
        assert m["histograms"]["load_factor"]["count"] > 0, who
    sm = sharded.metrics()
    assert set(sm["shard_unique"]) == {"0", "1"}
    assert sm["unique_skew_max_over_mean"] >= 1.0
    json.dumps(sm)


def test_forced_grow_records_waves_per_grow_histogram():
    """An undersized table forces the in-place auto-grow; the vitals
    must count it and record the waves-per-grow distribution."""
    ck = TwoPhaseSys(rm_count=3).checker().spawn_tpu(
        capacity=1 << 7, max_frontier=1 << 6, device=_cpu(),
    ).join()
    m = ck.metrics()
    assert m["unique_state_count"] == 288
    assert m["grows"] >= 1  # actual geometry changes (log_grow)
    assert m["overflow_retries"] >= m["grows"]  # every recovery re-run
    wpg = m["histograms"]["waves_per_grow"]
    assert wpg["count"] == m["overflow_retries"]


def test_explorer_metrics_serves_vitals_json_and_prometheus():
    from stateright_tpu.explorer.server import serve_checker

    ck = TwoPhaseSys(rm_count=3).checker().spawn_tpu(
        capacity=1 << 14, max_frontier=1 << 9, device=_cpu(),
    ).join()
    serve_checker(ck, ("127.0.0.1", 0), block=False)
    host, port = ck.explorer_address
    base = f"http://{host}:{port}"
    try:
        with urllib.request.urlopen(base + "/.metrics", timeout=10) as r:
            m = json.loads(r.read())
        assert m["histograms"]["wave_latency_sec"]["count"] > 0
        assert m["uniq_per_sec_ema"] > 0
        assert m["table_load_factor"] > 0

        req = urllib.request.Request(
            base + "/.metrics?format=prometheus"
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            ctype = r.headers.get("Content-Type", "")
            text = r.read().decode()
        assert ctype.startswith("text/plain")
        fams = parse_prometheus(text)
        lat = fams["stateright_wave_latency_sec"]
        assert lat["type"] == "histogram"
        names = {n for n, _, _ in lat["samples"]}
        assert {
            "stateright_wave_latency_sec_bucket",
            "stateright_wave_latency_sec_sum",
            "stateright_wave_latency_sec_count",
        } <= names
        assert fams["stateright_unique_state_count"]["type"] == "counter"
        assert (
            fams["stateright_unique_state_count"]["samples"][0][2] == 288
        )
        assert fams["stateright_table_load_factor"]["samples"][0][2] > 0

        # An Accept header preferring the text exposition (a scraper's
        # request) selects Prometheus without the query param.
        req = urllib.request.Request(
            base + "/.metrics", headers={"Accept": "text/plain"}
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.headers.get("Content-Type", "").startswith(
                "text/plain"
            )
    finally:
        ck.explorer_server.shutdown()
