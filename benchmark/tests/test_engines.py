"""How a cell reaches its engine: the one-chip cells reach ``spawn_tpu``
with the workload's own kwargs, as before the configuration could name
an engine; a sharded configuration's kwargs are translated as
``check-tpu --sharded`` translates them.  Also the depth bound of the
2pc reference, and the readers that depend on the cell's chips."""

import importlib.util
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import generator  # noqa: E402


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_test_{kind}_{name}".replace(".", "_").replace("-", "_"),
        BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


twophase = _load("reference", "twophase")


def _cell(name):
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((BENCH.parent / cfg["file"]).read_text())
    cell_file = json.loads((BENCH / "cells" / f"{name}.json").read_text())
    return cell, config, cell_file


class Spawned(Exception):
    pass


@pytest.mark.parametrize("name,kwargs", [
    ("paxos-3.exhaustive", {"capacity": 1 << 23, "max_frontier": 1 << 13}),
    ("twophase-8.exhaustive", {"capacity": 1 << 20, "max_frontier": 1 << 13}),
    ("paxos-3.hunt", {"capacity": 1 << 23, "max_frontier": 1 << 13}),
])
def test_one_chip_cells_reach_spawn_tpu_with_the_workload_kwargs(
        name, kwargs, monkeypatch):
    from stateright_tpu.core.checker import CheckerBuilder

    cell, config, cell_file = _cell(name)
    build = generator.model_builder(config, cell_file, cell["chips"])
    _, request = build()
    assert request == {"engine": "spawn_tpu", "kwargs": kwargs,
                       "target_max_depth": None}
    seen = []

    def spawn(self, **kw):
        seen.append((self._target_max_depth, kw))
        raise Spawned

    monkeypatch.setattr(CheckerBuilder, "spawn_tpu", spawn)
    with pytest.raises(Spawned):
        generator.one_request(build, {"finish_when": "ALL"})
    assert seen == [(None, kwargs)]


def test_sharded_kwargs_are_translated_then_overridden():
    spec = {"capacity": 1 << 20, "max_frontier": 1 << 13, "auto_tune": True,
            "log_capacity": 1 << 21, "waves_per_call": 8}
    got = generator.engine_kwargs("spawn_tpu_sharded", spec,
                                  {"capacity": 1 << 30, "dedup_factor": 1})
    assert got == {"capacity": 1 << 30, "chunk_size": 1 << 13,
                   "dedup_factor": 1}
    assert generator.engine_kwargs("spawn_tpu", spec, {}) == spec


def test_reference_unbounded_goldens():
    assert [twophase.check(n)["unique"] for n in (3, 5, 8)] == [
        288, 8832, 1745408]


def test_reference_bound_counts_the_last_level_and_checks_it_not():
    # 2pc 6: every resource manager has aborted first at level 7, so the
    # bound 7 counts those states without checking them, the bound 8
    # checks them.
    at7, at8 = twophase.check(6, 7), twophase.check(6, 8)
    assert (at7["unique"], at7["max_depth"], at7["discoveries"]) == (
        5653, 7, [])
    assert (at8["unique"], at8["max_depth"], at8["discoveries"]) == (
        10044, 8, ["abort agreement"])
    assert twophase.check(6, 100) == twophase.check(6)


def _roofline_ctx(chips):
    return {
        "trace": {"busy_s": 2.0, "window_s": 2.5},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "records": [{"unique": 1000, "generated": 9000, "state_width": 2}],
        "chips": chips,
    }


def test_wave_roofline_divides_by_the_cells_chips():
    read = _load("layer_metrics", "wave_roofline").read
    work = _load("", "work")
    need = work.bfs_bytes(1000, 9000, 2)
    one = read(_roofline_ctx(1))
    assert one == 100.0 * need / (2.0 * 819e9)  # the one-chip reading
    assert read(_roofline_ctx(4)) == pytest.approx(one / 4)


def test_exchange_occupancy_sums_bytes_over_requests():
    read = _load("layer_metrics", "exchange_occupancy").read
    one_chip = {"unique": 1, "exchange_bytes_total": None,
                "exchange_payload_bytes_total": None}
    assert read({"records": [one_chip]}) is None
    records = [
        {"exchange_bytes_total": 1000, "exchange_payload_bytes_total": 100},
        {"exchange_bytes_total": 3000, "exchange_payload_bytes_total": 500},
        {"error": "RuntimeError: lost"},
    ]
    assert read({"records": records}) == pytest.approx(15.0)


def test_every_metric_has_a_reader_and_its_cells_report_what_it_moves():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in bench["end_to_end"]}
    for kind, section in (("e2e_metrics", "end_to_end"),
                          ("layer_metrics", "per_layer")):
        for m in bench[section]:
            assert (BENCH / kind / f"{m['name']}.py").is_file(), m["name"]
    for m in bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= reports[m["moves"]], m

