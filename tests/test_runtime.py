"""Runtime subsystem: telemetry journal, geometry-backoff policy,
compile-cache placement, engine checkpoint/journal hooks, and the
crash-resilience acceptance test — a supervised CPU-backend check whose
child is killed mid-run resumes from the latest checkpoint and finishes
with an IDENTICAL discovery set and counts to an uninterrupted run.
"""

import json
import os
import sys

import pytest

from stateright_tpu.runtime.journal import Journal, last_event, read_journal
from stateright_tpu.runtime.supervisor import (
    CheckSpec,
    RunSupervisor,
    SupervisorConfig,
    journal_events,
    relax_geometry,
)

jax = pytest.importorskip("jax")

from stateright_tpu.models.twophase import TwoPhaseSys  # noqa: E402


# --- journal -----------------------------------------------------------------


def test_journal_append_read_roundtrip(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path) as j:
        j.append("wave", unique=10, depth=2)
        j.append("checkpoint", path="ck.npz")
    events = read_journal(path)
    assert [e["event"] for e in events] == ["wave", "checkpoint"]
    assert events[0]["unique"] == 10
    assert all("t" in e for e in events)
    assert last_event(path)["event"] == "checkpoint"
    assert last_event(path, "wave")["unique"] == 10


def test_journal_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "j.jsonl")
    Journal(path).append("wave", unique=1)
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"t": 1, "event": "wa')  # writer killed mid-write
    events = read_journal(path)
    assert len(events) == 1 and events[0]["unique"] == 1


def test_journal_concurrent_appenders_interleave_lines(tmp_path):
    path = str(tmp_path / "j.jsonl")
    a, b = Journal(path), Journal(path)
    for i in range(5):
        a.append("wave", src="a", i=i)
        b.append("wave", src="b", i=i)
    events = read_journal(path)
    assert len(events) == 10
    assert {e["src"] for e in events} == {"a", "b"}


def test_journal_rotation_rolls_over_mid_append(tmp_path):
    """Size-capped journals roll into ``.1..N`` segments: the append
    that would cross the cap first shifts segments (atomic renames
    under the lock), then lands whole in a fresh live file — no record
    is ever split across segments, and readers merge oldest-first."""
    import os

    path = str(tmp_path / "j.jsonl")
    j = Journal(path, max_bytes=256, max_segments=3)
    for i in range(30):
        j.append("e", i=i)
    j.close()
    names = sorted(os.listdir(tmp_path))
    assert "j.jsonl" in names and "j.jsonl.1" in names
    assert "j.jsonl.4" not in names  # oldest fell off at the cap
    # Every retained segment holds whole lines; merged read is a
    # contiguous, ordered suffix of what was appended.
    events = read_journal(path)
    idx = [e["i"] for e in events]
    assert idx == list(range(idx[0], 30))
    assert os.path.getsize(path) <= 256
    # A fresh instance on the same path keeps appending after the
    # existing segments (the reopen-after-rollover path).
    j2 = Journal(path, max_bytes=256, max_segments=3)
    j2.append("e", i=30)
    j2.close()
    assert read_journal(path)[-1]["i"] == 30
    assert last_event(path, "e")["i"] == 30


def test_journal_unrotated_default_never_renames(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)  # default: no cap, exactly the old behavior
    for i in range(50):
        j.append("e", i=i)
    j.close()
    import os

    assert sorted(os.listdir(tmp_path)) == ["j.jsonl"]
    assert len(read_journal(path)) == 50


def test_journal_reporter_streams_report_protocol(tmp_path):
    """JournalReporter adapts the standard Reporter protocol onto a
    journal: the reference's text report data lands as machine-readable
    events in the run artifact."""
    from stateright_tpu import JournalReporter

    path = str(tmp_path / "report.jsonl")
    (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_bfs()
        .join_and_report(JournalReporter(path, delay=0.05))
    )
    events = read_journal(path)
    done = [e for e in events if e["event"] == "done"]
    assert len(done) == 1 and done[0]["unique"] == 288
    discoveries = [e for e in events if e["event"] == "discovery"]
    assert {d["name"] for d in discoveries} == {
        "abort agreement", "commit agreement",
    }
    assert all("fingerprint_path" in d for d in discoveries)


# --- geometry backoff --------------------------------------------------------


def test_relax_goes_straight_to_dedup_one_never_stepwise():
    """The observed crash evidence: the intermediate stop (dd=2 at a
    doubled frontier) was itself a NEW worker-crash geometry; the relax
    must jump to the always-safe 1 in ONE step."""
    for dd in (2, 4, 8, 16):
        kwargs = {"dedup_factor": dd, "max_frontier": 1 << 14}
        relaxed = relax_geometry(kwargs)
        assert relaxed["dedup_factor"] == 1, f"stepwise relax from dd={dd}"
        assert relaxed["max_frontier"] == 1 << 14  # untouched on this step
        assert kwargs["dedup_factor"] == dd  # input not mutated


def test_relax_uses_engine_defaults_when_unset():
    # An empty kwargs dict means the engine default (dd=8) is in effect;
    # the first relax must still pin dd=1.
    assert relax_geometry({})["dedup_factor"] == 1
    assert relax_geometry({}, engine="sharded")["dedup_factor"] == 1


def test_relax_never_invents_a_frontier_from_defaults():
    """After dd=1, a kwargs dict WITHOUT an explicit frontier must be
    exhausted, not 'relaxed' to half the engine default: writing a
    default-derived frontier would override a smaller model-specific
    setting the caller never exposed (CLI tpu_kwargs), making the
    restarted geometry LARGER — the opposite of a backoff."""
    assert relax_geometry({"dedup_factor": 1}) is None
    assert relax_geometry({"dedup_factor": 1}, engine="sharded") is None
    step = relax_geometry({})  # dd pinned to 1...
    assert relax_geometry(step) is None  # ...then nothing else to relax


def test_relax_halves_frontier_then_waves_then_gives_up():
    kwargs = {"dedup_factor": 1, "max_frontier": 8192}
    step = relax_geometry(kwargs)
    assert step["max_frontier"] == 4096
    step = relax_geometry(step)
    assert step["max_frontier"] == 2048
    # At the frontier floor with no waves_per_call knob: exhausted.
    assert relax_geometry(step) is None
    # With an explicit waves_per_call, that halves next (per-call device
    # time is the crash driver), down to its floor.
    step["waves_per_call"] = 32
    step = relax_geometry(step)
    assert step["waves_per_call"] == 16
    step = relax_geometry(step)
    assert step["waves_per_call"] == 8
    assert relax_geometry(step) is None


def test_relax_sharded_uses_chunk_size():
    step = relax_geometry({"dedup_factor": 1, "chunk_size": 8192},
                          engine="sharded")
    assert step["chunk_size"] == 4096


# --- compile-cache placement --------------------------------------------------


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_env_wins_else_fixed_checkout_path(
    tmp_path, monkeypatch, from_env
):
    """The environment's directory wins; otherwise every entry point and
    every supervised child uses ``<checkout>/.jax_cache`` — never a path
    derived from a run directory, so restarts and later runs hit it."""
    from stateright_tpu.runtime import compile_cache as cc

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if from_env:
        want = str(tmp_path / "shared-cache")
        monkeypatch.setenv(cc.ENV_VAR, want)
    else:
        want = os.path.join(checkout, ".jax_cache")
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert cc.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.environ[cc.ENV_VAR] == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

    if not from_env:
        monkeypatch.delenv(cc.ENV_VAR)
    sup = RunSupervisor(
        SupervisorConfig(run_dir=str(tmp_path / "run")),
        child_argv=[sys.executable, "-c", "pass"],
    )
    assert sup._child_env()[cc.ENV_VAR] == want


# --- engine checkpoint/journal hooks (in-process, CPU backend) ---------------


def test_engine_journal_and_checkpoint_artifacts(tmp_path):
    """A checkpointing run leaves a parseable journal (wave telemetry with
    occupancy + device-call wall time, checkpoint events, engine_done) and
    a checkpoint that is itself a valid resumable snapshot."""
    journal_path = str(tmp_path / "journal.jsonl")
    ckpt = str(tmp_path / "checkpoint.npz")
    model = TwoPhaseSys(rm_count=3)
    ck = (
        model.checker()
        .spawn_tpu(
            capacity=1 << 14,
            max_frontier=1 << 7,
            waves_per_call=1,
            journal=journal_path,
            checkpoint_path=ckpt,
            checkpoint_every_waves=1,
            device=jax.devices("cpu")[0],
        )
        .join()
    )
    assert ck.unique_state_count() == 288
    events = read_journal(journal_path)
    waves = [e for e in events if e["event"] == "wave"]
    assert waves, "no wave telemetry in the journal"
    for w in waves:
        assert {"waves", "remaining", "tail", "unique", "states", "depth",
                "flags", "call_sec", "occupancy"} <= set(w)
    assert any(e["event"] == "checkpoint" for e in events)
    done = last_event(journal_path, "engine_done")
    assert done["unique"] == 288

    resumed = (
        model.checker()
        .spawn_tpu(
            capacity=1 << 14,
            max_frontier=1 << 7,
            resume_from=ckpt,
            journal=journal_path,
            device=jax.devices("cpu")[0],
        )
        .join()
    )
    assert resumed.unique_state_count() == 288
    assert last_event(journal_path, "resume")["path"] == ckpt


def test_sharded_checkpoint_resume_roundtrip(tmp_path):
    """The sharded engine exposes the same snapshot hooks: a bounded run
    snapshots, resumes to identical totals, and rejects a different
    model's snapshot — mirroring the single-chip round-trip test."""
    model = TwoPhaseSys(rm_count=3)
    journal_path = str(tmp_path / "sharded_journal.jsonl")
    full = (
        model.checker()
        .spawn_tpu_sharded(
            capacity=1 << 14,
            chunk_size=1 << 7,
            journal=journal_path,
            checkpoint_path=str(tmp_path / "sharded_ck.npz"),
            checkpoint_every_waves=4,
        )
        .join()
    )
    assert full.unique_state_count() == 288
    events = read_journal(journal_path)
    kinds = [e["event"] for e in events]
    assert "wave" in kinds and "checkpoint" in kinds
    assert last_event(journal_path, "engine_done")["unique"] == 288
    # The final sharded checkpoint is itself resumable: a resume of a
    # COMPLETED run finishes immediately with the same totals.
    redone = (
        model.checker()
        .spawn_tpu_sharded(
            capacity=1 << 14, chunk_size=1 << 7,
            resume_from=str(tmp_path / "sharded_ck.npz"),
        )
        .join()
    )
    assert redone.unique_state_count() == 288
    bounded = (
        model.checker()
        .target_state_count(300)
        .spawn_tpu_sharded(capacity=1 << 14, chunk_size=1 << 7)
        .join()
    )
    assert bounded.unique_state_count() < 288
    snap = str(tmp_path / "sharded.npz")
    bounded.save_snapshot(snap)

    resumed = (
        model.checker()
        .spawn_tpu_sharded(
            capacity=1 << 14, chunk_size=1 << 7, resume_from=snap
        )
        .join()
    )
    assert resumed.unique_state_count() == 288
    assert resumed.state_count() == full.state_count()
    assert resumed.max_depth() == full.max_depth()
    assert sorted(resumed.discoveries()) == sorted(full.discoveries())

    with pytest.raises(ValueError, match="snapshot does not match"):
        TwoPhaseSys(rm_count=4).checker().spawn_tpu_sharded(
            capacity=1 << 14, chunk_size=1 << 7, resume_from=snap
        ).join()


# --- the acceptance test: kill mid-run, resume, identical results ------------


def test_supervised_kill_mid_run_resumes_identical(tmp_path, monkeypatch):
    """A supervised CPU-backend check whose child dies mid-run (fault
    injection: the child ``os._exit``\\ s the moment its first checkpoint
    lands) auto-resumes from that checkpoint and reports the same
    ``unique_state_count``, ``state_count``, depth, and discovery set as
    an uninterrupted run; the journal records the checkpoint, the crash,
    and the resume."""
    model = TwoPhaseSys(rm_count=4)
    straight = (
        model.checker()
        .spawn_tpu(capacity=1 << 14, max_frontier=1 << 6, dedup_factor=1,
                   waves_per_call=2)
        .join()
    )

    monkeypatch.setenv(
        "STATERIGHT_RUNTIME_FAULT_EXIT_AFTER_CHECKPOINT", "137"
    )
    run_dir = str(tmp_path / "run")
    spec = CheckSpec(
        model_factory=TwoPhaseSys,
        factory_kwargs={"rm_count": 4},
        engine_kwargs={
            "capacity": 1 << 14,
            "max_frontier": 1 << 6,
            "dedup_factor": 1,
            "waves_per_call": 2,
        },
    )
    sup = RunSupervisor(
        SupervisorConfig(
            run_dir=run_dir,
            checkpoint_every_waves=2,
            checkpoint_every_sec=None,
            call_deadline_sec=240.0,
            poll_interval_sec=0.05,
            max_restarts=2,
        ),
        spec=spec,
    )
    result = sup.run()

    assert result["completed"]
    assert result["unique_state_count"] == straight.unique_state_count()
    assert result["state_count"] == straight.state_count()
    assert result["max_depth"] == straight.max_depth()
    assert result["discoveries"] == sorted(straight.discoveries())

    events = journal_events(run_dir)
    kinds = [e["event"] for e in events]
    assert "checkpoint" in kinds, "no checkpoint event in the journal"
    assert "crash" in kinds, "the child's death was not recorded"
    assert "resume" in kinds, "the restarted child did not resume"
    assert kinds.count("run_start") == 2  # original child + restarted one
    # The resumed child started from durable progress, not from scratch.
    resume = next(e for e in events if e["event"] == "resume")
    assert resume["unique"] > 0
    # The result file on disk matches what the supervisor returned.
    with open(os.path.join(run_dir, "result.json"), encoding="utf-8") as fh:
        assert json.load(fh) == result


def test_sharded_supervised_kill_mid_run_resumes_identical(
    tmp_path, monkeypatch
):
    """The sharded mirror of the kill-mid-run acceptance test, enabled
    by the shared wave-loop core (parallel/wave_loop.py): a supervised
    SHARDED child on the virtual mesh dies the moment its first
    checkpoint lands, auto-resumes from it, and reports the same
    totals and discovery set as an uninterrupted run."""
    model = TwoPhaseSys(rm_count=4)
    straight = (
        model.checker()
        .spawn_tpu_sharded(
            capacity=1 << 14, chunk_size=1 << 6, waves_per_call=2,
        )
        .join()
    )

    monkeypatch.setenv(
        "STATERIGHT_RUNTIME_FAULT_EXIT_AFTER_CHECKPOINT", "137"
    )
    run_dir = str(tmp_path / "run")
    spec = CheckSpec(
        model_factory=TwoPhaseSys,
        factory_kwargs={"rm_count": 4},
        engine="sharded",
        engine_kwargs={
            "capacity": 1 << 14,
            "chunk_size": 1 << 6,
            "waves_per_call": 2,
        },
    )
    sup = RunSupervisor(
        SupervisorConfig(
            run_dir=run_dir,
            checkpoint_every_waves=2,
            checkpoint_every_sec=None,
            call_deadline_sec=240.0,
            poll_interval_sec=0.05,
            max_restarts=2,
        ),
        spec=spec,
    )
    result = sup.run()

    assert result["completed"]
    assert result["unique_state_count"] == straight.unique_state_count()
    assert result["state_count"] == straight.state_count()
    assert result["max_depth"] == straight.max_depth()
    assert result["discoveries"] == sorted(straight.discoveries())

    events = journal_events(run_dir)
    kinds = [e["event"] for e in events]
    assert "checkpoint" in kinds
    assert "crash" in kinds
    assert "resume" in kinds
    assert kinds.count("run_start") == 2
    resume = next(e for e in events if e["event"] == "resume")
    assert resume["unique"] > 0


def test_sharded_resume_wrong_mesh_size_is_loud(tmp_path):
    """A sharded snapshot is bound to the mesh width that wrote it
    (gids encode the owner shard); resuming on a different width must
    fail with an error that NAMES both sizes, not a generic key
    mismatch."""
    import jax
    import numpy as np

    model = TwoPhaseSys(rm_count=3)
    bounded = (
        model.checker()
        .target_state_count(300)
        .spawn_tpu_sharded(
            mesh=jax.sharding.Mesh(
                np.array(jax.devices("cpu")[:4]), ("shards",)
            ),
            capacity=1 << 13, chunk_size=1 << 6,
        )
        .join()
    )
    snap = str(tmp_path / "mesh4.npz")
    bounded.save_snapshot(snap)
    with pytest.raises(
        ValueError, match=r"4-shard mesh and cannot resume on 2 shards"
    ):
        model.checker().spawn_tpu_sharded(
            mesh=jax.sharding.Mesh(
                np.array(jax.devices("cpu")[:2]), ("shards",)
            ),
            capacity=1 << 13, chunk_size=1 << 6, resume_from=snap,
        ).join()


def test_supervisor_deterministic_child_error_is_fatal(tmp_path):
    """A child that fails with a clean non-transient Python error (here:
    a model factory that raises) must NOT be retried into a crash loop;
    the supervisor raises with the child's error text."""
    from stateright_tpu.runtime.supervisor import SupervisorError

    spec = CheckSpec(model_factory=_raising_factory)
    sup = RunSupervisor(
        SupervisorConfig(
            run_dir=str(tmp_path / "run"),
            call_deadline_sec=120.0,
            poll_interval_sec=0.05,
            max_restarts=3,
        ),
        spec=spec,
    )
    with pytest.raises(SupervisorError, match="deliberately broken"):
        sup.run()
    events = journal_events(str(tmp_path / "run"))
    kinds = [e["event"] for e in events]
    # Exactly one attempt: deterministic errors never burn the restart
    # budget.
    assert kinds.count("run_start") == 1
    assert "give_up" in kinds


def _raising_factory():
    raise RuntimeError("deliberately broken model factory")


# --- knob cache (runtime/knob_cache.py) --------------------------------------


def test_knob_cache_roundtrip(tmp_path):
    from stateright_tpu.runtime.knob_cache import (
        drop_knobs, load_knobs, store_knobs,
    )

    d = str(tmp_path / "knobs")
    assert load_knobs(d, "k") is None
    store_knobs(d, "k", {"capacity": 1 << 20, "dedup_factor": 8},
                unique=314, discovery_sec=1.5)
    assert load_knobs(d, "k") == {"capacity": 1 << 20, "dedup_factor": 8}
    # Second key merges; first survives.
    store_knobs(d, "k2", {"max_frontier": 2048})
    assert load_knobs(d, "k") is not None
    assert load_knobs(d, "k2") == {"max_frontier": 2048}
    drop_knobs(d, "k")
    assert load_knobs(d, "k") is None
    assert load_knobs(d, "k2") is not None
    # Stored metadata is on disk for humans but not returned.
    data = json.load(open(os.path.join(d, "knobs.json")))
    assert data["k2"]["knobs"]["max_frontier"] == 2048


def test_knob_cache_degrades_on_torn_file(tmp_path):
    from stateright_tpu.runtime.knob_cache import load_knobs, store_knobs

    d = str(tmp_path / "knobs")
    store_knobs(d, "k", {"capacity": 4})
    with open(os.path.join(d, "knobs.json"), "w") as fh:
        fh.write('{"k": {"knobs": {"capa')  # torn write
    assert load_knobs(d, "k") is None  # degrade to rediscovery, no crash
    store_knobs(d, "k", {"capacity": 8})  # and the file heals on store
    assert load_knobs(d, "k") == {"capacity": 8}
