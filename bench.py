#!/usr/bin/env python
"""Driver benchmark: TPU wavefront checking throughput vs host BFS.

Headline workload (BASELINE.md metric): exhaustive `paxos check 3` — Single
Decree Paxos, 3 servers / 3 clients on a nonduplicating network with
per-state linearizability checking (1,194,428 unique states, depth 28;
reference workload examples/paxos.rs).  Also measured (optional phases that
can never zero the headline): time-to-first-violation on the
property-violating variant, and a 1-device-mesh `spawn_tpu_sharded` smoke so
the shard_map program runs on real TPU hardware every round.

Emit protocol (LAST JSON line is authoritative — the driver's parser takes
it; every earlier line is a valid fallback record from an earlier phase):

  phase 0  smoke: paxos c=2 (reference golden 16,668) on default knobs.
           A minimal-but-valid record is emitted the moment it passes, so
           ANY later crash — including in the headline warm-up, which
           zeroed round 4 — still leaves a parseable artifact.
  phase 1  headline: `paxos check 3` discovered with pure default engine
           knobs (auto-tune does all sizing), then measured best-of-3 at
           the discovered sizes.  Emitted as soon as the host denominator
           exists.  If the two-phase expansion path fails, the run falls
           back to the single-phase step kernel (and says so in the
           record) rather than dying.
  phase 2+ optional phases (native C++ denominator bound, warm-vs-cold
           serving, incremental re-check latency on a one-line model
           edit with zero-waves + verdict-equality gates (`recheck`,
           docs/INCREMENTAL.md), tiered out-of-core
           budget-vs-unconstrained with a verdict-equality gate,
           roofline trace, symmetry on/off cut,
           ttfv, sharded smoke + measured exchange occupancy, reference
           suite) add keys and re-emit;
           they can never zero earlier lines.  The observability keys —
           `wave_breakdown`, `hbm_util_frac`, `bottleneck_phase`,
           `exchange_occupancy`, `denominator_native` (VERDICT r5 weak
           #6/#9, docs/OBSERVABILITY.md) — come from phase_trace,
           phase_sharded_smoke, and phase_denominator_native; the
           `dedup_share`/`bytes_dedup` regression gauge (sortless
           claim-plane election vs the ISSUE-12 sort-rung fallback, at
           both densities) from phase_dedup and the
           `step_share`/`bytes_step` gauge (the frontier-sized step
           rung, ISSUE 14) from phase_step, both rungs folded through
           the knob cache.  The reference suite re-emits after EVERY
           workload, so a deadline kill mid-suite keeps the
           completed workloads in the artifact.  Discovered tuned_kwargs
           persist in a knob cache (.bench_knobs/, runtime/knob_cache.py)
           keyed by (workload, device, engine) — later rounds skip the
           re-discovery; golden gates drop stale
           entries.

Record shape: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
...} where value is unique-states/sec of the TPU wavefront checker (warm —
program compile excluded; the compile is a one-time per-(model, shape)
cost served by the program/persistent caches) and vs_baseline is the
ratio to the host BFS measured on this machine.

Robustness: a unique-state-count mismatch vs the golden is FATAL for
that phase's rate — a wrong-answer run must not post a number — and once
any record has been emitted the process always exits 0 so the artifact
survives.  Everything runs in this one process: a chip belongs to one
process at a time, so no child may need it while this process holds it.

DENOMINATOR HONESTY: the host engine is this package's reference-style
thread-pool BFS — pure Python, measured at `threads=os.cpu_count()` and
reported in the JSON (`denominator_*` keys).  Python threads are GIL-bound,
so this denominator is far slower than the reference's native Rust checker
would be on a many-core machine; the ratio is a same-machine, same-language
comparison, not a cross-implementation claim.
"""

import json
import os
import pathlib
import sys
import time
import traceback

_REPO = pathlib.Path(__file__).resolve().parent
# Virtual CPU shards for the measured-exchange phase (must be set before
# any jax import; appended so a driver-supplied XLA_FLAGS survives).
_XLA_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _XLA_FLAGS:
    os.environ["XLA_FLAGS"] = (
        _XLA_FLAGS + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, str(_REPO))

from stateright_tpu.runtime.compile_cache import use_compile_cache  # noqa: E402
from stateright_tpu.runtime.knob_cache import (  # noqa: E402
    drop_knobs,
    knob_key as _knob_key,
    load_knobs,
    store_knobs,
)

# Discovered tuned_kwargs persist here (the bench's checkpoint dir), keyed
# by (workload, device, engine); later rounds reload
# them instead of re-paying the ~21-min 2pc-check-10 discovery every round
# (VERDICT r5 weak #2).  Golden gates keep staleness safe: a cache entry
# whose measured run misses the golden is dropped and rediscovered.
KNOB_CACHE_DIR = os.environ.get(
    "BENCH_KNOB_CACHE_DIR", str(_REPO / ".bench_knobs")
)


# GLOBAL TIME BUDGET: the round-5 suite was killed by the driver's own
# timeout mid-workload (BENCH_r05.json rc=124), zeroing nothing — the
# emit-early protocol held — but burning phases that never got to run.
# The bench now budgets itself: every suite workload is gated on
# the remaining budget, phases that cannot fit are SKIPPED with a note in
# the record, and the process exits 0 with partial JSON instead of being
# killed mid-suite.
BENCH_TIME_BUDGET = float(os.environ.get("BENCH_TIME_BUDGET_SEC", "5400"))
_T_START = time.time()


def budget_remaining() -> float:
    return BENCH_TIME_BUDGET - (time.time() - _T_START)

# paxos check 3 has no reference-pinned count (the reference pins c=2 =
# 16,668, which our tests reproduce); this value is this framework's own
# measurement, pinned cross-engine (host BFS vs device vs sharded) by
# tests/test_cross_engine_pin.py, used here to detect regressions.
GOLDEN_UNIQUE = 1_194_428
GOLDEN_DEPTH = 28
SMOKE_UNIQUE = 16_668  # reference examples/paxos.rs:328 (paxos check 2)
HOST_TIME_SLICE = 60.0  # seconds of host BFS to establish the denominator
MEASURED_REPEATS = 3  # reference bench.sh COUNT=3; value = best-of-N

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_device_timed(make_checker):
    """Build + join a device checker; returns ``(checker, seconds)``."""
    t0 = time.time()
    return make_checker().join(), time.time() - t0


def run_device(make_checker):
    return run_device_timed(make_checker)[0]


def paxos_model(clients: int, never_decided: bool = False):
    from stateright_tpu.actor import Network
    from stateright_tpu.models.paxos import PaxosModelCfg

    return PaxosModelCfg(
        client_count=clients,
        server_count=3,
        network=Network.new_unordered_nonduplicating(),
        never_decided=never_decided,
    ).into_model()


def _twophase(rm: int):
    from stateright_tpu.models.twophase import TwoPhaseSys

    return TwoPhaseSys(rm_count=rm)


def _abd(clients: int, ordered: bool = False):
    from stateright_tpu.actor import Network
    from stateright_tpu.models.abd import AbdModelCfg

    return AbdModelCfg(
        client_count=clients,
        server_count=2,
        network=(
            Network.new_ordered()
            if ordered
            else Network.new_unordered_nonduplicating()
        ),
    ).into_model()


def _single_copy(clients: int):
    from stateright_tpu.actor import Network
    from stateright_tpu.models.single_copy_register import SingleCopyModelCfg

    return SingleCopyModelCfg(
        client_count=clients,
        server_count=1,
        network=Network.new_unordered_nonduplicating(),
    ).into_model()


# The reference's own benchmark list (bench.sh:27-34), run on device every
# round.  Goldens: 2pc ≤5 and register c2 are reference-pinned; the rest
# are this framework's cross-validated pins (depth-bounded host
# differentials + dual-engine agreement; see tests/ and PARITY.md).
REFERENCE_SUITE = [
    ("2pc_check_10", lambda: _twophase(10), 61_515_776, 32),
    ("paxos_check_6", lambda: paxos_model(6), 9_357_525, 28),
    ("single_copy_register_check_4", lambda: _single_copy(4), 400_233, 17),
    ("linearizable_register_check_2", lambda: _abd(2), 544, 25),
    ("linearizable_register_check_3_ordered",
     lambda: _abd(3, ordered=True), 46_516, 37),
]


def discover_and_measure(label: str, mk, want_unique: int, want_depth: int,
                         extras: dict = None):
    """THE measurement protocol, shared by the headline and every suite
    workload so the two cannot drift: a timed default-knob discovery run
    (auto-tune does all sizing) — SKIPPED when the knob cache already
    holds this workload's tuned sizes — a (unique, depth) golden gate,
    then up to MEASURED_REPEATS measured runs at ``tuned_kwargs()`` —
    each re-gated — with big workloads (>120s) measured once.  When
    ``extras`` (an out-dict) is given, the last measured run's
    ``host_share`` gauge (obs/timeline.host_share_of — host tail over
    host+device loop time) is captured into it before the checker is
    torn down.  Returns
    ``(discovery_sec, tuned, samples, knobs_cached)``; raises on any
    golden mismatch or device error (a wrong answer must never post a
    rate).  A cached entry that fails its first golden gate is dropped
    and the workload falls back to one full discovery."""
    import gc

    key = _knob_key(label)
    tuned = load_knobs(KNOB_CACHE_DIR, key)
    knobs_cached = tuned is not None
    discovery = 0.0
    if knobs_cached:
        log(f"{label}: tuned knobs from cache ({KNOB_CACHE_DIR}): {tuned}")
    else:
        log(f"{label}: discovery run (default knobs, auto-tune sizing)...")
        t0 = time.time()
        ck = run_device(lambda: mk().checker().spawn_tpu())
        discovery = time.time() - t0
        tuned = ck.tuned_kwargs()
        unique, depth = ck.unique_state_count(), ck.max_depth()
        del ck
        gc.collect()
        if (unique, depth) != (want_unique, want_depth):
            raise AssertionError(
                f"{label}: discovery golden mismatch: unique={unique} "
                f"depth={depth} != {want_unique}/{want_depth}"
            )
        store_knobs(
            KNOB_CACHE_DIR, key, tuned,
            unique=want_unique, depth=want_depth,
            discovery_sec=round(discovery, 1),
        )
        log(f"{label}: discovery {discovery:.1f}s (incl. compile); "
            f"knobs cached under {KNOB_CACHE_DIR}")
    log(f"{label}: measured runs {tuned}...")
    samples = []
    for rep in range(MEASURED_REPEATS):
        ck, dt = run_device_timed(
            lambda: mk().checker().spawn_tpu(**tuned)
        )
        unique, depth = ck.unique_state_count(), ck.max_depth()
        if extras is not None:
            from stateright_tpu.obs.timeline import host_share_of

            hs = host_share_of(ck.metrics())
            if hs is not None:
                extras["host_share"] = round(hs, 4)
        del ck
        gc.collect()
        if (unique, depth) != (want_unique, want_depth):
            if knobs_cached and not samples:
                # Stale cache entry (e.g. the engine's geometry defaults
                # moved under it): drop it and rediscover once — the
                # recursive call misses the cache, so a second mismatch
                # raises like any other golden failure.
                log(f"{label}: cached knobs failed the golden gate "
                    f"(unique={unique} depth={depth}); dropping cache "
                    "entry and rediscovering")
                drop_knobs(KNOB_CACHE_DIR, key)
                return discover_and_measure(
                    label, mk, want_unique, want_depth, extras=extras
                )
            raise AssertionError(
                f"{label}: measured golden mismatch: unique={unique} "
                f"depth={depth} != {want_unique}/{want_depth}"
            )
        samples.append(dt)
        log(f"{label}: measured[{rep}]: {dt:.2f}s = "
            f"{unique / dt:.0f} uniq/s")
        # Big workloads (minutes each) stop at TWO samples: the first
        # measured run traces+compiles the tuned shapes (discovery never
        # compiled them — its growth path visits different sizes), so a
        # single sample would include compile time the record claims to
        # exclude; the second run is warm via the program cache and
        # best-of-N drops the cold one.
        if dt > 120.0 and rep >= 1:
            break
    return discovery, tuned, samples, knobs_cached


def _measure_suite_workload(spec, entry: dict) -> None:
    """Run the shared protocol for ONE reference-suite workload; results
    land in ``entry`` (golden mismatches become error entries, so one
    wrong workload never hides the others)."""
    name, mk, want_unique, want_depth = spec
    try:
        discovery, tuned, samples, knobs_cached = discover_and_measure(
            f"suite: {name}", mk, want_unique, want_depth
        )
    except AssertionError as exc:
        entry["error"] = str(exc)
        log(entry["error"])
        return
    best = min(samples)
    entry["knobs_cached"] = knobs_cached
    entry["discovery_sec"] = round(discovery, 2)
    entry["unique_states"] = want_unique
    entry["depth"] = want_depth
    entry["sec"] = round(best, 2)
    entry["samples_sec"] = [round(s, 2) for s in samples]
    entry["unique_states_per_sec"] = round(want_unique / best, 1)
    log(
        f"suite: {name}: {want_unique} unique, best of "
        f"{len(samples)}: {best:.2f}s = "
        f"{want_unique / best:.0f} uniq/s"
    )


# A suite workload below this remaining budget cannot finish even its
# discovery run; skip it (with a note in the record) rather than start
# work the budget will kill.  A WARM workload — its tuned knobs already
# in the cache — skips the discovery entirely, so the gate drops to what
# measured runs alone need; without this split, a repeat round with a
# populated cache still skipped exactly the workloads the cache was
# built to capture.
_SUITE_MIN_BUDGET = 300.0
_SUITE_MIN_BUDGET_WARM = 120.0


def _suite_min_budget(name: str) -> tuple:
    """(min_budget_sec, warm) for one suite workload: warm when the knob
    cache already holds its tuned sizes."""
    warm = load_knobs(KNOB_CACHE_DIR, _knob_key(f"suite: {name}")) is not None
    return (_SUITE_MIN_BUDGET_WARM if warm else _SUITE_MIN_BUDGET), warm


def phase_reference_suite(record: dict) -> None:
    """Run the reference's full bench list on device, in this process.

    Partial results are durable: the record is re-emitted after EVERY
    workload (not just after the whole phase), so a deadline kill
    mid-suite still leaves driver-captured numbers for the workloads
    that finished."""
    suite: dict = {}
    record["reference_suite"] = suite
    for spec in REFERENCE_SUITE:
        name = spec[0]
        remaining = budget_remaining()
        min_budget, warm = _suite_min_budget(name)
        if remaining < min_budget:
            suite[name] = {"error": (
                "skipped: global time budget exhausted "
                f"({remaining:.0f}s remaining of {BENCH_TIME_BUDGET:.0f}s;"
                f" {'warm' if warm else 'cold'} gate {min_budget:.0f}s)"
            )}
            log(f"suite: {name}: {suite[name]['error']}")
            emit(record)
            continue
        if warm:
            log(f"suite: {name}: warm start (tuned knobs cached in "
                f"{KNOB_CACHE_DIR}; discovery skipped)")
        entry: dict = {}
        try:
            _measure_suite_workload(spec, entry)
        except Exception:  # noqa: BLE001 - one workload's error is data
            entry.setdefault("error", traceback.format_exc(limit=3))
            log(f"suite: {name}: failed:\n{entry['error']}")
        suite[name] = entry
        # Per-workload durability: the last JSON line always carries
        # every workload finished so far.
        emit(record)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def phase_ttfv(record: dict, threads: int, tuned: dict) -> None:
    """Time-to-first-violation on the never-decided variant (optional).

    Uses the headline run's auto-tuned engine sizes (same model shape) so
    no hand-tuned constants are involved."""
    from stateright_tpu.core.has_discoveries import HasDiscoveries

    def spawn():
        return (
            paxos_model(3, never_decided=True)
            .checker()
            .finish_when(HasDiscoveries.ANY_FAILURES)
            .spawn_tpu(**tuned)
        )

    log("ttfv: warming violating-variant program...")
    run_device(spawn)
    v, ttfv_tpu = run_device_timed(spawn)
    assert "never decided" in v.discoveries(), "violation not found on device"
    t0 = time.time()
    vh = (
        paxos_model(3, never_decided=True)
        .checker()
        .threads(threads)
        .finish_when(HasDiscoveries.ANY_FAILURES)
        .timeout(600)  # fail fast instead of hanging if the host regresses
        .spawn_bfs()
        .join()
    )
    ttfv_host = time.time() - t0
    assert "never decided" in vh.discoveries()
    log(f"ttfv: tpu={ttfv_tpu:.2f}s host={ttfv_host:.2f}s")
    record["ttfv_tpu_sec"] = round(ttfv_tpu, 2)
    record["ttfv_host_sec"] = round(ttfv_host, 2)


SYM_RM = 5
SYM_UNIQUE_FULL = 8_832   # reference examples/2pc.rs:158-159
# Full-record canon orbit count, pinned by tests/test_tpu_symmetry.py (the
# reference's DFS-with-symmetry reports 665 with its traversal-dependent
# tie-broken representative; the device canon is the exact orbit
# invariant — docs/SYMMETRY.md).
SYM_UNIQUE_CANON = 314
SYM_HOST_DFS = 665        # reference examples/2pc.rs:163-168, for context


def phase_symmetry(record: dict) -> None:
    """Device symmetry reduction (optional phase): with/without-symmetry
    unique-state counts and uniq/s on `2pc check 5` — the reference's own
    symmetry golden workload — both runs golden-gated, plus a
    budget-gated scale datapoint (`2pc check 10` with symmetry, whose
    non-sym count is the 61.5M suite golden)."""
    from stateright_tpu.models.twophase import TwoPhaseSys

    def mk():
        return TwoPhaseSys(rm_count=SYM_RM)

    entry: dict = {"workload": f"2pc_check_{SYM_RM}"}

    def measure(spawn, want):
        run_device(spawn)  # warm the program
        ck, dt = run_device_timed(spawn)
        u = ck.unique_state_count()
        assert u == want, (
            f"symmetry phase golden mismatch: unique={u} != {want}"
        )
        return u, dt

    u0, dt0 = measure(lambda: mk().checker().spawn_tpu(), SYM_UNIQUE_FULL)
    u1, dt1 = measure(
        lambda: mk().checker().symmetry().spawn_tpu(), SYM_UNIQUE_CANON
    )
    entry.update({
        "unique_no_sym": u0,
        "sec_no_sym": round(dt0, 3),
        "uniq_per_sec_no_sym": round(u0 / dt0, 1),
        "unique_sym": u1,
        "sec_sym": round(dt1, 3),
        "uniq_per_sec_sym": round(u1 / dt1, 1),
        "state_space_cut": round(u0 / u1, 2),
        "host_dfs_sym_unique": SYM_HOST_DFS,
    })
    record["symmetry"] = entry
    log(f"symmetry: 2pc({SYM_RM}) {u0} -> {u1} unique "
        f"({u0 / u1:.1f}x cut), sym {dt1:.2f}s")
    # Durability before the open-ended big run (same policy as the
    # per-workload emits in phase_reference_suite): the rm=5 numbers are
    # measured and golden-gated — a driver kill during the 2pc(10) leg
    # must not lose them.
    emit(record)
    if budget_remaining() < 900.0:
        return
    # Scale datapoint: the biggest reference bench workload, reduced.
    # The sym count is self-measured (no golden exists yet); the non-sym
    # side is the suite's pinned 61,515,776, so the CUT is still
    # golden-anchored on one side.
    ck, dt = run_device_timed(
        lambda: TwoPhaseSys(rm_count=10).checker().symmetry().spawn_tpu()
    )
    u = ck.unique_state_count()
    entry["big"] = {
        "workload": "2pc_check_10_sym",
        "unique_sym": u,
        "unique_no_sym": 61_515_776,
        "state_space_cut": round(61_515_776 / max(u, 1), 1),
        "sec_sym_incl_autotune": round(dt, 2),
        "uniq_per_sec_sym": round(u / dt, 1),
        "note": "sym count self-measured; non-sym count is the suite "
                "golden (2pc_check_10)",
    }
    log(f"symmetry: 2pc(10) sym {u} unique in {dt:.1f}s "
        f"({61_515_776 / max(u, 1):.0f}x cut)")


def phase_sharded_smoke(record: dict) -> None:
    """Run spawn_tpu_sharded on a 1-device mesh on the real chip (optional).

    All other sharded evidence is virtual CPU meshes; this validates the
    shard_map + all_to_all + donation path under the real TPU runtime and
    reports the overhead vs the single-chip engine on the same workload
    (paxos check 2, golden 16,668).
    """
    import numpy as np
    import jax

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("shards",))

    def spawn():
        return paxos_model(2).checker().spawn_tpu_sharded(
            mesh=mesh, capacity=1 << 20, chunk_size=1 << 11
        )

    log("sharded smoke: warming 1-device-mesh program on real chip...")
    run_device(spawn)
    c, sharded_dt = run_device_timed(spawn)
    assert c.unique_state_count() == 16_668, (
        f"sharded paxos2 unique={c.unique_state_count()} != 16668"
    )

    def spawn_single():
        return paxos_model(2).checker().spawn_tpu(
            capacity=1 << 20, max_frontier=1 << 11
        )

    run_device(spawn_single)
    s, single_dt = run_device_timed(spawn_single)
    assert s.unique_state_count() == 16_668
    log(
        f"sharded smoke: paxos2 sharded(1dev)={sharded_dt:.2f}s "
        f"single-chip={single_dt:.2f}s "
        f"overhead={sharded_dt / single_dt:.2f}x"
    )
    record["sharded_1dev_paxos2_sec"] = round(sharded_dt, 2)
    record["sharded_vs_single_overhead"] = round(sharded_dt / single_dt, 2)
    acc = c.accounting()
    record["sharded_accounting"] = {
        "waves": acc["waves"],
        "all_to_all_bytes_total": acc["all_to_all_bytes_total"],
        "exchange_occupancy": round(acc["exchange_occupancy"], 4),
        "unique_skew_max_over_mean": round(
            acc["unique_skew_max_over_mean"], 4
        ),
    }

    # MEASURED exchange metrics on a real multi-shard mesh: the 1-device
    # smoke elides the exchange entirely, so the occupancy evidence comes
    # from the 8-shard virtual CPU mesh (the same mesh the weak-scaling
    # table in docs/SHARDED_SCALING.md is generated on) — per-shard
    # candidate counters measured by the engine, golden-gated.  Since the
    # bucketed exchange (r06) this section is also the REGRESSION GAUGE
    # for the payload shape: occupancy must stay ≥10× the r05 fixed-
    # buffer baseline (0.28%) and the measured useful bytes (pure
    # candidate counts — bucketing must not change WHAT is exchanged,
    # only the buffers it rides in) must stay within 1% of the r05 run.
    cpu_devs = jax.devices("cpu")
    if len(cpu_devs) >= 8:
        from stateright_tpu.runtime.knob_cache import SHARDED_ENGINE

        # Warm-start the discovered bucket rung from the knob cache so a
        # repeat round skips any overflow-retry ramp (and fold the rung
        # found this round back in for the next one).
        key8 = _knob_key("paxos_check_2_sharded8", engine=SHARDED_ENGINE)
        cached8 = load_knobs(KNOB_CACHE_DIR, key8) or {}
        mesh8 = jax.sharding.Mesh(np.array(cpu_devs[:8]), ("shards",))
        c8 = run_device(
            lambda: paxos_model(2).checker().spawn_tpu_sharded(
                mesh=mesh8, capacity=1 << 16, chunk_size=1 << 9,
                bucket_slack=cached8.get("bucket_slack"),
            )
        )
        assert c8.unique_state_count() == 16_668, (
            f"virtual-8 paxos2 unique={c8.unique_state_count()} != 16668"
        )
        acc8 = c8.accounting()
        store_knobs(
            KNOB_CACHE_DIR, key8,
            {"bucket_slack": acc8["bucket_slack"]},
            golden_unique=16_668, shards=8,
        )
        # r05 baselines (BENCH_r05 round, fixed [n, u_sz] buffers,
        # capacity=1<<16 chunk=1<<9 on the virtual-8 mesh):
        R05_OCCUPANCY = 0.0028
        R05_USEFUL_BYTES = 3_425_968
        occ8 = acc8["exchange_occupancy"]
        useful8 = acc8["exchange_payload_bytes_total"]
        assert occ8 >= 10 * R05_OCCUPANCY, (
            f"bucketed-exchange regression: occupancy {occ8:.4f} < 10x "
            f"the r05 fixed-buffer baseline {R05_OCCUPANCY}"
        )
        assert abs(useful8 - R05_USEFUL_BYTES) / R05_USEFUL_BYTES <= 0.01, (
            f"bucketed exchange changed the USEFUL payload: {useful8} B "
            f"vs r05 {R05_USEFUL_BYTES} B (>1%) — the buckets must carry "
            "exactly the same candidates"
        )
        record["exchange_occupancy"] = round(occ8, 6)
        record["sharded_virtual8"] = {
            "waves": acc8["waves"],
            "exchange_occupancy": round(occ8, 6),
            "exchange_occupancy_gain_vs_r05": round(
                occ8 / R05_OCCUPANCY, 1
            ),
            "exchange_payload_bytes_total": useful8,
            "all_to_all_bytes_total": acc8["all_to_all_bytes_total"],
            "exchange_bucket_lanes": acc8["exchange_bucket_lanes"],
            "bucket_slack": acc8["bucket_slack"],
            "bucket_retries": acc8["bucket_retries"],
            "unique_skew_max_over_mean": round(
                acc8["unique_skew_max_over_mean"], 4
            ),
        }
        log(
            f"sharded virtual-8: paxos2 occupancy={occ8:.4f} "
            f"({occ8 / R05_OCCUPANCY:.0f}x r05) payload={useful8} B "
            f"useful of {acc8['all_to_all_bytes_total']} B transmitted "
            f"(bucket={acc8['exchange_bucket_lanes']} lanes, "
            f"slack={acc8['bucket_slack']}%, "
            f"retries={acc8['bucket_retries']})"
        )
    else:
        # Elided exchange moves zero bytes; the identity occupancy ×
        # transmitted = useful still holds at 0.0.
        record["exchange_occupancy"] = round(acc["exchange_occupancy"], 6)


def phase_trace(record: dict, tuned: dict) -> None:
    """Roofline trace of the headline workload (VERDICT r5 weak #6: BENCH
    reported states/sec and nothing else): run `paxos check 3` with
    trace=True at the headline's tuned sizes, golden-gate it, and emit
    `wave_breakdown` (per-phase seconds; the phases partition the traced
    wall time, so they sum to the measured wave time by construction),
    `hbm_util_frac` (modeled bytes / measured time / device peak,
    obs/roofline.py), and the named `bottleneck_phase`.  The traced rate
    is NOT the headline — per-wave dispatch+sync overhead is the
    documented trace cost (`trace_overhead_vs_fused` quantifies it)."""
    def spawn(**extra):
        b = paxos_model(3).checker()
        for k, v in extra.items():
            getattr(b, k)(v)
        return b.spawn_tpu(trace=True, **tuned)

    # Budget-gated like every other open-ended phase: the traced run is
    # deliberately un-fused (one host sync per wave) and must never eat
    # the suite phases' budget.  The
    # builder timeout is a hard stop; a timed-out partial run fails the
    # golden gate below and the phase is skipped, headline intact.
    if budget_remaining() < 600.0:
        record["trace_skipped"] = (
            "global time budget too low for a traced headline run "
            f"({budget_remaining():.0f}s remaining)"
        )
        log(f"trace: {record['trace_skipped']}")
        return
    # Bounded warm-up: a few waves compile every phase program without
    # paying a full traced run twice; the measured run below is warm.
    run_device(lambda: spawn(target_state_count=50_000))
    t_cap = max(120.0, budget_remaining() - 300.0)
    ck, dt = run_device_timed(lambda: spawn(timeout=t_cap))
    unique, depth = ck.unique_state_count(), ck.max_depth()
    if (unique, depth) != (GOLDEN_UNIQUE, GOLDEN_DEPTH):
        raise AssertionError(
            f"trace phase golden mismatch: unique={unique} depth={depth}"
            f" != {GOLDEN_UNIQUE}/{GOLDEN_DEPTH}"
        )
    s = ck.trace_summary()
    record["wave_breakdown"] = s["wave_breakdown"]
    record["wave_breakdown_frac"] = s["wave_breakdown_frac"]
    record["hbm_util_frac"] = s["hbm_util_frac"]
    record["hbm_peak_bytes_per_sec"] = s["hbm_peak_bytes_per_sec"]
    record["hbm_peak_estimated"] = s["hbm_peak_estimated"]
    record["trace_workload"] = "paxos_check_3"
    record["trace_sec"] = round(dt, 2)
    if record.get("tpu_wallclock_sec"):
        record["trace_overhead_vs_fused"] = round(
            dt / record["tpu_wallclock_sec"], 2
        )
    # The bottleneck names a DEVICE phase: the host-side readback is the
    # trace instrumentation's own documented cost, not an engine phase.
    # The tracer computes it (one definition shared with the CLI's
    # `trace:` line, obs/trace.py).
    record["bottleneck_phase"] = s["bottleneck_phase"]
    log(
        f"trace: paxos3 breakdown {s['wave_breakdown_frac']} "
        f"hbm_util={s['hbm_util_frac']} "
        f"bottleneck={record['bottleneck_phase']}"
    )


def phase_dedup(record: dict) -> None:
    """Dedup-path regression phase (ISSUE 12's rung ladder, re-gauged
    for ISSUE 14's sortless election): each gauge workload traced twice
    at the same engine sizes — once on the SORT-rung fallback path
    (`sortless=False`, rung warm-started from the knob cache: exactly
    the r08 configuration), once on the sortless claim-plane election
    (the default) — golden-gated per workload and fingerprint-equality-
    gated against each other.  TWO densities on purpose: 2pc(4) is the
    low-density gauge (most candidate lanes fresh, where the rung
    ladder already won 12.5×) and paxos2 the duplicate-heavy one where
    the sort itself stayed the bottleneck (r08: `bytes.dedup` only
    0.59× at the rung).  The claim election carries NO sort term at
    all, so the `bytes.dedup` drop must show at BOTH ends — that is
    the density-insensitivity claim this phase gates.  The top-level
    `dedup_share`/`bytes_dedup` trajectory keys carry the paxos2
    sortless numbers (comparable round over round against r08's
    sort-rung 608,862,208)."""
    import numpy as np

    if budget_remaining() < 420.0:
        record["dedup_skipped"] = (
            f"global time budget too low ({budget_remaining():.0f}s left)"
        )
        log(f"dedup: {record['dedup_skipped']}")
        return
    gauges = (
        # (label, model factory, reference golden, engine sizes)
        ("twophase_check_4", lambda: _twophase(4), 1_568,
         dict(capacity=1 << 13, max_frontier=1 << 10)),
        ("paxos_check_2", lambda: paxos_model(2), SMOKE_UNIQUE,
         dict(capacity=1 << 16, max_frontier=1 << 11)),
    )
    out = {}
    for name, mk, golden, base in gauges:
        key = _knob_key(f"{name}_dedup_rung")
        cached = load_knobs(KNOB_CACHE_DIR, key) or {}

        def spawn(mk=mk, base=base, **extra):
            return mk().checker().spawn_tpu(trace=True, **base, **extra)

        def traced_leg(name=name, golden=golden, spawn=spawn, **extra):
            run_device(lambda: spawn(**extra))  # warm the phase programs
            ck, dt = run_device_timed(lambda: spawn(**extra))
            unique = ck.unique_state_count()
            assert unique == golden, (
                f"dedup phase golden mismatch ({name}): "
                f"unique={unique} != {golden}"
            )
            return ck, dt

        sort_kw = {"sortless": False}
        if cached.get("sort_lanes"):
            sort_kw["sort_lanes"] = cached["sort_lanes"]
        sort_ck, sort_dt = traced_leg(**sort_kw)
        sl_ck, sl_dt = traced_leg()  # the sortless default path
        assert np.array_equal(
            sort_ck.discovered_fingerprints(),
            sl_ck.discovered_fingerprints(),
        ), f"{name}: sortless diverged from the sort-path discovery set"
        # Persist the sort path's PINNED rung only (sort_lanes_rung;
        # 0 = never tuned off the full buffer) so the fallback leg
        # stays warm round over round.
        discovered = int(sort_ck.metrics().get("sort_lanes_rung", 0) or 0)
        if discovered:
            store_knobs(
                KNOB_CACHE_DIR, key, {"sort_lanes": discovered},
                golden_unique=golden,
            )
        else:
            discovered = int(sort_ck.metrics()["sort_lanes"])
        s_sort = sort_ck.trace_summary()
        s_sl = sl_ck.trace_summary()
        share_sort = s_sort["wave_breakdown_frac"].get("dedup", 0.0)
        share_sl = s_sl["wave_breakdown_frac"].get("dedup", 0.0)
        bytes_sort = s_sort["bytes"]["dedup"]
        bytes_sl = s_sl["bytes"]["dedup"]
        assert bytes_sl <= bytes_sort, (
            f"{name}: bytes.dedup did not drop under the sortless "
            f"election: {bytes_sl} vs {bytes_sort}"
        )
        out[name] = {
            "sort_lanes_rung": discovered,
            "rung_cached": "sort_lanes" in cached,
            "dedup_share_sort": round(share_sort, 4),
            "dedup_share_sortless": round(share_sl, 4),
            "bytes_dedup_sort": int(bytes_sort),
            "bytes_dedup_sortless": int(bytes_sl),
            "bytes_dedup_ratio": round(bytes_sl / max(1, bytes_sort), 4),
            "bottleneck_sort": s_sort["bottleneck_phase"],
            "bottleneck_sortless": s_sl["bottleneck_phase"],
            "sec_sort": round(sort_dt, 2),
            "sec_sortless": round(sl_dt, 2),
        }
        log(
            f"dedup: {name} sort-rung={discovered} share "
            f"{share_sort:.3f} -> sortless {share_sl:.3f}, bytes.dedup "
            f"{bytes_sort} -> {bytes_sl} "
            f"({out[name]['bytes_dedup_ratio']}x), bottleneck "
            f"{s_sort['bottleneck_phase']} -> {s_sl['bottleneck_phase']}"
        )
    record["dedup_phase"] = out
    # Trajectory keys (obs/report.py picks dedup_share off the round):
    # the duplicate-heavy gauge's sortless numbers.
    record["dedup_share"] = out["paxos_check_2"]["dedup_share_sortless"]
    record["bytes_dedup"] = out["paxos_check_2"]["bytes_dedup_sortless"]


def phase_step(record: dict) -> None:
    """Step-geometry rung regression phase (ISSUE 14): 2pc(4) — the
    LOW-density gauge, where the candidate-lane scan over the
    worst-case ``B = max_frontier × max_actions`` was 56% of wave time
    after r08 moved the bottleneck off dedup — traced twice on the
    sortless default at a deliberately worst-case-sized chunk (the
    production stance: buffers sized for the biggest level, live
    levels a fraction of it): once PINNED past the full chunk
    (step_lanes past max_frontier clamps to the pre-ladder full-width
    scan and disarms the frontier tuner), once at the adaptive step
    rung warm-started from the knob cache — both golden-gated at 1,568
    and fingerprint-equality-gated against each other.  Reported: the
    traced `wave_breakdown` step share and modeled `bytes.step` for
    both legs, the discovered rung (folded back through the knob cache
    for the next round), and the byte ratio.  The top-level
    `step_share`/`bytes_step` keys are what the trajectory report
    tracks per round."""
    import numpy as np

    golden = 1_568  # 2pc(4), pinned by tests/test_tpu_wavefront.py
    if budget_remaining() < 420.0:
        record["step_skipped"] = (
            f"global time budget too low ({budget_remaining():.0f}s left)"
        )
        log(f"step: {record['step_skipped']}")
        return
    base = dict(capacity=1 << 13, max_frontier=1 << 12)
    key = _knob_key("twophase_check_4_step_rung")
    cached = load_knobs(KNOB_CACHE_DIR, key) or {}

    def spawn(step_lanes):
        kw = dict(base)
        if step_lanes is not None:
            kw["step_lanes"] = step_lanes
        return _twophase(4).checker().spawn_tpu(trace=True, **kw)

    def traced_leg(step_lanes):
        run_device(lambda: spawn(step_lanes))  # warm the phase programs
        ck, dt = run_device_timed(lambda: spawn(step_lanes))
        unique = ck.unique_state_count()
        assert unique == golden, (
            f"step phase golden mismatch: unique={unique} != {golden}"
        )
        return ck, dt

    full_ck, full_dt = traced_leg(1 << 30)  # clamps to the full chunk
    rung_ck, rung_dt = traced_leg(cached.get("step_lanes"))
    assert np.array_equal(
        full_ck.discovered_fingerprints(),
        rung_ck.discovered_fingerprints(),
    ), "step-rung run diverged from the fixed-geometry discovery set"
    # Persist the PINNED rung only (step_lanes_rung; 0 = the run never
    # tuned off the full chunk — caching the full width would pin the
    # next round's adaptive leg and measure nothing).
    discovered = int(rung_ck.metrics().get("step_lanes_rung", 0) or 0)
    if discovered:
        store_knobs(
            KNOB_CACHE_DIR, key, {"step_lanes": discovered},
            golden_unique=golden,
        )
    else:
        discovered = int(rung_ck.metrics()["step_lanes"])
    s_full = full_ck.trace_summary()
    s_rung = rung_ck.trace_summary()
    share_full = s_full["wave_breakdown_frac"].get("step", 0.0)
    share_rung = s_rung["wave_breakdown_frac"].get("step", 0.0)
    bytes_full = s_full["bytes"]["step"]
    bytes_rung = s_rung["bytes"]["step"]
    assert bytes_rung <= bytes_full, (
        f"bytes.step did not drop with the rung: {bytes_rung} vs "
        f"{bytes_full}"
    )
    record["step_phase"] = {
        "workload": "twophase_check_4",
        "step_lanes_full": int(full_ck.metrics()["step_lanes"]),
        "step_lanes_rung": discovered,
        "rung_cached": "step_lanes" in cached,
        "step_share_full": round(share_full, 4),
        "step_share_rung": round(share_rung, 4),
        "bytes_step_full": int(bytes_full),
        "bytes_step_rung": int(bytes_rung),
        "bytes_step_ratio": round(bytes_rung / max(1, bytes_full), 4),
        "bottleneck_full": s_full["bottleneck_phase"],
        "bottleneck_rung": s_rung["bottleneck_phase"],
        "sec_full": round(full_dt, 2),
        "sec_rung": round(rung_dt, 2),
    }
    # Trajectory keys (obs/report.py picks step_share off the round).
    record["step_share"] = round(share_rung, 4)
    record["bytes_step"] = int(bytes_rung)
    log(
        f"step: 2pc(4) rung={discovered} share {share_full:.3f} -> "
        f"{share_rung:.3f}, bytes.step {bytes_full} -> {bytes_rung} "
        f"({record['step_phase']['bytes_step_ratio']}x), bottleneck "
        f"{s_full['bottleneck_phase']} -> {s_rung['bottleneck_phase']}"
    )


def phase_denominator_native(record: dict) -> None:
    """Honest-denominator bound (VERDICT r5 weak #9): the single-threaded
    C++ hot-loop BFS in native/stateright_core.cpp on direct 2pc —
    successor generation + fingerprint + dedup only, NO property
    evaluation — so the number is an UPPER bound on a native
    single-thread checker's inner loop.  README's vs_baseline framing
    cites this phase.  Gated on the reference golden (2pc(5) = 8,832)
    before any rate is posted; the measured workload is the suite's
    biggest pinned golden when the budget allows."""
    from stateright_tpu.ops.native import available, twophase_bfs_native

    if not available():
        record["denominator_native"] = {
            "error": "no C++ toolchain for the native core"
        }
        return
    gate = twophase_bfs_native(5)
    assert gate["unique_states"] == 8_832, (
        f"native 2pc(5) unique={gate['unique_states']} != 8832"
    )
    if budget_remaining() > 900.0:
        n, want = 10, 61_515_776  # the suite's 2pc_check_10 pin
    else:
        n, want = 8, None  # self-measured scale point, no golden exists
    t0 = time.time()
    r = twophase_bfs_native(n)
    dt = time.time() - t0
    if want is not None and r["unique_states"] != want:
        raise AssertionError(
            f"native 2pc({n}) unique={r['unique_states']} != {want}"
        )
    record["denominator_native"] = {
        "workload": f"2pc_check_{n}",
        "impl": (
            "single-thread C++ hot-loop BFS (successor gen + fingerprint "
            "+ dedup; no property evaluation, no paths)"
        ),
        "unique_states": r["unique_states"],
        "golden_gated": want is not None,
        "sec": round(dt, 2),
        "unique_states_per_sec": round(r["unique_states"] / dt, 1),
        "note": (
            "upper bound on a native single-thread checker's inner "
            "loop; multiply by core count for an optimistic parallel "
            "bound (the reference's Rust checker also evaluates "
            "properties and tracks paths, which this loop omits)"
        ),
    }
    log(
        f"denominator_native: 2pc({n}) {r['unique_states']} unique in "
        f"{dt:.2f}s = {r['unique_states'] / dt:.0f} uniq/s (C++ 1 thread)"
    )


def phase_serving(record: dict) -> None:
    """Warm-vs-cold serving phase (docs/SERVING.md): submit the same
    workload twice through the checking service's scheduler — the cold
    job pays compile + auto-tune discovery, the warm one reuses the
    process's compiled programs and the first job's cached knobs.  The
    measured reduction is the service's warmup story; both runs are
    golden-gated (2pc rm=5 = 8,832, reference examples/2pc.rs:158-159)
    and the reuse counters are asserted, so a silently-cold second job
    fails the phase instead of posting a hollow number."""
    import tempfile

    from stateright_tpu.serve import CheckService

    # A fresh knob dir per round: the COLD job must genuinely discover.
    svc = CheckService(
        journal=None,
        knob_cache_dir=tempfile.mkdtemp(prefix="bench-serving-knobs-"),
    )
    try:
        spec = {"workload": "twophase", "n": 5, "engine": "tpu"}
        jobs = []
        for leg in ("cold", "warm"):
            job = svc.submit(dict(spec))
            if not job.wait(timeout=max(120.0, budget_remaining())):
                raise AssertionError(f"serving {leg} job never finished")
            assert job.state == "done", (
                f"serving {leg} job {job.state}: {job.error}"
            )
            u = job.result["unique_state_count"]
            assert u == SYM_UNIQUE_FULL, (
                f"serving {leg} golden mismatch: unique={u} != "
                f"{SYM_UNIQUE_FULL}"
            )
            jobs.append(job)
        cold, warm = jobs
        assert warm.result["knob_cache_hit"], (
            "second identical job missed the knob cache"
        )
        assert warm.result["program_cache_hits_delta"] > 0, (
            "second identical job compiled instead of reusing programs"
        )
        m = svc.metrics()
        record["serving"] = {
            "workload": "2pc_check_5",
            "cold_sec": cold.result["elapsed_sec"],
            "warm_sec": warm.result["elapsed_sec"],
            "warmup_saved_sec": round(
                cold.result["elapsed_sec"] - warm.result["elapsed_sec"], 3
            ),
            "knob_cache_hit_second": warm.result["knob_cache_hit"],
            "program_cache_hits_second":
                warm.result["program_cache_hits_delta"],
            "knob_cache_hits": m["knob_cache_hits"],
            "jobs_completed": m["jobs_completed"],
        }
        log(
            f"serving: 2pc(5) cold {cold.result['elapsed_sec']:.2f}s -> "
            f"warm {warm.result['elapsed_sec']:.2f}s "
            f"(knob cache hit, {warm.result['program_cache_hits_delta']} "
            "program-cache hits)"
        )
    finally:
        svc.scheduler.shutdown()


FLEET_BOUNDS = tuple(range(5, 13))  # 8 gang-compatible grid walks


def phase_fleet(record: dict) -> None:
    """Fleet gang-batching phase (fleet/, docs/SERVING.md "Fleet
    mode"): the same 8 gang-compatible jobs — one workload family,
    differing constants — drained twice through a real fleet worker,
    once serialized solo (``gang_max=1``: every job compiles its own
    constant-baked program, the pre-fleet cost model) and once
    gang-batched (one program, constants as data, one device dispatch
    per wave).  The GOLDEN GATE is verdict equality: every job's
    unique/state counts, depth, property rows, and discoveries must
    match between the two drains AND the known (bound+1)^2 closed form,
    or no rate is posted.  The gauge is gang-batched jobs/sec over the
    serialized baseline — the fleet's reason to exist on small jobs."""
    import tempfile

    from stateright_tpu.fleet import FleetStore, FleetWorker
    from stateright_tpu.serve.jobs import JobSpec

    if budget_remaining() < 120.0:
        raise AssertionError(
            f"global time budget too low ({budget_remaining():.0f}s left)"
        )

    def drain(gang_max: int):
        root = tempfile.mkdtemp(prefix=f"bench-fleet-g{gang_max}-")
        store = FleetStore(root)
        ids = [
            store.submit(JobSpec.from_dict(
                {"workload": "grid_walk", "n": b, "engine": "tpu"}
            ))
            for b in FLEET_BOUNDS
        ]
        worker = FleetWorker(root, poll_interval=0.005,
                             gang_max=gang_max)
        t0 = time.perf_counter()
        worker.run(once=True)
        elapsed = time.perf_counter() - t0
        view = store.fold()
        results = {}
        for jid, b in zip(ids, FLEET_BOUNDS):
            assert view.jobs[jid]["state"] == "done", (
                f"fleet job (bound={b}, gang_max={gang_max}) "
                f"{view.jobs[jid]['state']}: {view.jobs[jid]['error']}"
            )
            results[b] = store.read_result(jid)
        return elapsed, results, view

    solo_sec, solo_results, _ = drain(gang_max=1)
    gang_sec, gang_results, gang_view = drain(gang_max=8)
    assert gang_view.counters["gang_dispatches"] >= 1, (
        "gang drain never gang-batched"
    )
    occupancy = (
        gang_view.counters["gang_jobs_batched"]
        / gang_view.counters["gang_dispatches"]
    )

    # The golden gate: per-job verdicts bit-equal across drains and
    # matching the closed form — a fast wrong answer posts nothing.
    for b in FLEET_BOUNDS:
        for key in ("unique_state_count", "state_count", "max_depth",
                    "properties", "violation", "discoveries"):
            assert solo_results[b][key] == gang_results[b][key], (
                f"fleet verdict mismatch (bound={b}, {key}): "
                f"{solo_results[b][key]!r} != {gang_results[b][key]!r}"
            )
        assert gang_results[b]["unique_state_count"] == (b + 1) ** 2, (
            f"fleet golden mismatch (bound={b}): "
            f"{gang_results[b]['unique_state_count']} != {(b + 1) ** 2}"
        )

    speedup = solo_sec / gang_sec if gang_sec > 0 else 0.0
    jobs = len(FLEET_BOUNDS)
    assert speedup >= 2.0, (
        f"gang batching only {speedup:.2f}x over serialized solo "
        f"({solo_sec:.2f}s -> {gang_sec:.2f}s for {jobs} jobs); "
        "the fleet gauge demands >= 2x"
    )
    record["fleet"] = {
        "workload": "grid_walk_family",
        "jobs": jobs,
        "solo_sec": round(solo_sec, 3),
        "gang_sec": round(gang_sec, 3),
        "solo_jobs_per_sec": round(jobs / solo_sec, 2),
        "gang_jobs_per_sec": round(jobs / gang_sec, 2),
        "gang_occupancy": round(occupancy, 2),
        "gang_dispatches": gang_view.counters["gang_dispatches"],
    }
    # Top-level gauge the trajectory table tracks (obs/report.py).
    record["gang_speedup"] = round(speedup, 2)
    log(
        f"fleet: {jobs} gang-compatible jobs, serialized {solo_sec:.2f}s "
        f"-> gang {gang_sec:.2f}s ({speedup:.1f}x, occupancy "
        f"{occupancy:.1f}); verdicts bit-equal across both drains"
    )


TIERED_RM = 5
TIERED_BUDGET_MB = 0.05  # -> 4096-slot hot tier vs 8,832 uniques


def phase_tiered(record: dict) -> None:
    """Tiered out-of-core phase (docs/TIERED.md): `2pc check 5` (the
    reference-pinned 8,832 golden) unconstrained vs under a deliberately
    small `memory_budget_mb` that forces multiple hot-tier evictions.
    The VERDICT-EQUALITY GATE is the phase's point: the budget run's
    `discovered_fingerprints()` must be bit-identical to the
    unconstrained engine's — a tiered run that merely lands the right
    COUNT could still have swapped states.  Reported: both uniq/s, the
    out-of-core overhead ratio, and the spill/cold-probe accounting."""
    import numpy as np

    from stateright_tpu.models.twophase import TwoPhaseSys

    knobs = dict(max_frontier=1 << 10)

    def mk_plain():
        return TwoPhaseSys(rm_count=TIERED_RM).checker().spawn_tpu(
            capacity=1 << 15, **knobs
        )

    def mk_tiered():
        return TwoPhaseSys(rm_count=TIERED_RM).checker().spawn_tpu_tiered(
            memory_budget_mb=TIERED_BUDGET_MB, **knobs
        )

    log("tiered: warming programs...")
    run_device(mk_plain)
    ck0, dt0 = run_device_timed(mk_plain)
    u0 = ck0.unique_state_count()
    assert u0 == SYM_UNIQUE_FULL, (
        f"tiered phase golden mismatch (unconstrained): {u0}"
    )
    run_device(mk_tiered)
    ck1, dt1 = run_device_timed(mk_tiered)
    u1 = ck1.unique_state_count()
    assert u1 == SYM_UNIQUE_FULL, (
        f"tiered phase golden mismatch (budget-constrained): {u1}"
    )
    m = ck1.metrics()
    assert m.get("spills", 0) >= 2, (
        f"the budget did not force evictions (spills={m.get('spills')})"
    )
    # THE gate: identical discovery SETS, not just counts.
    assert np.array_equal(
        ck0.discovered_fingerprints(), ck1.discovered_fingerprints()
    ), "tiered discovery set diverged from the unconstrained engine"
    record["tiered"] = {
        "workload": f"2pc_check_{TIERED_RM}",
        "unique_states": u1,
        "memory_budget_mb": TIERED_BUDGET_MB,
        "hot_capacity": m["capacity"],
        "sec_unconstrained": round(dt0, 3),
        "uniq_per_sec_unconstrained": round(u0 / dt0, 1),
        "sec_tiered": round(dt1, 3),
        "uniq_per_sec_tiered": round(u1 / dt1, 1),
        "out_of_core_overhead": round(dt1 / dt0, 2),
        "spills": m["spills"],
        "spill_bytes_total": m.get("spill_bytes_total", 0),
        "cold_runs": m["cold_runs"],
        "cold_entries": m["cold_entries"],
        "cold_probe_passes_total": m.get("cold_probe_passes_total", 0),
        "cold_probe_bytes_total": m.get("cold_probe_bytes_total", 0),
        "verdict_equal": True,
    }
    log(
        f"tiered: 2pc({TIERED_RM}) {u1} unique bit-identical under a "
        f"{TIERED_BUDGET_MB} MB hot tier: {u0 / dt0:.0f} -> "
        f"{u1 / dt1:.0f} uniq/s ({dt1 / dt0:.2f}x), "
        f"{m['spills']} spills, {m['cold_entries']} cold entries"
    )


def phase_tiered_sharded(record: dict) -> None:
    """Composed tiered × sharded phase (docs/TIERED.md "Composing the
    levers"): `2pc check 5` (the reference-pinned 8,832 golden) on a
    1-device mesh, unconstrained sharded vs tiered-sharded under a
    spill-forcing PER-SHARD budget.  Same verdict-equality gate as the
    tiered phase — the budget run's `discovered_fingerprints()` must be
    bit-identical to the unconstrained engine's — plus the per-shard
    spill/cold accounting the composed engine adds."""
    import numpy as np
    import jax

    from stateright_tpu.models.twophase import TwoPhaseSys

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("shards",))
    knobs = dict(chunk_size=1 << 10)

    def mk_plain():
        return TwoPhaseSys(rm_count=TIERED_RM).checker().spawn_tpu_sharded(
            mesh=mesh, capacity=1 << 15, **knobs
        )

    def mk_ts():
        return (
            TwoPhaseSys(rm_count=TIERED_RM).checker()
            .spawn_tpu_tiered_sharded(
                mesh=mesh, memory_budget_mb=TIERED_BUDGET_MB, **knobs
            )
        )

    log("tiered_sharded: warming programs...")
    run_device(mk_plain)
    ck0, dt0 = run_device_timed(mk_plain)
    u0 = ck0.unique_state_count()
    assert u0 == SYM_UNIQUE_FULL, (
        f"tiered_sharded golden mismatch (unconstrained): {u0}"
    )
    run_device(mk_ts)
    ck1, dt1 = run_device_timed(mk_ts)
    u1 = ck1.unique_state_count()
    assert u1 == SYM_UNIQUE_FULL, (
        f"tiered_sharded golden mismatch (budget-constrained): {u1}"
    )
    m = ck1.metrics()
    assert m.get("spills", 0) >= 2, (
        f"the per-shard budget did not force evictions "
        f"(spills={m.get('spills')})"
    )
    # THE gate: identical discovery SETS, not just counts.
    assert np.array_equal(
        ck0.discovered_fingerprints(), ck1.discovered_fingerprints()
    ), "tiered-sharded discovery set diverged from the unconstrained engine"
    record["tiered_sharded"] = {
        "workload": f"2pc_check_{TIERED_RM}",
        "unique_states": u1,
        "n_shards": int(mesh.devices.size),
        "memory_budget_mb_per_shard": TIERED_BUDGET_MB,
        "sec_unconstrained": round(dt0, 3),
        "uniq_per_sec_unconstrained": round(u0 / dt0, 1),
        "sec_tiered_sharded": round(dt1, 3),
        "uniq_per_sec_tiered_sharded": round(u1 / dt1, 1),
        "out_of_core_overhead": round(dt1 / dt0, 2),
        "spills": m["spills"],
        "cold_runs": m["cold_runs"],
        "cold_entries": m["cold_entries"],
        "verdict_equal": True,
    }
    log(
        f"tiered_sharded: 2pc({TIERED_RM}) {u1} unique bit-identical "
        f"under a {TIERED_BUDGET_MB} MB/shard hot tier "
        f"({int(mesh.devices.size)}-shard mesh): {u0 / dt0:.0f} -> "
        f"{u1 / dt1:.0f} uniq/s ({dt1 / dt0:.2f}x), "
        f"{m['spills']} spills, {m['cold_entries']} cold entries"
    )


RECHECK_RM = 4  # 2pc(4): 1,568 uniques — big enough to time, fast cold
RECHECK_REPEATS = 5  # median over this many re-eval legs
RECHECK_WIDEN_FROM, RECHECK_WIDEN_TO = 40, 44  # GridWalk bounds


def phase_recheck(record: dict) -> None:
    """Incremental re-checking phase (incr/, docs/INCREMENTAL.md): the
    success metric ROADMAP item #5 names — MEDIAN RE-CHECK LATENCY ON A
    ONE-LINE MODEL EDIT, tracked in the trajectory like warm-vs-cold
    serving.  Three legs, all verdict-gated:

    - cold: 2pc(RM) journaled into a fresh store (the baseline the
      re-check is measured against);
    - property edit: the TwoPhaseEdited fixture (one property appended,
      codec/constants identical) re-checked RECHECK_REPEATS times —
      every leg must classify property_only, dispatch ZERO exploration
      waves, and produce a verdict identical to a from-scratch run of
      the edited model;
    - constant widening: GridWalk's bound raised — the seeded run's
      discovered_fingerprints() must be bit-identical to an
      unconstrained cold run at the new bound.
    """
    import statistics
    import tempfile

    import numpy as np

    from stateright_tpu.incr import incremental_check
    from stateright_tpu.models.fixtures import GridWalk, TwoPhaseEdited
    from stateright_tpu.models.twophase import TwoPhaseSys
    from stateright_tpu.runtime.journal import read_journal

    store_dir = tempfile.mkdtemp(prefix="bench-recheck-store-")
    jpath = os.path.join(store_dir, "journal.jsonl")
    knobs = dict(capacity=1 << 14, max_frontier=1 << 9)
    golden = 1_568  # 2pc(4), pinned by tests/test_tpu_wavefront.py

    def waves() -> int:
        return sum(
            1 for e in read_journal(jpath) if e.get("event") == "wave"
        )

    # Leg 1: the cold baseline, journaled into the store.
    ck, info = incremental_check(
        TwoPhaseSys(rm_count=RECHECK_RM).checker(), store_dir,
        engine_kwargs=dict(knobs), journal=jpath,
    )
    assert info["mode"] == "cold", info
    assert ck.unique_state_count() == golden, ck.unique_state_count()
    cold_sec = info["sec"]

    # Reference verdict for the edited model: a from-scratch run.
    ref = run_device(
        lambda: TwoPhaseEdited.build(RECHECK_RM).checker().spawn_tpu(
            **knobs
        )
    )
    assert ref.unique_state_count() == golden

    # Leg 2: the one-line property edit, re-checked repeatedly
    # (store_result=False keeps every leg a genuine re-eval instead of
    # a verdict hit on the first leg's stored entry).
    secs = []
    for _ in range(RECHECK_REPEATS):
        w0 = waves()
        ck2, info2 = incremental_check(
            TwoPhaseEdited.build(RECHECK_RM).checker(), store_dir,
            engine_kwargs=dict(knobs), journal=jpath, store_result=False,
        )
        assert info2["mode"] == "property_only", info2
        assert waves() == w0, "property re-check dispatched exploration waves"
        secs.append(info2["sec"])
    assert sorted(ck2.discoveries()) == sorted(ref.discoveries())
    for name, path in ref.discoveries().items():
        assert ck2.discoveries()[name] == path, f"path diverged: {name}"
    assert ck2.state_count() == ref.state_count()
    median_sec = round(statistics.median(secs), 4)

    # Leg 3: constant widening, fingerprint-equality gated.
    ck3, info3 = incremental_check(
        GridWalk(bound=RECHECK_WIDEN_FROM).checker(), store_dir,
        engine_kwargs=dict(capacity=1 << 13, max_frontier=1 << 7),
        journal=jpath,
    )
    assert info3["mode"] == "cold", info3
    t_widen0 = time.time()
    ck4, info4 = incremental_check(
        GridWalk(bound=RECHECK_WIDEN_TO).checker(), store_dir,
        engine_kwargs=dict(capacity=1 << 13, max_frontier=1 << 7),
        journal=jpath,
    )
    widen_sec = time.time() - t_widen0
    assert info4["mode"] == "constant_widening", info4
    cold_widen = run_device(
        lambda: GridWalk(bound=RECHECK_WIDEN_TO).checker().spawn_tpu(
            capacity=1 << 13, max_frontier=1 << 7
        )
    )
    assert np.array_equal(
        ck4.discovered_fingerprints(),
        cold_widen.discovered_fingerprints(),
    ), "seeded widening diverged from the unconstrained cold run"

    record["recheck"] = {
        "workload": f"2pc_check_{RECHECK_RM}",
        "cold_sec": round(cold_sec, 3),
        "recheck_median_sec": median_sec,
        "recheck_secs": [round(s, 4) for s in secs],
        "speedup_vs_cold": round(cold_sec / max(median_sec, 1e-9), 1),
        "zero_waves": True,
        "verdict_equal": True,
        "widen_workload": (
            f"gridwalk_{RECHECK_WIDEN_FROM}_to_{RECHECK_WIDEN_TO}"
        ),
        "widen_seeded_states": info4.get("seeded_states"),
        "widen_sec": round(widen_sec, 3),
        "widen_unique": ck4.unique_state_count(),
        "widen_fingerprints_equal": True,
    }
    # Top-level gauge the trajectory table tracks (obs/report.py).
    record["recheck_median_sec"] = median_sec
    log(
        f"recheck: 2pc({RECHECK_RM}) cold {cold_sec:.2f}s -> one-line "
        f"property edit median {median_sec:.3f}s over {RECHECK_REPEATS} "
        f"legs ({cold_sec / max(median_sec, 1e-9):.0f}x), zero waves; "
        f"widen {RECHECK_WIDEN_FROM}->{RECHECK_WIDEN_TO} seeded "
        f"{info4.get('seeded_states')} states, fingerprints bit-equal"
    )


ENSEMBLE_MEMBERS = 1024
ENSEMBLE_STEPS = 48
ENSEMBLE_SEED = 3
ENSEMBLE_CHAOS = '{"default": {"drop": 0.1, "reorder": 0.05}}'


def phase_ensemble(record: dict) -> None:
    """Chaos-ensemble phase (ensemble/engine.py,
    docs/CHAOS_ENSEMBLES.md): one device dispatch sweeping
    ENSEMBLE_MEMBERS independent fault schedules over the ABD workload
    with the known-violating ``skip_ack`` hook — the GOLDEN GATE: the
    sweep must find a failing seed, shrink it, and host-replay it to a
    rejected history, or the posted throughput is hollow.  Metrics: raw
    schedules/sec for the dispatch (includes the one-time compile — the
    honest single-dispatch cost) and time-to-first-failing-seed."""
    from stateright_tpu.ensemble import run_ensemble

    if budget_remaining() < 240.0:
        raise AssertionError(
            f"global time budget too low ({budget_remaining():.0f}s left)"
        )

    result = run_ensemble(
        members=ENSEMBLE_MEMBERS,
        seed=ENSEMBLE_SEED,
        chaos=ENSEMBLE_CHAOS,
        steps=ENSEMBLE_STEPS,
        fault="skip_ack",
        shrink=True,
        replay=True,
    )
    assert result.dispatches == 1
    assert len(result.failing) > 0, (
        "the known-violating skip_ack ensemble found no failing seed"
    )
    assert result.confirmed, (
        "no device-found failing seed replayed to a host-rejected history"
    )
    assert result.repro is not None and result.repro["steps"] <= ENSEMBLE_STEPS

    record["ensemble"] = {
        "workload": "abd_skip_ack",
        "members": result.members,
        "steps": result.steps,
        "dispatch_sec": round(result.elapsed_sec, 3),
        "schedules_per_sec": round(result.schedules_per_sec, 1),
        "ttff_sec": result.ttff_sec,
        "failing": len(result.failing),
        "confirmed": len(result.confirmed),
        "shrink_steps": result.shrink_steps,
        "repro_steps": result.repro["steps"],
        "repro_seed": result.repro["seed"],
    }
    # Top-level gauge the trajectory table tracks (obs/report.py).
    record["ensemble_schedules_per_sec"] = round(
        result.schedules_per_sec, 1
    )
    log(
        f"ensemble: {result.members} schedules in one dispatch, "
        f"{result.schedules_per_sec:.0f} sched/s, "
        f"{len(result.failing)} failing, ttff {result.ttff_sec}s; "
        f"shrunk to {result.repro['steps']} steps and host-replay "
        "REJECTED (fault attribution journaled)"
    )


def _force_single_phase() -> bool:
    """Disable the two-phase expansion path (engine falls back to the
    single-phase step kernel).  Returns True if anything changed."""
    from stateright_tpu.models.paxos_compiled import PaxosCompiled

    if hasattr(PaxosCompiled, "step_valid"):
        del PaxosCompiled.step_valid
        return True
    return False


def phase_smoke(threads: int) -> dict:
    """Phase 0: tiny reference golden on default knobs + a minimal valid
    record, emitted BEFORE the expensive headline warm-up is attempted
    (the round-4 artifact was zeroed by a warm-up crash).  Even phase 0
    gets the single-phase fallback: a deterministic two-phase regression
    must still produce an artifact, not a zero-JSON exit."""
    def smoke_run():
        run_device(lambda: paxos_model(2).checker().spawn_tpu())  # compile
        ck, dt = run_device_timed(
            lambda: paxos_model(2).checker().spawn_tpu()
        )
        unique = ck.unique_state_count()
        if unique != SMOKE_UNIQUE:
            # Inside the fallback scope: a silently-wrong two-phase run
            # must trigger the single-phase retry, same as a crash.
            raise AssertionError(
                f"smoke paxos2 unique={unique} != {SMOKE_UNIQUE}"
            )
        return ck, dt

    fallback_reason = None
    try:
        ck, dt = smoke_run()
    except Exception as exc:
        # Try the single-phase fallback once: on a two-phase regression
        # it saves the artifact.  The record carries the reason so a
        # fallback run is never mistaken for a healthy two-phase
        # measurement.
        if not _force_single_phase():
            raise
        fallback_reason = f"{type(exc).__name__}: {exc}"[:300]
        log("smoke: device run failed; retrying single-phase:")
        log(traceback.format_exc(limit=5))
        ck, dt = smoke_run()
    unique = ck.unique_state_count()
    t0 = time.time()
    host = (
        paxos_model(2).checker().threads(threads).timeout(120).spawn_bfs()
        .join()
    )
    host_dt = time.time() - t0
    host_rate = host.unique_state_count() / host_dt
    rate = unique / dt
    log(f"smoke: paxos2 tpu {unique} unique in {dt:.2f}s (warm) = "
        f"{rate:.0f} uniq/s; host {host_rate:.0f} uniq/s")
    record = {
        "metric": "paxos2_smoke_unique_states_per_sec",
        "value": round(rate, 1),
        "unit": "unique states/sec",
        "vs_baseline": round(rate / host_rate, 2),
        "phase": "smoke0",
        "note": (
            "fallback record emitted before the headline phases; a later "
            "line (paxos3 headline) supersedes this one"
        ),
    }
    if fallback_reason:
        record["single_phase_reason"] = fallback_reason
    emit(record)
    return record


def phase_headline(record: dict, threads: int) -> dict:
    """Phase 1: `paxos check 3` — default-knob auto-tune discovery, then
    best-of-N measured at the discovered sizes.  Falls back to the
    single-phase step kernel if the two-phase path fails.  Returns the
    tuned kwargs for later phases."""
    from stateright_tpu.models.paxos_compiled import PaxosCompiled

    # False already here if the smoke phase had to fall back.
    two_phase = hasattr(PaxosCompiled, "step_valid")
    single_phase_reason = record.get("single_phase_reason")
    extras: dict = {}
    try:
        discovery, tuned, samples, knobs_cached = discover_and_measure(
            "headline", lambda: paxos_model(3), GOLDEN_UNIQUE, GOLDEN_DEPTH,
            extras=extras,
        )
    except Exception as exc:
        # Try single-phase once (a two-phase regression still yields a
        # headline).  The record says why, so a fallback run is never
        # mistaken for a healthy two-phase measurement.
        if not _force_single_phase():
            raise
        two_phase = False
        single_phase_reason = f"{type(exc).__name__}: {exc}"[:300]
        log("headline: device run failed; retrying single-phase:")
        log(traceback.format_exc(limit=5))
        discovery, tuned, samples, knobs_cached = discover_and_measure(
            "headline", lambda: paxos_model(3), GOLDEN_UNIQUE, GOLDEN_DEPTH,
            extras=extras,
        )
    best = min(samples)
    tpu_rate = GOLDEN_UNIQUE / best

    log(f"host BFS denominator ({HOST_TIME_SLICE:.0f}s slice, "
        f"threads={threads})...")
    t0 = time.time()
    host = (
        paxos_model(3)
        .checker()
        .threads(threads)
        .timeout(HOST_TIME_SLICE)
        .spawn_bfs()
        .join()
    )
    host_dt = time.time() - t0
    host_rate = host.unique_state_count() / host_dt
    log(
        f"host: {host.unique_state_count()} unique in {host_dt:.2f}s = "
        f"{host_rate:.0f} uniq/s"
    )

    record.clear()
    record.update({
        "metric": "paxos3_unique_states_per_sec",
        "value": round(tpu_rate, 1),
        "unit": "unique states/sec",
        "vs_baseline": round(tpu_rate / host_rate, 2),
        "denominator_unique_states_per_sec": round(host_rate, 1),
        "denominator_impl": (
            "this package's thread-pool BFS (pure Python, GIL-bound)"
        ),
        "denominator_threads": threads,
        # Honest framing (VERDICT r5 weak #5): the ratio is a
        # same-machine, same-language comparison.  The reference's
        # native Rust checker would be a far stronger denominator on a
        # many-core box; vs_baseline is NOT a cross-implementation claim.
        "denominator_caveat": (
            "pure-Python GIL-bound BFS on this box; the reference's "
            "native Rust checker would be orders faster — vs_baseline "
            "is a same-machine/same-language ratio, not a "
            "cross-implementation claim"
        ),
        "tpu_unique_states": GOLDEN_UNIQUE,
        "tpu_wallclock_sec": round(best, 2),
        "samples_sec": [round(s, 2) for s in samples],
        "tpu_warmup_sec": round(discovery, 1),
        "tuned_kwargs": {k: int(v) for k, v in tuned.items()},
        "tuned_kwargs_cached": knobs_cached,
        "two_phase": two_phase,
    })
    if "host_share" in extras:
        # The host-tail gauge (obs/timeline.py): host / (host + device)
        # loop time of the last measured run — the trajectory table
        # tracks it so a creeping host tail is visible across rounds
        # even while uniq/s holds.
        record["host_share"] = extras["host_share"]
    if single_phase_reason:
        record["single_phase_reason"] = single_phase_reason
    # The score of record: emitted the moment it exists, so no later phase
    # (or crash) can zero it.
    emit(record)
    return tuned


def phase_trajectory(record: dict) -> None:
    """Cross-round trajectory: render the BENCH_r*.json history (the
    rounds the driver has committed so far) into
    docs/BENCH_TRAJECTORY.md via obs/report.py — closing the "perf
    trajectory lives in seven disconnected artifacts" gap — and fold
    the regression verdict into this round's record.  Host-only and
    milliseconds; a flagged regression is a loud record key, not a
    failure (the HEADLINE golden gates correctness; this gauges
    trend)."""
    import glob as _glob

    from stateright_tpu.obs.report import (
        bench_trajectory, render_trajectory_markdown,
    )

    rounds = sorted(_glob.glob(str(_REPO / "BENCH_r*.json")))
    if not rounds:
        record["trajectory_skipped"] = "no BENCH_r*.json rounds present"
        return
    traj = bench_trajectory(rounds)
    out = _REPO / "docs" / "BENCH_TRAJECTORY.md"
    out.write_text(render_trajectory_markdown(traj), encoding="utf-8")
    record["trajectory_rounds"] = len(traj["rounds"])
    record["trajectory_regressions"] = traj["regressions"]
    log(
        f"trajectory: {len(traj['rounds'])} rounds -> {out}; "
        f"{len(traj['regressions'])} regression(s) flagged"
    )


# Every optional phase, in run order.  Named up front so ANY early exit
# can mark the not-yet-run tail as skipped in the artifact — a partial
# BENCH json must say what is missing, not just stop (the r02/r04 rc=1
# and r05 rc=124 modes all produced artifacts that undercounted what
# was skipped).
OPTIONAL_PHASES = (
    "trajectory",
    "denominator_native",
    "serving",
    "fleet",
    "recheck",
    "ensemble",
    "tiered",
    "tiered_sharded",
    "trace",
    "dedup",
    "step",
    "symmetry",
    "ttfv",
    "sharded_smoke",
    "reference_suite",
)


def main() -> None:
    use_compile_cache()
    import jax

    threads = os.cpu_count() or 1
    log(f"device: {jax.devices()[0]}; host threads: {threads}; "
        f"time budget: {BENCH_TIME_BUDGET:.0f}s")

    # THE ARTIFACT CONTRACT (enforced end to end): once main() is
    # entered, the process always exits 0 with at least one valid JSON
    # line — a phase-0 failure emits an explicit zero-value error
    # record rather than dying with no artifact (the r02/r04 rc=1
    # mode), and every later failure marks the phases it skipped.
    try:
        record = phase_smoke(threads)
    except Exception:
        err = traceback.format_exc()
        log("smoke phase failed; emitting an error artifact:")
        log(err)
        emit({
            "metric": "bench_failed_in_smoke",
            "value": 0.0,
            "unit": "unique states/sec",
            "vs_baseline": 0.0,
            "error": err[-2000:],
            "skipped_phases": ["headline", *OPTIONAL_PHASES],
        })
        return

    # From here on a record exists: any failure must exit 0 so the
    # artifact survives (the last emitted line stays authoritative).
    try:
        tuned = phase_headline(record, threads)
    except Exception:
        err = traceback.format_exc()
        log("headline failed (smoke record stands):")
        log(err)
        record.setdefault("phase_errors", {})["headline"] = err[-1500:]
        record["skipped_phases"] = list(OPTIONAL_PHASES)
        emit(record)
        return
    record["time_budget_sec"] = BENCH_TIME_BUDGET

    # Optional phases — each failure is logged, recorded under
    # phase_errors, and skipped, never fatal; each is gated on the
    # remaining global budget so the process exits 0 with partial
    # results instead of being killed mid-suite.  The reference suite
    # runs last: its big workloads are the ones that have crashed the
    # TPU worker.
    impls = {
        # trajectory and denominator_native are host-only (no device
        # risk) and cheap; trace reuses the headline's tuned sizes.
        "trajectory": phase_trajectory,
        "denominator_native": phase_denominator_native,
        "serving": phase_serving,
        "fleet": phase_fleet,
        "recheck": phase_recheck,
        "ensemble": phase_ensemble,
        "tiered": phase_tiered,
        "tiered_sharded": phase_tiered_sharded,
        "trace": lambda r: phase_trace(r, tuned),
        "dedup": phase_dedup,
        "step": phase_step,
        "symmetry": phase_symmetry,
        "ttfv": lambda r: phase_ttfv(r, threads, tuned),
        "sharded_smoke": phase_sharded_smoke,
        "reference_suite": phase_reference_suite,
    }
    for phase_name in OPTIONAL_PHASES:
        remaining = budget_remaining()
        if remaining < 180.0:
            record.setdefault("budget_skipped_phases", []).append(phase_name)
            log(f"phase {phase_name}: skipped, global time budget "
                f"exhausted ({remaining:.0f}s remaining)")
            emit(record)
            continue
        try:
            impls[phase_name](record)
            # Re-emit after EVERY phase: same headline values, extra keys
            # accreted — if the driver kills the bench mid-suite, the last
            # line still carries every phase that finished.
        except Exception:  # noqa: BLE001 - optional phase, log + continue
            err = traceback.format_exc()
            log(f"optional phase {phase_name} failed "
                "(headline already emitted):")
            log(err)
            record.setdefault("phase_errors", {})[phase_name] = err[-1500:]
        emit(record)


if __name__ == "__main__":
    try:
        main()
    except Exception:  # noqa: BLE001 - the artifact contract: rc=0
        # A truly unexpected escape (main() already catches per-phase):
        # log it, but never turn an emitted artifact into an rc!=0 run.
        log(traceback.format_exc())
    sys.exit(0)
