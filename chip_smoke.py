#!/usr/bin/env python
"""Chip smoke: drive the main path once on a local TPU and check it.

All phases run in this one process: a chip belongs to one process at a
time, so no phase starts a child that needs it.

- headline: ``paxos check 3`` (3 servers, 3 clients, unordered
  nonduplicating network) built as ``check-tpu`` builds it — the spec's
  model and ``tpu_kwargs``, auto-tune doing the sizing.  Gated on the
  golden (1,194,428 unique, depth 28) and on the host engine's discovery
  names.
- parity: ``paxos check 2`` on the chip and, in this same process, the
  same engine on the CPU backend; their ``discovered_fingerprints()``
  must be equal as sets, and the host BFS must count 16,668.
- service: the in-process checking service answers a cold and then a
  warm ``2pc check 3`` job with the host engine's results.

``--chips 4`` runs only the sharded phase: ``paxos check 3`` on
``spawn_tpu_sharded`` over four chips against ``spawn_tpu`` on chip 0.

Every line before the last is a smoke timing from one run, not a
benchmark number.  The last line is the JSON verdict; without a TPU the
script exits non-zero and prints none.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

PAXOS3_UNIQUE, PAXOS3_DEPTH = 1_194_428, 28
# The host engine needs ~10 min for the whole paxos 3 space; its
# discoveries are taken from the exact depth-11 prefix instead
# (tests/test_cross_engine_pin.py pins that prefix at 21,838 states).
PAXOS3_HOST_DEPTH, PAXOS3_HOST_UNIQUE = 11, 21_838
PAXOS2_UNIQUE = 16_668  # reference examples/paxos.rs:328


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Phase:
    """Times one phase and reports its compile seconds and program-cache
    hits from the process-wide metrics registry."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from stateright_tpu.obs.metrics import GLOBAL

        self._compile0 = GLOBAL.get("compile_sec_total", 0.0)
        self._hits0 = GLOBAL.get("program_cache_hits", 0)
        self._t0 = time.perf_counter()
        log(f"[{self.name}] start")
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        from stateright_tpu.obs.metrics import GLOBAL

        log(
            f"[{self.name}] smoke timing (one run, not a benchmark): "
            + json.dumps({
                "wall_sec": round(time.perf_counter() - self._t0, 3),
                "compile_sec_total": round(
                    GLOBAL.get("compile_sec_total", 0.0) - self._compile0, 3
                ),
                "program_cache_hits": (
                    GLOBAL.get("program_cache_hits", 0) - self._hits0
                ),
            })
        )
        return False


def check_tpu_build(n: int):
    """The model and engine kwargs ``check-tpu N`` uses for paxos."""
    from stateright_tpu.serve.workloads import build_model

    model, spec, _ = build_model("paxos", n)
    return model, dict(spec.tpu_kwargs)


def lane_clamp_report(ck) -> dict:
    """Where the tuned geometry sits against the device-safe lane clamp
    (wavefront.max_safe_unique_lanes)."""
    from stateright_tpu.parallel.hashset import unique_buffer_size
    from stateright_tpu.parallel.wavefront import max_safe_unique_lanes

    cm = ck._compiled
    tuned = ck.tuned_kwargs()
    lanes = unique_buffer_size(
        tuned["max_frontier"] * cm.max_actions, tuned["dedup_factor"]
    )
    cap = max_safe_unique_lanes(cm.state_width)
    return {"unique_lanes": lanes, "clamp": cap, "at_clamp": lanes >= cap}


def phase_headline() -> None:
    with Phase("headline paxos check 3"):
        model, kwargs = check_tpu_build(3)
        ck = model.checker().threads(os.cpu_count() or 1).spawn_tpu(
            **kwargs
        ).join()
        unique, depth = ck.unique_state_count(), ck.max_depth()
        log(f"paxos 3: unique={unique} depth={depth} "
            f"discoveries={sorted(ck.discoveries())}")
        log(f"paxos 3: auto-tuned geometry {json.dumps(ck.tuned_kwargs())}")
        log(f"paxos 3: lane clamp {json.dumps(lane_clamp_report(ck))}")
        expect((unique, depth) == (PAXOS3_UNIQUE, PAXOS3_DEPTH),
               f"paxos 3 golden: {unique}/{depth} != "
               f"{PAXOS3_UNIQUE}/{PAXOS3_DEPTH}")
        host_model, _ = check_tpu_build(3)
        host = (
            host_model.checker().threads(os.cpu_count() or 1)
            .target_max_depth(PAXOS3_HOST_DEPTH).spawn_bfs().join()
        )
        expect(host.unique_state_count() == PAXOS3_HOST_UNIQUE,
               f"host paxos 3 depth-{PAXOS3_HOST_DEPTH} prefix: "
               f"{host.unique_state_count()} != {PAXOS3_HOST_UNIQUE}")
        expect(sorted(ck.discoveries()) == sorted(host.discoveries()),
               f"paxos 3 discoveries {sorted(ck.discoveries())} != host "
               f"{sorted(host.discoveries())}")


def phase_parity() -> None:
    import jax
    import numpy as np

    with Phase("parity paxos check 2, chip vs CPU backend"):
        model, kwargs = check_tpu_build(2)
        chip = model.checker().spawn_tpu(**kwargs).join()
        model, kwargs = check_tpu_build(2)
        cpu = model.checker().spawn_tpu(
            device=jax.devices("cpu")[0], **kwargs
        ).join()
        model, _ = check_tpu_build(2)
        host = model.checker().threads(os.cpu_count() or 1).spawn_bfs().join()
        counts = [c.unique_state_count() for c in (chip, cpu, host)]
        log(f"paxos 2: unique chip/cpu/host = {counts}")
        expect(counts == [PAXOS2_UNIQUE] * 3,
               f"paxos 2 unique {counts} != {PAXOS2_UNIQUE}")
        fp_chip = np.unique(chip.discovered_fingerprints())
        fp_cpu = np.unique(cpu.discovered_fingerprints())
        expect(fp_chip.size == PAXOS2_UNIQUE
               and np.array_equal(fp_chip, fp_cpu),
               "paxos 2 discovered_fingerprints differ between chip and CPU")
        expect(sorted(chip.discoveries()) == sorted(host.discoveries()),
               "paxos 2 discoveries differ from the host engine")


def phase_service() -> None:
    from stateright_tpu.serve import DONE, CheckService
    from stateright_tpu.serve.workloads import build_model

    with Phase("service 2pc check 3, cold then warm"):
        model, _, _ = build_model("twophase", 3)
        host = model.checker().spawn_bfs().join()
        with tempfile.TemporaryDirectory() as knobs:
            svc = CheckService(knob_cache_dir=knobs)
            try:
                for label in ("cold", "warm"):
                    job = svc.submit({"workload": "twophase", "n": 3})
                    expect(job.wait(600), f"{label} job never finished")
                    expect(job.state == DONE,
                           f"{label} job {job.state}: {job.error}")
                    r = job.result
                    log(f"service {label}: unique={r['unique_state_count']}"
                        f" depth={r['max_depth']} knob_cache_hit="
                        f"{r['knob_cache_hit']} program_cache_hits_delta="
                        f"{r['program_cache_hits_delta']}")
                    expect(
                        (r["unique_state_count"], r["max_depth"])
                        == (host.unique_state_count(), host.max_depth()),
                        f"{label} job counts differ from the host engine",
                    )
                    expect(sorted(r["discoveries"])
                           == sorted(host.discoveries()),
                           f"{label} job discoveries differ from the host")
                expect(r["knob_cache_hit"] is True,
                       "warm job missed the knob cache")
            finally:
                svc.scheduler.shutdown()


def phase_sharded(n_chips: int) -> None:
    import jax
    import numpy as np

    with Phase(f"sharded paxos check 3 over {n_chips} chips vs chip 0"):
        devices = jax.devices()[:n_chips]
        mesh = jax.sharding.Mesh(np.array(devices), ("shards",))
        model, kwargs = check_tpu_build(3)
        # check-tpu --sharded's translation of the spec's kwargs.
        sharded = model.checker().spawn_tpu_sharded(
            mesh=mesh, capacity=kwargs["capacity"],
            chunk_size=kwargs["max_frontier"],
        ).join()
        model, kwargs = check_tpu_build(3)
        single = model.checker().spawn_tpu(
            device=devices[0], **kwargs
        ).join()
        per_shard = sharded.accounting()["unique_per_shard"]
        log(f"sharded: unique={sharded.unique_state_count()} depth="
            f"{sharded.max_depth()} unique_per_shard={per_shard}")
        log(f"single: unique={single.unique_state_count()} depth="
            f"{single.max_depth()}")
        expect((sharded.unique_state_count(), sharded.max_depth())
               == (single.unique_state_count(), single.max_depth())
               == (PAXOS3_UNIQUE, PAXOS3_DEPTH),
               "sharded and single-chip counts or depth differ")
        expect(len(per_shard) == n_chips and min(per_shard) > 0,
               f"a shard holds no states: {per_shard}")
        expect(np.array_equal(np.unique(sharded.discovered_fingerprints()),
                              np.unique(single.discovered_fingerprints())),
               "sharded and single-chip fingerprint sets differ")
        expect(sorted(sharded.discoveries()) == sorted(single.discoveries()),
               "sharded and single-chip discoveries differ")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded phase over four chips",
    )
    args = parser.parse_args(argv)

    from stateright_tpu.runtime.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "this check does not fall back to the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    log(f"devices: {devices}")

    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_headline()
        phase_parity()
        phase_service()
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
