"""Per-example command-line interface, shared by every model module.

The reference ships each example as a mini-binary with ``check`` /
``check-sym`` / ``check-simulation`` / ``explore`` / ``spawn`` subcommands
and a NETWORK positional parsed through the network name registry
(reference: examples/paxos.rs:355-513, src/actor/network.rs:318-331) — the
"embedded TLC" UX: run a model from a shell, point a browser at
``explore``.  Here every model module under ``stateright_tpu.models`` is
runnable the same way::

    python -m stateright_tpu.models.paxos check 2
    python -m stateright_tpu.models.paxos check-tpu 3
    python -m stateright_tpu.models.twophase check-sym 5
    python -m stateright_tpu.models.paxos explore 2 localhost:3017
    python -m stateright_tpu.models.paxos spawn

This package adds two subcommands the reference does not have: ``check-dfs``
(the reference folds it into per-example flags) and ``check-tpu`` (the TPU
wavefront engine, for models with a compiled form).
"""

from __future__ import annotations

import os
import sys
from typing import Any, Callable, Optional

from .actor.network import Network


def _usage(name: str, spec: "CliSpec") -> str:
    lines = [f"usage for {name}:"]
    n_meta = spec.n_meta
    net = " [NETWORK]" if spec.default_network else ""
    lines.append(f"  check [{n_meta}]{net}")
    lines.append(f"  check-dfs [{n_meta}]{net}")
    if spec.symmetry:
        tpu_flag = " [--tpu]" if spec.tpu else ""
        lines.append(f"  check-sym [{n_meta}]{net}{tpu_flag}")
    lines.append(f"  check-simulation [{n_meta}] [SEED]{net}")
    if spec.tpu:
        lines.append(f"  check-tpu [{n_meta}]{net}"
                     " [--supervise] [--checkpoint-dir DIR] [--resume]"
                     " [--sharded[=SHARDS]] [--bucket-slack PCT]"
                     " [--sort-lanes N] [--sortless|--no-sortless]"
                     " [--step-lanes N]"
                     " [--tiered [--trace]] [--memory-budget-mb MB]"
                     " [--store-dir DIR] [--incremental]"
                     " [--xprof-dir DIR]")
        lines.append(f"  reshard [{n_meta}] IN.npz OUT.npz --shards M{net}")
    lines.append(f"  explore [{n_meta}] [ADDRESS]{net}")
    lines.append(
        "  serve [ADDRESS] [--journal PATH] [--journal-max-mb MB]"
        " [--knob-cache DIR] [--workers N] [--store-dir DIR]"
        " [--fleet-dir DIR]"
    )
    lines.append(
        "  fleet-worker --fleet-dir DIR [--knob-cache DIR]"
        " [--lease-sec S] [--gang-max K] [--accept-big]"
        " [--preempt-after S] [--once]"
    )
    lines.append(
        "  fleet {submit|status|cancel|quota} --fleet-dir DIR ..."
    )
    lines.append(
        f"  submit [{n_meta}]{net} [--address ADDR] [--engine ENGINE]"
        " [--portfolio K] [--portfolio-seed S] [--priority P]"
        " [--no-wait]"
    )
    lines.append("  status [JOB_ID] [--address ADDR]")
    lines.append(
        "  report <journal.jsonl | BENCH-glob | dir> [--json]"
        " [--out FILE] [--threshold FRAC] [--timeline-out FILE]"
    )
    lines.append("  watch <journal.jsonl> [--interval SEC] [--once]")
    lines.append(
        "  timeline export <journal.jsonl | run-dir | fleet-dir>..."
        " [--out FILE]"
    )
    if spec.spawn is not None:
        lines.append(
            "  spawn [--chaos SPEC_JSON] [--seed N] [--audit]"
            " [--journal PATH] [--duration SEC] [--metrics-port PORT]"
            " [--trace]"
        )
    if spec.ensemble:
        lines.append(
            "  check-ensemble [--members K] [--seed N]"
            " [--chaos SPEC_JSON] [--steps T] [--fault HOOK]"
            " [--journal PATH] [--no-shrink] [--no-replay]"
        )
    if spec.default_network:
        lines.append(f"NETWORK: one of {' | '.join(Network.names())}")
    return "\n".join(lines)


class CliSpec:
    def __init__(
        self,
        name: str,
        build: Callable[..., Any],  # build(n) or build(n, network) -> Model
        default_n: int,
        n_meta: str = "N",
        default_network: Optional[str] = None,
        symmetry: bool = False,
        tpu: bool = False,
        tpu_kwargs: Optional[dict] = None,
        spawn: Optional[Callable[[], Any]] = None,
        default_address: str = "localhost:3017",
        target_max_depth: Optional[int] = None,
        tpu_target_max_depth: Optional[int] = None,
        ensemble: bool = False,
    ):
        self.name = name
        self.build = build
        self.default_n = default_n
        self.n_meta = n_meta
        self.default_network = default_network
        self.symmetry = symmetry
        self.tpu = tpu
        self.tpu_kwargs = tpu_kwargs or {}
        self.spawn = spawn
        self.default_address = default_address
        self.ensemble = ensemble
        self.target_max_depth = target_max_depth
        # Device-run depth override: raft's reference default (12) needs
        # ~4x10^7 stored states — beyond one chip's HBM at its state
        # width — so its check-tpu bounds depth where a single chip can
        # hold the store (models/raft_compiled.py documents the math).
        self.tpu_target_max_depth = tpu_target_max_depth


def _parse_n(args, default):
    if args and args[0].isdigit():
        return int(args.pop(0))
    return default


def _extract_runtime_flags(args):
    """Pull the supervised-run flags out of the positional stream (they
    may appear anywhere after the subcommand).  Returns
    ``(positional_args, supervise, checkpoint_dir, resume, trace,
    sharded, bucket_slack, sort_lanes, sortless, step_lanes, tiered,
    memory_budget_mb, store_dir, incremental)`` —
    ``sharded`` is None (single-chip), 0 (mesh over every visible
    device), or a mesh width; ``bucket_slack`` is the sharded engine's
    exchange-bucket rung in percent; ``sort_lanes`` the dedup-sort
    geometry rung, ``sortless``/``--no-sortless`` the dedup-path
    selection (claim-plane election vs the sorted fallback), and
    ``step_lanes`` the frontier-sized chunk rung (any device engine;
    docs/OBSERVABILITY.md "Sortless dedup and the rung ladders");
    ``tiered``/``memory_budget_mb`` select
    the out-of-core engine under an HBM budget (docs/TIERED.md; the
    budget flag alone implies ``--tiered``); ``store_dir`` /
    ``incremental`` route the check through the persistent verification
    store (docs/INCREMENTAL.md: ``--store-dir`` alone records the run,
    ``--incremental`` additionally reuses stored entries);
    ``xprof_dir`` wraps the run in a JAX profiler trace
    (``jax.profiler.start_trace``) with per-quantum step annotations
    whose names match the journal's host-span phases
    (docs/OBSERVABILITY.md "Timeline export and profiling") — or raises
    ``ValueError`` on a malformed flag."""
    supervise = False
    resume = False
    trace = False
    ckpt_dir = None
    sharded = None
    bucket_slack = None
    sort_lanes = None
    sortless = None
    step_lanes = None
    tiered = False
    memory_budget_mb = None
    store_dir = None
    incremental = False
    xprof_dir = None
    out = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--supervise":
            supervise = True
        elif a == "--incremental":
            incremental = True
        elif a == "--store-dir" or a.startswith("--store-dir="):
            if a == "--store-dir":
                i += 1
                if i >= len(args):
                    raise ValueError("--store-dir requires a directory")
                store_dir = args[i]
            else:
                store_dir = a.split("=", 1)[1]
            if not store_dir:
                raise ValueError(
                    "--store-dir requires a non-empty directory"
                )
        elif a == "--resume":
            resume = True
        elif a == "--trace":
            trace = True
        elif a == "--tiered":
            tiered = True
        elif a == "--memory-budget-mb" or a.startswith("--memory-budget-mb="):
            if a == "--memory-budget-mb":
                i += 1
                if i >= len(args):
                    raise ValueError(
                        "--memory-budget-mb requires a size in MB"
                    )
                val = args[i]
            else:
                val = a.split("=", 1)[1]
            try:
                memory_budget_mb = float(val)
            except ValueError:
                raise ValueError(
                    "--memory-budget-mb requires a number of MB "
                    "(fractions allowed)"
                ) from None
            import math

            if not math.isfinite(memory_budget_mb) or memory_budget_mb <= 0:
                # float() parses "nan"/"inf" happily; they must die here
                # as a usage error, not as a traceback deep in spawn.
                raise ValueError(
                    "--memory-budget-mb must be positive and finite"
                )
            tiered = True
        elif a == "--sharded":
            sharded = 0  # all visible devices
        elif a.startswith("--sharded="):
            try:
                sharded = int(a.split("=", 1)[1])
            except ValueError:
                raise ValueError("--sharded=SHARDS requires an integer")
            if sharded < 1:
                raise ValueError("--sharded=SHARDS requires SHARDS >= 1")
        elif a == "--bucket-slack" or a.startswith("--bucket-slack="):
            if a == "--bucket-slack":
                i += 1
                if i >= len(args):
                    raise ValueError(
                        "--bucket-slack requires a percentage"
                    )
                val = args[i]
            else:
                val = a.split("=", 1)[1]
            try:
                bucket_slack = int(val)
            except ValueError:
                raise ValueError(
                    "--bucket-slack requires an integer percentage"
                )
            if bucket_slack < 1:
                raise ValueError("--bucket-slack must be >= 1")
        elif a == "--sort-lanes" or a.startswith("--sort-lanes="):
            if a == "--sort-lanes":
                i += 1
                if i >= len(args):
                    raise ValueError("--sort-lanes requires a lane count")
                val = args[i]
            else:
                val = a.split("=", 1)[1]
            try:
                sort_lanes = int(val)
            except ValueError:
                raise ValueError(
                    "--sort-lanes requires an integer lane count"
                ) from None
            if sort_lanes < 1:
                raise ValueError("--sort-lanes must be >= 1")
        elif a == "--sortless":
            sortless = True
        elif a == "--no-sortless":
            sortless = False
        elif a == "--step-lanes" or a.startswith("--step-lanes="):
            if a == "--step-lanes":
                i += 1
                if i >= len(args):
                    raise ValueError("--step-lanes requires a lane count")
                val = args[i]
            else:
                val = a.split("=", 1)[1]
            try:
                step_lanes = int(val)
            except ValueError:
                raise ValueError(
                    "--step-lanes requires an integer lane count"
                ) from None
            if step_lanes < 1:
                raise ValueError("--step-lanes must be >= 1")
        elif a == "--xprof-dir" or a.startswith("--xprof-dir="):
            if a == "--xprof-dir":
                i += 1
                if i >= len(args):
                    raise ValueError("--xprof-dir requires a directory")
                xprof_dir = args[i]
            else:
                xprof_dir = a.split("=", 1)[1]
            if not xprof_dir:
                raise ValueError(
                    "--xprof-dir requires a non-empty directory"
                )
        elif a == "--checkpoint-dir":
            i += 1
            if i >= len(args):
                raise ValueError("--checkpoint-dir requires a directory")
            ckpt_dir = args[i]
        elif a.startswith("--checkpoint-dir="):
            ckpt_dir = a.split("=", 1)[1]
            if not ckpt_dir:
                # An empty value (e.g. --checkpoint-dir=$DIR with DIR
                # unset) would resolve to the CWD, where a non-resume
                # supervised run DELETES run-artifact-named files.
                raise ValueError(
                    "--checkpoint-dir requires a non-empty directory"
                )
        else:
            out.append(a)
        i += 1
    return (
        out, supervise, ckpt_dir, resume, trace, sharded, bucket_slack,
        sort_lanes, sortless, step_lanes, tiered, memory_budget_mb,
        store_dir, incremental, xprof_dir,
    )


def _parse_chaos_flags(args, trace: bool = False):
    """Parse the ``spawn`` subcommand's chaos/observability flags.
    Returns ``(leftover_args, ChaosOptions | None)``; raises
    ``ValueError`` on a malformed flag or chaos spec.  ``--chaos @FILE``
    reads the spec JSON from a file.  ``trace`` arrives pre-parsed (the
    shared runtime-flag parser consumed ``--trace``); it alone — like
    ``--metrics-port`` — is enough to build options around an empty
    (fault-free) chaos spec, so a spawned system can be traced or
    scraped without injecting any faults."""
    from .runtime.chaos import ChaosSpec

    spec_json = None
    seed = 0
    audit = False
    journal = None
    duration = 10.0
    metrics_port = None
    seen_any = bool(trace)
    out = []
    i = 0

    def value_of(flag):
        nonlocal i
        i += 1
        if i >= len(args):
            raise ValueError(f"{flag} requires a value")
        return args[i]

    while i < len(args):
        a = args[i]
        if a == "--chaos":
            spec_json, seen_any = value_of(a), True
        elif a == "--seed":
            v = value_of(a)
            try:
                seed = int(v)
            except ValueError:
                raise ValueError("--seed requires an integer") from None
            seen_any = True
        elif a == "--audit":
            audit, seen_any = True, True
        elif a == "--journal":
            journal, seen_any = value_of(a), True
        elif a == "--metrics-port" or a.startswith("--metrics-port="):
            v = a.split("=", 1)[1] if "=" in a else value_of(a)
            try:
                metrics_port = int(v)
            except ValueError:
                raise ValueError(
                    "--metrics-port requires a port number (0 = ephemeral)"
                ) from None
            if metrics_port < 0 or metrics_port > 65535:
                raise ValueError("--metrics-port must be in [0, 65535]")
            seen_any = True
        elif a == "--duration":
            v = value_of(a)
            try:
                duration = float(v)
            except ValueError:
                raise ValueError("--duration requires seconds") from None
            if duration <= 0:
                raise ValueError("--duration must be positive")
            seen_any = True
        else:
            out.append(a)
        i += 1
    if not seen_any:
        return out, None
    if spec_json is None:
        spec_json = "{}"  # --audit/--seed alone: fault-free chaos harness
    if spec_json.startswith("@"):
        try:
            with open(spec_json[1:], "r", encoding="utf-8") as f:
                spec_json = f.read()
        except OSError as e:
            raise ValueError(f"--chaos {spec_json}: {e}") from None
    chaos = ChaosOptions(
        spec=ChaosSpec.from_json(spec_json),
        seed=seed,
        audit=audit,
        journal=journal,
        duration=duration,
        metrics_port=metrics_port,
        trace=trace,
    )
    return out, chaos


def _run_check_ensemble(spec: "CliSpec", args) -> int:
    """The ``check-ensemble`` verb: one device dispatch sweeping K
    independent fault schedules (ensemble/engine.py), shrinking and
    host-replaying any failing seed.  Exits ``VIOLATION_RC`` when a
    failing schedule was found (host-confirmed when replay is on), so
    CI gates on it like on ``check-tpu``."""
    import json as _json

    from .runtime.supervisor import VIOLATION_RC

    members, seed, steps = 1024, 0, 64
    chaos_json, fault, journal = None, None, None
    shrink, replay = True, True
    i = 0

    def value_of(flag):
        nonlocal i
        i += 1
        if i >= len(args):
            raise ValueError(f"{flag} requires a value")
        return args[i]

    def int_of(flag, minimum=0):
        v = value_of(flag)
        try:
            n = int(v)
        except ValueError:
            raise ValueError(f"{flag} requires an integer") from None
        if n < minimum:
            raise ValueError(f"{flag} must be >= {minimum}")
        return n

    try:
        while i < len(args):
            a = args[i]
            if a == "--members":
                members = int_of(a, minimum=1)
            elif a == "--seed":
                seed = int_of(a)
            elif a == "--steps":
                steps = int_of(a, minimum=1)
            elif a == "--chaos":
                chaos_json = value_of(a)
            elif a == "--fault":
                fault = value_of(a)
            elif a == "--journal":
                journal = value_of(a)
            elif a == "--no-shrink":
                shrink = False
            elif a == "--no-replay":
                replay = False
            else:
                raise ValueError(f"unknown check-ensemble flag: {a}")
            i += 1
        if chaos_json is not None and chaos_json.startswith("@"):
            try:
                with open(chaos_json[1:], "r", encoding="utf-8") as f:
                    chaos_json = f.read()
            except OSError as e:
                raise ValueError(f"--chaos {chaos_json}: {e}") from None
        from .ensemble import run_ensemble

        result = run_ensemble(
            members=members,
            seed=seed,
            chaos=chaos_json,
            steps=steps,
            fault=fault,
            journal=journal,
            shrink=shrink,
            replay=replay,
        )
    except ValueError as e:
        print(e, file=sys.stderr)
        return 2
    print(_json.dumps(result.to_dict(), sort_keys=True, default=str))
    found = result.confirmed if replay else result.failing
    if found:
        print(
            f"failing schedule discovered: member "
            f"{result.repro['member']}, seed {result.repro['seed']}",
            file=sys.stderr,
        )
        return VIOLATION_RC
    return 0


class ChaosOptions:
    """Parsed ``spawn --chaos`` flags, handed to a chaos-capable spawn
    target (one whose callable accepts a ``chaos`` keyword).
    ``metrics_port`` serves the runtime's live ``/.metrics`` and
    ``trace`` turns on the causal trace envelope (docs/OBSERVABILITY.md
    "Actor-runtime observability")."""

    def __init__(self, spec, seed, audit, journal, duration,
                 metrics_port=None, trace=False):
        self.spec = spec
        self.seed = seed
        self.audit = audit
        self.journal = journal
        self.duration = duration
        self.metrics_port = metrics_port
        self.trace = trace


def _parse_network(args, spec):
    """Consume the NETWORK positional (front of the remaining args).  An
    unknown name is an error, like the reference's FromStr parse
    (src/actor/network.rs:318-331) — never a silent default."""
    if spec.default_network is None:
        return None
    if args:
        return Network.from_name(args.pop(0))
    return Network.from_name(spec.default_network)


def _reject_leftovers(args, spec):
    if args:
        print(f"unexpected argument(s): {' '.join(args)}", file=sys.stderr)
        print(_usage(spec.name, spec), file=sys.stderr)
        raise SystemExit(2)


def _build(spec, n, network):
    if spec.default_network is None:
        return spec.build(n)
    return spec.build(n, network)


def _checkpointed_tpu_kwargs(ckpt_dir: str, resume: bool) -> dict:
    """Engine kwargs pointing the journal/checkpoint hooks into a run
    directory (the supervised child's layout, also usable stand-alone):
    journal.jsonl telemetry, checkpoint.npz snapshots, a relax.json
    geometry override left by the supervisor's backoff, and resume from
    the latest checkpoint when asked."""
    from .runtime.supervisor import (
        CHECKPOINT_FILE, JOURNAL_FILE, RELAX_FILE, load_json_or_default,
    )

    run_dir = os.path.abspath(ckpt_dir)
    os.makedirs(run_dir, exist_ok=True)
    # A torn relax.json degrades to no overrides, never a crash.
    kwargs: dict = dict(
        load_json_or_default(os.path.join(run_dir, RELAX_FILE), {})
    )
    ckpt = os.path.join(run_dir, CHECKPOINT_FILE)
    kwargs["journal"] = os.path.join(run_dir, JOURNAL_FILE)
    kwargs["checkpoint_path"] = ckpt
    if resume and os.path.exists(ckpt):
        kwargs["resume_from"] = ckpt
    return kwargs


def _run_supervised(spec: "CliSpec", n, network, ckpt_dir: str,
                    resume: bool, tiered: bool = False,
                    memory_budget_mb=None, sharded=None) -> int:
    """Parent mode for ``check-tpu --supervise``: re-invoke this model
    module's own CLI as the supervised child (with ``--checkpoint-dir``/
    ``--resume``), watch its journal for death and hangs, and restart it
    from the latest checkpoint until the check completes.  Tiered and
    mesh flags are forwarded verbatim so the restarted child resumes
    the same out-of-core run on the same mesh width (its checkpoint
    embeds the cold tiers and the shard count)."""
    from .runtime.supervisor import (
        RunSupervisor, SupervisorConfig, SupervisorError,
    )

    run_dir = os.path.abspath(ckpt_dir)
    module = _module_name(spec)
    if module is None:
        print(
            "--supervise requires running the model module via "
            "`python -m stateright_tpu.models.<name>` (the supervisor "
            "re-invokes that module as the child)",
            file=sys.stderr,
        )
        return 2
    child = [sys.executable, "-m", module, "check-tpu", str(n)]
    if network is not None:
        child.append(network.kind)
    if tiered:
        child.append("--tiered")
    if memory_budget_mb is not None:
        child.append(f"--memory-budget-mb={memory_budget_mb}")
    if sharded is not None:
        child.append("--sharded" if sharded == 0 else f"--sharded={sharded}")
    child += ["--checkpoint-dir", run_dir, "--resume"]
    if tiered and sharded is not None:
        engine = "tiered-sharded"
    elif tiered:
        engine = "tiered"
    else:
        engine = "tpu"
    # Seed the geometry backoff with the child's ACTUAL engine knobs:
    # the policy only relaxes knobs it can see, so without these the
    # frontier/waves steps could never fire in CLI mode.  The sharded
    # engines speak chunk_size, so the single-chip names translate the
    # same way the check-tpu dispatch translates them.
    backoff_kwargs = dict(spec.tpu_kwargs)
    if sharded is not None:
        if "max_frontier" in backoff_kwargs:
            backoff_kwargs["chunk_size"] = backoff_kwargs.pop("max_frontier")
        for single_chip_only in ("log_capacity", "waves_per_call",
                                 "auto_tune"):
            backoff_kwargs.pop(single_chip_only, None)
    sup = RunSupervisor(
        SupervisorConfig(
            run_dir=run_dir,
            resume=resume,
            inherit_output=True,
            call_deadline_sec=600.0,
            engine=engine,
        ),
        child_argv=child,
        engine_kwargs=backoff_kwargs,
    )
    try:
        result = sup.run()
    except SupervisorError as e:
        print(e, file=sys.stderr)
        return 1
    if not result.get("completed", True):
        print(
            "supervised run hit its wall deadline; partial progress is "
            f"checkpointed in {run_dir}",
            file=sys.stderr,
        )
        return 1
    # Propagate the child's verdict: a supervised check that completed
    # WITH a violation still gates (VIOLATION_RC), it just isn't a
    # crash the supervisor retries.
    return sup.last_child_rc or 0


def _run_reshard(spec: "CliSpec", args) -> int:
    """The ``reshard`` verb: re-key a sharded or tiered-sharded
    checkpoint onto a new mesh width (docs/TIERED.md "Elastic
    resharding").  Re-routes every logged state row to its owner under
    the new width and writes a tiered-sharded snapshot that resumes on
    an M-shard mesh — host-side work plus single-device fingerprint
    evaluation; the target mesh need not be attached."""
    import json as _json

    if not spec.tpu:
        print(f"{spec.name} has no compiled TPU form", file=sys.stderr)
        return 2
    shards = None
    rest = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--shards" or a.startswith("--shards="):
            if a == "--shards":
                i += 1
                if i >= len(args):
                    print("--shards requires a value", file=sys.stderr)
                    return 2
                raw = args[i]
            else:
                raw = a.split("=", 1)[1]
            try:
                shards = int(raw)
            except ValueError:
                print(f"--shards requires an integer, got {raw!r}",
                      file=sys.stderr)
                return 2
        else:
            rest.append(a)
        i += 1
    if shards is None or shards < 1:
        print(
            "reshard requires --shards M (the new mesh width, >= 1): "
            f"reshard [{spec.n_meta}] IN.npz OUT.npz --shards M",
            file=sys.stderr,
        )
        return 2
    n = _parse_n(rest, spec.default_n)
    if len(rest) < 2:
        print(
            "reshard requires the snapshot paths: "
            f"reshard [{spec.n_meta}] IN.npz OUT.npz --shards M",
            file=sys.stderr,
        )
        return 2
    in_path, out_path = rest.pop(0), rest.pop(0)
    try:
        network = _parse_network(rest, spec)
    except ValueError as e:
        print(e, file=sys.stderr)
        return 2
    _reject_leftovers(rest, spec)
    model = _build(spec, n, network)
    from .tiered.reshard import reshard_snapshot

    try:
        summary = reshard_snapshot(model, in_path, out_path, shards)
    except (ValueError, KeyError, OSError) as e:
        print(e, file=sys.stderr)
        return 1
    # One parseable line so shell pipelines (and the CI reshard smoke)
    # can gate on the conversion without reading the snapshot back.
    print("reshard: " + _json.dumps(summary, sort_keys=True, default=int))
    return 0


# --- checking-service client verbs (docs/SERVING.md) -------------------------

def _module_name(spec: "CliSpec") -> Optional[str]:
    """The model module's runnable dotted name — the build callable's
    __module__, EXCEPT when this process was started as `python -m
    <module>`: then the lambda lives in __main__ and the real name is on
    __main__.__spec__ (set by runpy)."""
    module = spec.build.__module__
    if module == "__main__":
        main_spec = getattr(sys.modules.get("__main__"), "__spec__", None)
        if main_spec is not None and main_spec.name:
            module = main_spec.name
    return None if module == "__main__" else module


def _workload_name(spec: "CliSpec") -> Optional[str]:
    """The service workload name this model module is registered under
    (serve/workloads.py): the module's last dotted component."""
    module = _module_name(spec)
    if module is None or not module.startswith("stateright_tpu.models."):
        return None
    return module.rsplit(".", 1)[1]


def _http_json(method: str, url: str, body=None, timeout: float = 30.0):
    """One JSON request against the checking service; raises ValueError
    with the server's error message on a 4xx/5xx."""
    import json as _json
    import urllib.error
    import urllib.request

    data = None if body is None else _json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return _json.loads(resp.read())
    except urllib.error.HTTPError as e:
        try:
            detail = _json.loads(e.read()).get("error", "")
        except Exception:
            detail = ""
        raise ValueError(
            f"{method} {url}: HTTP {e.code}"
            + (f": {detail}" if detail else "")
        ) from None
    except urllib.error.URLError as e:
        raise ValueError(
            f"cannot reach the checking service at {url}: {e.reason} "
            "(start one with the `serve` subcommand or "
            "`python -m stateright_tpu.serve`)"
        ) from None


class SubmitOptions:
    def __init__(self):
        # One source of truth for the service's default address: the
        # daemon entry point the client verbs talk to.
        from .serve.__main__ import DEFAULT_ADDRESS

        self.address = DEFAULT_ADDRESS
        self.engine: Optional[str] = None
        self.portfolio = 0
        self.portfolio_seed = 0
        self.priority = 0
        self.no_wait = False


def _parse_submit_flags(args):
    """Flags for ``submit``/``status``; returns (positionals, options)
    or raises ValueError."""
    opts = SubmitOptions()
    out = []
    i = 0

    def value_of(flag, cast=str):
        nonlocal i
        i += 1
        if i >= len(args):
            raise ValueError(f"{flag} requires a value")
        try:
            return cast(args[i])
        except ValueError:
            raise ValueError(f"{flag} requires a {cast.__name__}") from None

    while i < len(args):
        a = args[i]
        if a == "--address":
            opts.address = value_of(a)
        elif a == "--engine":
            opts.engine = value_of(a)
        elif a == "--portfolio":
            opts.portfolio = value_of(a, int)
        elif a == "--portfolio-seed":
            opts.portfolio_seed = value_of(a, int)
        elif a == "--priority":
            opts.priority = value_of(a, int)
        elif a == "--no-wait":
            opts.no_wait = True
        else:
            out.append(a)
        i += 1
    return out, opts


def _run_submit(spec: "CliSpec", args) -> int:
    """Client half of the checking service: POST this model as a job,
    poll to a terminal state, exit on the verdict — 0 clean,
    VIOLATION_RC on a discovered violation, 1 on failure/cancellation
    (so CI can gate on a served check exactly like on check-tpu)."""
    import json as _json

    from .runtime.supervisor import VIOLATION_RC

    try:
        args, opts = _parse_submit_flags(args)
    except ValueError as e:
        print(e, file=sys.stderr)
        return 2
    n = _parse_n(args, spec.default_n)
    network = None
    if spec.default_network is not None and args and args[0] in Network.names():
        network = args.pop(0)
    _reject_leftovers(args, spec)
    workload = _workload_name(spec)
    if workload is None:
        print(
            "submit requires running the model module via "
            "`python -m stateright_tpu.models.<name>` (the job names "
            "that workload to the service)",
            file=sys.stderr,
        )
        return 2
    body = {
        "workload": workload,
        "n": n,
        "engine": opts.engine or ("tpu" if spec.tpu else "bfs"),
        "priority": opts.priority,
    }
    if network is not None:
        body["network"] = network
    if opts.portfolio:
        body["portfolio"] = {
            "size": opts.portfolio, "seed": opts.portfolio_seed,
        }
    base = f"http://{opts.address}"
    try:
        resp = _http_json("POST", base + "/jobs", body)
    except ValueError as e:
        print(e, file=sys.stderr)
        return 1
    job_id = resp["id"]
    print(f"submitted {job_id} ({workload} n={n}) to {base}")
    if opts.no_wait:
        return 0
    while True:
        try:
            snap = _http_json(
                "GET", f"{base}/jobs/{job_id}/result?wait=10",
                timeout=30.0,
            )
        except ValueError as e:
            print(e, file=sys.stderr)
            return 1
        if snap["state"] not in ("queued", "running"):
            break
    print(_json.dumps(snap, sort_keys=True))
    if snap["state"] != "done":
        print(f"job {job_id} {snap['state']}: {snap.get('error') or ''}",
              file=sys.stderr)
        return 1
    if (snap.get("result") or {}).get("violation"):
        print(
            f"violation discovered: {snap['result']['violation']}",
            file=sys.stderr,
        )
        return VIOLATION_RC
    return 0


def _run_status(spec: "CliSpec", args) -> int:
    import json as _json

    try:
        args, opts = _parse_submit_flags(args)
    except ValueError as e:
        print(e, file=sys.stderr)
        return 2
    job_id = args.pop(0) if args else None
    _reject_leftovers(args, spec)
    base = f"http://{opts.address}"
    url = f"{base}/jobs/{job_id}" if job_id else f"{base}/jobs"
    try:
        print(_json.dumps(_http_json("GET", url), sort_keys=True))
    except ValueError as e:
        print(e, file=sys.stderr)
        return 1
    return 0


def example_main(spec: CliSpec, argv=None) -> int:
    from .core.report import WriteReporter
    from .runtime.compile_cache import use_compile_cache

    use_compile_cache()

    args = list(sys.argv[1:] if argv is None else argv)
    if not args or args[0] in ("-h", "--help", "help"):
        print(_usage(spec.name, spec))
        return 0
    sub = args.pop(0)
    try:
        (
            args, supervise, ckpt_dir, resume, trace, sharded, bucket_slack,
            sort_lanes, sortless, step_lanes, tiered, memory_budget_mb,
            store_dir, incremental, xprof_dir,
        ) = _extract_runtime_flags(args)
    except ValueError as e:
        print(e, file=sys.stderr)
        return 2
    if incremental and (store_dir is None or sub != "check-tpu"):
        print(
            "--incremental requires check-tpu with --store-dir DIR (the "
            "persistent verification store it reuses; "
            "docs/INCREMENTAL.md)",
            file=sys.stderr,
        )
        return 2
    if store_dir is not None and sub not in ("check-tpu", "serve"):
        print(
            "--store-dir requires the check-tpu subcommand (or `serve`, "
            "where it enables the service's verification store; "
            "docs/INCREMENTAL.md)",
            file=sys.stderr,
        )
        return 2
    if store_dir is not None and (
        sharded is not None or tiered or trace or supervise or resume
        or ckpt_dir is not None
    ):
        print(
            "--store-dir does not combine with --sharded/--tiered/"
            "--trace/--supervise/--checkpoint-dir/--resume (the store "
            "journals plain spawn_tpu runs; run those modes without the "
            "store)",
            file=sys.stderr,
        )
        return 2
    if xprof_dir is not None and (sub != "check-tpu" or supervise):
        print(
            "--xprof-dir requires the check-tpu subcommand without "
            "--supervise (the profiler wraps one in-process run; "
            "docs/OBSERVABILITY.md \"Timeline export and profiling\")",
            file=sys.stderr,
        )
        return 2
    if (sharded is not None or bucket_slack is not None) and sub != "check-tpu":
        print(
            "--sharded/--bucket-slack require the check-tpu subcommand",
            file=sys.stderr,
        )
        return 2
    if sort_lanes is not None and sub != "check-tpu":
        print(
            "--sort-lanes requires the check-tpu subcommand (it sizes "
            "the device engines' dedup-sort rung)",
            file=sys.stderr,
        )
        return 2
    if (sortless is not None or step_lanes is not None) and sub != "check-tpu":
        print(
            "--sortless/--no-sortless/--step-lanes require the "
            "check-tpu subcommand (they select the device engines' "
            "dedup path and chunk rung)",
            file=sys.stderr,
        )
        return 2
    if tiered and sub != "check-tpu":
        print(
            "--tiered/--memory-budget-mb require the check-tpu "
            "subcommand (the tiered engine is the out-of-core wavefront; "
            "docs/TIERED.md)",
            file=sys.stderr,
        )
        return 2
    if tiered and sharded is not None and trace:
        print(
            "--tiered --sharded does not combine with --trace (the "
            "composed pod-scale engine has no traced mode; trace the "
            "single-chip tiered engine)",
            file=sys.stderr,
        )
        return 2
    if bucket_slack is not None and sharded is None:
        print(
            "--bucket-slack requires --sharded (it sizes the sharded "
            "engine's per-destination exchange buckets)",
            file=sys.stderr,
        )
        return 2
    if (
        sharded is not None and not tiered
        and (supervise or resume or ckpt_dir)
    ):
        print(
            "--sharded alone does not combine with --supervise/"
            "--checkpoint-dir/--resume from the CLI yet; use "
            "runtime.RunSupervisor with engine='sharded', or add "
            "--tiered (the tiered-sharded engine checkpoints, resumes, "
            "and supervises from the CLI; docs/TIERED.md)",
            file=sys.stderr,
        )
        return 2
    if (supervise or ckpt_dir or resume) and sub != "check-tpu":
        print(
            "--supervise/--checkpoint-dir/--resume require the check-tpu "
            "subcommand (the host engines have no snapshot support)",
            file=sys.stderr,
        )
        return 2
    if trace and sub not in ("check-tpu", "spawn"):
        print(
            "--trace requires the check-tpu subcommand with --tiered "
            "(the tiered engine's phase-timed waves) or spawn (the actor "
            "runtime's causal trace envelope); docs/OBSERVABILITY.md",
            file=sys.stderr,
        )
        return 2
    if trace and sub == "check-tpu" and not tiered:
        print(
            "check-tpu --trace requires --tiered (the phase-timed "
            "breakdown of the tiered engine's separately dispatched "
            "waves); profile the fused wave program with --xprof-dir DIR "
            "(docs/OBSERVABILITY.md \"Timeline export and profiling\")",
            file=sys.stderr,
        )
        return 2
    if trace and (supervise or resume):
        # Traced runs are diagnostic and do not support resume; a
        # supervised child auto-resumes on restart, so the combination
        # is refused loudly instead of dying mid-restart.
        print(
            "--trace cannot be combined with --supervise/--resume "
            "(traced runs do not resume; run the trace unsupervised)",
            file=sys.stderr,
        )
        return 2
    if supervise and ckpt_dir is None:
        print("--supervise requires --checkpoint-dir DIR", file=sys.stderr)
        return 2
    if resume and ckpt_dir is None:
        # Silently starting from scratch would discard exactly the
        # progress the flag was meant to continue.
        print("--resume requires --checkpoint-dir DIR", file=sys.stderr)
        return 2
    threads = os.cpu_count() or 1

    if sub in ("check", "check-bfs", "check-dfs", "check-sym", "check-tpu"):
        # check-sym --tpu: run the symmetry-reduced check on the TPU
        # wavefront engine (dedup on the compiled model's canonical-row
        # fingerprint, parallel/canon.py) instead of the host DFS.
        tpu_sym = sub == "check-sym" and "--tpu" in args
        if tpu_sym:
            args = [a for a in args if a != "--tpu"]
        n = _parse_n(args, spec.default_n)
        try:
            network = _parse_network(args, spec)
        except ValueError as e:
            print(e, file=sys.stderr)
            return 2
        _reject_leftovers(args, spec)
        if supervise:
            if not spec.tpu:
                print(f"{spec.name} has no compiled TPU form",
                      file=sys.stderr)
                return 2
            return _run_supervised(
                spec, n, network, ckpt_dir, resume,
                tiered=tiered, memory_budget_mb=memory_budget_mb,
                sharded=sharded,
            )
        xprof_active = False
        if xprof_dir is not None:
            # Hardware profiler hook (docs/OBSERVABILITY.md "Timeline
            # export and profiling"): wrap the whole run in a JAX
            # profiler trace.  The fused loop's per-quantum
            # StepTraceAnnotation names match the journal's host-span
            # phases, so the xprof timeline aligns with the journal's
            # `timeline export` view.
            from .obs.timeline import set_xprof

            try:
                import jax

                jax.profiler.start_trace(os.path.abspath(xprof_dir))
            except Exception as e:
                print(
                    f"--xprof-dir: profiler unavailable: {e}",
                    file=sys.stderr,
                )
                return 2
            set_xprof(True)
            xprof_active = True
        model = _build(spec, n, network)
        print(f"Checking {spec.name} with {spec.n_meta.lower()}={n}"
              + (f", network={network.kind}" if network is not None else ""))
        builder = model.checker().threads(threads)
        if sub == "check-tpu" and spec.tpu_target_max_depth is not None:
            builder = builder.target_max_depth(spec.tpu_target_max_depth)
        elif spec.target_max_depth is not None:
            # Some examples bound their default check (e.g. raft's
            # target_max_depth(12), examples/raft.rs:520-535).
            builder = builder.target_max_depth(spec.target_max_depth)
        if sub == "check-dfs":
            checker = builder.spawn_dfs()
        elif sub == "check-sym":
            if not spec.symmetry:
                print(f"{spec.name} has no symmetry reduction", file=sys.stderr)
                return 2
            if tpu_sym:
                if not spec.tpu:
                    print(f"{spec.name} has no compiled TPU form",
                          file=sys.stderr)
                    return 2
                checker = builder.symmetry().spawn_tpu(**dict(spec.tpu_kwargs))
            else:
                checker = builder.symmetry().spawn_dfs()
        elif sub == "check-tpu":
            if not spec.tpu:
                print(f"{spec.name} has no compiled TPU form", file=sys.stderr)
                return 2
            tpu_kwargs = dict(spec.tpu_kwargs)
            if ckpt_dir is not None:
                tpu_kwargs.update(_checkpointed_tpu_kwargs(ckpt_dir, resume))
            if sort_lanes is not None:
                # The dedup-sort geometry rung — a knob every device
                # engine accepts (single-chip, sharded, tiered).
                tpu_kwargs["sort_lanes"] = sort_lanes
            if sortless is not None:
                # Dedup-path selection: the claim-plane election
                # (default) vs the sorted fallback rung.
                tpu_kwargs["sortless"] = sortless
            if step_lanes is not None:
                # The frontier-sized chunk rung (the second ladder).
                tpu_kwargs["step_lanes"] = step_lanes
            if sharded is not None:
                # Multi-chip run over the first SHARDS visible devices
                # (0 = all).  The spec's single-chip kwargs translate:
                # max_frontier becomes the per-shard chunk, and the
                # single-chip-only knobs drop.
                import jax
                import numpy as _np

                devs = jax.devices()
                n_mesh = sharded or len(devs)
                if n_mesh > len(devs):
                    print(
                        f"--sharded={n_mesh} exceeds the {len(devs)} "
                        "visible devices",
                        file=sys.stderr,
                    )
                    return 2
                mesh = jax.sharding.Mesh(
                    _np.array(devs[:n_mesh]), ("shards",)
                )
                if "max_frontier" in tpu_kwargs:
                    tpu_kwargs["chunk_size"] = tpu_kwargs.pop(
                        "max_frontier"
                    )
                for single_chip_only in (
                    "log_capacity", "waves_per_call", "auto_tune",
                ):
                    tpu_kwargs.pop(single_chip_only, None)
                if bucket_slack is not None:
                    tpu_kwargs["bucket_slack"] = bucket_slack
                if tiered:
                    # The composed pod-scale engine: the sharded BFS
                    # with the HBM budget applied PER SHARD
                    # (docs/TIERED.md "Composing the levers").
                    if memory_budget_mb is not None:
                        tpu_kwargs["memory_budget_mb"] = memory_budget_mb
                    checker = builder.spawn_tpu_tiered_sharded(
                        mesh=mesh, **tpu_kwargs
                    )
                else:
                    checker = builder.spawn_tpu_sharded(
                        mesh=mesh, **tpu_kwargs
                    )
            elif tiered:
                # Out-of-core run under an HBM budget (docs/TIERED.md).
                # The budget is authoritative in the engine itself: it
                # overrides any spec-tuned capacity hint riding along.
                if memory_budget_mb is not None:
                    tpu_kwargs["memory_budget_mb"] = memory_budget_mb
                # --trace: the phase-timed breakdown (docs/
                # OBSERVABILITY.md); with --checkpoint-dir the enriched
                # wave records land in the run dir's journal.jsonl.
                checker = builder.spawn_tpu_tiered(trace=trace, **tpu_kwargs)
            elif store_dir is not None:
                # Incremental re-checking through the persistent
                # verification store (docs/INCREMENTAL.md): classify
                # the spec delta and take the cheapest sound path —
                # verdict cache / property re-eval / seeded widening /
                # loud cold run.  The store's journal.jsonl carries the
                # incr_* evidence plus any engine events.
                from .incr.recheck import incremental_check

                checker, recheck_info = incremental_check(
                    builder,
                    store_dir,
                    engine_kwargs=tpu_kwargs,
                    journal=os.path.join(
                        os.path.abspath(store_dir), "journal.jsonl"
                    ),
                    reuse=incremental,
                )
            else:
                checker = builder.spawn_tpu(**tpu_kwargs)
        else:
            checker = builder.spawn_bfs()
        checker.join_and_report(WriteReporter(sys.stdout))
        if xprof_active:
            from .obs.timeline import set_xprof

            import jax

            set_xprof(False)
            jax.profiler.stop_trace()
            print(f"xprof: profiler trace written under {xprof_dir}")
        if sub == "check-tpu" and store_dir is not None:
            # One parseable line with the recheck classification, so
            # shell pipelines and the CI smoke can gate on the mode
            # without reading the store journal.
            import json as _json

            print("recheck: " + _json.dumps(recheck_info, sort_keys=True))
        if sub == "check-tpu" and trace:
            # One parseable line with the tiered run's roofline
            # reduction, so shell pipelines can gate on it without
            # reading the journal.
            import json as _json

            print("trace: " + _json.dumps(checker.trace_summary()))
        if sub == "check-tpu":
            # Gateable verdict (docs/SERVING.md): a COMPLETED check that
            # discovered a counterexample exits VIOLATION_RC so CI and
            # service callers can gate on the result without parsing the
            # report.  Examples (sometimes-property discoveries) are not
            # violations.
            from .runtime.supervisor import VIOLATION_RC

            violations = sorted(
                name for name in checker.discoveries()
                if checker.discovery_classification(name) == "counterexample"
            )
            if violations:
                print(
                    "violation discovered: " + ", ".join(violations),
                    file=sys.stderr,
                )
                return VIOLATION_RC
        return 0

    if sub == "check-simulation":
        n = _parse_n(args, spec.default_n)
        seed = int(args.pop(0)) if args and args[0].isdigit() else 0
        try:
            network = _parse_network(args, spec)
        except ValueError as e:
            print(e, file=sys.stderr)
            return 2
        _reject_leftovers(args, spec)
        model = _build(spec, n, network)
        print(f"Simulating {spec.name} with {spec.n_meta.lower()}={n}, "
              f"seed={seed}")
        from .core.simulation import UniformChooser

        (
            model.checker()
            .threads(threads)
            .target_state_count(1_000_000)
            .spawn_simulation(seed, UniformChooser())
            .join_and_report(WriteReporter(sys.stdout))
        )
        return 0

    if sub == "explore":
        # Positionals mirror the reference: [N] [ADDRESS] [NETWORK].
        n = _parse_n(args, spec.default_n)
        address = spec.default_address
        if args and args[0] not in Network.names():
            address = args.pop(0)
        try:
            network = _parse_network(args, spec)
        except ValueError as e:
            print(e, file=sys.stderr)
            return 2
        _reject_leftovers(args, spec)
        host, _, port = address.partition(":")
        try:
            port = int(port or 3017)
        except ValueError:
            print(f"invalid ADDRESS port: {address!r}", file=sys.stderr)
            return 2
        model = _build(spec, n, network)
        print(
            f"Exploring state space for {spec.name} with "
            f"{spec.n_meta.lower()}={n} on http://{host}:{port}"
        )
        model.checker().threads(threads).serve((host, port))
        return 0

    if sub == "check-ensemble":
        if not spec.ensemble:
            print(
                f"{spec.name} has no ensemble workload (check-ensemble "
                "needs a model with a compiled fault hook; "
                "docs/CHAOS_ENSEMBLES.md)",
                file=sys.stderr,
            )
            return 2
        return _run_check_ensemble(spec, args)

    if sub == "spawn":
        if spec.spawn is None:
            print(f"{spec.name} has no spawn target", file=sys.stderr)
            return 2
        try:
            args, chaos = _parse_chaos_flags(args, trace=trace)
        except ValueError as e:
            print(e, file=sys.stderr)
            return 2
        _reject_leftovers(args, spec)
        if chaos is None:
            rc = spec.spawn()
            return int(rc) if rc else 0
        import inspect

        if "chaos" not in inspect.signature(spec.spawn).parameters:
            print(
                f"{spec.name}'s spawn target is not chaos-capable "
                "(it takes no `chaos` keyword)",
                file=sys.stderr,
            )
            return 2
        rc = spec.spawn(chaos=chaos)
        return int(rc) if rc else 0

    if sub == "serve":
        # The checking-service daemon (serve/server.py): one process,
        # one mesh, many jobs — every registered workload is servable,
        # whichever model module launched it.  --store-dir was consumed
        # by the shared runtime-flag parser above; hand it back to the
        # daemon's own parser.
        from .serve.__main__ import main as serve_main

        if store_dir is not None:
            args = args + ["--store-dir", store_dir]
        return serve_main(args)

    if sub == "fleet-worker":
        # One fleet worker process: claims jobs from the shared durable
        # store and runs them on this process's backend (fleet/worker.py,
        # docs/SERVING.md "Fleet mode").
        from .fleet.worker import worker_main

        return worker_main(args)

    if sub == "fleet":
        # Fleet operator verbs: submit/status/cancel/quota against a
        # fleet directory (fleet/__main__.py).
        from .fleet.__main__ import main as fleet_main

        return fleet_main(args)

    if sub == "submit":
        return _run_submit(spec, args)

    if sub == "status":
        return _run_status(spec, args)

    if sub == "report":
        # Journal analytics / bench trajectory (obs/report.py,
        # docs/OBSERVABILITY.md "Run reports"): model-agnostic, rides on
        # every model CLI like `serve` does.
        from .obs.report import report_main

        return report_main(args)

    if sub == "watch":
        # Live journal tail -> one-line refreshing progress view
        # (obs/watch.py, docs/OBSERVABILITY.md "watch"); model-agnostic
        # like `report`.  `--once` prints a single snapshot (the CI
        # smoke's mode).
        from .obs.watch import watch_main

        return watch_main(args)

    if sub == "timeline":
        # Journal -> Chrome trace-event export (obs/timeline.py,
        # docs/OBSERVABILITY.md "Timeline export and profiling"):
        # merges run/serve/fleet journals onto one aligned timeline for
        # Perfetto / chrome://tracing.  Model-agnostic like `report`.
        from .obs.timeline import timeline_main

        return timeline_main(args)

    if sub == "reshard":
        return _run_reshard(spec, args)

    print(_usage(spec.name, spec))
    return 2


# --- shared spawn helper for register-harness systems ------------------------


def spawn_register_system(
    make_actors, count: int, name: str, make_transport=None,
    metrics_port=None, trace: bool = False, journal=None,
) -> None:
    """Run register-protocol servers over real localhost UDP, mirroring the
    reference examples' ``spawn`` subcommands (examples/paxos.rs:488-512):
    servers at 127.0.0.1:3000+i, JSON-over-datagram message encoding, until
    interrupted.  ``make_actors(ids)`` builds the server actors given their
    real socket-addr ``Id``s (peers must reference these, not model
    indices).  ``make_transport(ids)`` overrides the wire — e.g. a
    ``runtime.chaos.FaultyTransport`` wrapping UDP (with the chaos spec's
    model indices remapped onto the real ids), which is how
    ``spawn --chaos`` (without ``--audit``) injects faults into a system
    being poked externally with ``nc -u``.

    Observability (docs/OBSERVABILITY.md "Actor-runtime observability"):
    the transport is wrapped in an ``ObservedTransport`` — per-link
    datagram/byte counters always, the causal trace envelope under
    ``trace=True`` (``actor_span`` events into ``journal``) — and
    ``metrics_port`` serves the runtime's live ``GET /.metrics`` (JSON +
    Prometheus; 0 picks an ephemeral port, printed at startup)."""
    from .actor.ids import Id
    from .actor.obs import ObservedTransport, serve_actor_metrics
    from .actor.spawn import spawn
    from .actor.transport import UdpTransport
    from .actor.wire import wire_deserialize, wire_serialize

    ids = [
        Id.from_socket_addr((127, 0, 0, 1), 3000 + i) for i in range(count)
    ]
    base = make_transport(ids) if make_transport is not None else UdpTransport()
    transport = ObservedTransport(base, trace=trace, journal=journal)
    server_actors = make_actors(ids)
    print(f"A set of {name} servers is now running on:")
    for i in ids:
        print(f"  udp://127.0.0.1:{i.to_socket_addr()[1]}")
    print("Messages are JSON, e.g.:")
    print('  {"__t": "Get", "request_id": 1}')
    print('  {"__t": "Put", "request_id": 2, "value": "X"}')
    runtime = spawn(
        wire_serialize,
        wire_deserialize,
        wire_serialize,
        wire_deserialize,
        list(zip(ids, server_actors)),
        transport=transport,
        metrics=transport.registry,
    )
    metrics_server = None
    if metrics_port is not None:
        metrics_server = serve_actor_metrics(
            runtime, ("127.0.0.1", int(metrics_port))
        )
        host, port = metrics_server.server_address[:2]
        print(f"Metrics: http://{host}:{port}/.metrics "
              "(?format=prometheus for the text exposition)")
    try:
        runtime.join()
    except KeyboardInterrupt:
        runtime.stop(raise_errors=False)
    finally:
        if metrics_server is not None:
            metrics_server.shutdown()
