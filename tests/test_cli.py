"""End-to-end CLI tests: every model module is a runnable mini-binary with
check/check-sym/check-simulation/check-tpu/explore/spawn subcommands,
mirroring the reference examples' pico_args CLIs (examples/paxos.rs:355-513).
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(module, *args, timeout=180):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", f"stateright_tpu.models.{module}", *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )


def test_check_subcommand_single_copy_register():
    r = run_cli("single_copy_register", "check", "2")
    assert r.returncode == 0, r.stderr
    assert "unique=93" in r.stdout  # examples/single-copy-register.rs:111
    assert 'Discovered "value chosen" example' in r.stdout


def test_check_sym_subcommand_twophase():
    r = run_cli("twophase", "check-sym", "5")
    assert r.returncode == 0, r.stderr
    assert "unique=665" in r.stdout  # examples/2pc.rs:163-168


def test_check_sym_tpu_subcommand_twophase():
    """check-sym --tpu: the symmetry-reduced check on the device
    wavefront engine — dedup on the canonical-row fingerprint.  The
    full-record canon is the exact orbit invariant: 80 classes at rm=3
    (tests/test_tpu_symmetry.py pins the recipe; docs/SYMMETRY.md
    explains why this differs from the host DFS's tie-broken 107)."""
    r = run_cli("twophase", "check-sym", "3", "--tpu", timeout=600)
    assert r.returncode == 0, r.stderr
    assert "unique=80" in r.stdout


def test_network_positional():
    r = run_cli("single_copy_register", "check", "2", "ordered")
    assert r.returncode == 0, r.stderr
    assert "network=ordered" in r.stdout
    assert "Done." in r.stdout


def test_unknown_network_name_errors():
    r = run_cli("single_copy_register", "check", "2", "ordred")
    assert r.returncode == 2
    assert "unable to parse network name" in r.stderr


def test_unexpected_argument_errors():
    r = run_cli("twophase", "check", "3", "extra")
    assert r.returncode == 2
    assert "unexpected argument" in r.stderr


def test_check_simulation_subcommand():
    r = run_cli("increment", "check-simulation", "2", "7")
    assert r.returncode == 0, r.stderr
    assert "Done." in r.stdout


def test_usage_on_no_args():
    r = run_cli("paxos")
    assert r.returncode == 0
    assert "check [CLIENT_COUNT] [NETWORK]" in r.stdout
    assert "spawn" in r.stdout
    for name in ("ordered", "unordered_duplicating", "unordered_nonduplicating"):
        assert name in r.stdout


def test_unknown_subcommand_fails():
    r = run_cli("paxos", "frobnicate")
    assert r.returncode == 2


def test_explore_subcommand_serves_http():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "stateright_tpu.models.single_copy_register",
            "explore",
            "2",
            "localhost:3919",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=env,
        cwd=REPO,
    )
    try:
        status = None
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    "http://localhost:3919/.status", timeout=2
                ) as resp:
                    status = json.loads(resp.read())
                break
            except Exception:
                time.sleep(0.3)
        assert status is not None, "explorer never came up"
        assert "properties" in status or "model" in status
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_spawn_subcommand_real_udp_paxos():
    """`spawn` runs the checked actors on real UDP: a Put reaches quorum
    and returns PutOk; a Get on a *different* replica returns the decided
    value (the reference's spawn UX, examples/paxos.rs:488-512)."""
    import socket

    # The spawn subcommand binds fixed localhost ports (the reference UX);
    # skip rather than fail when the environment already holds them.
    for port in (3000, 3001, 3002, 3103):
        probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            probe.bind(("127.0.0.1", port))
        except OSError:
            pytest.skip(f"udp port {port} unavailable in this environment")
        finally:
            probe.close()

    sys.path.insert(0, REPO)
    from stateright_tpu.actor.register import Get, GetOk, Internal, Put, PutOk
    from stateright_tpu.actor.wire import register_wire_types, wire_deserialize, wire_serialize
    from stateright_tpu.models.paxos import (
        Accept, Accepted, Decided, Prepare, Prepared,
    )

    register_wire_types(
        Put, Get, PutOk, GetOk, Internal, Prepare, Prepared, Accept,
        Accepted, Decided,
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "stateright_tpu.models.paxos", "spawn"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=env,
        cwd=REPO,
    )
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.bind(("127.0.0.1", 3103))
        s.settimeout(20)
        time.sleep(2.0)
        s.sendto(
            wire_serialize(Put(request_id=1, value="X")), ("127.0.0.1", 3000)
        )
        msg, _ = s.recvfrom(65535)
        assert wire_deserialize(msg) == PutOk(request_id=1)
        s.sendto(wire_serialize(Get(request_id=2)), ("127.0.0.1", 3001))
        msg, _ = s.recvfrom(65535)
        assert wire_deserialize(msg) == GetOk(request_id=2, value="X")
    finally:
        s.close()
        proc.terminate()
        proc.wait(timeout=10)


@pytest.mark.slow
def test_check_tpu_subcommand():
    r = run_cli("twophase", "check-tpu", "3", timeout=300)
    assert r.returncode == 0, r.stderr
    assert "unique=288" in r.stdout


def test_runtime_flags_require_check_tpu():
    r = run_cli("twophase", "check", "3", "--supervise")
    assert r.returncode == 2
    assert "check-tpu" in r.stderr
    r = run_cli("twophase", "check-sym", "3", "--checkpoint-dir", "/tmp/x")
    assert r.returncode == 2


def test_supervise_requires_checkpoint_dir():
    r = run_cli("twophase", "check-tpu", "3", "--supervise")
    assert r.returncode == 2
    assert "--checkpoint-dir" in r.stderr


def test_resume_requires_checkpoint_dir():
    # Silently starting fresh would discard the progress the flag was
    # meant to continue.
    r = run_cli("twophase", "check-tpu", "3", "--resume")
    assert r.returncode == 2
    assert "--checkpoint-dir" in r.stderr


def test_checkpoint_dir_flag_value_missing_is_clean_error():
    r = run_cli("twophase", "check-tpu", "3", "--checkpoint-dir")
    assert r.returncode == 2
    assert "requires a directory" in r.stderr


def test_trace_flag_requires_check_tpu_and_rejects_resume():
    r = run_cli("twophase", "check", "3", "--trace")
    assert r.returncode == 2
    assert "check-tpu" in r.stderr
    # The phase-timed trace is the tiered engine's; the fused program is
    # profiled with --xprof-dir.
    r = run_cli("twophase", "check-tpu", "3", "--trace")
    assert r.returncode == 2
    assert "--tiered" in r.stderr and "--xprof-dir" in r.stderr
    r = run_cli("twophase", "check-tpu", "3", "--tiered", "--trace",
                "--resume", "--checkpoint-dir", "/tmp/x")
    assert r.returncode == 2
    assert "--trace" in r.stderr


@pytest.mark.slow
def test_check_tpu_trace_emits_breakdown(tmp_path):
    """`check-tpu --tiered --trace` completes with the golden count,
    prints the one-line roofline reduction, and (with --checkpoint-dir)
    leaves the enriched wave-trace records in the run journal
    (docs/OBSERVABILITY.md)."""
    run_dir = str(tmp_path / "trace-run")
    r = run_cli(
        "twophase", "check-tpu", "3", "--tiered", "--trace",
        "--checkpoint-dir", run_dir, timeout=600,
    )
    assert r.returncode == 0, r.stderr
    assert "unique=288" in r.stdout
    trace_line = next(
        ln for ln in r.stdout.splitlines() if ln.startswith("trace: ")
    )
    summary = json.loads(trace_line[len("trace: "):])
    assert summary["traced_waves"] >= 1
    assert set(summary["wave_breakdown"]) == {
        "step", "canon", "dedup", "cold_probe", "append", "readback",
    }
    from stateright_tpu.runtime.journal import read_journal

    waves = [
        e for e in read_journal(os.path.join(run_dir, "journal.jsonl"))
        if e["event"] == "wave"
    ]
    assert waves and all("wave_breakdown" in w for w in waves)


@pytest.mark.slow
def test_check_tpu_supervised_writes_journal_and_checkpoint(tmp_path):
    """`check-tpu --supervise --checkpoint-dir` completes the check
    through the run supervisor and leaves the run artifacts: a JSONL
    journal with wave telemetry and an engine_done event, plus a
    checkpoint snapshot."""
    run_dir = str(tmp_path / "run")
    r = run_cli(
        "twophase", "check-tpu", "3", "--supervise",
        "--checkpoint-dir", run_dir, timeout=600,
    )
    assert r.returncode == 0, r.stderr
    assert "unique=288" in r.stdout  # the child's report streams through
    events = [
        json.loads(ln)
        for ln in open(os.path.join(run_dir, "journal.jsonl"))
        if ln.strip()
    ]
    kinds = [e["event"] for e in events]
    assert "supervisor_start" in kinds
    assert "wave" in kinds
    assert "engine_done" in kinds
    assert "supervisor_done" in kinds
    assert os.path.exists(os.path.join(run_dir, "checkpoint.npz"))


def test_check_tpu_violating_model_exits_violation_rc():
    """Satellite pin: a COMPLETED check-tpu that discovered a property
    violation exits VIOLATION_RC (4) so CI and service callers can gate
    on the verdict.  fixtures = TrapCounter, the known-violating
    compiled workload ("reaches limit" counterexample)."""
    r = run_cli("fixtures", "check-tpu", "5", timeout=600)
    assert r.returncode == 4, (r.returncode, r.stderr)
    assert "violation discovered: reaches limit" in r.stderr
    assert 'Discovered "reaches limit" counterexample' in r.stdout


def test_usage_lists_service_verbs():
    r = run_cli("twophase")
    for verb in ("serve [ADDRESS]", "submit [RM_COUNT]", "status [JOB_ID]"):
        assert verb in r.stdout, r.stdout


def test_submit_without_server_is_clean_error():
    # Port 9 (discard) refuses connections; the client must say what to
    # start, not stack-trace.
    r = run_cli("twophase", "submit", "3", "--address", "127.0.0.1:9")
    assert r.returncode == 1
    assert "cannot reach the checking service" in r.stderr


def test_submit_rejects_bad_flag_values():
    r = run_cli("twophase", "submit", "3", "--portfolio", "x")
    assert r.returncode == 2
    assert "--portfolio requires a int" in r.stderr
    r = run_cli("twophase", "submit", "3", "--address")
    assert r.returncode == 2
    assert "requires a value" in r.stderr


@pytest.mark.slow
def test_serve_submit_status_end_to_end(tmp_path):
    """The service UX end to end through real processes: a daemon, a
    clean submit (rc 0), a violating portfolio submit (rc VIOLATION_RC),
    and status.  The per-push CI serve smoke covers the same flow; this
    is the nightly in-tree pin."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    address = "127.0.0.1:3923"
    journal = str(tmp_path / "journal.jsonl")
    daemon = subprocess.Popen(
        [sys.executable, "-m", "stateright_tpu.serve", address,
         "--journal", journal, "--knob-cache", str(tmp_path / "knobs")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, cwd=REPO,
    )
    try:
        deadline = time.time() + 60
        up = False
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://{address}/.status", timeout=2
                ) as resp:
                    up = json.loads(resp.read())["service"] is not None
                break
            except Exception:
                time.sleep(0.3)
        assert up, "service daemon never came up"
        clean = run_cli("twophase", "submit", "3", "--address", address,
                        timeout=600)
        assert clean.returncode == 0, clean.stderr
        assert "submitted job-" in clean.stdout
        viol = run_cli("fixtures", "submit", "5", "--address", address,
                       "--portfolio", "3", timeout=600)
        assert viol.returncode == 4, (viol.returncode, viol.stderr)
        assert "violation discovered: reaches limit" in viol.stderr
        status = run_cli("twophase", "status", "--address", address)
        assert status.returncode == 0
        jobs = json.loads(status.stdout.strip().splitlines()[-1])
        assert [j["state"] for j in jobs] == ["done", "done"]
        events = [json.loads(ln)["event"] for ln in open(journal)]
        assert "portfolio_winner" in events
    finally:
        daemon.terminate()
        daemon.wait(timeout=10)


def test_wire_codec_malformed_messages_raise_valueerror():
    """A hand-typed probe datagram with wrong fields must surface as
    ValueError (which the UDP runtime drops) — never a TypeError that
    would kill a replica thread."""
    sys.path.insert(0, REPO)
    from stateright_tpu.actor.register import Put
    from stateright_tpu.actor.wire import register_wire_types, wire_deserialize

    register_wire_types(Put)
    with pytest.raises(ValueError):
        wire_deserialize(b'{"__t": "Put", "request_id": 1}')  # missing value
    with pytest.raises(ValueError):
        wire_deserialize(b'{"__t": "NoSuchType"}')
    with pytest.raises(ValueError):
        wire_deserialize(b'{"__tup": 5}')


def test_explore_invalid_port_is_clean_error():
    r = run_cli("paxos", "explore", "2", "localhost:abc")
    assert r.returncode == 2
    assert "invalid ADDRESS port" in r.stderr


# --- spawn --chaos: the fault-injecting runtime surface ----------------------


def test_spawn_chaos_rejects_malformed_spec():
    r = run_cli("abd", "spawn", "--chaos", '{"drop": 1.5}')
    assert r.returncode == 2
    assert "probability" in r.stderr


def test_spawn_chaos_rejects_bad_flag_values():
    r = run_cli("abd", "spawn", "--chaos", "{}", "--seed", "x")
    assert r.returncode == 2
    assert "--seed requires an integer" in r.stderr
    r = run_cli("abd", "spawn", "--seed")
    assert r.returncode == 2
    assert "requires a value" in r.stderr


def test_spawn_chaos_on_non_capable_model_is_clean_error():
    r = run_cli("paxos", "spawn", "--chaos", "{}")
    assert r.returncode == 2
    assert "not chaos-capable" in r.stderr


def test_spawn_chaos_audit_end_to_end(tmp_path):
    """The headline chaos flow: a seeded, hermetic ABD cluster under
    drop+duplicate+reorder, audited for linearizability, journaling every
    injected fault — exit code reports the verdict."""
    journal = str(tmp_path / "journal.jsonl")
    r = run_cli(
        "abd", "spawn",
        "--chaos", '{"drop": 0.1, "duplicate": 0.1, "reorder": 0.15}',
        "--seed", "7", "--audit", "--journal", journal,
        "--duration", "30",
        timeout=180,
    )
    assert r.returncode == 0, r.stderr
    verdict = json.loads(r.stdout.strip().splitlines()[-1])
    assert verdict["consistent"] is True
    assert verdict["returned"] >= 1
    from stateright_tpu.runtime.journal import read_journal

    kinds = [e["event"] for e in read_journal(journal)]
    assert kinds[0] == "chaos_start"
    assert "audit" in kinds
    assert any(k.startswith("chaos_") for k in kinds[1:])
