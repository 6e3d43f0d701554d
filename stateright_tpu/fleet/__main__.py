"""Fleet operator CLI: ``python -m stateright_tpu.fleet VERB`` (also
reachable as the ``fleet-worker`` and ``fleet`` verbs of the main CLI,
stateright_tpu/cli.py).

- ``worker``  — run one fleet worker against ``--fleet-dir``
- ``submit``  — append one job to the fleet store; ``--wait`` blocks
  for the verdict and exits with the supervisor's VIOLATION_RC on a
  property violation (scriptable exactly like ``check-tpu``)
- ``status``  — one fold of the store: workers, counters, job table
- ``cancel``  — request cancellation of one job
- ``quota``   — set/clear a tenant's admission quota
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional


def _submit_main(argv: List[str]) -> int:
    from ..runtime.supervisor import VIOLATION_RC
    from ..serve.jobs import JobSpec
    from .store import DONE, FAILED, FleetStore, TERMINAL

    ap = argparse.ArgumentParser(
        prog="fleet submit", description="queue one job on the fleet"
    )
    ap.add_argument("--fleet-dir", required=True)
    ap.add_argument("workload", help="a SERVABLE workload name")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--network", default=None)
    ap.add_argument("--engine", default="tpu")
    ap.add_argument("--engine-kwargs", default=None,
                    help="JSON object of engine keyword overrides")
    ap.add_argument("--symmetry", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tenant", default="default")
    ap.add_argument("--priority", type=int, default=0)
    ap.add_argument("--portfolio", type=int, default=None,
                    help="diversified portfolio of this size across "
                         "the fleet's workers")
    ap.add_argument("--wait", type=float, default=None,
                    help="block up to SECONDS for the verdict; exit "
                         f"{VIOLATION_RC} on violation")
    args = ap.parse_args(argv)

    spec_dict = {
        "workload": args.workload, "engine": args.engine,
        "seed": args.seed, "symmetry": args.symmetry,
    }
    if args.n is not None:
        spec_dict["n"] = args.n
    if args.network is not None:
        spec_dict["network"] = args.network
    if args.engine_kwargs:
        spec_dict["engine_kwargs"] = json.loads(args.engine_kwargs)
    if args.portfolio is not None:
        spec_dict["portfolio"] = {"size": args.portfolio,
                                  "seed": args.seed}
    store = FleetStore(args.fleet_dir)
    job_id = store.submit(
        JobSpec.from_dict(spec_dict), tenant=args.tenant,
        priority=args.priority,
    )
    print(job_id)
    if args.wait is None:
        return 0
    deadline = time.monotonic() + args.wait
    while time.monotonic() < deadline:
        rec = store.fold().jobs.get(job_id)
        if rec is not None and rec["state"] in TERMINAL:
            result = store.read_result(job_id) or {}
            json.dump(
                {"id": job_id, "state": rec["state"],
                 "violation": rec["violation"], "error": rec["error"],
                 "unique_state_count": result.get("unique_state_count")},
                sys.stdout, indent=2,
            )
            print()
            if rec["state"] == DONE:
                return VIOLATION_RC if rec["violation"] else 0
            return 1 if rec["state"] == FAILED else 0
        time.sleep(0.2)
    print(f"timeout: job {job_id} not terminal after {args.wait}s",
          file=sys.stderr)
    return 1


def _status_main(argv: List[str]) -> int:
    from .store import FleetStore

    ap = argparse.ArgumentParser(
        prog="fleet status", description="one fold of the fleet store"
    )
    ap.add_argument("--fleet-dir", required=True)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable dump of the full fold")
    args = ap.parse_args(argv)
    view = FleetStore(args.fleet_dir).fold()
    if args.json:
        json.dump(
            {"jobs": view.jobs, "workers": view.workers,
             "counters": view.counters, "torn": view.torn},
            sys.stdout, indent=2, default=str,
        )
        print()
        return 0
    print(f"fleet {args.fleet_dir}")
    counts = view.counts()
    print("  jobs:    " + "  ".join(
        f"{k}={v}" for k, v in sorted(counts.items())
    ))
    print("  counters:" + "".join(
        f" {k}={v}" for k, v in sorted(view.counters.items()) if v
    ))
    for wid, w in sorted(view.workers.items()):
        desc = w.get("desc") or {}
        state = "stopped" if w.get("stopped") else "alive"
        print(f"  worker {wid}: {desc.get('platform')}"
              f"/{desc.get('device_kind')} {state}")
    for jid, j in sorted(view.jobs.items()):
        wl = (j["spec"] or {}).get("workload", "?")
        extra = ""
        if j["worker"]:
            extra += f" worker={j['worker']}"
        if j["attempt"]:
            extra += f" attempt={j['attempt']}"
        if j["gang"]:
            extra += f" gang={j['gang']}"
        if j["violation"]:
            extra += f" VIOLATION={j['violation']!r}"
        if j["error"]:
            extra += f" error={j['error']!r}"
        print(f"  {jid} {j['state']:<9} {wl}{extra}")
    return 0


def _cancel_main(argv: List[str]) -> int:
    from .store import FleetStore

    ap = argparse.ArgumentParser(prog="fleet cancel")
    ap.add_argument("--fleet-dir", required=True)
    ap.add_argument("job_id")
    args = ap.parse_args(argv)
    ok = FleetStore(args.fleet_dir).cancel(args.job_id)
    print("cancelled" if ok else "not cancellable (unknown or terminal)")
    return 0 if ok else 1


def _quota_main(argv: List[str]) -> int:
    from .store import FleetStore

    ap = argparse.ArgumentParser(
        prog="fleet quota",
        description="per-tenant admission quota (max active jobs)",
    )
    ap.add_argument("--fleet-dir", required=True)
    ap.add_argument("tenant")
    ap.add_argument("limit", nargs="?", default=None,
                    help="max active jobs; omit or 'none' to clear")
    args = ap.parse_args(argv)
    store = FleetStore(args.fleet_dir)
    limit = (None if args.limit in (None, "none")
             else int(args.limit))
    store.set_quota(args.tenant, limit)
    print(json.dumps(store.quotas(), sort_keys=True))
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    from ..runtime.compile_cache import use_compile_cache

    use_compile_cache()
    argv = sys.argv[1:] if argv is None else list(argv)
    verbs = {
        "submit": _submit_main, "status": _status_main,
        "cancel": _cancel_main, "quota": _quota_main,
    }
    if argv and argv[0] == "worker":
        from .worker import worker_main

        return worker_main(argv[1:])
    if argv and argv[0] in verbs:
        return verbs[argv[0]](argv[1:])
    print("usage: python -m stateright_tpu.fleet "
          "{worker|submit|status|cancel|quota} ...", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
