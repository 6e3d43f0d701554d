"""Fleet-backed serving: the HTTP surface of serve/server.py over a
shared durable store instead of an in-process scheduler.

``serve --fleet-dir DIR`` swaps :class:`FleetService` in for
``CheckService`` — the endpoints, request/response shapes, and error
codes stay identical (serve/server.py's Handler is reused verbatim),
but the server process runs no checks itself: ``POST /jobs`` appends to
the fleet journal, and separately-launched ``fleet-worker`` processes
(fleet/worker.py) claim and run them.  The server can therefore restart
freely — every job it ever accepted is in the store — and many servers
can front the same fleet directory.

What necessarily differs from in-process mode:

- ``/jobs/{id}/explore`` returns 409: completed checkers live (and die)
  in worker processes, so there is no retained checker to attach the
  Explorer to.  Re-run the workload locally to explore it.
- ``/.metrics`` aggregates the FLEET view: job counts folded from the
  journal, the ``fleet_*``/``gang_*`` counters (COUNTERS in
  fleet/store.py), per-worker vitals from their last heartbeat, and
  gang occupancy (mean jobs per device dispatch — the batching win).
"""

from __future__ import annotations

import time
from typing import List, Optional

from ..serve.jobs import JobSpec
from ..serve.workloads import workload_names
from .store import FleetStore, QUEUED, RUNNING, TERMINAL


class FleetJobView:
    """Read-only job handle shaped like serve/jobs.Job for the HTTP
    handler: ``id``/``state``/``snapshot()``/``wait()``.  State is
    re-folded from the journal on each access — the store is the truth,
    this object is a cursor."""

    def __init__(self, service: "FleetService", job_id: str):
        self._service = service
        self.id = job_id

    def _record(self) -> Optional[dict]:
        return self._service.fleet.fold().jobs.get(self.id)

    @property
    def state(self) -> str:
        rec = self._record()
        return rec["state"] if rec else "unknown"

    @property
    def explorer_address(self):
        return None

    def snapshot(self) -> dict:
        rec = self._record()
        if rec is None:
            return {"id": self.id, "state": "unknown"}
        out = {
            "id": self.id,
            "state": rec["state"],
            "spec": rec["spec"],
            "tenant": rec["tenant"],
            "priority": rec["priority"],
            "attempt": rec["attempt"],
            "worker": rec["worker"],
            "error": rec["error"],
            "result": None,
        }
        if rec["group"]:
            out["group"] = rec["group"]
        if rec.get("gang"):
            out["gang"] = rec["gang"]
        if rec["state"] in TERMINAL:
            out["result"] = self._service.fleet.read_result(self.id)
        return out

    def wait(self, timeout: float = 0.0) -> bool:
        """Block until terminal (the ``?wait=`` result endpoint); the
        poll is against the journal fold, so progress made by any
        worker process is visible."""
        deadline = time.monotonic() + timeout
        while True:
            rec = self._record()
            if rec is None or rec["state"] in TERMINAL:
                return rec is not None
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.1)


class FleetService:
    """Drop-in for serve/server.CheckService over a fleet directory.
    Also its own ``store`` shim: the Handler reads
    ``service.store.list()`` and that is the only JobStore surface it
    uses."""

    def __init__(self, fleet_dir: str, lease_sec: float = 15.0):
        self.fleet = FleetStore(fleet_dir, lease_sec=lease_sec)
        self.fleet_dir = fleet_dir
        self.store = self  # Handler reads service.store.list()
        self.started_at = time.time()
        self.http_server = None
        self.address = None
        self.journal = self.fleet.journal
        self.journal.append("service_start", fleet_dir=fleet_dir)

    # -- store shim -----------------------------------------------------------

    def list(self) -> List[FleetJobView]:
        view = self.fleet.fold()
        return [FleetJobView(self, jid) for jid in sorted(view.jobs)]

    def counts(self) -> dict:
        return self.fleet.fold().counts()

    # -- CheckService surface -------------------------------------------------

    def submit(self, spec, tenant: str = "default",
               priority: int = 0) -> FleetJobView:
        if isinstance(spec, dict):
            spec = dict(spec)
            tenant = str(spec.pop("tenant", tenant))
            priority = int(spec.pop("priority", priority))
            spec = JobSpec.from_dict(spec)
        job_id = self.fleet.submit(
            spec, tenant=tenant, priority=priority
        )
        return FleetJobView(self, job_id)

    def get(self, job_id: str) -> Optional[FleetJobView]:
        if self.fleet.fold().jobs.get(job_id) is None:
            return None
        return FleetJobView(self, job_id)

    def cancel(self, job_id: str) -> bool:
        return self.fleet.cancel(job_id)

    def explore(self, job, port: int = 0):
        raise ValueError(
            f"job {job.id} ran on a fleet worker; fleet mode retains no "
            "checkers to explore — run the workload in-process "
            "(serve without --fleet-dir, or the check-tpu CLI) to "
            "attach the Explorer"
        )

    def metrics(self) -> dict:
        view = self.fleet.fold()
        out = {
            "service": "stateright-tpu-serve",
            "mode": "fleet",
            "uptime_sec": round(time.time() - self.started_at, 1),
            "fleet_dir": self.fleet_dir,
            "jobs": view.counts(),
            "journal_torn_lines": view.torn,
        }
        out.update(view.counters)
        # Gang occupancy: mean jobs per device dispatch.  1.0 means the
        # batcher never found compatible work; a queue of compatible
        # jobs drives it toward gang_max.
        dispatches = view.counters.get("gang_dispatches", 0)
        if dispatches:
            out["gang_occupancy"] = round(
                view.counters.get("gang_jobs_batched", 0) / dispatches, 3
            )
        active = [
            j for j in view.jobs.values()
            if j["state"] in (QUEUED, RUNNING)
        ]
        out["jobs_active"] = len(active)
        out["workers"] = {
            wid: {
                "platform": (w.get("desc") or {}).get("platform"),
                "device_kind": (w.get("desc") or {}).get("device_kind"),
                "accept_big": (w.get("desc") or {}).get("accept_big"),
                "alive": not w.get("stopped"),
                "last_seen": w.get("last_seen"),
                "vitals": w.get("vitals") or {},
            }
            for wid, w in view.workers.items()
        }
        out["workers_alive"] = sum(
            1 for w in view.workers.values() if not w.get("stopped")
        )
        # Fleet-wide histogram merge: per-worker vitals ship histogram
        # SNAPSHOTS (wave latency, host spans, job spans) through the
        # journal; bucket-wise addition folds them into one fleet view.
        # Commutative, so the merged view cannot depend on worker
        # enumeration order (pinned in tests/test_timeline.py).
        from ..obs.metrics import merge_histogram_snapshots

        merged = merge_histogram_snapshots(*(
            (w.get("vitals") or {}).get("histograms") or {}
            for w in view.workers.values()
        ))
        if merged:
            out["histograms"] = merged
        return out

    def status(self) -> dict:
        view = self.fleet.fold()
        return {
            "service": "stateright-tpu-serve",
            "mode": "fleet",
            "uptime_sec": round(time.time() - self.started_at, 1),
            "fleet_dir": self.fleet_dir,
            "workers": sum(
                1 for w in view.workers.values() if not w.get("stopped")
            ),
            "jobs": view.counts(),
            "workloads": workload_names(),
        }

    def shutdown(self) -> None:
        if self.http_server is not None:
            self.http_server.shutdown()
        self.journal.append("service_stop")
        self.journal.close()
