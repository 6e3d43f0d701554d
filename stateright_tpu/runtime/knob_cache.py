"""Persisted engine-knob cache: discovered ``tuned_kwargs`` survive the
process.

A default-knob auto-tune run discovers right-sized engine knobs
(``tuned_kwargs()``).  Discovery is the expensive half — ~21 minutes for
the 61.5M-state ``2pc check 10`` — and would be re-paid by every
process if the result never left it.  This cache stores each workload's
tuned kwargs as one JSON object keyed by (workload, model identity,
device, engine geometry), under a directory of the caller's choosing;
fleet workers and the checking service reload instead of
rediscovering.

Staleness is harmless by construction: the engines' auto-tune grows
undersized knobs in place mid-run, and the caller golden-gates every
measured run — a cache entry that no longer reproduces the golden is
dropped (:func:`drop_knobs`) and the caller falls back to a fresh
discovery.  Writes are atomic (write + rename) so concurrent processes
can never leave a torn file.  Within one process every mutation holds a
module lock around its read-merge-write, so the checking service's
concurrent jobs (serve/scheduler.py) never lose each other's entries;
ACROSS processes (fleet workers) last-whole-file-writer wins,
which is fine for a cache whose entries are all independently
rediscoverable.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Optional

KNOBS_FILE = "knobs.json"

# Engine/protocol tags for knob_key(): bump when a default-geometry or
# knob-semantics change makes old entries misleading.  The sharded tag
# changed when the exchange went bucketed — its entries now carry the
# discovered ``bucket_slack`` rung (parallel/wave_loop.py), so a warm
# start skips the bucket overflow-retry ramp as well as auto-tune;
# pre-bucketing entries have no rung and must not shadow that.  Bumped
# to v2 for the adaptive sort-geometry ladder (entries carry the
# discovered ``sort_lanes`` rung), and to v3 for the sortless default +
# step ladder: v3 entries carry the discovered dedup path
# (``sortless`` 0/1 — a fallen-back workload must warm-start on the
# sort path without re-paying the fallback retry) and the ``step_lanes``
# rung; a v2 entry with an explicit ``sort_lanes`` would silently force
# every warm repeat onto the sort path and forfeit the election.
SINGLE_CHIP_ENGINE = "tpu-wavefront-v3"
SHARDED_ENGINE = "tpu-sharded-bucketed-v3"
# Tiered entries persist the budget-derived capacity (tiered/engine.py
# pins it — the in-HBM right-sizing rule would silently un-tier a
# warm-started repeat), so they must never shadow single-chip entries;
# the serve scheduler additionally keys their LABEL by the job's
# memory_budget_mb so entries never shadow each other across budgets.
TIERED_ENGINE = "tpu-tiered-v3"
# The composed engine shares neither geometry: its table is per-shard
# AND budget-pinned, so entries must shadow neither sharded nor tiered
# warm starts (the scheduler budget-keys the label here too).
TIERED_SHARDED_ENGINE = "tpu-tiered-sharded-v1"

# Serializes read-merge-write cycles within this process (two service
# jobs storing knobs for different workloads must both survive).
_LOCK = threading.Lock()


def _path(cache_dir: str) -> str:
    return os.path.join(cache_dir, KNOBS_FILE)


def knob_key(label: str, engine: str = SINGLE_CHIP_ENGINE) -> str:
    """The canonical cache key: workload label + device identity +
    engine/protocol version (geometry defaults change what discovery
    finds).  One definition shared by every caller (the checking
    service, serve/scheduler.py, and the fleet workers) so the key
    FORMAT cannot drift; each caller keeps its own label namespace
    (e.g. "serve:twophase:5").  Imports jax lazily — callers already
    run on a device."""
    import jax

    d = jax.devices()[0]
    kind = getattr(d, "device_kind", d.platform)
    return f"{label}|{d.platform}|{kind}|{engine}"


def _read_all(cache_dir: str) -> dict:
    """The whole cache, {} on any read/parse failure — a torn or
    hand-edited file degrades to rediscovery, never a crash."""
    try:
        with open(_path(cache_dir), "r", encoding="utf-8") as fh:
            data = json.load(fh)
        return data if isinstance(data, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _write_all(cache_dir: str, data: dict) -> None:
    os.makedirs(cache_dir, exist_ok=True)
    tmp = _path(cache_dir) + f".tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=2, sort_keys=True)
    os.replace(tmp, _path(cache_dir))


def load_knobs(cache_dir: str, key: str) -> Optional[dict]:
    """The cached kwargs dict for ``key``, or None.  Values come back as
    plain ints (engine kwargs are all integer knobs — except the tiered
    engines' fractional ``memory_budget_mb``, which stays a float)."""
    entry = _read_all(cache_dir).get(key)
    if not isinstance(entry, dict):
        return None
    knobs = entry.get("knobs")
    if not isinstance(knobs, dict) or not knobs:
        return None
    try:
        return {
            str(k): (float(v) if k == "memory_budget_mb" else int(v))
            for k, v in knobs.items()
        }
    except (TypeError, ValueError):
        return None


def store_knobs(cache_dir: str, key: str, knobs: dict, **meta) -> None:
    """Merge one entry into the cache file (atomic write + rename, under
    the process lock).  ``meta`` keys (e.g. the golden count that
    validated the knobs) are stored alongside for human inspection; only
    ``knobs`` is read back."""
    with _LOCK:
        data = _read_all(cache_dir)
        # Geometry knobs are integers — EXCEPT memory_budget_mb, the
        # tiered engines' fractional-MB budget (int() would floor the
        # spill-forcing test budgets to 0 and change the derived cap).
        data[key] = {"knobs": {
            k: (float(v) if k == "memory_budget_mb" else int(v))
            for k, v in knobs.items()
        }, **meta}
        _write_all(cache_dir, data)


def drop_knobs(cache_dir: str, key: str) -> None:
    """Invalidate one entry (a golden-gate failure at cached knobs, or a
    served job that errored at cached sizes)."""
    with _LOCK:
        data = _read_all(cache_dir)
        if data.pop(key, None) is not None:
            _write_all(cache_dir, data)
