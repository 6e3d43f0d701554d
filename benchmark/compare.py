"""The comparison that decides ``correct``: every request of the window
against the expected answer of the cell, which is pinned in the cell's
file or computed by the plain reference after the window.

Each number compared has its own limit.  All comparisons here are
exact, so every limit is 0; PERF.md gives the readings of sound runs
and of the control that each limit sits between.  A sharded check is
also held to its layout: every shard holds its share of the states.
"""

from __future__ import annotations

import importlib.util
import pathlib
from typing import Dict, List

REFERENCE_DIR = pathlib.Path(__file__).resolve().parent / "reference"


def expected(cell_file: Dict, kind: str, n: int) -> Dict:
    """The cell's expected answer: pinned in its file, or computed by its
    plain reference at the configuration's size."""
    if "expect" in cell_file:
        return dict(cell_file["expect"])
    name = cell_file["reference"]
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", REFERENCE_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    got = mod.check(n, **cell_file.get("reference_args", {}))
    if kind == "hunt":
        prop = cell_file["discovery"]
        return {"discovery": prop, "path_states": got["first_found_at"][prop]}
    return {"unique": got["unique"], "max_depth": got["max_depth"],
            "discoveries": got["discoveries"]}


def _ok(records: List[Dict]) -> List[Dict]:
    return [r for r in records if "error" not in r]


def compare_exhaustive(records: List[Dict], want: Dict) -> Dict[str, Dict]:
    done = _ok(records)
    unique_gap = max((abs(r["unique"] - want["unique"]) for r in done),
                     default=0)
    depth_gap = max((abs(r["max_depth"] - want["max_depth"]) for r in done),
                    default=0)
    wrong_names = sum(r["discoveries"] != sorted(want["discoveries"])
                      for r in done)
    out = {
        "unique_gap": {"value": unique_gap, "limit": 0},
        "depth_gap": {"value": depth_gap, "limit": 0},
        "discovery_names_wrong": {"value": wrong_names, "limit": 0},
        "requests_failed": {"value": len(records) - len(done), "limit": 0},
    }
    sharded = [r["unique_per_shard"] for r in done
               if r.get("unique_per_shard")]
    if sharded:
        out["shards_underfull"] = {
            "value": max(map(shards_underfull, sharded)), "limit": 0}
    return out


def shards_underfull(per_shard: List[int]) -> int:
    """Shards that hold less than half of an even share of the states:
    an owner-sharded visited set spreads its states over every shard by
    fingerprint, so none does unless states stay where they were made."""
    even = sum(per_shard) / len(per_shard)
    return sum(u < even / 2 for u in per_shard)


def compare_hunt(records: List[Dict], want: Dict) -> Dict[str, Dict]:
    done = _ok(records)
    name = want["discovery"]
    found = [r["paths"][name] for r in done if name in r["paths"]]
    path_gap = max((abs(p["states"] - want["path_states"]) for p in found),
                   default=0)
    return {
        "not_found": {"value": len(done) - len(found), "limit": 0},
        "path_gap": {"value": path_gap, "limit": 0},
        "holds_at_end": {"value": sum(p["holds_at_end"] for p in found),
                         "limit": 0},
        "requests_failed": {"value": len(records) - len(done), "limit": 0},
    }


COMPARISONS = {"exhaustive": compare_exhaustive, "hunt": compare_hunt}


def compare(kind: str, records: List[Dict], want: Dict) -> Dict[str, Dict]:
    out = COMPARISONS[kind](records, want)
    if not records:
        out["requests_failed"] = {"value": 1, "limit": 0}
    return out


def passed(compared: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def wrong_requests(kind: str, records: List[Dict], want: Dict) -> int:
    """How many requests failed or gave a wrong answer."""
    return sum(
        "error" in r or not passed(COMPARISONS[kind]([r], want))
        for r in records
    )
