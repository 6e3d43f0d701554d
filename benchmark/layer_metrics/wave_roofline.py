"""The fused wave program's share of its memory roofline, in %: the bytes
a breadth-first search needs for the window's checks (work.py) over the
device's busy seconds in the trace times the chip's HBM bandwidth
(peaks.json)."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_work", pathlib.Path(__file__).resolve().parents[1] / "work.py")
_work = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_work)


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    done = [r for r in ctx["records"]
            if "error" not in r and r.get("state_width")]
    if not t or not peaks or not done or t["busy_s"] <= 0:
        return None
    need = sum(_work.bfs_bytes(r["unique"], r["generated"], r["state_width"])
               for r in done)
    return 100.0 * need / (t["busy_s"] * peaks["hbm_bytes_per_s"])
