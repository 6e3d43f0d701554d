#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by its name: the cell in
``BENCHMARK.json``, its configuration file, its traffic file
(``traffic/<mix>.json``, read by ``generator.py``), its expected answer
(``cells/<cell>.json``), and one reader per metric
(``e2e_metrics/<name>.py``, ``layer_metrics/<name>.py``).  A later cell
or metric adds files and edits none.

Order: place the compile cache, refuse without the chips, build the
configuration, warm up (set-up ends here), measure for ``--seconds``,
then compare with the expected answer.  ``--trace 1`` profiles the
measured window and reports the per-layer metrics; ``--trace 0``
reports the end-to-end metrics.  The last line of standard output is
the result; the last lines of standard error are the numbers compared,
each beside its limit.  Without a TPU, or with fewer chips than the
cell asks for, it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT_DIR = ROOT / ".bench_out"
# The benchmark's own compile cache, at a fixed path in the checkout; apart
# from the program's default (<checkout>/.jax_cache), which test runs fill.
CACHE_DIR = OUT_DIR / "jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT))

from compare import compare, expected, passed, wrong_requests  # noqa: E402
from generator import model_builder, run_window, warm_up  # noqa: E402


class NoChip(RuntimeError):
    pass


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_reader(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_" + kind + "_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_spec(name: str) -> dict:
    """The cell and every file it names."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}: one of {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(ROOT / config["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "cell_file": load_json(BENCH / "cells" / f"{name}.json"),
    }


def metrics_of(bench: dict, section: str, cell: str) -> list:
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


class CompileClock:
    """Sums JAX's backend-compile events (a persistent-cache load
    included) by phase: set-up, window, after."""

    def __init__(self):
        self.phase = "setup"
        self.sec: dict = {}
        self.count: dict = {}

    def __call__(self, event, duration, **_kw):
        if event == COMPILE_EVENT:
            self.sec[self.phase] = self.sec.get(self.phase, 0.0) + duration
            self.count[self.phase] = self.count.get(self.phase, 0) + 1


def start_jax(chips: int, require_chip: bool):
    """Compile cache inside the checkout, then the chip check."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)  # JAX does not make it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from stateright_tpu.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    # Every program goes to the cache, so that a run after the first
    # compiles nothing; no eviction (it fails on entries written without
    # it, and the cache holds tens of MB).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu" and len(devices) >= chips
    if require_chip and not on_chip:
        raise NoChip(
            f"needs {chips} TPU chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform} device(s)")
    return jax, devices, clock, on_chip


def peak_row(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def run(name: str, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, spec: dict = None) -> dict:
    """One run of one cell; returns the result line as a dict.  Off the
    chip (``require_chip=False``, tests only) it reports no metrics."""
    spec = spec or cell_spec(name)
    cell, traffic = spec["cell"], spec["traffic"]
    jax, devices, clock, on_chip = start_jax(cell["chips"], require_chip)
    used = devices[: cell["chips"]]
    peaks = peak_row(used[0].device_kind) if on_chip else None
    if trace:
        from stateright_tpu.obs.timeline import set_xprof

        set_xprof(True)  # the program's host spans become TraceAnnotations
    build = model_builder(spec["config"],
                          spec["cell_file"].get("model_overrides", {}))
    warm_up(build, traffic, spans=trace)

    trace_dir = OUT_DIR / f"trace-{name}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    clock.phase = "window"
    setup_s = time.perf_counter() - T_START
    records, window_s = run_window(build, traffic, seconds, spans=trace)
    clock.phase = "after"
    if len(records) < traffic.get("min_window_requests", 1):
        print(f"benchmark: only {len(records)} requests in the window; the "
              f"traffic asks for {traffic['min_window_requests']}",
              file=sys.stderr)
    reduced = {}
    if trace:
        jax.profiler.stop_trace()
    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used
    ) if on_chip else None
    if trace:
        from trace_reduce import reduce_trace

        reduced = reduce_trace(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    want = expected(spec["cell_file"], traffic["kind"],
                    spec["config"]["n"])  # the plain reference runs here
    compared = compare(traffic["kind"], records, want)
    ctx = {
        "records": records, "window_s": window_s, "setup_s": setup_s,
        "compile": clock, "trace": reduced, "peaks": peaks,
    }
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    if on_chip:
        for m in metrics_of(spec["bench"], section, name):
            value = load_reader(
                "layer_metrics" if trace else "e2e_metrics", m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": passed(compared),
        "attempted": len(records),
        "failed": wrong_requests(traffic["kind"], records, want),
        "metrics": metrics,
        "device": device,
    }
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["seed"] = seed
    result["window_compiles"] = clock.count.get("window", 0)
    result["errors"] = [r["error"] for r in records if "error" in r][:3]
    result["compared"] = compared  # last key: the numbers and their limits
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 3
    for key, c in result["compared"].items():
        print(f"compared {key}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
