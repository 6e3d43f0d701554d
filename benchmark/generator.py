"""The one load generator: reads a traffic file's parameters and drives
the system under test with them.

A request is one check as a user of ``check-tpu`` runs it: build the
model, spawn the configuration's engine with the workload's kwargs
(``spawn_tpu``: auto-tune on, no knob cache), ``join()``, and read the
result, discoveries with their paths included.  A configuration may name
``spawn_tpu_sharded`` as its ``engine``: the kwargs are then translated as
``check-tpu --sharded`` translates them and the engine runs over a 1-D
mesh of the cell's chips.  Its ``kwargs`` object is merged over the
workload's; a cell file's ``target_max_depth`` bounds every check.  The
loop is closed with one client: the next request starts when the last
one has returned.
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext
from typing import Callable, Dict, List, Tuple


# The single-chip engine's knobs that ``check-tpu --sharded`` drops.
SINGLE_CHIP_ONLY = ("log_capacity", "waves_per_call", "auto_tune")


def engine_kwargs(engine: str, spec_kwargs: Dict, cfg_kwargs: Dict) -> Dict:
    """The workload's kwargs as ``check-tpu`` hands them to ``engine``,
    with the configuration's own ``kwargs`` merged over them."""
    kwargs = dict(spec_kwargs)
    if engine == "spawn_tpu_sharded":
        if "max_frontier" in kwargs:
            kwargs["chunk_size"] = kwargs.pop("max_frontier")
        for key in SINGLE_CHIP_ONLY:
            kwargs.pop(key, None)
    kwargs.update(cfg_kwargs)
    return kwargs


def model_builder(cfg: Dict, cell_file: Dict, chips: int
                  ) -> Callable[[], Tuple[object, Dict]]:
    """``() -> (model, request)`` for a configuration file: ``request``
    holds the ``engine`` to spawn, its ``kwargs`` and the cell's
    ``target_max_depth`` (None when unbounded).  A cell's property
    variant (``model_overrides``) is applied to the actor model's
    configuration dataclass (``model.cfg``)."""
    from stateright_tpu.serve.workloads import build_model

    overrides = cell_file.get("model_overrides", {})
    engine = cfg["engine"]
    mesh = None
    if engine == "spawn_tpu_sharded":
        import jax
        import numpy as np

        mesh = jax.sharding.Mesh(np.array(jax.devices()[:chips]), ("shards",))

    def build():
        model, spec, _ = build_model(cfg["workload"], cfg["n"], cfg["network"])
        if overrides:
            model = dataclasses.replace(model.cfg, **overrides).into_model()
        kwargs = engine_kwargs(engine, spec.tpu_kwargs, cfg.get("kwargs", {}))
        if mesh is not None:
            kwargs["mesh"] = mesh
        return model, {"engine": engine, "kwargs": kwargs,
                       "target_max_depth": cell_file.get("target_max_depth")}

    return build


def annotate(name: str, on: bool):
    if not on:
        return nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def one_request(build, traffic: Dict, spans: bool = False) -> Dict:
    """Run one check and return what the comparison and the metrics
    read.  ``latency_s`` runs from the spawn to the discoveries read."""
    from stateright_tpu.core.has_discoveries import HasDiscoveries

    model, request = build()
    builder = model.checker()
    builder = builder.finish_when(getattr(HasDiscoveries, traffic["finish_when"]))
    if request["target_max_depth"] is not None:
        builder = builder.target_max_depth(request["target_max_depth"])
    t0 = time.perf_counter()
    with annotate("bench/spawn", spans):
        ck = getattr(builder, request["engine"])(**request["kwargs"])
    with annotate("bench/join", spans):
        ck.join()
    with annotate("bench/discoveries", spans):
        found = ck.discoveries()
    t1 = time.perf_counter()
    metrics = ck.metrics()
    exchange = metrics.get("accounting", {})
    compiled = getattr(ck, "_compiled", None)
    rec = {
        "latency_s": t1 - t0,
        "unique": ck.unique_state_count(),
        "generated": ck.state_count(),
        "max_depth": ck.max_depth(),
        "discoveries": sorted(found),
        "paths": {},
        "state_width": getattr(compiled, "state_width", None),
        "host_sec": metrics.get("host_sec_total"),
        "device_call_sec": metrics.get("device_call_sec_total"),
        # the sharded engine's all_to_all (useful and transmitted bytes)
        # and its layout; None on the single-chip engine
        "exchange_payload_bytes_total": exchange.get(
            "exchange_payload_bytes_total"),
        "exchange_bytes_total": exchange.get("all_to_all_bytes_total"),
        "unique_per_shard": exchange.get("unique_per_shard"),
    }
    for name, path in found.items():
        states = path.into_states()
        prop = model.get_property(name)
        rec["paths"][name] = {
            "states": len(states),
            # what the last state says of the property it was found for
            "holds_at_end": bool(prop.condition(model, states[-1])),
        }
    return rec


def run_window(build, traffic: Dict, seconds: float, spans: bool = False
               ) -> Tuple[List[Dict], float]:
    """Requests back to back until ``seconds`` have passed; the request
    running then is finished and counted.  Returns the records (a
    request that raised is a record with ``error``) and the window's
    wall seconds."""
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise ValueError("the generator drives a closed loop with one client")
    records: List[Dict] = []
    t0 = time.perf_counter()
    with annotate("bench/window", spans):
        while time.perf_counter() - t0 < seconds:
            try:
                with annotate("bench/request", spans):
                    records.append(one_request(build, traffic, spans))
            except Exception as exc:  # a failed request counts as failed
                records.append({"error": f"{type(exc).__name__}: {exc}"})
    return records, time.perf_counter() - t0


def warm_up(build, traffic: Dict, spans: bool = False) -> None:
    """The set-up's requests: every shape the window will use, compiled
    and loaded (a whole check grows through every geometry it needs)."""
    for _ in range(int(traffic.get("warmup_requests", 1))):
        one_request(build, traffic, spans)
