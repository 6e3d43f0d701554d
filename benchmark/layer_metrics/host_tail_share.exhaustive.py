"""Host-tail share of the exhaustive cells' wave loop (parallel/wave_loop.py),
from the program's host spans: see _host_tail.py."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_host_tail", pathlib.Path(__file__).with_name("_host_tail.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)


def read(ctx):
    return _mod.share(ctx)
