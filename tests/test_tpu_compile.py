"""AOT compiles of the main path for a described TPU v5e chip.

Nothing here runs on a chip: the installed TPU compiler compiles for a
``v5e:2x2`` topology that is described, not attached, so a program the
chip's compiler would refuse fails here at no chip time
(/opt/skills/guides/on-chip-measurement, section 2).  The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU library, and xdist workers each import every test file.
"""

import os
import signal

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Loading libtpu installs a handler that prints a stack trace on
    # SIGTERM.  A test run cut by its time limit must end on its own
    # progress line, so SIGTERM gets its default action back.
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def _on(sharding, x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def test_graft_entry_step_compiles_at_paxos3_width(one_chip):
    import __graft_entry__ as g

    forward, (states,) = g.entry()
    compiled = jax.jit(forward).lower(_on(one_chip, states)).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_fused_run_program_compiles_for_paxos3(one_chip):
    """The single-chip fused wave program (``TpuChecker`` seed → run) of
    ``paxos check 3`` at a small geometry."""
    from stateright_tpu.parallel.wavefront import TpuChecker
    from stateright_tpu.serve.workloads import build_model

    class _Unstarted(TpuChecker):
        def _run(self):  # build the programs only; search nothing
            self._done.set()

    model, _, _ = build_model("paxos", 3)
    ck = _Unstarted(model.checker(), capacity=1 << 16, max_frontier=1 << 9)
    ck.join()
    seed, run = ck._build_run()
    seeded = jax.eval_shape(
        seed,
        jax.ShapeDtypeStruct(
            (ck._max_frontier, ck._compiled.state_width), jnp.uint32
        ),
        jax.ShapeDtypeStruct((), jnp.uint32),
    )
    compiled = run.lower(*[_on(one_chip, x) for x in seeded]).compile()
    mem = compiled.memory_analysis()
    # Donated in place: the carry aliases its inputs.
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes
    assert seeded[0].shape == (1 << 16,)
    assert np.dtype(seeded[2].dtype) == np.uint32
