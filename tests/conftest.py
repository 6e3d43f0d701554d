"""Test configuration.

TPU/JAX tests run on a virtual 8-device CPU mesh so multi-chip sharding is
exercised without hardware; set up before any jax import.
"""

import os
import sys

# Hermetic default: the cpu platform.  To run the tpu-marked goldens on a
# machine with a chip, opt in explicitly:
#   STATERIGHT_TPU_TEST_PLATFORM=tpu pytest -m tpu
_platform = os.environ.get("STATERIGHT_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# If jax is already imported, the env var is too late — pin the config
# directly, before any backend initializes.
if "jax" in sys.modules:
    import jax

    jax.config.update("jax_platforms", _platform)

# The wavefront programs take tens of seconds to compile cold but are
# stable across runs: keep them in the one fixed cache directory.
from stateright_tpu.runtime.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()
