"""Test configuration: CPU backend with 8 virtual devices.

Mirrors the reference strategy of testing distributed logic without a real
cluster (SURVEY.md §4): the CPU XLA client is the "fake backend", and
--xla_force_host_platform_device_count=8 gives a virtual 8-chip mesh for SPMD
tests.  Must run before jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# Pin the config too: an environment or start-up file that set the platform
# after the lines above must not move the tests off the virtual 8-device mesh.
jax.config.update("jax_platforms", "cpu")

# XLA CPU lowers f32 dots to reduced precision by default; numeric comparisons
# against numpy need exact f32 matmuls.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _fresh_process_state():
    np.random.seed(0)
    import paddle_tpu

    paddle_tpu.seed(0)
    yield
    # a test (or an example it ran) that called fleet.init leaves the
    # process-wide mesh set, and model code traced by LATER tests of this
    # worker then takes its under-a-mesh branches: which tests failed
    # depended on which files shared a worker
    from paddle_tpu.distributed import fleet

    fleet.shutdown()
