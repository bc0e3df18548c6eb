import os

# The suite runs on the CPU, also on a machine with a TPU: a test process
# that took the chip would hold it from the program that needs it.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# 8 local CPU devices for multi-device shard_map tests (NOT the 512-device
# production mesh — that is exercised only by launch/dryrun.py).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.parallel.sharding import FusionConfig, ParallelContext  # noqa: E402
from repro.compat import make_mesh  # noqa: E402


@pytest.fixture(scope="session")
def mesh():
    return make_mesh((2, 4), ("data", "model"))


@pytest.fixture(scope="session")
def ctx(mesh):
    return ParallelContext.from_mesh(mesh)


@pytest.fixture(scope="session")
def ctx1d():
    m = make_mesh((8,), ("model",))
    return ParallelContext.from_mesh(m)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
