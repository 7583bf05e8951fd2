import os

# 8 placeholder devices for the distribution/integration tests (the dry-run
# uses 512, but only inside launch/dryrun.py).  Harmless for single-device
# tests: unsharded computations run on device 0.  Must be set before the
# first jax import anywhere in the session.
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def chip_smoke():
    """The repository-root ``chip_smoke.py``, imported as a module."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
