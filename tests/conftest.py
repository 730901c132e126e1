"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see ONE device;
multi-device tests spawn subprocesses that set the flag before importing jax."""

import jax
import numpy as np
import pytest
from hypothesis import settings

from repro.core import emucxl as ecxl

# A property test's first example includes JAX's first compile, which can take
# far longer than hypothesis' 200 ms default deadline; later examples do not.
settings.register_profile("repro", deadline=None)
settings.load_profile("repro")


@pytest.fixture()
def lib():
    """A fresh, initialized emucxl instance with small tiers."""
    inst = ecxl.EmuCXL()
    inst.init(local_capacity=1 << 24, remote_capacity=1 << 26)
    yield inst
    if inst._initialized:
        inst.exit()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
