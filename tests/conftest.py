import os

# Tests run on the single host device (multi-device tests set their own
# fake-device flag in their own subprocesses, never globally).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
