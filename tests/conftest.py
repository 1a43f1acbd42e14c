import os

# Tests run on the single host device (the dry-run sets its own 512-device
# flag in its own subprocesses, never globally).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
