import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    import numpy as np
    return np.random.default_rng(0)
