"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see ONE device
(the dry-run sets its own 512-device flag in its own process).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def assert_close(a, b, atol=1e-4, rtol=1e-4, msg=""):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=atol, rtol=rtol, err_msg=msg,
    )
