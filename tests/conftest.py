"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see the real (1) device;
only the dry-run pins 512 fake devices, and multi-device collective tests
spawn subprocesses with their own flags."""

import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
