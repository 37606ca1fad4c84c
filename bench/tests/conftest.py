"""A run of a cell sets JAX's matmul precision for the whole process
(the configuration's ``matmul_precision``).  Each test here gives the
setting back, so that a test run after it in the same process (a
compile for the chip among them) sees what it would see alone."""

import jax
import pytest


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)
