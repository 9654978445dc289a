import os

# Tests run on the single real CPU device (the dry-run sets its own XLA_FLAGS
# in-process; do NOT force 512 host devices here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

from repro.utils.compile_cache import enable_compile_cache

# Persist XLA compilations across pytest runs: the suite is compile-bound on
# CPU (model graphs under grad/vmap/scan), so reruns drop from minutes to
# seconds.
enable_compile_cache()


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
