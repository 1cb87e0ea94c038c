# NOTE: deliberately NO global XLA_FLAGS here — smoke tests and benchmarks
# must see the single real CPU device; only launch/dryrun.py (and the
# subprocess tests that invoke it) force the 512-placeholder-device platform.
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
