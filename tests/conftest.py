import os

# Tests run on the single real CPU device (the dry-run sets its own 512-device
# flag in a subprocess; never set it globally here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest
from hypothesis import settings

from repro.data import make_simulated_pool, make_workload

# Hypothesis profile pinned for CI stability: no per-example deadline (hosted
# runners stall unpredictably under load — deadline flakes are pure noise)
# and derandomized so a red property test reproduces from the log.
settings.register_profile("ci", deadline=None, derandomize=True)
settings.load_profile("ci")


@pytest.fixture(scope="session")
def agnews():
    return make_workload("agnews", n_train=512, n_val=128, n_test=256, seed=1)


@pytest.fixture(scope="session")
def gsm8k():
    return make_workload("gsm8k", n_train=512, n_val=128, n_test=256, seed=1)


@pytest.fixture(scope="session")
def pool():
    return make_simulated_pool("qwen3")


@pytest.fixture(scope="session")
def fitted_rb(agnews, pool):
    from repro.core import Robatch

    return Robatch(pool, agnews, coreset_size=64, router_kind="knn").fit()
