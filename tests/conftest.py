# Tests run on the single real CPU device (fake multi-device hosts are set
# up ONLY inside subprocess tests, never here).
import os
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import warnings

warnings.filterwarnings("ignore", category=DeprecationWarning)

import jax
import pytest


@pytest.fixture(scope="session")
def plan():
    from repro.core.plan import single_device_plan
    return single_device_plan()


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
