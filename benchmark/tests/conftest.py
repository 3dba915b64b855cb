"""The benchmark's own tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os

import pytest


@pytest.fixture(autouse=True, scope="session")
def _cpu_only():
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        pytest.skip("the benchmark's tests rehearse on the CPU: set JAX_PLATFORMS=cpu")
