import os
import sys
from pathlib import Path

# tests run against the source tree (PYTHONPATH=src also works)
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

# Smoke tests must see exactly ONE device (the dry-run sets its own flag in a
# subprocess); keep any user XLA_FLAGS but never force a device count here.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

from repro.util import enable_compile_cache  # noqa: E402

# The same persistent compile cache as every other entry point: a test run
# pre-warms the simulator/predictor compiles for the benchmarks after it.
enable_compile_cache()
import pytest  # noqa: E402

from repro.configs.base import ShapeConfig  # noqa: E402


@pytest.fixture(scope="session")
def tiny_train_shape():
    return ShapeConfig("tiny_train", 32, 2, "train")


@pytest.fixture(scope="session")
def tiny_prefill_shape():
    return ShapeConfig("tiny_prefill", 32, 2, "prefill")


@pytest.fixture()
def rng():
    return jax.random.key(0)
