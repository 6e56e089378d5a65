import os
import tempfile

# src/ reaches sys.path via pyproject [tool.pytest.ini_options] pythonpath
# (inserted before this conftest is imported; pytest>=7 is pinned).

# Tests run on the single host device.  The 512-device environment is ONLY
# for launch/dryrun.py (set there before any jax import); distributed tests
# spawn subprocesses with their own XLA_FLAGS.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Keep layouts hermetic: a developer's local autotune sweep (written to
# results/tuning/) must not leak tuned tile geometry into default
# build_layout() calls under test.  Tests that exercise the tuning cache
# set REPRO_TUNING_DIR / cache_dir themselves.
os.environ["REPRO_TUNING_DIR"] = tempfile.mkdtemp(
    prefix="repro-tuning-test-")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
