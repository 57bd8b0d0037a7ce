"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Mirrors SURVEY.md §5's implication #4: distribution is tested without TPU
hardware via XLA's host-platform device-count flag. Must run before jax
initializes its backends, hence the env mutation at import time.
"""

# Force the CPU backend with 8 virtual devices so multi-chip paths run
# without hardware. tpu_olap.utils.platform is the one home for the
# env/config mutation (shared with bench.py's CPU rehearsal and tools).
from tpu_olap.utils.platform import force_cpu_devices  # noqa: E402

force_cpu_devices(8)  # raises if a backend beat us to initialization

import jax  # noqa: E402

# Engine.__init__ places the persistent compile cache at
# <checkout>/.jax_cache (platform.configure_compile_cache). A test session
# must not fill it with XLA:CPU entries that then travel with every chip
# call (the chip tool copies the tree as it stands): switch the cache off
# for the session — this sets no directory.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; register the marker so the soak
    # variants (e.g. tests/test_chaos_recovery.py) deselect cleanly
    # without an unknown-marker warning
    config.addinivalue_line(
        "markers", "slow: out-of-tier-1 soak tests (deselected by "
        "-m 'not slow')")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)
