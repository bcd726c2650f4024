import os
import sys

# Tests must see the single real CPU device (the 512-device override is
# confined to launch/dryrun.py per the dry-run spec).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Prefer the real hypothesis (installed in CI via requirements-dev.txt); fall
# back to the deterministic shim in tests/_compat for hermetic environments.
try:
    import hypothesis  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "_compat"))

import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def _lock_order_witness():
    """Opt-in lockdep: REPRO_LOCK_WITNESS=1 wraps every lock created by
    repro.* modules for the whole session and fails teardown if the
    acquisition-order graph contains a cycle (potential deadlock, even if
    no run ever deadlocked). Nightly runs the threaded test modules under
    this; the default path patches nothing."""
    if os.environ.get("REPRO_LOCK_WITNESS") != "1":
        yield
        return
    from repro.statics import witness as _witness

    wit = _witness.install()
    yield
    _witness.uninstall()
    wit.assert_no_cycles()


@pytest.fixture
def rng():
    return np.random.default_rng(0)

