import os
import sys

# Make `pytest tests/` work without PYTHONPATH=src (and never set XLA device
# flags here — smoke tests must see exactly 1 CPU device; the dry-run tests
# spawn subprocesses with their own DRYRUN_DEVICES).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test (large-fleet smokes); deselect with "
        "-m 'not slow'")
