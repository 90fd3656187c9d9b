"""Test-suite bootstrap: keep every test on the CPU backend.

The tests run on the CPU on any host, including one with an accelerator:
``JAX_PLATFORMS`` is set (unless the caller already set it) before any test
module imports JAX, so neither this process nor the child processes some
tests start reach for the chip.  Pallas kernels then run in interpret mode.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def pytest_addoption(parser):
    parser.addoption(
        "--update-golden",
        action="store_true",
        default=False,
        help="rewrite tests/cachesim/golden/*.json from the current engines "
        "instead of asserting against them (commit the diff deliberately)",
    )
