"""Shared test fixtures.  NOTE: no XLA_FLAGS here — tests must see the
container's single CPU device (the 512-device flag belongs ONLY to
launch/dryrun.py)."""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def mesh():
    """Single-device (1, 1) ("data", "model") mesh — rule logic is
    device-count independent; the 512-way layouts are exercised by the
    dryrun and the forced-host-device subprocess tests."""
    from repro.dist import make_mesh

    return make_mesh((1, 1), ("data", "model"))
