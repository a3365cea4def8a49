# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device; only launch/dryrun.py (and subprocess-based mesh
# tests) force a host-platform device count.
import contextlib

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@contextlib.contextmanager
def interpreted():
    """Inside the block, `repro.kernels.ops` runs the Pallas kernels in
    the interpreter where it would run the numpy references (off TPU).
    For tests that compare both paths in one test."""
    from repro.kernels import ops

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_pallas_interpret", lambda: True)
        yield


@pytest.fixture
def interpreted_kernels():
    """The whole test runs the GF(256) steps on the interpreted kernels."""
    with interpreted():
        yield
