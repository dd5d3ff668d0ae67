"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches see
the real single CPU device; only dryrun.py forces 512 host devices."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _strict_guard_policy():
    """Pin the degradation policy to 'raise' for every test.

    'raise' is also the default; pinning it here keeps a stray
    ``$REPRO_ON_FAILURE=fallback`` from letting an engine bug silently
    demote to the XLA oracle, under which every engine-vs-reference
    equivalence test would vacuously pass. Chaos tests opt into
    fallback explicitly via ``robust.failure_policy('fallback')``.
    Also guarantees no armed fault site leaks across tests.
    """
    from repro import config
    from repro.robust import faults

    prev = config._ON_FAILURE
    config.set_on_failure("raise")
    yield
    config._ON_FAILURE = prev
    faults.disarm()
