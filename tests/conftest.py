"""Shared fixtures.  NOTE: do NOT set XLA_FLAGS / host device count here —
smoke tests and benchmarks must see the real (single) device; only
``repro.launch.dryrun`` (run as its own process) forces 512 host devices.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(0)
