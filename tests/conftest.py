import os
import sys

# tests must see ONE cpu device (the dry-run alone uses 512 host devices)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# tests keep JAX's persistent compile cache off, even where they call an
# entry point that would turn it on (src/repro/launch/compile_cache.py)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
