import importlib.util
import pathlib
import sys

import numpy as np
import pytest

import jax

# NOTE: no XLA_FLAGS here — the tests must see ONE device. Multi-device
# tests spawn subprocesses (``run_sub`` in test_dist.py).

jax.config.update("jax_enable_x64", False)

# Optional-dep shim: the property tests use `hypothesis`, but the tier-1
# suite must collect (and the properties still run, deterministically)
# without it. Install tests/_hypothesis_stub.py under the real name only
# when the library is absent.
if importlib.util.find_spec("hypothesis") is None:
    _stub_path = pathlib.Path(__file__).with_name("_hypothesis_stub.py")
    _spec = importlib.util.spec_from_file_location("hypothesis", _stub_path)
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis"] = _mod


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
