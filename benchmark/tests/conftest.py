"""The benchmark's own tests run on the CPU at tiny Kronecker scales:
``pytest benchmark/tests``.  Nothing here is a device number."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _program_defaults(monkeypatch):
    """The harness refuses LUX_* settings: a cell measures defaults."""
    for k in [k for k in os.environ if k.startswith("LUX_")]:
        monkeypatch.delenv(k)
