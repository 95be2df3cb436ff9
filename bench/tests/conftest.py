import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _x32():
    """The benchmark computes in 32 bits; another conftest may have turned
    64-bit mode on for the process."""
    import jax

    with jax.enable_x64(False):
        yield
