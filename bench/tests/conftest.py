"""CPU tests of the benchmark at tiny sizes (cells in ``tinycells.py``)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = Path(__file__).resolve().parent
for p in (str(HERE.parents[1]), str(HERE.parents[1] / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from tinycells import build_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return build_root(tmp_path)
