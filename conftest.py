"""The tiny size of each benchmark cell's traffic, for the loops that
``benchmark/tests/tiny_cells.TRAFFIC`` does not list: the rwkv6 training
cell runs its own loop over the same job as the granite training cell.

It sits at the root, not in ``benchmark/tests``: pytest loads the root's
conftest before ``tests/conftest.py``, whose helpers tests import by the
module name ``conftest``, and a conftest it loaded later would take that
name from them."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmark" / "tests"))

import tiny_cells  # noqa: E402

tiny_cells.TRAFFIC.setdefault("train_rwkv6", dict(tiny_cells.TRAFFIC["train"]))
