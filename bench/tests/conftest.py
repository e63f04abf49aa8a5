"""CPU tests of the benchmark: ``python -m pytest bench/tests``.

Kernels run in interpret mode. Nothing here measures a time."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]
