"""Tests of the benchmark itself, on the CPU: ``python -m pytest bench/tests``.

They sit outside the repository's test paths, and import the benchmark's
modules from ``bench/``."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
