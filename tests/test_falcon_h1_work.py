"""Tier 1 runs `tests/`: the falcon_h1 work functions' hand counts and
the new cell's wiring live with the benchmark
(benchmark/tests/test_falcon_h1_work.py) and are collected here too."""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)        # `benchmark` is a top-level package

from benchmark.tests.test_falcon_h1_work import *  # noqa: E402,F401,F403
