"""Tests of the benchmark's own yardstick.  Not collected by the repo's
tier-1 run (which collects ``tests/``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
