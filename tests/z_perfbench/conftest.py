"""The benchmark's own tests: its files are imported flat, as `run.py` does.

The directory's name sorts after `test_*.py` on purpose: collected first, these
files moved every other file to another xdist worker, and
`tests/test_chip_smoke.py` then ran in a worker where an earlier test file
had left `DLT_COST_TABLE=0` in the environment, and failed."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
