"""Where the benchmark lives, for the tests beside this file: puts
``benchmark/`` on ``sys.path`` so that ``harness`` imports as it does under
``benchmark/run.py``."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
