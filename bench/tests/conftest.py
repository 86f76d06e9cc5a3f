"""Self-tests of the benchmark harness, on the CPU:

    python -m pytest bench/tests

They import the harness's modules from ``bench/`` and the program from
``src/``."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
