"""Make the benchmark's modules and the program importable for its tests:
``PYTHONPATH=src python -m pytest bench/tests`` from the checkout root."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
