"""Puts the benchmark's own modules and the system under test on the path.

A module of its own, not a ``conftest.py``: the suite's other tests import
helpers from ``tests/conftest.py`` by the module name ``conftest``."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
