"""Root pytest conftest: path bootstrap.

Makes ``repro`` importable even when the caller forgot ``PYTHONPATH=src``
(the tier-1 command sets it; IDEs often don't), before any test module is
imported.
"""

import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
# benchmarks/ is imported as a package by test_sim_and_engine
_ROOT = os.path.dirname(os.path.abspath(__file__))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
