"""Shared benchmark bootstrap: make the repo importable when run as
``python benchmarks/foo.py``.  ``import _bootstrap`` as the first line of every
benchmark (benchmarks/ is sys.path[0] for direct script runs)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
