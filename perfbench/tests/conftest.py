"""The benchmark's own tests: `JAX_PLATFORMS=cpu python -m pytest
perfbench/tests -q`.  They run on the CPU at the tiny presets; no number
they see is a device number."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))):
    if p not in sys.path:
        sys.path.insert(0, p)
