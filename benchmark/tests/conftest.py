"""The benchmark's own tests: the trace reduction, the byte counts of the
roofline, and whole runs on the CPU with faults planted under the timed
path.  Run them from the root of the repo:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
