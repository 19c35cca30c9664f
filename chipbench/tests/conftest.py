"""The benchmark's CPU tests; run them by path:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
