"""The benchmark's own tests run on the CPU, at sizes a test run can hold:
`python3 -m pytest perfbench/tests -q`. They are not part of the repo's
tier-1 suite (that collects `tests/` only)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
