"""The benchmark's own tests: CPU, tiny sizes. They live under a path of the
benchmark (``BENCHMARK.json`` ``paths``) that the repo's tier-1 command
collects."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
