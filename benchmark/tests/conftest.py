"""The benchmark's own tests: CPU only, under a minute, outside tier-1
(`python -m pytest benchmark/tests -q`)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
