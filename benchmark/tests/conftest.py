"""The harness's own tests: ``python -m pytest benchmark/tests -q`` from
the root of the checkout, a few seconds on the CPU. Not part of the
repo's tier-1 suite under ``tests/``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
