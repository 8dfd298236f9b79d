"""The benchmark's own checks: ``python -m pytest benchmark/tests -q`` from
the root of the repo. Not part of tier-1. They run on the CPU, asked for by
name; the persistent compile cache stays off (an ahead-of-time TPU compile
written to it cannot be read back without a chip)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
