"""The benchmark's own tests, on the CPU: ``python -m pytest bench/tests``
from the checkout's root (tier-1 collects only ``tests/``)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
