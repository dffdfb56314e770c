"""The benchmark's tests: ``python -m pytest bench/tests`` from the root
of a checkout.  They run on the CPU; the chip is never needed."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
