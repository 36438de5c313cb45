"""The benchmark's own checks run on the CPU: ``python -m pytest bench/checks``
(outside the repo's tier-1 ``testpaths``)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
