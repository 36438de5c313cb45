"""The benchmark's own checks run on the CPU: ``python -m pytest bench/checks``
(outside the repo's tier-1 ``testpaths``). The CPU backend shows four
devices, so that agents' placement on a four-chip cell can be checked."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FLAG).strip()
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
