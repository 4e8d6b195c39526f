"""The benchmark's own tests run on the CPU: the harness at a tiny size,
the mesh cell on 4 virtual devices, the trace reducer on a recorded chip
trace.  Run them from the root of the checkout:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
