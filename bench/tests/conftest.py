"""Tests of the benchmark itself, on the CPU, with no chip.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They are not under the repository's ``testpaths``, so the repository's
own test run does not collect them.
"""

import os
import sys
from pathlib import Path

# four virtual CPU devices, for the four-chip cell's mesh; set before JAX loads
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
