"""The benchmark's own tests run on the CPU, with four virtual devices for
the mesh cell; both must be set before JAX is first imported.

    python -m pytest benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

_BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parents[1] / "src")]
