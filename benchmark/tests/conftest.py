"""The benchmark's own tests run on the CPU, in seconds: the yardstick's
arithmetic (trace reduction, FLOP counts), the reference against the system
at toy widths, and the consistency of BENCHMARK.json with the files under
``benchmark/``.  Run: ``python -m pytest benchmark/tests -q``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
