"""The benchmark's own tests: ``python -m pytest benchmark/tests``.
On the CPU, toy sizes. Not part of tier-1 (``tests/``)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the dp=4 cell rehearses on four virtual CPU devices
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
