"""Run with ``python -m pytest benchmark/tests`` from the repo's root (not
part of tier-1)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the mesh rehearsal needs four devices, and JAX reads this once
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
