"""The self-test runs on the CPU, the train driver on four virtual
devices. Nothing here is a device measurement."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
GRID = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(GRID))
for p in (GRID, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
