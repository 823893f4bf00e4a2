"""The harness's own checks run without a chip: CPU, four virtual devices."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
for p in (os.path.dirname(PERFBENCH), PERFBENCH, os.path.join(PERFBENCH, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)
