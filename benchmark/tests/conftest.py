"""The benchmark's own tests run on the CPU backend with four virtual
devices (the dp=4 rehearsal); run them by hand:

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
