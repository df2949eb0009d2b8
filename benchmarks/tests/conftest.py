import os
import sys

# the tests run on the CPU, wherever they are started from
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# four virtual devices, so that the four-chip cell's rehearsal shards for real
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
