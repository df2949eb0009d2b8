import os
import sys

import pytest

# the tests run on the CPU, wherever they are started from
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# four virtual devices, so that the four-chip cell's rehearsal shards for real
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


@pytest.fixture(autouse=True)
def _own_trace_dir(tmp_path, monkeypatch):
    """Each test's traced runs write their profile under its own directory: tests that run
    at once in several processes would otherwise clear one another's profile before it is
    read."""
    from benchmarks import harness
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "bench_trace"))
