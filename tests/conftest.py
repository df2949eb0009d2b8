"""Test harness config.

Mirrors the reference's test substrate choice (local-mode Spark ≈ SURVEY
§4.1): all "distributed" behavior is tested on a single host with 8
virtual CPU devices via XLA_FLAGS, so multi-chip sharding code paths run
anywhere. Must run before jax is first imported.
"""

import os

# set, not setdefault: tier-1 is a CPU suite by design, and a machine
# with a chip exports JAX_PLATFORMS=tpu,cpu
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("KERAS_BACKEND", "jax")
# Keep TF (used only for reading TF-era artifacts) quiet and off any GPU.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")

import numpy as np
import pytest

_TESTS = os.path.dirname(os.path.abspath(__file__))
_BENCHMARK_TESTS = os.path.join(os.path.dirname(_TESTS), "benchmarks", "tests")


def pytest_configure(config):
    """A run of all of ``tests/`` (tier-1's command names nothing else)
    also collects ``benchmarks/tests/``: the benchmark's own comparers,
    readers and rehearsals, which judge every PR. They are added as a
    second argument, so each of their files keeps a node id of its own
    (under ``--dist loadfile``, a worker of its own). An xdist worker
    is handed the controller's arguments, that one among them."""
    given = [os.path.abspath(str(a).split("::")[0]) for a in config.args]
    if _TESTS in given and _BENCHMARK_TESTS not in given:
        config.args.append(_BENCHMARK_TESTS)



@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def image_dir(tmp_path_factory, rng):
    """A directory of small real image files (the reference committed
    tests/resources/images/*.jpg; we synthesize equivalents)."""
    from PIL import Image
    d = tmp_path_factory.mktemp("images")
    sizes = [(32, 48), (64, 64), (21, 33), (128, 96)]
    for i, (h, w) in enumerate(sizes):
        arr = rng.integers(0, 255, size=(h, w, 3), dtype=np.uint8)
        Image.fromarray(arr, "RGB").save(d / f"img_{i}.png")
    # one jpeg and one grayscale png
    arr = rng.integers(0, 255, size=(40, 40, 3), dtype=np.uint8)
    Image.fromarray(arr, "RGB").save(d / "img_jpg.jpg", quality=95)
    arr = rng.integers(0, 255, size=(16, 16), dtype=np.uint8)
    Image.fromarray(arr, "L").save(d / "img_gray.png")
    # one non-image file that must be ignored
    (d / "notes.txt").write_text("not an image")
    return str(d)


@pytest.fixture
def loaded_ahead(monkeypatch):
    """Make the engine's look one partition ahead deterministic: wait
    for the load it looks at. (The engine itself never waits; a test
    that counts carried boundaries cannot race a pool thread.)"""
    from concurrent.futures import wait

    from sparkdl_tpu.data import engine as engine_mod
    real = engine_mod._OrderedPartitions.take_ready

    def patient(self):
        fut = self._state.pending.get(self._state.next_to_yield)
        if fut is not None:
            wait([fut], timeout=30)
        return real(self)
    monkeypatch.setattr(engine_mod._OrderedPartitions, "take_ready",
                        patient)
