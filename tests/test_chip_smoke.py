"""chip_smoke.py on the CPU: the refusal, the legs at tiny sizes with
TestNet, the compile-cache placement helper, and the source-keyed
native shim build. The chip run itself happens through the chip tool
(`python chip_smoke.py`); what tier-1 can hold is that the script
refuses anything but a TPU and that every leg's own checks pass on a
small model."""

import os
import shutil
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402  (repo root on sys.path first)

SRC = (16, 16)
BATCH = 8
N = 32


def test_refuses_without_a_tpu_and_runs_no_leg():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert "leg=" not in proc.stdout and "{" not in proc.stdout


def test_verdict_line_has_the_contract_keys_and_no_others():
    import json
    got = json.loads(chip_smoke.verdict_line("tpu", "TPU v5 lite", 1))
    assert got == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.fixture(scope="module")
def transform_leg(tmp_path_factory):
    return chip_smoke.leg_transform(
        "TestNet", BATCH, N, SRC, str(tmp_path_factory.mktemp("smoke")))


def test_transform_leg(transform_leg):
    assert transform_leg["features"].shape == (N, 16)
    assert transform_leg["packed"].shape == (N, 16 * 16 * 3 // 2)


def test_serve_leg(transform_leg):
    out = chip_smoke.leg_serve(transform_leg["mf"], BATCH,
                               transform_leg["packed"])
    assert out["max_inflight"] == 2


def test_fit_leg(tmp_path):
    out = chip_smoke.leg_fit(str(tmp_path))
    assert out["losses"][-1] < out["losses"][0]


def test_parity_leg(tmp_path):
    assert chip_smoke.leg_parity(str(tmp_path))["top1"] >= 0.95


def test_kernel_leg_interpreted():
    out = chip_smoke.leg_kernel(2, (12, 20), 20, interpret=True)
    assert set(out["max_abs_diff"]) == {"12->20", "20->20"}


def test_mesh_leg(transform_leg, tmp_path):
    import jax
    out = chip_smoke.leg_mesh(
        transform_leg["mf"], transform_leg["corpus"], SRC, 2, N,
        transform_leg["features"], str(tmp_path))
    assert out["devices"] == len(jax.local_devices()) == 8


class TestCompileCachePlacement:
    def test_env_placed_cache_is_left_to_jax(self, monkeypatch):
        import jax

        from sparkdl_tpu.utils import compile_cache
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: calls.append((name, value)))
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        assert compile_cache.configure_compile_cache() == \
            "/somewhere/else"
        assert calls == []

    def test_default_is_the_checkout(self, monkeypatch):
        import jax

        from sparkdl_tpu.utils import compile_cache
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: calls.append((name, value)))
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(REPO_ROOT, ".jax_cache")
        assert compile_cache.configure_compile_cache() == want
        assert calls == [(compile_cache.CONFIG_OPTION, want)]


def test_native_shim_rebuilds_when_the_source_hash_differs(
        tmp_path, monkeypatch):
    """The binary's name carries its source's hash: a changed source
    is a different name, so it builds — and a binary keyed on any
    other source is never loaded, whatever its file times say."""
    from sparkdl_tpu import native
    if native.get_lib() is None:
        pytest.skip("no toolchain: the shim cannot build here")
    old_sha = native.source_sha()
    src = tmp_path / "sparkdl_host.cpp"
    shutil.copy(native._SRC, src)
    with open(src, "a") as f:
        f.write("\n// a different source\n")
    # a binary left behind by the OLD source, newer than the new source
    stale = tmp_path / f"_sparkdl_host.{old_sha}.so"
    shutil.copy(native._lib_path(old_sha), stale)
    os.utime(stale, (2e9, 2e9))

    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    new_sha = native.source_sha()
    assert new_sha != old_sha
    assert native.get_lib() is not None
    assert native.build_info()["source_sha"] == new_sha
    assert os.path.exists(tmp_path / f"_sparkdl_host.{new_sha}.so")
    assert not stale.exists()
    # a second resolution loads the keyed binary without rebuilding
    built_at = os.path.getmtime(tmp_path / f"_sparkdl_host.{new_sha}.so")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.get_lib() is not None
    assert os.path.getmtime(
        tmp_path / f"_sparkdl_host.{new_sha}.so") == built_at
