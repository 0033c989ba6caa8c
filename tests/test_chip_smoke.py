"""The compile-cache helper (sub-second, no JAX subprocess) and a
``slow``-marked CPU dry run of ``chip_smoke.py`` at toy sizes with its
three owner processes."""

import json
import os
import subprocess
import sys

import jax
import pytest

from ddstore_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_env_set_leaves_config_alone(monkeypatch, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_dir_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # Fixed: a second call (another process, another day) names the same
    # directory.
    assert compile_cache.enable_compile_cache() == want


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [1, 4])
def test_chip_smoke_dry_run(n_dev):
    """One virtual device takes the one-chip legs, four take the dp=4 and
    dp=2 x sp=2 legs with their one-device comparisons."""
    env = dict(os.environ, XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={n_dev}"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--dry-run"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0] == "DRY RUN (cpu)"
    result = json.loads(lines[-1])
    assert result == {"ok": True, "dry_run": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": n_dev}}
    kernel = "kernel" if n_dev == 1 else "kernel (dp=2 x sp=2)"
    for name in ("store", kernel, "ragged"):
        assert f"--- leg {name} ok" in proc.stdout
    if n_dev == 1:
        # the experts leg: the described configurations against their
        # references, then the scan and the convolution alone
        assert "--- leg experts ok" in proc.stdout
        for line in ("glm47-flash-ep8 b=1", "lfm2-8b-a1b-ep4 b=1",
                     "nemotron3-nano-ep16 b=1", "sdar-30b-a3b-ep8 b=1",
                     "== the recurrence in float32",
                     "conv + bias + silu kernels"):
            assert line in proc.stdout, line


@pytest.mark.slow
def test_chip_smoke_fails_without_a_tpu():
    """Without --dry-run the script pins the TPU platform itself: on this
    CPU box it must exit non-zero, print no result line, and leave no
    owner process behind."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
