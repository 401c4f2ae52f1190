"""Bring-up plumbing: the compile-cache placement, the device table, and
``chip_smoke.py``'s refusal to report success without a TPU."""

import os
import subprocess
import sys
import types

import jax
import pytest

from repro.kernels import tuning
from repro.launch import compile_cache, roofline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Restore jax's cache-directory setting: the suite never turns the
    persistent cache on, so nothing may compile while it is set."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # left to jax, which reads the variable itself: no other dir is set
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_repo_path(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert first == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_unknown_tpu_kind_raises(monkeypatch):
    with pytest.raises(ValueError, match="TPU v99"):
        roofline.device_spec("TPU v99")
    # the tuner's lookup on a TPU it has no row for: an error, not v5e
    monkeypatch.setattr(tuning.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tuning.jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="TPU v99", platform="tpu")])
    with pytest.raises(ValueError, match="TPU v99"):
        tuning.vmem_budget()


def test_v5e_row_and_cpu_target():
    spec = roofline.device_spec("TPU v5 lite")
    assert (spec.peak_flops, spec.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in spec.source
    # off-TPU the tuner sizes tiles for the v5e it models
    assert tuning.device_spec() is roofline.V5E
    assert tuning.vmem_limit_bytes() <= spec.vmem_bytes


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr
