"""CPU checks of the run plumbing: the compile-cache helper, the kernel
path counters and ``chip_smoke.py``'s refusal to run without a TPU."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.kernels import ops as kops
from repro.util import compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads it


def test_compile_cache_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert first == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_untileable_matmul_is_counted():
    key = ("dense_fwd", "no_tile")
    before = kops.KERNEL_TRACES[key]
    x = jnp.ones((13, 13), jnp.float32)
    y = kops.dense_fwd(x, x, "int8")
    assert y.shape == (13, 13)
    assert kops.KERNEL_TRACES[key] == before + 1
    assert "dense_fwd/no_tile=" in kops.format_kernel_traces()


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=300)
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "no TPU" in last["error"]
