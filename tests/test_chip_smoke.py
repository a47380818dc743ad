"""``chip_smoke.py`` rehearsed on the CPU at the config's smoke-test widths,
and the compile-cache placement its entry points share."""

import json
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(tmp_path, *args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=600)


def _last_json(r) -> dict:
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    return last


def test_chip_smoke_rehearsal_on_cpu(tmp_path):
    r = _smoke(tmp_path, "--cpu", "--reduced")
    last = _last_json(r)
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 1
    assert "phase A vs B: 8/8 streams equal" in r.stdout


def test_chip_smoke_four_replicas_on_four_virtual_devices(tmp_path):
    r = _smoke(tmp_path, "--chips", "4", "--cpu", "--reduced", devices=4)
    assert _last_json(r)["device"]["count"] == 4
    held = [ln for ln in r.stdout.splitlines()
            if ln.startswith("replicas=4: replica")]
    assert len(held) == 4 and len({ln.split(" on ")[1] for ln in held}) == 4
    assert "4 replicas vs 1: 16/16 streams equal" in r.stdout


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    r = _smoke(tmp_path)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_leaves_a_set_env_dir_alone(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_files_land_in_the_env_dir(tmp_path):
    # a fresh process, as the entry points run: JAX reads the variable,
    # the helper sets nothing, and the compiled program lands there
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
"""], env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())
