"""repro.launch.compile_cache: the persistent compilation cache goes where
JAX_COMPILATION_CACHE_DIR says and nowhere else, or to the checkout's fixed
.jax_cache when that is unset; the test suite keeps it off."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_off_under_the_test_suite():
    assert compile_cache.enable() is None


def test_checkout_path_when_env_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_enable_compilation_cache", False)


def test_env_dir_is_the_only_cache(tmp_path):
    """A launcher run with JAX_COMPILATION_CACHE_DIR set writes its cache
    entries there and sets no directory of its own."""
    from repro.data import SimConfig, simulate_family, write_fasta

    fam = simulate_family(SimConfig(n_leaves=4, root_len=60, seed=0))
    write_fasta(tmp_path / "in.fasta", fam.names, fam.seqs)
    cache = tmp_path / "cache"
    before = set((ROOT / ".jax_cache").glob("*")) \
        if (ROOT / ".jax_cache").is_dir() else set()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.msa_run", "--fasta",
         str(tmp_path / "in.fasta"), "--out", str(tmp_path / "out"),
         "--method", "plain", "--backend", "jnp", "--tree", "none"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert any(cache.iterdir())
    after = set((ROOT / ".jax_cache").glob("*")) \
        if (ROOT / ".jax_cache").is_dir() else set()
    assert after == before
