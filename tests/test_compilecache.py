"""Persistent XLA compilation-cache wiring (launch/compilecache).

The grid's short runs are warm-up dominated, so ``--compile-cache``
turns on jax's persistent compilation cache with the min-compile-time
floor dropped to zero.  Under test:

  * placement: ``JAX_COMPILATION_CACHE_DIR``, when set, is used exactly
    (no subdirectory, and an explicit directory does not override it);
    otherwise the default is ``.jax_cache/`` at the checkout root, which
    git ignores;
  * ``enable`` creates the directory, a fresh program populates it, and
    recompiling the same program after dropping the in-memory caches is
    served FROM DISK — observed through the module's hit/miss counters,
    the same numbers the bench surfaces as ``compile_time_s/*``'s
    derived column.

The enable tests snapshot and restore the jax config (and reset the
in-process cache handle) so the rest of the suite never writes cache
files or pays lookup overhead.
"""
import contextlib
import os

import jax
import jax.numpy as jnp

from repro.launch import compilecache


@contextlib.contextmanager
def _restored_cache_config():
    from jax.experimental.compilation_cache import \
        compilation_cache as cc

    old_dir = jax.config.jax_compilation_cache_dir
    old_min_t = jax.config.jax_persistent_cache_min_compile_time_secs
    old_min_b = jax.config.jax_persistent_cache_min_entry_size_bytes
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min_t)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          old_min_b)
        cc.reset_cache()


def test_env_cache_dir_is_used_exactly(monkeypatch, tmp_path):
    env_dir = tmp_path / "from-env"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
    assert compilecache.default_cache_dir() == str(env_dir)
    with _restored_cache_config():
        # the env var wins over 'auto' and over an explicit directory
        assert compilecache.enable("auto") == str(env_dir)
        assert compilecache.enable(str(tmp_path / "other")) == str(env_dir)
        assert jax.config.jax_compilation_cache_dir == str(env_dir)
        jax.jit(lambda x: (x * 5.375 - 0.25).sum())(
            jnp.arange(89, dtype=jnp.float32)).block_until_ready()
    assert os.listdir(env_dir), "the compile must land in the env dir"
    assert not (tmp_path / "other").exists()


def test_default_cache_dir_is_checkout_jax_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compilecache.default_cache_dir() == os.path.join(root,
                                                            ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_enable_persists_and_serves_from_disk(monkeypatch, tmp_path):
    """``enable`` -> fresh program persisted (a miss, files on disk);
    same program after ``jax.clear_caches()`` -> deserialized from disk
    (a hit).  The counters are how the bench's ``compile_time_s/*``
    derived column distinguishes a warm-from-disk run from a cold one."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    target = tmp_path / "cc"
    with _restored_cache_config():
        path = compilecache.enable(str(target))
        assert path == str(target) and os.path.isdir(path)
        assert compilecache.cache_dir() == path
        # idempotent re-point
        assert compilecache.enable(str(target)) == path

        # an odd shape + odd constants: a program no other test compiles
        f = jax.jit(lambda x: (x * 3.125 + 0.625).sum())
        x = jnp.arange(97, dtype=jnp.float32)
        before = compilecache.counters()
        f(x).block_until_ready()
        assert os.listdir(path), "compile must persist an executable"
        mid = compilecache.counters()
        assert mid["misses"] >= before["misses"] + 1, \
            "a never-seen program must count as a cache miss"

        jax.clear_caches()   # drop the in-memory executable cache
        g = jax.jit(lambda x: (x * 3.125 + 0.625).sum())
        g(x).block_until_ready()
        after = compilecache.counters()
        assert after["hits"] >= mid["hits"] + 1, \
            "recompiling the same program must be served from disk"
