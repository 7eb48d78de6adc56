"""Placement of JAX's persistent compilation cache by the entry points."""
import jax

from repro.launch.compile_cache import (DEFAULT_CACHE_DIR, ENV_VAR, REPO_ROOT,
                                        setup_compile_cache)


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = setup_compile_cache()
        assert path == str(REPO_ROOT / ".jax_cache") == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        # a fixed path inside this checkout, not a temporary one
        assert (REPO_ROOT / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert setup_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper overrides nothing
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
