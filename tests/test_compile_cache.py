"""The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise at one fixed path in the repo; a process that sees
more than one device keeps none."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_follows_the_environment(monkeypatch, tmp_path,
                                       restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing else is set
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_a_fixed_repo_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_CACHE)
    assert compile_cache.REPO_CACHE.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path


def test_cache_is_off_with_more_than_one_device(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: ["d0", "d1"])
    was = jax.config.jax_enable_compilation_cache
    try:
        assert compile_cache.enable_compile_cache() is None
        assert not jax.config.jax_enable_compilation_cache
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
