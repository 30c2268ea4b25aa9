"""``enable_compile_cache``: JAX's own variable wins; otherwise one fixed
directory under the repository."""
import os

import jax
import pytest

from repro.common.utils import REPO_ROOT, enable_compile_cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_fixed_repo_path_when_unset(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == os.path.join(REPO_ROOT, ".cache", "jax_compile")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path          # same path every call


def test_environment_variable_left_to_jax(monkeypatch, cache_config,
                                          tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
