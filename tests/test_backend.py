"""utils.backend.enable_compile_cache: where the persistent cache lives."""
from pathlib import Path

import jax
import pytest

from consensus_specs_tpu.utils import backend


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    backend.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    # an explicit default never overrides the environment
    backend.enable_compile_cache(str(tmp_path / "elsewhere"))
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    backend.enable_compile_cache()
    repo = Path(__file__).resolve().parents[1]
    assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
