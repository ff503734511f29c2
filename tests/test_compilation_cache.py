"""enable_persistent_cache: where the cache lives is decided outside the
program (JAX_COMPILATION_CACHE_DIR), else it is <checkout>/.xla_cache."""

import os

import jax
import pytest

from kubernetes_tpu.utils import compilation_cache

_CONFIG_KEYS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_entry_size_bytes",
    "jax_persistent_cache_min_compile_time_secs",
)


@pytest.fixture
def jax_cache_config():
    """The suite runs on jax's in-memory cache; put the config back."""
    saved = {k: getattr(jax.config, k) for k in _CONFIG_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_dir_is_left_alone(monkeypatch, tmp_path, jax_cache_config):
    monkeypatch.delenv("KTPU_COMPILATION_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    jax.config.update("jax_compilation_cache_dir", "sentinel-from-jax")
    assert compilation_cache.enable_persistent_cache() == str(
        tmp_path / "outside")
    # the program set no directory (jax reads the env var itself at start-up)
    assert jax.config.jax_compilation_cache_dir == "sentinel-from-jax"
    # ... and created none
    assert not (tmp_path / "outside").exists()
    # the thresholds are still the program's to set
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_default_is_checkout_xla_cache(monkeypatch, tmp_path,
                                       jax_cache_config):
    monkeypatch.delenv("KTPU_COMPILATION_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compilation_cache.DEFAULT_CACHE_DIR == os.path.join(
        checkout, ".xla_cache")
    monkeypatch.setattr(compilation_cache, "DEFAULT_CACHE_DIR",
                        str(tmp_path / ".xla_cache"))
    got = compilation_cache.enable_persistent_cache()
    assert got == str(tmp_path / ".xla_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert os.path.isdir(got)


def test_off_switch(monkeypatch, tmp_path, jax_cache_config):
    monkeypatch.setenv("KTPU_COMPILATION_CACHE", "0")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compilation_cache.enable_persistent_cache() == ""
    assert jax.config.jax_compilation_cache_dir == before


def test_no_second_way_to_name_a_directory(monkeypatch, tmp_path,
                                           jax_cache_config):
    """KTPU_COMPILATION_CACHE=<path> used to name the directory; it is an
    on/off switch now (an invalid bool degrades to on)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("KTPU_COMPILATION_CACHE", str(tmp_path / "elsewhere"))
    monkeypatch.setattr(compilation_cache, "DEFAULT_CACHE_DIR",
                        str(tmp_path / ".xla_cache"))
    assert compilation_cache.enable_persistent_cache() == str(
        tmp_path / ".xla_cache")
    assert not (tmp_path / "elsewhere").exists()
