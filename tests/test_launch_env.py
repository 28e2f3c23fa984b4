"""What the entry points set up before anything compiles: the
persistent compile cache's directory and the TPU compiler flags."""

import os

import jax
import pytest

from repro.launch import compile_cache
from repro.launch import train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_uses_environment_dir(monkeypatch, tmp_path,
                                            cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "env"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "env")
    # nothing else was set: JAX keeps the directory it read
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "env")


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch,
                                                    cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), \
            f"{first} is not git-ignored"


@pytest.mark.parametrize("have,expect", [
    ("", " ".join(train.TPU_FLAGS)),
    # the host's own flags survive, and a flag it names keeps its value
    ("--xla_jf_rematerialization_percent_shared_memory_limit=97 "
     "--xla_enable_async_all_gather=false",
     "--xla_jf_rematerialization_percent_shared_memory_limit=97 "
     "--xla_enable_async_all_gather=false "
     + " ".join(train.TPU_FLAGS[1:])),
])
def test_tpu_flags_append_to_host_flags(monkeypatch, have, expect):
    monkeypatch.setenv("LIBTPU_INIT_ARGS", have)
    assert train.append_tpu_flags() == expect
    assert os.environ["LIBTPU_INIT_ARGS"] == expect
    # idempotent: a second call adds nothing
    assert train.append_tpu_flags() == expect
