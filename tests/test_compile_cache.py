"""The persistent compilation cache directory of the entry points."""
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_env_var_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_fixed_dir_inside_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_same_dir_across_calls(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.enable() == compile_cache.enable()


def test_training_cli_main_leaves_the_cache_alone(monkeypatch, restore_cache_dir):
    """Only the script entry enables the cache: tests that call ``main``
    keep their process's compiles out of the checkout's cache."""
    from repro.launch import train

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    with pytest.raises(SystemExit):  # rejected after argument parsing
        train.main(["--experiment", "p2p_lm", "--peer-axis", "pod"])
    assert jax.config.jax_compilation_cache_dir == before
