"""The entry points' persistent compilation cache: it goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to the checkout's fixed
``.jax_cache/``, which git ignores."""
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("env_dir", [None, "/var/cache/repro-jax"])
def test_cache_dir_from_env_or_checkout(env_dir, monkeypatch):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    was = jax.config.jax_compilation_cache_dir
    try:
        compile_cache.enable_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert got == (env_dir or str(REPO / ".jax_cache"))


def test_checkout_cache_dir_is_git_ignored():
    assert compile_cache.CHECKOUT_CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
