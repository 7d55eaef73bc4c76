"""JAX's persistent compilation cache, switched on by the entry points.

``chip_smoke.py``, ``benchmarks/run.py``, ``launch/train.py``,
``launch/serve.py`` and ``python -m repro.fleet`` call
:func:`enable_compile_cache` before their first compile; no library
module does so at import, and the tests leave the cache off.

The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when it is set,
and otherwise in the fixed ``.jax_cache/`` at the root of the checkout
(git ignores it).  The path never comes from a temporary name, a process
id or the time, so a later run of the same checkout finds its entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX's persistent compilation cache at its directory."""
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(CHECKOUT_CACHE_DIR))
