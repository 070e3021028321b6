"""JAX's persistent compilation cache for the command-line entry points.

``enable()`` is called by the scripts — ``python -m repro.launch.train`` and
``python -m repro.launch.serve`` (in their ``__main__`` blocks, so a caller
of ``main`` keeps its own cache setting), ``benchmarks/run.py`` and
``chip_smoke.py`` — and never when a module is imported, so importing the
package leaves the cache as JAX configured it.  Call it before the first
compile: JAX decides once per process whether the cache is in use.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX uses that directory and
nothing here names another.  Otherwise the cache lives at ``.jax_cache`` in
the root of the checkout: a fixed path, never a temporary name, a pid or the
time, so the next process finds what this one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
