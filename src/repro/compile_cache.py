"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``,
the benchmarks' mains) call ``use_compile_cache()`` before anything
compiles; library modules never do, so importing ``repro`` changes no
JAX setting.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the directory is part of
what a later run must find again (git ignores it). Either way every
program is written to the cache, however fast it compiled: the engine's
prefill and decode programs compile in about a second each, under JAX's
default one-second threshold, and together make most of a cold start.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
