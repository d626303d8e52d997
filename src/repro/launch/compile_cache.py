"""Where the entry points keep JAX's persistent compilation cache.

The SIMDRAM paths compile one small program per lowered trace and operand
shape, so a run that starts cold spends much of its time compiling.  The
entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``) call
:func:`configure_compile_cache` from their ``main``; importing this module
changes nothing, so tests never pick the cache up.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def configure_compile_cache() -> None:
    """Keep every compiled program across runs.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    the cache key and a directory that moves never hits.  The minimum
    compile time is lowered to zero so the many short compiles are kept.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
