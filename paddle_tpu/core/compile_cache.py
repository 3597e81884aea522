"""Where JAX's persistent compilation cache lives.

ResNet-50 through the TPP conv path is about fifty Mosaic kernel
compiles per cold process, so every entry point that compiles for the
chip (``chip_smoke.py``, ``python -m paddle_tpu.trainer``, ``python -m
paddle_tpu.serving``, ``benchmarks/run.py``) calls :func:`configure` before its
first compile.  The directory is part of the cache key, so it is either
what ``JAX_COMPILATION_CACHE_DIR`` says — jax reads that variable itself
and nothing is set in code — or one fixed path inside the checkout,
never a temp name, pid or timestamp.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.jax_cache`` (git-ignored): the directory that holds
    the ``paddle_tpu`` package, so every process of one checkout agrees."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")


def configure() -> str:
    """Place the compilation cache; returns the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = default_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
