"""What the process runs on, decided once: Pallas mode and the compile cache.

Pallas kernels run in interpret mode exactly when JAX's backend is the CPU;
on an accelerator they are compiled, never interpreted in silence.  Every
kernel asks :func:`pallas_interpret` when it is traced, so a jitted caller
keeps the mode it was first traced with.

Entry points call :func:`use_compile_cache` before their first compile.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = REPO / ".jax_cache"


def pallas_interpret() -> bool:
    """True on the CPU backend (the Pallas interpreter), False otherwise."""
    return jax.default_backend() == "cpu"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at a fixed path inside
    the checkout, never a per-run name, so the next run reads it back.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
