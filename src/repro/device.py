"""Where the program runs, decided in one place.

* ``interpret_mode()`` — Pallas kernels compile only for the TPU; off it the
  benchmark and autotune sweeps run them in interpret mode (their reports
  carry the backend, so such numbers never pass for chip numbers).
* ``require_tpu()`` — entry points that exist to run on the chip fail
  instead of quietly switching to interpret impls.
* ``enable_compile_cache()`` — JAX's persistent compilation cache, so a
  second run of an entry point reuses the first one's executables.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = [
    "CACHE_ENV",
    "DEFAULT_CACHE_DIR",
    "enable_compile_cache",
    "interpret_mode",
    "require_tpu",
]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: fixed and inside the checkout: the cache key includes nothing of the
#: path, but a directory that moved between runs is a directory never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def interpret_mode() -> bool:
    """True where Pallas kernels must run interpreted (no TPU backend)."""
    return jax.default_backend() != "tpu"


def require_tpu(what: str) -> None:
    """Raise unless JAX's default backend is a TPU."""
    if interpret_mode():
        raise RuntimeError(
            f"{what} needs a TPU, but JAX's backend is "
            f"{jax.default_backend()!r}; name an *_interpret impl explicitly "
            "to run the kernels interpreted on this host"
        )


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``.jax_cache`` at the
    repository root (git-ignored)."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
