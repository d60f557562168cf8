"""Where the persistent XLA compilation cache lives — one rule for every
entry point (``chip_smoke.py``, ``benchmark/run.py``, the ``experiments/``
mains, ``tests/conftest.py``).

A cold compile of the LM train step or a 12-layer prefill takes tens of
seconds; the cache turns a second run on the same machine into a load.
Two cases, no others:

- ``JAX_COMPILATION_CACHE_DIR`` is set: jax reads it into
  ``jax_compilation_cache_dir`` by itself, and this module sets **no**
  directory — whoever runs the program decides where compiled code is kept.
- it is not set: the cache goes to :data:`CACHE_DIR`, one fixed,
  git-ignored directory at the root of the checkout, derived from this
  package's own location. Never ``~``, a temporary name, a pid or the time:
  a directory that moves between runs never hits.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the in-checkout default (listed in ``.gitignore`` and ``.chiprunignore``)
CACHE_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns the directory in
    use. Call before the first compile. Idempotent."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


__all__ = ["CACHE_DIR", "enable_compile_cache"]
