"""Where compiled programs are kept between processes.

One helper, called by every entry point that compiles for the chip
(`chip_smoke.py`, `bench.py`, `python -m kubeflow_tpu.serving`, the
launcher's ``--module`` path), so the cache can be placed from outside
and no second directory is ever set in code:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  this sets nothing;
- where it is not, the cache goes to one fixed directory inside the
  checkout (``.jax_cache/``, git-ignored). The directory is part of the
  cache's key, so it is never built from a temporary name, a pid or the
  time: a path that moves never hits.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_IN_CHECKOUT = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Call before the first compilation of the process."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_IN_CHECKOUT))
    return str(_IN_CHECKOUT)
