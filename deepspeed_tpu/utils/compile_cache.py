"""Where the persistent XLA compilation cache lives — one rule, called from
``deepspeed_tpu.initialize``, ``InferenceEngineV2.__init__`` and
``chip_smoke.py``.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and this
  code sets no directory at all, so whoever runs the program decides where
  compiled executables persist.
- unset: one fixed path inside the checkout, ``<repo>/.jax_cache``.  The path
  is part of what makes a later process find the entries again, so it is
  never built from a temp name, a pid or a time.

Cold compile of the serving engine is minutes (one Mosaic compile of the
packed-ctx kernel alone is about a minute at Mistral-7B widths); a second
process on the same machine should pay none of it.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
