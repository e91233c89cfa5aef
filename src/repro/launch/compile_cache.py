"""Where the entry points keep JAX's persistent compilation cache.

`enable()` is called by `launch/train.py`, `launch/serve.py` and
`chip_smoke.py` before they compile anything. When the environment sets
JAX_COMPILATION_CACHE_DIR, JAX reads it itself and nothing is set here.
Otherwise, on an accelerator, the cache goes to one fixed, git-ignored
directory inside the checkout, `<repo>/.jax_cache`: never a temp name, a
pid or a time, because a cache whose path moves is never hit again. On
the CPU it stays off: XLA:CPU compiles these programs in seconds, and
its cached programs warn about host features on every load.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable(backend: str | None = None) -> str | None:
    """Point the persistent compilation cache at its directory (see the
    module docstring) and return that directory, or None where it stays
    off. `backend` defaults to `jax.default_backend()`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    if (backend or jax.default_backend()) == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
