"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``examples/train_har.py``, ``benchmarks/run.py``)
calls ``enable()`` once at start-up, before its first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that directory
  from the environment and nothing is set in code;
* without it the cache lives at ``<checkout>/.jax_cache`` (gitignored) — a
  fixed path, because the path is part of what a later run looks up, so a
  name that changes per run (temporary, pid, time) would never hit.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's own cache directory (src/repro/ -> checkout root)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir(environ: Mapping[str, str] | None = None) -> str:
    """The directory the cache uses under ``environ`` (default: os.environ)."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable() -> str:
    """Point JAX's persistent compilation cache at ``cache_dir()``."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
