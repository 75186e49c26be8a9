"""Where JAX's persistent compilation cache lives.

Entry points that compile at real size (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/memsys_explorer.py``) call
:func:`enable` once before their first compile.  Importing the package
does not, so the CPU tests and the described-topology compiles stay
uncached.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed inside the checkout, because the path is part of the cache key
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and nothing else is configured.  Otherwise the cache goes
    to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
