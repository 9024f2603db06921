"""Persistent compilation cache of the entry points.

Called from ``main`` of the train and serve CLIs and from
``chip_smoke.py`` — never on import, so importing the package (tests,
benchmarks) leaves JAX's cache settings alone.
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

#: fixed cache path inside the checkout (git-ignored); the path is part
#: of every entry's key, so it must not move between runs
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left as it is.  Otherwise accelerator programs are cached in
    ``CHECKOUT_CACHE``; on the CPU backend (tests, CPU rehearsals) the
    cache stays off and None is returned — reloaded XLA:CPU entries only
    add host-feature warnings to runs that compile in seconds.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
