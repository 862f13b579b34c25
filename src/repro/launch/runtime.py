"""Run-time set-up shared by the entry points: the devices they run on and
where JAX keeps its persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax

#: root of the checkout this module was loaded from (src/repro/launch/..)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing is changed.  Otherwise the cache lives at ``<checkout>/.jax_cache``.
    The path is part of every cache entry's key, so it is fixed: never a
    temporary name, a PID or a time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> Dict[str, object]:
    """The devices JAX runs on, as it reports them."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
