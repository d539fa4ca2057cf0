"""The persistent XLA compile cache: one helper for every entry point.

GPT-2-medium's train step takes minutes to compile cold, and every new
process (a chip-tool call, a fleet replica, a benchmark run) starts
with no compiled code unless the cache is on disk. The directory is part
of the cache key, so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is
  set in code, so the operator's directory is the only one;
- otherwise ``<checkout>/.jax_cache``: a fixed path inside the checkout
  (git-ignored), never a temp dir, a pid or a timestamp.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory. Also
    installs the compile log (``obs/compile_log.py``) and the collector's
    pause hook (``obs/tracer.py``): this is the call every entry point
    makes before it builds a program."""
    from consensusml_tpu.obs import compile_log, tracer

    compile_log.install()
    tracer.install_gc_hook()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
