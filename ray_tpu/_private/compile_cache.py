"""JAX's persistent compilation cache, placeable from outside.

A cold process on the chip spends most of its first minutes compiling:
the train step, one prefill per (tile, bucket) and one fused decode per
block size. `enable()` is called by chip_smoke.py, bench.py and the
engines' entry points (`LLMEngine`, the trainer's worker loop) before
they compile anything, so a second process finds those programs on disk.

The directory is part of the cache key's environment, so it never moves:
where `JAX_COMPILATION_CACHE_DIR` is set it stays in force untouched,
otherwise the cache lives at `.jax_cache/` beside the `ray_tpu` package
(listed in `.gitignore`) — never a temporary name, pid or timestamp.
"""

from __future__ import annotations

import os
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> Optional[str]:
    """Turn the cache on for this process; returns the directory in
    force. None on a CPU backend: compiles there take seconds, and
    tier-1 must not depend on files an earlier run left behind."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    if not os.environ.get(ENV) \
            and jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # A compile before this call latched "no cache" for the process.
        compilation_cache.reset_cache()
    # The default (1 s) would drop the many small serve programs.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
