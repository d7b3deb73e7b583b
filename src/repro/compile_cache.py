"""JAX's persistent compilation cache, shared by every run of a checkout.

``enable_compile_cache(checkout)`` is called once at the start of each
command line entry point (``chip_smoke.py``, the benchmark CLIs) before
anything compiles, so a second run finds the executables of the first.
The caller names its checkout: the package itself may be installed
anywhere, and a cache under the install would be shared by every
checkout on the machine.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache"]


def enable_compile_cache(checkout: Path) -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache`` (listed in .gitignore), a fixed path, since
    a cache that moves between runs is never found again. Every compile
    is cached however short it was: the search kernels compile in well
    under JAX's default one-second threshold, and a run compiles dozens
    of them."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(checkout) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
