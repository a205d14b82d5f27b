"""JAX's persistent compilation cache for the entry points.

Entry points call :func:`enable_compile_cache` from their ``main()`` — never
at import — so a compile paid once (a Pallas kernel, the tick program) is
found again by the next process on the same machine. ``JAX_COMPILATION_CACHE_DIR``
wins when set; otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(listed in ``.gitignore``). The path is part of what makes an entry findable,
so it is never derived from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
