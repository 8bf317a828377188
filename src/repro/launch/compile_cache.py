"""Where JAX keeps its persistent compilation cache: one place for every
entry point (the train driver, the FaaS worker, ``chip_smoke.py``).

The directory is part of what makes an entry findable again, so it never
derives from a temp name, a pid or the time.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself, and no other directory is set
here); otherwise the cache lives at ``<checkout>/.jax_cache``, which
``.gitignore`` lists.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its directory and return it.  Call
    before the first compile: JAX decides once per process whether the
    cache is on."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
