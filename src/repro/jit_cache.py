"""JAX's persistent compilation cache, kept at one fixed place.

A directory named after a temporary name, a pid or the time is never
found again by the next run.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is
set; otherwise compiled programs go to ``<checkout>/.jax_cache`` (listed
in ``.gitignore``).  ``<checkout>`` is the directory above ``src/`` that
this module is imported from, so the default assumes a source checkout,
as every entry script of the repo arranges; a package installed into
``site-packages`` should be run with ``JAX_COMPILATION_CACHE_DIR`` set.
Nothing is written under the system temp directory:
the TPU runtime's own logs are turned off unless ``TPU_LOG_DIR`` says
where they go.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compilation of this process
    and return its directory.  Call before the first computation: the
    TPU runtime reads ``TPU_LOG_DIR`` when it starts."""
    import jax

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
