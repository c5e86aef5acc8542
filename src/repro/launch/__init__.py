"""Launchers: mesh construction, dry-run, training, serving, assessment,
and the assessment-as-a-service daemon (``qa_serve``)."""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    used as it is.  Otherwise the cache lives at ``<checkout>/.jax_cache``
    (git-ignored): a fixed path, never built from a temp name, a process id
    or the time, because a cache that moves is never hit.  Entry points call
    this from ``main()``, never at import, so library users and tests keep
    the cache off.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
