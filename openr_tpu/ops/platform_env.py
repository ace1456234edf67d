"""Where JAX keeps its persistent compilation cache."""

from __future__ import annotations

import os

_COMPILE_CACHE_ENABLED = False


def compile_cache_dir() -> str:
    """The fixed cache path when ``JAX_COMPILATION_CACHE_DIR`` is unset:
    ``<checkout>/.jax_compile_cache`` in a source checkout (listed in
    .gitignore), else the user's XDG cache (an installed package never
    litters the interpreter tree).  The path is part of the cache's
    key, so it must not move between runs."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if os.path.isdir(os.path.join(repo, "native")):
        return os.path.join(repo, ".jax_compile_cache")
    return os.path.join(
        os.environ.get(
            "XDG_CACHE_HOME", os.path.join(os.path.expanduser("~"), ".cache")
        ),
        "openr_tpu",
        "xla",
    )


def enable_persistent_compile_cache() -> None:
    """Persist XLA executables across process restarts.

    The reference is an AOT-compiled C++ binary: its cold boot never
    pays compilation.  Our device kernels are jit-compiled, so the first
    full build after daemon start pays one-time XLA compilation; JAX's
    persistent compilation cache removes that from every boot after the
    first on a given machine and kernel shape (a restarting router
    daemon is the common case; a brand-new shape is not).

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache and this
    sets no directory in code; otherwise :func:`compile_cache_dir`.
    JAX's own ``JAX_ENABLE_COMPILATION_CACHE=false`` turns it off.
    Idempotent; call before the first jit.
    """
    global _COMPILE_CACHE_ENABLED
    if _COMPILE_CACHE_ENABLED:
        return
    try:
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            path = compile_cache_dir()
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        # cache even fast compiles: cold boot strings dozens of kernel
        # shapes together, and the default 1s floor would skip many
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
        _COMPILE_CACHE_ENABLED = True
    except Exception:  # noqa: BLE001 — cache is an optimization only
        import logging

        logging.getLogger(__name__).warning(
            "persistent compile cache unavailable", exc_info=True
        )
