"""Where a process keeps jax's persistent compilation cache.

A leaf: it imports nothing of the platform, so the trainer and the
decode engine join the cache without loading the control plane.
``warmup/compilecache.py`` (the cache SERVICE, which stages artifacts
into this same directory) re-exports these names.
"""

from __future__ import annotations

import os

# The one place a process's jax persistent-cache directory is decided
# when nobody placed it from outside: a fixed, git-ignored directory at
# the root of the checkout (the parent of this package). The path is
# part of jax's cache key, so it must not depend on tempfile, a pid or
# a clock — a directory that moves never hits.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compile_cache",
)


def process_cache_dir() -> str:
    """Where this process's jax persistent compilation cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` if set — that directory and no
    other — else the fixed in-checkout directory. Touches neither jax
    nor the filesystem (launchers use it to find the directory their
    children will use)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


def install_process_cache() -> str:
    """Join THIS process to the persistent compilation cache at
    :func:`process_cache_dir` and return that path. The trainer and the
    decode engine call it before their first trace.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax has read the variable
    itself at import and this function sets no directory; it only
    checks that jax's setting IS that directory (a variable exported
    after ``import jax``, or a ``jax.config.update`` elsewhere, would
    otherwise leave the process caching somewhere nobody placed).
    Without it, the fixed in-checkout directory is configured.

    jax's minimum-compile-time threshold (1 s) is zeroed, for a
    measured reason: under it a repeated ``chip_smoke.py`` on a v5e
    compiled 40 of its 59 programs again (11.2 s of a 47.6 s run, PR
    21) — the streaming init and the engine are many small programs.
    Tests are not affected: tests/conftest.py turns the cache off.

    Errors propagate: a process that was meant to share a cache and
    silently does not pays every cold compile, every run."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        configured = jax.config.jax_compilation_cache_dir
        if configured != env:
            raise RuntimeError(
                f"JAX_COMPILATION_CACHE_DIR={env!r} but jax caches at "
                f"{configured!r}: export the variable before jax is "
                "imported, and set the directory nowhere else"
            )
        return env
    os.makedirs(_CHECKOUT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR
