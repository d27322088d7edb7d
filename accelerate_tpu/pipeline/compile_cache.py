"""Persistent XLA compilation cache — default-on, placed from outside.

Warmup compiles are the dominant startup cost of a large GSPMD program
(minutes at scale); XLA can serialize compiled executables and re-load them
keyed by (HLO, flags, topology) and, here, this library's source
(:func:`library_digest`: the executable carries the source's names).
:func:`enable_compile_cache` turns that cache on for both entry points
(``Accelerator.__init__`` and
``ServingEngine.__init__``):

- ``JAX_COMPILATION_CACHE_DIR`` set → jax itself reads that variable; this
  module never touches ``jax_compilation_cache_dir`` then, so whoever runs the
  program decides where the cache lives;
- unset → one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored).  The path is part of the cache key, so it is never derived
  from ``$HOME``, a temporary name, a pid or a time;
- ``ACCELERATE_TPU_COMPILE_CACHE=`` (set but empty) → cache OFF (the test
  suite's hermeticity switch).

Because the cache is default-on (and caches every program, however small),
the directory is bounded: jax's LRU eviction is configured to
``ACCELERATE_TPU_COMPILE_CACHE_MAX_BYTES`` (default 1 GiB; ``0`` or negative
→ unbounded).

Cache *hits* are surfaced through the telemetry compile counters: jax emits a
``/jax/compilation_cache/cache_hits`` monitoring event per hit, which
telemetry's listener tallies as ``jit.cache_hits`` next to ``jit.compiles``.
The latter counts every compile REQUEST that missed the in-memory jit cache —
jax's event wraps its compile-or-get-cached, so a persistent-cache hit is
counted there too — and the cache's misses are ``jit.compiles -
jit.cache_hits``.
"""

from __future__ import annotations

import functools
import hashlib
import os
from typing import Optional

__all__ = [
    "ENV_COMPILE_CACHE",
    "ENV_COMPILE_CACHE_MAX_BYTES",
    "DEFAULT_COMPILE_CACHE_DIR",
    "DEFAULT_COMPILE_CACHE_MAX_BYTES",
    "compile_cache_max_bytes_from_env",
    "enable_compile_cache",
    "library_digest",
]

ENV_COMPILE_CACHE = "ACCELERATE_TPU_COMPILE_CACHE"
ENV_COMPILE_CACHE_MAX_BYTES = "ACCELERATE_TPU_COMPILE_CACHE_MAX_BYTES"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
DEFAULT_COMPILE_CACHE_MAX_BYTES = 1 << 30  # 1 GiB LRU bound


def compile_cache_max_bytes_from_env() -> int:
    """Size bound for the cache directory: default 1 GiB; ``0`` or negative
    (or unparseable) opts out of eviction (jax's ``-1`` = unbounded)."""
    raw = os.environ.get(ENV_COMPILE_CACHE_MAX_BYTES)
    if raw is None or not raw.strip():
        return DEFAULT_COMPILE_CACHE_MAX_BYTES
    try:
        max_bytes = int(raw.strip())
    except ValueError:
        import warnings

        warnings.warn(
            f"{ENV_COMPILE_CACHE_MAX_BYTES}={raw!r} is not an integer; "
            "leaving the compilation cache unbounded"
        )
        return -1
    return max_bytes if max_bytes > 0 else -1


@functools.cache
def library_digest() -> str:
    """sha256 over this package's Python sources (relative path and bytes, in
    sorted order): what an executable in the cache was compiled from."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digest = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(package):
        dirnames.sort()
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                digest.update(os.path.relpath(path, package).encode())
                with open(path, "rb") as f:
                    digest.update(f.read())
    return digest.hexdigest()


def _key_by_library_source() -> None:
    """An executable from the cache keeps the metadata of the source that
    compiled it, and jax leaves metadata out of the cache key: a program
    whose named scopes or kernel names changed comes back with the old
    ``op_name``s, in every profile and in every metric read from one (seen on
    the v5e: the serving programs of the commit before the scopes, out of the
    machine's cache).  jax's own switch for that
    (``jax_compilation_cache_include_metadata_in_key``) also keys on the source
    line of every frame of the caller's stack, so that one function called
    from two places misses; the names are this library's, so the key takes
    this library's source instead, through the hook jax keeps for additions
    to the key.  A library edit costs one cold start; the user's edits none."""
    from jax._src import cache_key

    if hasattr(cache_key.custom_hook, "library_digest"):
        return
    outer = cache_key.custom_hook

    def custom_hook() -> str:
        return outer() + " accelerate_tpu=" + custom_hook.library_digest

    custom_hook.library_digest = library_digest()
    cache_key.custom_hook = custom_hook


def enable_compile_cache() -> Optional[str]:
    """Turn jax's persistent compilation cache on.  Returns the active
    directory, or ``None`` when the cache is disabled.  Idempotent; a
    filesystem that refuses the directory forfeits the cache with a warning
    instead of taking down the run."""
    if os.environ.get(ENV_COMPILE_CACHE, "on").strip() == "":
        return None
    import jax

    placed_outside = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not placed_outside and jax.config.jax_compilation_cache_dir != DEFAULT_COMPILE_CACHE_DIR:
        try:
            os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        except OSError as e:
            import warnings

            warnings.warn(f"persistent compilation cache unavailable ({e}); continuing without it")
            return None
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
        # jax latches "cache unused" on the FIRST compile; a process that
        # already compiled something must reset that latch or the directory
        # set just now is silently ignored.
        from jax.experimental.compilation_cache import compilation_cache as _cc

        _cc.reset_cache()
    _key_by_library_source()
    # Cache every program: the default 1s floor skips exactly the small
    # programs a CPU-smoke run compiles, and at TPU scale everything worth
    # running clears 1s anyway.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # ...but a default-on cache-everything policy needs a bound, or the
    # directory grows forever on long-lived machines.
    jax.config.update("jax_compilation_cache_max_size", compile_cache_max_bytes_from_env())
    return jax.config.jax_compilation_cache_dir
