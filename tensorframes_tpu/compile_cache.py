"""Persistent XLA executable cache wiring.

The in-process jit cache (``Program.jitted`` and friends) amortizes
compiles within one process, and shape-canonical bucketing
(``ops/bucketing.py``) keeps the signature count O(log shape) — but
nothing survives the process: every cold start of a serving replica or a
bench run pays full XLA compile for every program.  jax ships a
content-addressed persistent compilation cache keyed by (HLO, compile
options, backend); this module is the one place it gets wired, and the
only code in the repo that writes ``jax_compilation_cache_dir``.

Where the cache lives, in precedence order:

1. ``JAX_COMPILATION_CACHE_DIR`` — jax's own variable.  When it is set,
   jax's reading of it stands and nothing here touches the directory
   setting: an operator (or the machine the program is handed) places
   the cache from outside.  A ``TFS_COMPILE_CACHE`` or explicit path
   that names a different directory yields, with one log line.
2. an explicit ``configure(path)`` / the ``TFS_COMPILE_CACHE`` env var
   (honored at package import, so every entry point shares the knob).
3. entry points only (``configure_entry_point()``: ``chip_smoke.py``,
   ``bench.py``, ``bridge/replica.py``): ``<checkout>/.cache/jax``,
   derived from this file's location — a fixed path, because the path
   is part of the cache key and a directory that moves never hits.

A plain library import with none of these set stays a no-op.

Whenever a cache is active the min-compile-time / min-entry-size floors
are dropped so the small block programs the verbs build are persisted
too, not just multi-second model compiles.  Hit/miss accounting rides
:mod:`tensorframes_tpu.observability`'s jax-monitoring listeners
(``counters()["persistent_cache_hits"]``), which is how the bench proves
a second process skipped XLA instead of asserting it.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

from . import envutil

ENV_VAR = "TFS_COMPILE_CACHE"
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_log = logging.getLogger("tensorframes_tpu.compile_cache")

_configured_dir: Optional[str] = None


def default_dir() -> str:
    """``<checkout>/.cache/jax`` — the entry points' cache home when no
    environment variable places it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, ".cache", "jax")


def configure(path: Optional[str] = None) -> bool:
    """Enable jax's persistent compilation cache at ``path`` (or
    ``$TFS_COMPILE_CACHE``), unless ``$JAX_COMPILATION_CACHE_DIR``
    already places it.  Returns True when a cache is active.

    Safe to call repeatedly; re-pointing at a new path reconfigures."""
    global _configured_dir
    path = path or envutil.env_raw(ENV_VAR) or None
    if not path:
        return _configured_dir is not None
    path = os.path.abspath(path)
    placed = envutil.env_raw(JAX_ENV_VAR)
    if placed:
        if os.path.abspath(placed) != path:
            envutil.warn_once(
                _log, "compile_cache.yield",
                "%s=%s places the compile cache; %s yields to it",
                JAX_ENV_VAR, placed, path,
            )
        path = placed
    if _configured_dir == path:
        return True
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from . import observability

    # default floors (1 s, 0 bytes) would skip every small verb program —
    # the exact executables whose per-restart recompiles this cache
    # exists to kill
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not placed:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # jax latches cache-enabled-ness at the FIRST compile of the
        # process: if anything compiled before this call, the latch
        # reads "disabled" forever.  reset_cache() clears the latch (and
        # the in-memory cache object) so a mid-process configure takes
        # effect.  A cache placed by the env var was enabled from jax's
        # import on and needs no reset.
        compilation_cache.reset_cache()
    observability.install_counters()
    _configured_dir = path
    return True


def configure_entry_point() -> str:
    """The cache for a program started from a checkout: wherever the
    environment places it, else :func:`default_dir`.  Returns the active
    directory."""
    configure(envutil.env_raw(ENV_VAR) or default_dir())
    return _configured_dir


def cache_dir() -> Optional[str]:
    """The active persistent cache directory, or None."""
    return _configured_dir


def deconfigure() -> None:
    """Turn the persistent cache back off (tests)."""
    global _configured_dir
    if _configured_dir is None:
        return
    if not envutil.env_raw(JAX_ENV_VAR):
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()
    _configured_dir = None
