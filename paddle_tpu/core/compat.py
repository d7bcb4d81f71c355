"""The one import point for the JAX names whose public home has moved.

``shard_map``, ``enable_x64``, ``lax.axis_size`` and ``jax.export`` all changed
address between jax releases. Every in-repo and in-test use goes through this
module (the ``compat-shim`` lint rule enforces it), so the next move is a
one-file change (SURVEY §4: version-drift collection errors silently dropped
three files from tier-1). It holds the spellings of the installed jax only; a
branch for a jax that is not installed cannot be run and is not kept.
"""
from __future__ import annotations

import os

import jax
from jax import enable_x64, shard_map  # noqa: F401
from jax.lax import axis_size  # noqa: F401  (NameError when the axis is unbound)

__all__ = [
    "shard_map", "shard_map_check_kwargs", "jax_export", "axis_size",
    "enable_x64", "enable_persistent_compilation_cache",
]


def shard_map_check_kwargs(value=False):
    """Kwargs dict disabling (or enabling) shard_map's replication check."""
    return {"check_vma": value}


def jax_export():
    """The ``jax.export`` module. It is a lazily imported submodule: plain
    attribute access on ``jax`` raises AttributeError until something imports
    it, so callers take it from here."""
    import jax.export as m

    return m


# <checkout>/.jax_cache: a FIXED path, because the directory is part of the
# cache key (a cache that moves never hits), and inside the checkout, because
# a sealed machine that copies the tree keeps nothing under $HOME.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_compilation_cache():
    """Turn on JAX's persistent compilation cache so re-runs warm-start
    compiles (the flush-executable signatures are stable across processes).

    A directory that is already configured — ``JAX_COMPILATION_CACHE_DIR`` in
    the environment, or ``jax.config.update`` before importing paddle_tpu —
    is left exactly as it is: the cache is process-global and nothing here
    may point it elsewhere. Otherwise the directory is
    ``FLAGS_xla_persistent_cache_dir`` or ``<checkout>/.jax_cache``.
    ``FLAGS_xla_persistent_cache=0`` disables the whole thing. Returns the
    directory in force, or None."""
    from ..framework import flags as _flags

    if not _flags.flag("FLAGS_xla_persistent_cache", True):
        return None
    _atomic_cache_writes()
    existing = jax.config.jax_compilation_cache_dir
    if existing:
        return existing
    d = _flags.flag("FLAGS_xla_persistent_cache_dir") or _DEFAULT_CACHE_DIR
    try:
        os.makedirs(d, exist_ok=True)
    except OSError:
        return None  # read-only checkout: run without a persistent cache
    # jax's default threshold (1s) is tuned for serving-sized programs; a
    # train step's flush executable compiles faster than that on CPU yet is
    # exactly what a warm restart wants back
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(_flags.flag("FLAGS_xla_persistent_cache_min_compile_secs", 0.5)),
    )
    jax.config.update("jax_compilation_cache_dir", d)
    return d


_atomic_writes_patched = False


def _atomic_cache_writes():
    """Make the persistent-cache entry write ATOMIC. jax 0.9.0's
    ``LRUCache.put`` still writes the payload with a bare ``write_bytes``: a
    process killed mid-write (the common fate of a run cut off at its time
    limit, SIGKILL) leaves a truncated serialized executable, and every later
    process that deserializes it crashes — observed as a deterministic
    segfault in a single test until the cache dir is cleared. tmp-file +
    ``os.replace`` makes a torn entry impossible; readers either see nothing
    or a full write."""
    global _atomic_writes_patched
    if _atomic_writes_patched:
        return
    import time

    from jax._src import lru_cache as _lru

    orig_put = _lru.LRUCache.put

    def atomic_put(self, key, val):
        # Pre-write the payload file atomically; the original put then sees
        # it existing and skips its own (torn-write-prone) write_bytes while
        # still doing the lock bookkeeping. Thread/process-safe: no global
        # state, and a concurrent os.replace of the same entry just wins with
        # identical bytes. (When LRU eviction is explicitly enabled, a
        # pre-written entry escapes the eviction size accounting —
        # acceptable: this repo runs the cache unbounded, and a
        # slightly-over-budget cache beats a segfaulting one.)
        if key:
            try:
                path = self.path / f"{key}{_lru._CACHE_SUFFIX}"
                if not path.exists():
                    # atime sidecar FIRST: orig_put early-returns on an
                    # existing payload without writing it, and eviction
                    # read_bytes()-es every entry's atime
                    atime = self.path / f"{key}{_lru._ATIME_SUFFIX}"
                    atime.write_bytes(time.time_ns().to_bytes(8, "little"))
                    tmp = path.with_name(f"{path.name}.tmp{os.getpid()}")
                    tmp.write_bytes(val)
                    os.replace(tmp, path)
            except OSError:
                pass  # fall through: orig_put raises or handles it
        return orig_put(self, key, val)

    _lru.LRUCache.put = atomic_put
    _atomic_writes_patched = True
