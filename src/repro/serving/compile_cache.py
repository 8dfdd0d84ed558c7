"""Persistent compilation cache wiring — cold-start hardening.

A restarted serving process pays one XLA compilation per
``(precision, guided, refresh)`` step variant before it can serve its
first request: the recompile storm.  JAX's persistent compilation cache
keys each compiled executable by the hash of its lowered HLO and stores
it on disk, so a warm restart *loads* every step variant instead of
recompiling it — time-to-first-tick drops from compile-bound to
deserialize-bound.

``enable_persistent_cache`` routes every subsequent compilation in this
process through an on-disk directory.  It is process-global (the cache
is keyed by HLO hash, so unrelated programs sharing a directory are
fine) and idempotent.  The thresholds default to "cache everything":
the CPU-scale demo UNets compile in well under JAX's default 1-second
floor, which would silently skip them.

Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(then no other directory is ever configured), else the directory a caller
passes, else ``DEFAULT_CACHE_DIR`` — ``.jax_cache`` at the checkout's
root.  The path is part of each entry's key, so it is fixed: never a
temporary directory, a PID or a timestamp.

Usage (the engine, ``launch/serve.py`` and ``chip_smoke.py`` call this
for you)::

    from repro.serving.compile_cache import (default_cache_dir,
                                             enable_persistent_cache)
    enable_persistent_cache(default_cache_dir())
    engine.warmup(precisions=('fp32', 'w8a8'))   # cold: compiles + stores
    # ... restart the process ...
    engine.warmup(precisions=('fp32', 'w8a8'))   # warm: loads from disk
"""
from __future__ import annotations

import os
from typing import Optional

import jax

#: Environment variable naming the one cache directory, when set.
ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'

#: The cache directory when neither the environment nor a caller names one.
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), '..', '..', '..',
    '.jax_cache'))

#: The directory routed through ``enable_persistent_cache`` in this
#: process, or None when the persistent cache is off.
_ACTIVE_DIR: Optional[str] = None

#: Size bound (bytes) applied to the active directory, or None for
#: unbounded.  Enforced by ``trim_cache`` (LRU eviction), which the
#: engine calls after every warmup that populates the cache.
_MAX_BYTES: Optional[int] = None

#: Executables evicted by the size bound in this process — surfaced via
#: ``cache_entries(..., with_evictions=True)``.
_EVICTED = 0

#: Optional config flags applied best-effort (names vary across JAX
#: releases; absence is not an error).
_OPTIONAL_FLAGS = (
    # let XLA's own autotune/kernel caches piggyback on the directory
    ('jax_persistent_cache_enable_xla_caches', 'all'),
)


def default_cache_dir(cache_dir: Optional[str] = None) -> str:
    """The cache directory to use: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``cache_dir``, else ``DEFAULT_CACHE_DIR``."""
    return os.path.abspath(os.environ.get(ENV_VAR) or cache_dir
                           or DEFAULT_CACHE_DIR)


def enable_persistent_cache(cache_dir: str,
                            min_entry_size_bytes: int = -1,
                            min_compile_time_secs: float = 0.0,
                            max_bytes: Optional[int] = None) -> str:
    """Route every XLA compilation through a persistent on-disk cache.

    Creates the directory if needed and returns its absolute path.  With
    ``$JAX_COMPILATION_CACHE_DIR`` set, that directory is used in place
    of ``cache_dir``.
    ``min_entry_size_bytes=-1`` / ``min_compile_time_secs=0.0`` cache
    every executable regardless of size or compile time (JAX's defaults
    skip sub-second compiles, which covers every CPU-scale demo model).
    Idempotent: re-enabling with the same directory is a no-op.

    ``max_bytes`` bounds the directory: a long-lived serving fleet
    accretes one executable per (model, mesh, step-variant) forever, so
    without a bound the cache dir grows without limit.  The bound is
    enforced now and after every engine warmup (``trim_cache``), evicting
    least-recently-used entries first.
    """
    cache_dir = default_cache_dir(os.path.expanduser(cache_dir))
    os.makedirs(cache_dir, exist_ok=True)
    global _ACTIVE_DIR, _MAX_BYTES
    jax.config.update('jax_compilation_cache_dir', cache_dir)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes',
                      min_entry_size_bytes)
    jax.config.update('jax_persistent_cache_min_compile_time_secs',
                      min_compile_time_secs)
    for flag, value in _OPTIONAL_FLAGS:
        try:
            jax.config.update(flag, value)
        except (AttributeError, ValueError):       # pragma: no cover
            pass
    _reset_cache_state()
    if max_bytes is None and cache_dir == _ACTIVE_DIR:
        # idempotent re-enable (e.g. engine.warmup after an explicit
        # enable with a bound): keep the configured bound
        max_bytes = _MAX_BYTES
    _ACTIVE_DIR = cache_dir
    _MAX_BYTES = max_bytes
    if max_bytes is not None:
        trim_cache(cache_dir, max_bytes)
    return cache_dir


def _reset_cache_state() -> None:
    """Drop JAX's latched cache-used decision.  JAX checks "is a cache
    configured?" once, at the first compilation of the process — a serve
    process that compiled anything (even backend init probes) before
    ``enable_persistent_cache`` would otherwise silently never persist.
    The on-disk entries are untouched; only process state resets."""
    try:
        from jax.experimental.compilation_cache import (
            compilation_cache as cc)
        cc.reset_cache()
    except Exception:                              # pragma: no cover
        pass


def disable_persistent_cache() -> None:
    """Turn the persistent cache off for subsequent compilations (tests
    use this to avoid leaking a temporary directory into later work)."""
    global _ACTIVE_DIR, _MAX_BYTES
    jax.config.update('jax_compilation_cache_dir', None)
    _reset_cache_state()
    _ACTIVE_DIR = None
    _MAX_BYTES = None


def _entry_files(d: str):
    """(path, size, last_use) for every cache entry.  Last use is
    ``max(atime, mtime)``: reads bump atime where the filesystem tracks
    it, and mtime covers ``noatime`` mounts (creation order then stands
    in for recency — still the right eviction order for a write-once
    cache)."""
    out = []
    for name in os.listdir(d):
        path = os.path.join(d, name)
        if not os.path.isfile(path):
            continue
        st = os.stat(path)
        out.append((path, st.st_size, max(st.st_atime, st.st_mtime)))
    return out


def trim_cache(cache_dir: Optional[str] = None,
               max_bytes: Optional[int] = None) -> int:
    """Enforce the size bound on ``cache_dir`` (default: the active
    directory and its configured bound): evict least-recently-used
    executables until the directory fits.  Returns the number of
    entries evicted (also accumulated into the process-wide eviction
    counter).  A no-op when no bound is configured."""
    global _EVICTED
    d = cache_dir or _ACTIVE_DIR
    budget = max_bytes if max_bytes is not None else _MAX_BYTES
    if d is None or budget is None or not os.path.isdir(d):
        return 0
    files = _entry_files(d)
    total = sum(size for _, size, _ in files)
    if total <= budget:
        return 0
    evicted = 0
    for path, size, _ in sorted(files, key=lambda f: f[2]):
        if total <= budget:
            break
        try:
            os.remove(path)
        except OSError:                            # pragma: no cover
            continue                # concurrent reader won the race
        total -= size
        evicted += 1
    _EVICTED += evicted
    return evicted


def cache_evictions() -> int:
    """Executables evicted by the size bound in this process."""
    return _EVICTED


def active_cache_dir() -> Optional[str]:
    """The directory enabled in this process, or None."""
    return _ACTIVE_DIR


def cache_entries(cache_dir: Optional[str] = None,
                  with_evictions: bool = False):
    """Number of persisted executables in ``cache_dir`` (default: the
    active directory).  0 when the cache is off or the directory is
    empty — a cold/warm probe compares this before and after warmup.
    ``with_evictions=True`` returns ``(entries, evicted)`` so callers
    can tell an empty-because-cold directory from one the size bound
    has been evicting from."""
    d = cache_dir or _ACTIVE_DIR
    n = 0
    if d is not None and os.path.isdir(d):
        n = sum(1 for name in os.listdir(d)
                if os.path.isfile(os.path.join(d, name)))
    return (n, _EVICTED) if with_evictions else n
