"""Persistent XLA compile cache wiring (docs/warmup.md "Compile
cache").

jax ships an on-disk compilation cache (keyed by a hash of the lowered
HLO + compile options + backend version + the cache directory itself);
a restarted process that finds the same directory REUSES yesterday's
executables instead of re-lowering and re-compiling them.  The warmup
replayer (warmup/replayer.py) drives the top-N corpus queries through
the real compile paths at startup, so every hit lands here at disk speed
instead of XLA-compile speed — that's the whole warm-start story: the
corpus remembers WHAT to compile, this cache remembers the COMPILED
BYTES.

Where the cache lives, in order of precedence:

1. ``JAX_COMPILATION_CACHE_DIR`` in the environment: jax reads it
   itself.  The program sets no directory in code and never prunes it —
   whoever placed it owns it.
2. ``compile-cache-dir`` set to a path: that path (``off`` disables the
   program's own cache).
3. otherwise ``DEFAULT_DIR``, one fixed path inside the checkout.  The
   directory is part of every cache key, so a cache that moved with the
   data dir (a fresh ``mkdtemp`` per run) never hit.

jax initialises its cache once per process, so the first ``Server``
decides and every later one in the process reports the same directory.

This module is deliberately thin glue:

* ``configure(dir)`` puts the process on its cache directory (only
  where none is in effect yet) and drops both
  min-compile-time/min-entry-size floors to zero — the defaults skip
  sub-second compiles, which on CPU smoke runs is everything.
* ``prune(dir, max_mb)`` LRU-prunes the cache directory to the
  ``compile-cache-mb`` bound by file mtime (jax touches entries on
  read), oldest first.  Runs at startup (before the cache is hot) and
  on clean shutdown, on directories ``owned`` by the program only.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.compile-cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".compile-cache")


def owned() -> bool:
    """Whether the cache directory is the program's own to prune — not
    one the environment placed."""
    return not os.environ.get(ENV_VAR)


def configure(cache_dir: str) -> str | None:
    """Put this process's persistent compilation cache on its directory
    and return the one in effect (None: disabled).  A directory already
    in effect — from the environment, or from an earlier ``Server`` in
    this process — is left alone; otherwise ``cache_dir`` ("" =
    ``DEFAULT_DIR``, "off" = none) is created and set.  An unwritable
    directory disables the cache (``cacheEnabled=false`` on the warmup
    status surface): a warm start is an optimization, never a boot
    requirement."""
    import jax
    # default floors skip fast/small compiles; the corpus replays
    # exactly the programs we want cached, so cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    if cache_dir == "off":
        return None
    target = cache_dir or DEFAULT_DIR
    try:
        os.makedirs(target, exist_ok=True)
    except OSError:
        return None
    jax.config.update("jax_compilation_cache_dir", target)
    return target


def cache_stats(cache_dir: str) -> dict:
    """{files, bytes} for the status surfaces; never raises."""
    files = total = 0
    try:
        for name in os.listdir(cache_dir):
            p = os.path.join(cache_dir, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            if os.path.isfile(p):
                files += 1
                total += st.st_size
    except OSError:
        pass
    return {"files": files, "bytes": total}


def prune(cache_dir: str, max_mb: int) -> dict:
    """Delete oldest-by-mtime cache files until the directory fits
    ``max_mb`` (0 = unbounded).  Returns {files, bytes, removed,
    removedBytes}; never raises — a prune failure costs disk, not
    availability."""
    entries = []
    total = 0
    try:
        for name in os.listdir(cache_dir):
            p = os.path.join(cache_dir, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            if os.path.isfile(p):
                entries.append((st.st_mtime, st.st_size, p))
                total += st.st_size
    except OSError:
        return {"files": 0, "bytes": 0, "removed": 0, "removedBytes": 0}
    removed = removed_bytes = 0
    if max_mb and max_mb > 0:
        limit = max_mb * 1024 * 1024
        entries.sort()  # oldest mtime first — LRU victims
        i = 0
        while total > limit and i < len(entries):
            _, size, p = entries[i]
            i += 1
            try:
                os.unlink(p)
            except OSError:
                continue
            total -= size
            removed += 1
            removed_bytes += size
    return {"files": len(entries) - removed, "bytes": total,
            "removed": removed, "removedBytes": removed_bytes}
