"""Device-side delta overlay application (docs/ingest.md).

An ingest flush leaves its new words in the fragment's journal
(storage/fragment.py ingest_apply); resident device arrays absorb them
as a scatter-OR of a few KB instead of a re-upload of the whole dense
tensor.  Two consumers:

* per-fragment mirrors (``Fragment.device``) call ``apply_overlay``
  here — a plain single-device jit;
* mesh stacked blocks OR the journal inside a shard_map program
  (parallel/mesh_exec.py ``_apply_stack_overlay``), which reuses
  ``merge_chunks`` for the host-side prep.

The scatter is expressed as ``flat.at[idx].add(vals & ~flat[idx])`` —
an ADD of exactly the missing bits.  With host-deduplicated indices the
add equals the OR, and (unlike a plain ``.set``) it stays correct when
masked-out lanes collide on a dummy index, because adding zero commutes
with everything.  Indices travel as (row, word) int32 pairs, never a
flattened int64 — jax's default int width would silently truncate a
``row * 32768 + word`` offset past 2^31 on large fragments.  On the
device a row's words are a (256, 128) word tile (ops/bitset.py
"Representation"): the program finds word ``w`` at ``[w // 128, w % 128]``.
"""

from __future__ import annotations

import numpy as np


def merge_chunks(chunks) -> tuple[np.ndarray, np.ndarray]:
    """Combine journal chunks [(epoch, flat idx, val), ...] into unique
    sorted flat indices with OR-merged word values — the host dedupe
    that makes the device scatter collision-free."""
    if not chunks:
        z = np.zeros(0, dtype=np.int64)
        return z, z.astype(np.uint32)
    idx = np.concatenate([c[1] for c in chunks])
    val = np.concatenate([c[2] for c in chunks])
    uniq, inv = np.unique(idx, return_inverse=True)
    out = np.zeros(uniq.size, dtype=np.uint32)
    np.bitwise_or.at(out, inv, val)
    return uniq, out


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def pad_overlay(flat_idx: np.ndarray, vals: np.ndarray, words: int,
                member: np.ndarray | None = None):
    """(row int32, word int32, val uint32) arrays padded to a pow2
    length so one compiled scatter serves a bucket of overlay sizes;
    with ``member`` (the mesh path's stacked-row index per word) a
    fourth padded int32 array leads the tuple.  Padding lanes carry
    val 0 at (member 0, row 0, word 0) — their contribution ``0 & ~x``
    is zero, so colliding with a real lane is harmless."""
    k = _pow2(max(int(flat_idx.size), 1))
    row = np.zeros(k, dtype=np.int32)
    word = np.zeros(k, dtype=np.int32)
    val = np.zeros(k, dtype=np.uint32)
    n = flat_idx.size
    row[:n] = (flat_idx // words).astype(np.int32)
    word[:n] = (flat_idx % words).astype(np.int32)
    val[:n] = vals
    if member is None:
        return row, word, val
    m = np.zeros(k, dtype=np.int32)
    m[:n] = member
    return m, row, word, val


_JIT_CACHE: dict = {}


def apply_overlay(mirror, flat_idx: np.ndarray, vals: np.ndarray,
                  words: int):
    """OR deduplicated journal words into a dense [rows, 256, 128] device
    mirror of ``words`` words a row; returns the updated array (the old
    one stays valid for any in-flight computation that captured it)."""
    import jax

    from ..ops.bitset import word_at

    row, word, val = pad_overlay(flat_idx, vals, words)
    key = ("mirror", mirror.shape, row.size)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        def body(m, r, w, v):
            at = (r,) + word_at(w)
            return m.at[at].add(v & ~m[at])

        fn = _JIT_CACHE[key] = jax.jit(body)
    # index/value args stay uncommitted numpy: the computation follows
    # the mirror's (possibly committed) placement
    return fn(mirror, row, word, val)
