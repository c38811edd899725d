"""Dense uint32 bitset kernels — the TPU-native replacement for the reference
engine's roaring container op matrix (roaring/roaring.go:3160-4770: intersect,
union, difference, xor, shift, flip, intersectionCount, Count/CountRange).

Representation
--------------
On the host a *segment* is one shard-row of bits as a dense
``uint32[SHARD_WORDS]`` vector (little-endian within each word:
shard-column ``c`` lives at word ``c >> 5``, bit ``c & 31``) and a
*fragment tensor* stacks rows: ``uint32[n_rows, SHARD_WORDS]``.

On the device the word axis is a trailing ``(words // 128, 128)`` *word
tile* — ``core.WORD_TILE = (256, 128)`` at shard width: a segment is
``uint32[256, 128]``, a fragment mirror ``uint32[n_rows, 256, 128]``, a
stacked block ``uint32[S, n_rows, 256, 128]``; word ``w`` sits at
``[w >> 7, w & 127]``, so the row-major bytes are the host's.  The TPU
tiles an array's two minor dimensions into (8 sublanes x 128 lanes) vector
registers.  With the word axis flat the row axis was the sublane axis: one
row was 512 B of every 4 KiB tile, a row take read eight rows to keep one
and every pass over a segment filled one sublane of eight (PERF.md §6,
PR 32).  With the tile, a row is 32 whole registers, ``n_rows`` and ``S``
are untiled major dimensions, a row take is an offset, and every
elementwise pass and popcount-reduce runs on full registers.  There is one
device shape and every kernel below takes it: ``to_tile`` / ``from_tile``
are the host boundary (a view of host numpy at ``device_put`` and after
``device_get``, never an op in a program); ``row_counts`` reduces over
the tile.  Kernels that need the linear word order (``shift``, ``set_bits`` /
``clear_bits``) say so and flatten at their own edges.  Ops are pure jax
functions, shape-polymorphic in the row count and in ``words`` (a
multiple of 128), so one jitted executable serves every fragment with the
same row count.

The adaptive array/bitmap/run container forms of the reference survive,
but split across two layers: COMPUTE is always dense — the branchy
(op x container-type^2) dispatch matrix of the reference would defeat XLA
fusion — while RESIDENCY may be compressed (ops/containers.py): sparse
fragments stay HBM-resident as packed array/bitmap/run container streams
and are decoded to dense tiles on device at op time, inside the same
executable that runs these kernels (a 2048-word container is sixteen
whole sublane-groups, ``(16, 128)``, of its row's tile).  Decode-at-op-time
keeps every op below this line a branch-free dense kernel yet lets
residency cost compressed bytes instead of the 100x dense blowup
(docs/memory-budget.md "Compressed residency").

Host-side packing/unpacking helpers (numpy) live at the bottom; they are the
import/export boundary, mirroring roaring's serializer role.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import SHARD_WORDS, WORD_BITS, WORD_BITS_EXP, WORD_LANES

_FULL_WORD = np.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# The word tile (module docstring, "Representation").
# ---------------------------------------------------------------------------

def tile_shape(words: int = SHARD_WORDS) -> tuple[int, int]:
    """The trailing device dimensions of a row of ``words`` words."""
    if words % WORD_LANES:
        raise ValueError(f"{words} words are no whole number of "
                         f"{WORD_LANES}-lane rows")
    return words // WORD_LANES, WORD_LANES


def to_tile(x):
    """[..., W] -> [..., W // 128, 128]: the host side of ``device_put``
    (a view of a numpy array)."""
    return x.reshape(x.shape[:-1] + tile_shape(x.shape[-1]))


def from_tile(x):
    """[..., T, 128] -> [..., T * 128]: the host side of ``device_get``
    (a view of a numpy array), and the edge of the few kernels that need
    the linear word order."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def word_at(w):
    """Word index (host or traced) -> its position in the tile, to follow
    a row index: ``frag[(r,) + word_at(w)]``."""
    return w // WORD_LANES, w % WORD_LANES


def word_bit_np(cols):
    """Column ids -> (word index, single-bit mask) on host (numpy).  The one
    place the word geometry (WORD_BITS_EXP) is spelled out for packing."""
    cols = np.asarray(cols)
    w = cols >> WORD_BITS_EXP
    bit = np.uint32(1) << (cols & (WORD_BITS - 1)).astype(np.uint32)
    return w, bit


def word_bit(cols):
    """Traced variant of word_bit_np for device code."""
    w = cols >> WORD_BITS_EXP
    bit = jnp.uint32(1) << (cols & (WORD_BITS - 1)).astype(jnp.uint32)
    return w, bit


# ---------------------------------------------------------------------------
# Boolean algebra (roaring/roaring.go:3160 intersect, :3382 union, :3828
# difference, :4175 xor).  Trivial on dense bitsets; XLA fuses chains of these
# into a single pass over HBM, which is the whole point.
# ---------------------------------------------------------------------------

def intersect(a, b):
    return jnp.bitwise_and(a, b)


def union(a, b):
    return jnp.bitwise_or(a, b)


def difference(a, b):
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


def xor(a, b):
    return jnp.bitwise_xor(a, b)


def union_many(segs):
    """n-way union (roaring/roaring.go:739 unionInPlace).  ``segs`` is a
    stacked ``uint32[n, T, 128]`` tensor; reduces along axis 0 in one
    pass."""
    return jax.lax.reduce(
        segs, np.uint32(0), jax.lax.bitwise_or, dimensions=(0,)
    )


# ---------------------------------------------------------------------------
# Population counts (roaring/roaring.go:407 Count, :436 CountRange, :3021
# intersectionCount).  popcount on the VPU + an integer tree-reduce; counts
# fit int32 (<= 2^20 per segment), summed as int32 on device.
# ---------------------------------------------------------------------------

def popcount_words(a):
    return jax.lax.population_count(a).astype(jnp.int32)


def count(seg):
    """Total set bits of a segment (or of all rows if given [n, T, 128]:
    reduces over every axis — use row_counts for per-row)."""
    return jnp.sum(popcount_words(seg), dtype=jnp.int32)


def row_counts(frag):
    """Per-row popcount of a fragment tensor uint32[n, T, 128] ->
    int32[n]; any leading dimensions stay ([B, n, T, 128] -> [B, n]).
    Tiles add elementwise and lanes are crossed once a row."""
    return jnp.sum(popcount_words(frag), axis=(-2, -1), dtype=jnp.int32)


def intersection_count(a, b):
    """popcount(a & b) without materialising the intersection
    (roaring/roaring.go:3021-3158)."""
    return jnp.sum(popcount_words(jnp.bitwise_and(a, b)), dtype=jnp.int32)


@jax.jit
def intersection_counts_matrix(a, b):
    """Pairwise intersection counts between two row sets:
    uint32[n, T, 128] x uint32[m, T, 128] -> int32[n, m].

    This is the GroupBy hot loop (executor.go:3058 groupByIterator does it
    pair-at-a-time over roaring containers); batching it into one
    popcount-and-reduce lets the VPU stream both operand sets once per tile.
    """
    return row_counts(a[:, None] & b[None])


# ---------------------------------------------------------------------------
# Range masks and ranged ops (roaring/roaring.go:436 CountRange, :2982 flip,
# :562 OffsetRange).
# ---------------------------------------------------------------------------

def _range_mask(start: int, end: int, tile: tuple[int, int]):
    """uint32[tile] mask with bits [start, end) set.  start/end are traced
    or static scalars in [0, words*32].  Needs each word's linear index:
    an iota over the flat words, viewed as the tile."""
    start = jnp.asarray(start, jnp.int32)
    end = jnp.asarray(end, jnp.int32)
    base = jnp.arange(tile[0] * tile[1], dtype=jnp.int32).reshape(tile) \
        * WORD_BITS
    lo = jnp.clip(start - base, 0, WORD_BITS)
    hi = jnp.clip(end - base, 0, WORD_BITS)
    # (1<<hi)-1 with hi==32 overflows 32-bit shifts; build from the top:
    # mask_hi = all bits below hi = ~0 >> (32-hi), except hi==0 -> 0.
    full = jnp.uint32(0xFFFFFFFF)
    mask_hi = jnp.where(
        hi == 0, jnp.uint32(0), full >> (WORD_BITS - hi).astype(jnp.uint32)
    )
    mask_lo = jnp.where(
        lo == 0, jnp.uint32(0), full >> (WORD_BITS - lo).astype(jnp.uint32)
    )
    return mask_hi & ~mask_lo


def count_range(seg, start, end):
    """Count bits in [start, end) (roaring/roaring.go:436)."""
    mask = _range_mask(start, end, seg.shape[-2:])
    return jnp.sum(popcount_words(seg & mask), dtype=jnp.int32)


def flip(seg, start, end):
    """Toggle bits in [start, end) (roaring/roaring.go:2982)."""
    return seg ^ _range_mask(start, end, seg.shape[-2:])


def keep_range(seg, start, end):
    """Zero every bit outside [start, end)."""
    return seg & _range_mask(start, end, seg.shape[-2:])


# ---------------------------------------------------------------------------
# Shift (roaring/roaring.go:4288): move every bit up by one column.  Used by
# PQL Shift(row, n).  Bits shifted past the shard boundary are dropped, which
# matches per-segment shift in the reference (row.go:248 Shift).
# ---------------------------------------------------------------------------

def shift(seg, n: int = 1):
    """Shift bits toward higher column ids by static ``n`` >= 0.  A carry
    crosses words in their linear order, so the pass runs on the flat
    words and re-tiles its result."""
    if n == 0:
        return seg
    word_shift, bit_shift = divmod(n, WORD_BITS)
    seg = from_tile(seg)
    w = seg.shape[-1]
    if word_shift:
        pad = [(0, 0)] * (seg.ndim - 1) + [(word_shift, 0)]
        seg = jnp.pad(seg, pad)[..., :w]
    if bit_shift:
        lo = seg << np.uint32(bit_shift)
        carry = seg >> np.uint32(WORD_BITS - bit_shift)
        pad = [(0, 0)] * (seg.ndim - 1) + [(1, 0)]
        carry = jnp.pad(carry, pad)[..., :w]
        seg = lo | carry
    return to_tile(seg)


# ---------------------------------------------------------------------------
# Batched mutation.  The reference mutates roaring containers in place
# (roaring.go:228 Add); under XLA we batch positions and scatter into a
# donated buffer.  The storage layer keeps the authoritative copy host-side
# (see storage/fragment.py) and uses these for device-resident updates.
# ---------------------------------------------------------------------------

def _word_updates(frag, rows, cols):
    """Collapse a (row, col) batch into per-word OR masks with *unique* target
    words.  XLA has no scatter-OR, and ``.at[].set`` keeps an arbitrary
    duplicate, so positions sharing a 32-bit word must be pre-combined: sort
    by flat word index, OR bits of equal keys with an associative scan, and
    keep only the last (fully accumulated) entry of each run.

    Returns (targets, masks): int32 flat word indices (invalid/duplicate
    entries pointed one-past-the-end, to be dropped) and the OR-mask per
    entry.  Fragment must have < 2^31 / W rows (always true: W=32768 allows
    65k rows; real fragments are far smaller).  The flat word index
    ``row * W + word`` is the tiled fragment's row-major offset too.
    """
    n_words = frag.shape[-2] * frag.shape[-1]
    total = frag.size
    if total >= 2**31:
        raise ValueError(
            f"fragment too large for int32 scatter keys: {frag.shape} "
            f"(max {2**31 // n_words - 1} rows at {n_words} words)"
        )
    valid = rows >= 0
    r = jnp.maximum(rows, 0).astype(jnp.int32)
    w, bit = word_bit(cols)
    w = w.astype(jnp.int32)
    bit = jnp.where(valid, bit, jnp.uint32(0))
    key = r * n_words + w
    key = jnp.where(valid, key, total)  # sort invalid entries to the end
    order = jnp.argsort(key)
    key, bit = key[order], bit[order]

    def comb(x, y):
        kx, bx = x
        ky, by = y
        return ky, by | jnp.where(kx == ky, bx, jnp.uint32(0))

    key, acc = jax.lax.associative_scan(comb, (key, bit))
    is_last = jnp.concatenate(
        [key[1:] != key[:-1], jnp.ones((1,), dtype=bool)]
    )
    targets = jnp.where(is_last, key, total)  # total = out of bounds -> drop
    return targets, acc


@functools.partial(jax.jit, donate_argnums=0)
def set_bits(frag, rows, cols):
    """Set bits (rows[i], cols[i]) in fragment uint32[n, T, 128].  Duplicate
    positions and positions sharing a word are handled correctly; padding
    entries may use row == -1 (ignored)."""
    targets, masks = _word_updates(frag, rows, cols)
    flat = frag.reshape(-1)
    cur = flat.at[targets].get(mode="fill", fill_value=0)
    out = flat.at[targets].set(cur | masks, mode="drop")
    return out.reshape(frag.shape)


@functools.partial(jax.jit, donate_argnums=0)
def clear_bits(frag, rows, cols):
    """Clear bits (rows[i], cols[i]); same duplicate/padding semantics as
    set_bits."""
    targets, masks = _word_updates(frag, rows, cols)
    flat = frag.reshape(-1)
    cur = flat.at[targets].get(mode="fill", fill_value=0)
    out = flat.at[targets].set(cur & ~masks, mode="drop")
    return out.reshape(frag.shape)


# ---------------------------------------------------------------------------
# Host-side packing (numpy) — the import/export boundary.  Mirrors the role of
# roaring's serializer (roaring/roaring.go:1046 WriteTo / 1258 iterator).
# ---------------------------------------------------------------------------

def pack_columns(cols: np.ndarray, words: int = SHARD_WORDS) -> np.ndarray:
    """Sorted-or-not shard-local column ids -> uint32[words] bitset."""
    out = np.zeros(words, dtype=np.uint32)
    w, bit = word_bit_np(np.asarray(cols, dtype=np.int64))
    np.bitwise_or.at(out, w, bit)
    return out


def pack_fragment(rows: np.ndarray, cols: np.ndarray, n_rows: int,
                  words: int = SHARD_WORDS) -> np.ndarray:
    """(row, col) pairs -> uint32[n_rows, words] fragment tensor."""
    out = np.zeros((n_rows, words), dtype=np.uint32)
    rows = np.asarray(rows, dtype=np.int64)
    w, bit = word_bit_np(np.asarray(cols, dtype=np.int64))
    np.bitwise_or.at(out, (rows, w), bit)
    return out


def unpack_columns(seg: np.ndarray) -> np.ndarray:
    """uint32[words] bitset -> sorted int64 column ids."""
    seg = np.ascontiguousarray(np.asarray(seg, dtype=np.uint32))
    bits = np.unpackbits(seg.view(np.uint8), bitorder="little")
    return np.nonzero(bits)[0].astype(np.int64)


def unpack_fragment(frag: np.ndarray):
    """uint32[n, words] -> (row_ids, col_ids) int64 arrays, row-major order."""
    frag = np.ascontiguousarray(np.asarray(frag, dtype=np.uint32))
    n, w = frag.shape
    bits = np.unpackbits(frag.view(np.uint8), bitorder="little").reshape(n, w * 32)
    r, c = np.nonzero(bits)
    return r.astype(np.int64), c.astype(np.int64)
