"""Compressed container stream + device decode kernels — roaring's
array/bitmap/run container algebra lowered to the TPU (PAPER.md's stated
target; ROADMAP item 1).

A fragment's device mirror no longer has to be the dense
``uint32[rows, SHARD_WORDS]`` tensor: it can stay HBM-resident as a
*packed container stream* — per-container key/type/count/offset tables
plus one payload word buffer — and be decoded to dense tiles ON DEVICE
only at op time, inside the same XLA program that runs the query op.
Residency then costs compressed bytes (8 bytes per non-zero word for
uniformly sparse data, a few words per run for clustered data) instead of
the full dense footprint — the 100x dense blowup that made over-budget
working sets stream at ~1/340th of resident throughput (BENCH_r05_local
leg 6 vs 5).

Container forms (the word-granularity analog of roaring/roaring.go:64-69;
a container covers ``CONTAINER_WORDS`` = 2048 words = 2^16 bits):

* **array** (type 0): ``count`` (word, value) entries, chosen for
  sparse containers (fewer than 1024 non-zero words, where 2 words an
  entry beat the bitmap's 2048).  The entries of ALL of a fragment's
  array containers lie outside the payload, in two arrays of 32 *class
  streams*: ``a_idx[32, L]`` holds each entry's flat word index
  (``row * SHARD_WORDS + word``) and ``a_val[32, L]`` its value, the
  entry of word ``w`` in stream ``q * 8 + c`` for its register group
  ``q = (w >> 13) & 3`` and its sublane ``c = (w >> 7) & 7``, each
  stream ascending and padded with ``ARRAY_PAD``.  A row of the
  device's word tile is 128 words and a vector register eight such
  rows, so an entry lies in the SUBLANE of the register that the word
  it names has in the tile, and the eight streams of group ``q`` name
  words of registers ``8q`` .. ``8q + 7`` alone: the TPU's gather
  along the lanes (``kernels.fused_row_counts``) fetches a filter's
  words for a whole register of entries at once, and a walk over the
  8 registers of a group replaces one over a shard's 256 rows.
  Decodes by one scatter.
* **bitmap** (type 1): the container's 2048 words verbatim.  Chosen for
  dense containers; decodes by contiguous copy — compression-neutral by
  design, so dense corpora never regress.
* **run** (type 2): ``count`` bit-level [start, end) pairs (u32 each,
  within the container's 2^16-bit span).  Chosen when few runs cover the
  container's bits (Store'd full rows, clustered ingests); decodes via
  per-word range masks.

Decode is a pure jax function (``decode_block``) compiled
shape-polymorphically per (rows, container-count, payload, array-entry,
run-count) power-of-two bucket, so one executable serves every fragment
in a bucket; the mesh executor calls it INSIDE its vmapped shard_map
bodies so decoded dense tiles exist only as XLA temporaries for the
duration of one launch (the reusable dense workspace,
docs/memory-budget.md), never as persistent HBM residents.

Everything here runs through XLA.  The one hand-scheduled Pallas
kernel, the count of a compressed field's rows under a filter with no
row of it decoded, lives in ops/kernels.py (``fused_row_counts``),
selected by the ``container-kernels`` knob (``kernels.resolve()``) with
an XLA form of the same count behind it — the ``jnp`` backend, the kill
switch; this module is the decoders and the host-side pack/oracle
layer.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from ..core import CONTAINER_WORDS, SHARD_WORDS, WORD_BITS

# Container type codes (device-side selectors; padding rows use -1).
TYPE_ARRAY = 0
TYPE_BITMAP = 1
TYPE_RUN = 2

# Array form wins while 2 payload words per entry undercut the bitmap's
# CONTAINER_WORDS; at >= CONTAINER_WORDS // 2 non-zero words the bitmap
# copy is smaller AND decodes cheaper.
ARRAY_WORDS_MAX = CONTAINER_WORDS // 2 - 1  # 1023

# Run containers are only chosen up to this many runs: device decode
# costs O(runs x CONTAINER_WORDS) per container (each run contributes a
# masked OR over the tile), so unbounded run counts would trade HBM for
# unbounded VPU work.  Clustered data this form exists for (Store'd
# rows, range ingests) sits at 1-16 runs.
RUN_MAX = 64

# ... and only where the run form saves at least this many payload
# words over the array or bitmap form: chance neighbours in sparse data
# are no clustering.
RUN_SAVES_MIN = 8

# Padding of the array entries' class streams: no word's index (a
# compressed fragment's flat indices stay below 2^31 - 1), and above
# every one, so a padded stream still ascends.
ARRAY_PAD = np.int32((1 << 31) - 1)
# Class streams of a fragment's array entries: one a sublane of each
# group of a filter tile's registers (eight a group).
ARRAY_CLASSES = 8
ARRAY_GROUPS = 4
ARRAY_STREAMS = ARRAY_GROUPS * ARRAY_CLASSES
# ...each a whole number of registers' lanes.
ARRAY_LANES = 128

# Dense fragments beyond this many rows never compress: the decode
# scatter's flat int32 indices must stay below 2^31 — a fragment's words
# (rows * SHARD_WORDS) and two scratch words an array entry, of which it
# has at most half as many as words (``_stream_targets``).
MAX_COMPRESSED_ROWS = (1 << 29) // SHARD_WORDS - 1


_CONTAINER_SHIFT = CONTAINER_WORDS.bit_length() - 1
assert 1 << _CONTAINER_SHIFT == CONTAINER_WORDS


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (0 stays 0) — the shape-bucketing unit
    that keeps one compiled decode executable serving many fragments."""
    return 0 if n <= 0 else 1 << (int(n) - 1).bit_length()


def _eighths_bucket(n: int, pow2_max: int) -> int:
    """A power of two up to ``pow2_max``, and beyond a multiple of an
    eighth of the power of two below ``n``: the padding stays under an
    eighth."""
    if n <= pow2_max:
        return pow2_bucket(n)
    step = (1 << ((int(n) - 1).bit_length() - 1)) // 8
    return -(-int(n) // step) * step


def payload_bucket(n: int) -> int:
    """The payload's shape bucket: a power of two up to 2^14 words, and
    beyond a multiple of an eighth of the power of two below ``n`` — the
    shard-axis bucket's rule (``MeshExecutor._bucket``), for its reason:
    every stacked fragment holds its bucket, so doubling costs up to half
    of what compression saved (a 4.3 MB stream held 8 MiB: 954 such
    fragments did not fit beside the rest, PERF.md PR 37), while an
    eighth's steps keep the padding under an eighth and every bucket a
    whole number of 2048-word blocks."""
    return _eighths_bucket(n, 1 << 14)


def stream_bucket(n: int) -> int:
    """The class streams' shape bucket: 0 for none, else one register's
    lanes up to a power of two of 2^11 lanes and, past it, eighths of
    one (multiples of 256 lanes): ``payload_bucket``'s rule from a
    quarter of its length, since 32 streams a quarter as long as eight
    would otherwise round up to as much as 1.78 times the bytes."""
    return 0 if n <= 0 else max(ARRAY_LANES, _eighths_bucket(n, 1 << 11))


def stream_of(flat, groups: int = ARRAY_GROUPS):
    """The class stream of each flat word index: ``q * 8 + c`` for the
    word's register group ``q`` (of ``groups``) and sublane ``c`` in the
    device's ``(256, 128)`` word tile."""
    regs = SHARD_WORDS // (ARRAY_CLASSES * ARRAY_LANES)     # 32
    group = ((flat >> 10) & (regs - 1)) // (regs // groups)
    return group * ARRAY_CLASSES + ((flat >> 7) & (ARRAY_CLASSES - 1))


@dataclasses.dataclass(frozen=True)
class Packed:
    """One fragment's packed container stream (host arrays, built from
    the sparse word store without materialising the dense tensor)."""
    keys: np.ndarray      # int32[C] container ids (flat_word // 2048), sorted
    types: np.ndarray     # int32[C] TYPE_*
    counts: np.ndarray    # int32[C] entries (array) / words (bitmap) / runs
    offsets: np.ndarray   # int32[C] payload word offset (0: array)
    payload: np.ndarray   # uint32[P] bitmap blocks, then run pairs
    a_idx: np.ndarray     # int32[32, L] array entries' flat word indices
    a_val: np.ndarray     # uint32[32, L] ... and their values
    r_max: int            # largest run-container run count

    @property
    def array_words(self) -> int:
        """Entries of all array containers."""
        return int(self.counts[self.types == TYPE_ARRAY].sum())

    @property
    def a_len(self) -> int:
        """Length of a class stream (the longest of the 32, in whole
        ``ARRAY_LANES``; 0: no array container)."""
        return int(self.a_idx.shape[1])

    @property
    def nbytes(self) -> int:
        return int(self.keys.nbytes + self.types.nbytes +
                   self.counts.nbytes + self.offsets.nbytes +
                   self.payload.nbytes + self.a_idx.nbytes +
                   self.a_val.nbytes)

    def type_histogram(self) -> dict[str, int]:
        t = self.types
        return {"array": int(np.count_nonzero(t == TYPE_ARRAY)),
                "bitmap": int(np.count_nonzero(t == TYPE_BITMAP)),
                "run": int(np.count_nonzero(t == TYPE_RUN))}


def class_streams(flat: np.ndarray, val: np.ndarray,
                  groups: int = ARRAY_GROUPS):
    """(a_idx int32[8 * groups, L], a_val uint32[8 * groups, L]) of array
    entries given by ascending flat word indices and their values: each
    entry in the stream of its word's register group and sublane
    (``stream_of``), streams ascending, padded to a whole number of
    ``ARRAY_LANES`` with ``ARRAY_PAD`` / 0."""
    n, streams = flat.size, groups * ARRAY_CLASSES
    if n == 0:
        return (np.zeros((streams, 0), dtype=np.int32),
                np.zeros((streams, 0), dtype=np.uint32))
    cls = stream_of(flat, groups).astype(np.intp)
    order = np.argsort(cls, kind="stable")      # ascending within a class
    per = np.bincount(cls, minlength=streams)
    L = -(-int(per.max()) // ARRAY_LANES) * ARRAY_LANES
    a_idx = np.full((streams, L), ARRAY_PAD, dtype=np.int32)
    a_val = np.zeros((streams, L), dtype=np.uint32)
    c = cls[order]
    at = np.arange(n) - np.repeat(np.cumsum(per) - per, per)
    a_idx[c, at] = flat[order]
    a_val[c, at] = val[order]
    return a_idx, a_val


def _bit_runs(dense_words: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """([starts], [ends]) of the set-bit runs of one container's 2048
    words, bit-level [start, end) within the 2^16-bit span."""
    bits = np.unpackbits(dense_words.view(np.uint8), bitorder="little")
    d = np.diff(bits.astype(np.int8))
    starts = np.nonzero(d == 1)[0] + 1
    ends = np.nonzero(d == -1)[0] + 1
    if bits[0]:
        starts = np.concatenate(([0], starts))
    if bits[-1]:
        ends = np.concatenate((ends, [bits.size]))
    return starts, ends


def estimate_packed_bytes(idx: np.ndarray) -> int:
    """Upper bound on pack_words' output size from the sparse indices
    alone (run containers only shrink it) — the cheap density-heuristic
    input that decides compressed vs dense residency without packing:
    a census of the containers and of the array entries' classes."""
    if idx.size == 0:
        return 0
    cid = idx >> _CONTAINER_SHIFT
    start = np.concatenate(([0], np.flatnonzero(cid[1:] != cid[:-1]) + 1))
    cnt = np.diff(start, append=idx.size)
    is_array = cnt <= ARRAY_WORDS_MAX
    per = np.bincount(stream_of(idx[np.repeat(is_array, cnt)]),
                      minlength=ARRAY_STREAMS)
    stream = -(-int(per.max()) // ARRAY_LANES) * ARRAY_LANES
    return 4 * (CONTAINER_WORDS * int(np.count_nonzero(~is_array))
                + 2 * ARRAY_STREAMS * stream) + 16 * cnt.size


def pack_words(idx: np.ndarray, val: np.ndarray) -> Packed:
    """Pack a fragment's sparse word store (sorted flat indices + word
    values, storage/fragment.py) into a container stream, choosing the
    cheapest form per container (the optimize heuristic of
    roaring.go:2232, word-granular).  One pass of whole-array numpy over
    the store: a node that holds more index than HBM packs every sparse
    fragment before its first query, and a Python step a container
    (two thousand to a 128-row fragment) made that minutes."""
    if idx.size == 0:
        z = np.zeros(0, dtype=np.int32)
        return Packed(z, z.copy(), z.copy(), z.copy(),
                      np.zeros(0, dtype=np.uint32),
                      *class_streams(idx, val), 0)
    # the store is sorted: a container starts where the id changes
    # (CONTAINER_WORDS is a power of two; shifts and masks, not a
    # 64-bit divide a word)
    cid = idx >> _CONTAINER_SHIFT
    start = np.concatenate(
        ([0], np.flatnonzero(cid[1:] != cid[:-1]) + 1))
    cnt = np.diff(start, append=idx.size)
    C = start.size
    keys = cid[start].astype(np.int32)
    w_off = (idx & (CONTAINER_WORDS - 1)).astype(np.uint32)
    of = np.repeat(np.arange(C), cnt)       # each stored word's container
    # a stored word continues its predecessor's word run when that one
    # is the word before it in the same container
    joins = np.zeros(idx.size, dtype=bool)
    joins[1:] = (np.diff(idx) == 1) & (of[1:] == of[:-1])
    word_runs = np.add.reduceat(~joins, start, dtype=np.int64)
    # bit runs: a run starts at a set bit whose lower neighbour is clear,
    # and bit 0's neighbour is bit 31 of a joined predecessor.  Every gap
    # between stored words forces a separate bit run, so only containers
    # of few word runs can be run containers at all.
    carry = np.zeros(idx.size, dtype=np.uint32)
    carry[1:] = np.where(joins[1:], val[:-1] >> np.uint32(31), 0)
    starts = val & ~((val << np.uint32(1)) | carry)
    nr = np.add.reduceat(np.bitwise_count(starts), start, dtype=np.int64)
    # a run container has to save eight words over the form it
    # replaces: two neighbouring words that happen to share a run would
    # save two, and cost every fragment stacked beside them the run
    # decode (RUN_SAVES_MIN)
    is_run = (word_runs <= RUN_MAX) & (nr <= RUN_MAX) & \
        (2 * nr + RUN_SAVES_MIN <= np.minimum(2 * cnt, CONTAINER_WORDS))
    is_array = ~is_run & (cnt <= ARRAY_WORDS_MAX)
    types = np.where(is_run, TYPE_RUN, np.where(
        is_array, TYPE_ARRAY, TYPE_BITMAP)).astype(np.int32)
    counts = np.where(is_run, nr, np.where(
        is_array, cnt, CONTAINER_WORDS)).astype(np.int32)
    # payload order: bitmap containers first, so each one starts on a
    # CONTAINER_WORDS boundary and is one block of the payload seen as
    # [blocks, 2048]; then the run containers.  The array entries lie
    # outside it, in their class streams
    sizes = np.where(is_run, 2 * nr, np.where(
        is_array, 0, CONTAINER_WORDS)).astype(np.int64)
    at = np.zeros(C, dtype=np.int64)
    base = 0
    for mask in (types == TYPE_BITMAP, is_run):
        at[mask] = base + np.cumsum(sizes[mask]) - sizes[mask]
        base += int(sizes[mask].sum())
    payload = np.zeros(base, dtype=np.uint32)
    w_type, w_at = types[of], at[of]
    bm = w_type == TYPE_BITMAP
    payload[w_at[bm] + w_off[bm]] = val[bm]
    ar = w_type == TYPE_ARRAY
    for i in np.flatnonzero(is_run):        # few: the clustered rows
        a, n = int(start[i]), int(cnt[i])
        dense = np.zeros(CONTAINER_WORDS, dtype=np.uint32)
        dense[w_off[a: a + n]] = val[a: a + n]
        starts_b, ends_b = _bit_runs(dense)
        o = int(at[i])
        payload[o: o + 2 * starts_b.size: 2] = starts_b
        payload[o + 1: o + 2 * starts_b.size: 2] = ends_b
    return Packed(keys, types, counts, at.astype(np.int32), payload,
                  *class_streams(idx[ar], val[ar]),
                  int(nr[is_run].max(initial=0)))


def unpack_packed(p: Packed, rows: int,
                  words: int = SHARD_WORDS) -> np.ndarray:
    """Host (numpy) decode oracle: the dense tensor a Packed stream
    represents — the differential reference for the device kernel."""
    out = np.zeros(rows * words, dtype=np.uint32)
    for i in range(p.keys.size):
        base = int(p.keys[i]) * CONTAINER_WORDS
        off = int(p.offsets[i])
        n = int(p.counts[i])
        t = int(p.types[i])
        if t == TYPE_BITMAP:
            out[base: base + CONTAINER_WORDS] = \
                p.payload[off: off + CONTAINER_WORDS]
        elif t == TYPE_RUN:
            pairs = p.payload[off: off + 2 * n].astype(np.int64)
            for s, e in pairs.reshape(n, 2):
                w0, w1 = s // WORD_BITS, (e - 1) // WORD_BITS
                for w in range(w0, w1 + 1):
                    lo = max(s - w * WORD_BITS, 0)
                    hi = min(e - w * WORD_BITS, WORD_BITS)
                    m = ((1 << hi) - 1) & ~((1 << lo) - 1)
                    out[base + w] |= np.uint32(m & 0xFFFFFFFF)
    live = p.a_idx != ARRAY_PAD
    out[p.a_idx[live]] = p.a_val[live]
    return out.reshape(rows, words)


# ---------------------------------------------------------------------------
# Device decode.  Pure jnp — callable inside vmapped shard_map bodies
# (the decode fuses into the op's executable) or standalone via
# upload_decode (Fragment.device()'s compressed upload path).
# ---------------------------------------------------------------------------

def run_tiles(types, counts, offsets, payload, r_bucket: int):
    """``uint32[C, 2048]``: the tile of every run container (zeros for
    the others), from per-word range masks over at most ``r_bucket``
    runs a container."""
    import jax
    import jax.numpy as jnp

    j = jnp.arange(CONTAINER_WORDS, dtype=jnp.int32)
    r = jnp.arange(r_bucket, dtype=jnp.int32)
    full = jnp.uint32(0xFFFFFFFF)

    def run_tile(typ, cnt, off):
        valid = (r < cnt) & (typ == TYPE_RUN)
        rs = jnp.where(valid, payload.at[off + 2 * r].get(
            mode="fill", fill_value=0).astype(jnp.int32), 0)
        re = jnp.where(valid, payload.at[off + 2 * r + 1].get(
            mode="fill", fill_value=0).astype(jnp.int32), 0)
        base = j * WORD_BITS                       # [cw]
        lo = jnp.clip(rs[:, None] - base[None, :], 0, WORD_BITS)
        hi = jnp.clip(re[:, None] - base[None, :], 0, WORD_BITS)
        mhi = jnp.where(hi == 0, jnp.uint32(0),
                        full >> (WORD_BITS - hi).astype(jnp.uint32))
        mlo = jnp.where(lo == 0, jnp.uint32(0),
                        full >> (WORD_BITS - lo).astype(jnp.uint32))
        return jax.lax.reduce(mhi & ~mlo, np.uint32(0),
                              jax.lax.bitwise_or, dimensions=(0,))

    return jax.vmap(run_tile)(types, counts, offsets)


def _bitmap_blocks(keys, types, offsets, payload, first, n: int):
    """``uint32[n, 2048]``: the tiles of container keys ``first`` ..
    ``first + n`` as far as bitmap containers hold them (zeros for the
    others), in ONE gather of whole blocks of the payload, by a
    tile -> block map made from the C-entry tables."""
    import jax.numpy as jnp
    cw = CONTAINER_WORDS
    # whole blocks (the shape buckets already are) and one block of
    # zeros behind them: what a tile with no bitmap container takes
    blocks = jnp.pad(payload, (0, (-payload.shape[0]) % cw + cw)).reshape(
        -1, cw)
    none = blocks.shape[0] - 1
    rel = keys - first
    hit = (keys >= 0) & (types == TYPE_BITMAP) & (rel >= 0) & (rel < n)
    src = jnp.full((n,), none, dtype=jnp.int32).at[
        jnp.where(hit, rel, n)].set(
        jnp.minimum(offsets // cw, none), mode="drop")
    return blocks.at[src].get(mode="promise_in_bounds")


def _run_rows(keys, types, counts, offsets, payload, first, n: int,
              r_bucket: int, out):
    """``out`` ``[n, 2048]`` with the tiles of the run containers among
    keys ``first`` .. ``first + n`` set (a scatter of rows)."""
    import jax.numpy as jnp
    rel = keys - first
    hit = (keys >= 0) & (types == TYPE_RUN) & (rel >= 0) & (rel < n)
    return out.at[jnp.where(hit, rel, n)].set(
        run_tiles(types, counts, offsets, payload, r_bucket), mode="drop")


def decode_block(keys, types, counts, offsets, payload, a_idx, a_val, *,
                 rows: int, words: int = SHARD_WORDS, a_bucket: int = 0,
                 r_bucket: int = 0):
    """Decode one fragment's packed container stream to dense
    ``uint32[rows, words]`` on device.

    ``keys/types/counts/offsets``: int32[C], keys ascending (padded
    entries use key -1 / type -1 — they decode to nothing).
    ``payload``: uint32[P]; ``a_idx`` / ``a_val``: the array entries'
    class streams, ``[32, a_bucket]``.  ``a_bucket``/``r_bucket``: static
    per-bucket stream length and run-count maximum; 0 compiles that
    container form out entirely (a sparse-only corpus pays no run-mask
    code, a run-only corpus no scatter).

    What moves is rows of words wherever it can be, because a TPU
    gathers and scatters single words two or three orders slower than it
    copies them (PERF.md PR 37): bitmap containers are whole blocks of
    the payload, taken in one gather of rows; run containers compute
    their tile from per-word range masks and are placed by a scatter of
    rows; the array entries are the one scatter of single words, an
    entry a word (5 ns each on a v5e), which is why the serving path
    takes rows of a compressed field (``decode_row``) or counts them
    where they lie (``kernels.fused_row_counts``) and decodes a whole
    fragment only where a reducer reads all of it.

    Word indices are unique by construction (one container a key, one
    entry a word), so plain set is exact — and the scatter is told that
    they ascend, without which the TPU compiler takes the updates one
    by one, some hundred times slower (``_scatter_streams``).
    """
    import jax.numpy as jnp

    total = rows * words
    if keys.shape[0] == 0 or rows == 0:
        return jnp.zeros((rows, words), dtype=jnp.uint32)
    cw = CONTAINER_WORDS
    tiles = total // cw
    out = _bitmap_blocks(keys, types, offsets, payload, 0, tiles)
    if r_bucket:
        out = _run_rows(keys, types, counts, offsets, payload, 0, tiles,
                        r_bucket, out)
    out = out.reshape(rows, words)
    if a_bucket:
        # containers are disjoint: the array entries' words are zero in
        # what the blocks and the runs made
        out = out | _scatter_streams(a_idx, a_val, 0, rows, words)
    return out


def _scatter_streams(a_idx, a_val, first, rows: int, words: int):
    """``uint32[rows, words]``: the entries of the class streams
    ``a_idx`` / ``a_val`` ``[streams, n]`` that lie in rows ``first`` ..
    ``first + rows`` (``first`` may be traced), each set at its word;
    all else zero.  ONE scatter whose indices ascend, are unique and in
    bounds, and are told to be: each stream fills a plane of its own —
    the words of its register group and sublane, in the order the
    stream has them — with scratch words before it for the entries below
    ``first`` and behind it for those past the last row and the padding,
    each entry its own; the planes are then interleaved into word order,
    ``(rows, group, register, sublane, lane)``, a copy of rows.  (Told
    to ascend with indices out of bounds to be dropped, the chip lost
    updates, PERF.md §6: hence the scratch.)"""
    import jax.numpy as jnp
    streams, n = a_idx.shape
    flat, span, plane = _stream_targets(a_idx, first, rows, words)
    planes = jnp.zeros(streams * span, dtype=jnp.uint32).at[
        flat.reshape(-1)].set(
        a_val.reshape(-1), mode="promise_in_bounds",
        indices_are_sorted=True, unique_indices=True).reshape(
        streams, span)[:, n: n + plane]
    groups = streams // ARRAY_CLASSES
    return planes.reshape(groups, ARRAY_CLASSES, rows,
                          plane // (rows * ARRAY_LANES), ARRAY_LANES
                          ).transpose(2, 0, 3, 1, 4).reshape(rows, words)


def _stream_targets(a_idx, first, rows: int, words: int):
    """(flat int32[streams, n], span, plane): where ``_scatter_streams``
    sets each entry — stream ``s`` owns ``span`` = n + ``plane`` + n
    words from ``s * span``: n scratch words, the plane, n scratch words
    — so that, read stream after stream, the indices strictly ascend."""
    import jax.numpy as jnp
    streams, n = a_idx.shape
    per = words // streams                  # a row's words in one stream
    regs = per // ARRAY_LANES               # the registers of a group
    plane = rows * per
    lo = first * words
    at = jnp.arange(n, dtype=jnp.int32)[None, :]
    rel = a_idx - lo
    # the word's place in its stream's plane: row, register, lane
    w = rel % words
    pos = (rel // words) * per + ((w >> 10) & (regs - 1)) * ARRAY_LANES \
        + (w & (ARRAY_LANES - 1))
    below, past = a_idx < lo, rel >= rows * words
    span = n + plane + n
    flat = jnp.where(below, at, jnp.where(past, n + plane + at, n + pos)) \
        + jnp.arange(streams, dtype=jnp.int32)[:, None] * span
    return flat, span, plane


def row_has_entries(a_idx, rid, words: int = SHARD_WORDS):
    """Whether any array entry of ``a_idx`` (any leading axes: a stacked
    block's streams) lies in row ``rid`` — what ``decode_row`` takes as
    ``has_array`` so that a launch whose row is bitmap containers in
    every shard skips the scatter."""
    import jax.numpy as jnp
    lo = rid * words
    return jnp.any((a_idx >= lo) & (a_idx < lo + words))


def decode_row(keys, types, counts, offsets, payload, a_idx, a_val, rid,
               *, rows: int, words: int = SHARD_WORDS, a_bucket: int = 0,
               r_bucket: int = 0, has_array=None):
    """Row ``rid`` (traced) of one fragment's packed stream as dense
    ``uint32[words]``, zeros for a row the fragment does not hold: what a
    plan's row take costs of a compressed field, a row and not the
    fragment.  Its bitmap containers are sixteen blocks of the payload;
    its array entries are one stretch of each class stream, found by
    counting the entries below the row (the streams ascend) and
    scattered under ``has_array`` only — a scalar that is not batched
    over the shards (``row_has_entries`` of the launch's whole block),
    so that under ``vmap`` the conditional stays one, and a launch over
    rows held as bitmap containers runs no scatter at all."""
    import jax
    import jax.numpy as jnp

    if keys.shape[0] == 0 or rows == 0:
        return jnp.zeros((words,), dtype=jnp.uint32)
    cw = CONTAINER_WORDS
    tpr = words // cw
    # a row id past the fragment reads as the row behind its last: empty
    rid = jnp.clip(jnp.asarray(rid, dtype=jnp.int32), 0, rows)
    out = _bitmap_blocks(keys, types, offsets, payload, rid * tpr, tpr)
    if r_bucket:
        out = _run_rows(keys, types, counts, offsets, payload, rid * tpr,
                        tpr, r_bucket, out)
    out = out.reshape(words)
    if a_bucket:
        # a row has SHARD_WORDS / 32 words a stream: no row has more
        # entries in one
        w = min(a_bucket, words // a_idx.shape[0])
        lo = rid * words

        def scatter(out):
            # the row's stretch of each stream starts where the entries
            # below the row end (a slice that would pass the stream's
            # end is moved back, and begins with entries below the row)
            start = jnp.sum(a_idx < lo, axis=1).astype(jnp.int32)
            take = jax.vmap(lambda a, s: jax.lax.dynamic_slice(a, (s,), (w,)))
            return out | _scatter_streams(
                take(a_idx, start), take(a_val, start), rid, 1, words)[0]

        if has_array is None:
            out = scatter(out)
        else:
            out = jax.lax.cond(has_array, scatter, lambda o: o, out)
    return out


def pad_packed(p: Packed) -> tuple[np.ndarray, ...]:
    """Pad a Packed stream's arrays to their buckets (padding
    containers use key/type -1, padding entries ``ARRAY_PAD``) — the
    per-fragment staging unit the compiled decode buckets expect."""
    cb = pow2_bucket(p.keys.size)
    pb = payload_bucket(p.payload.size)
    ab = stream_bucket(p.a_len)
    keys = np.full(cb, -1, dtype=np.int32)
    types = np.full(cb, -1, dtype=np.int32)
    counts = np.zeros(cb, dtype=np.int32)
    offsets = np.zeros(cb, dtype=np.int32)
    c = p.keys.size
    keys[:c] = p.keys
    types[:c] = p.types
    counts[:c] = p.counts
    offsets[:c] = p.offsets
    payload = np.zeros(pb, dtype=np.uint32)
    payload[: p.payload.size] = p.payload
    a_idx = np.full((ARRAY_STREAMS, ab), ARRAY_PAD, dtype=np.int32)
    a_val = np.zeros((ARRAY_STREAMS, ab), dtype=np.uint32)
    a_idx[:, : p.a_len] = p.a_idx
    a_val[:, : p.a_len] = p.a_val
    return keys, types, counts, offsets, payload, a_idx, a_val


@functools.lru_cache(maxsize=None)
def _decode_jit(rows: int, words: int, a_bucket: int, r_bucket: int):
    import jax

    def _traced(*a, **k):
        # runs only while jax traces — the compile registry's exact
        # per-bucket compile detector (docs/observability.md)
        from ..utils import devobs
        devobs.COMPILES.mark_traced()
        from . import bitset
        # the mirror is the device's word tile, [rows, 256, 128]
        return bitset.to_tile(decode_block(*a, **k))

    return jax.jit(functools.partial(
        _traced, rows=rows, words=words, a_bucket=a_bucket,
        r_bucket=r_bucket))


def upload_decode(p: Packed, rows: int, target=None,
                  words: int = SHARD_WORDS):
    """Ship a packed stream to the device and decode it there to the
    dense mirror, uint32[rows, 256, 128] (ops/bitset.py
    "Representation") — Fragment.device()'s compressed upload path.  The
    transfer moves compressed bytes; the sparse->dense expansion happens
    on device instead of in host memory + on the wire.  Each (rows,
    buckets) decode bucket reports its compiles to the device compile
    registry like the mesh executables do."""
    import time as _time

    import jax

    from ..utils import devobs

    arrs = [jax.device_put(a, target) for a in pad_packed(p)]
    a_b, r_b = stream_bucket(p.a_len), pow2_bucket(p.r_max)
    fn = _decode_jit(rows, words, a_b, r_b)
    reg = devobs.COMPILES
    reg.begin_call()
    t0 = _time.perf_counter()
    out = fn(*arrs)
    if reg.traced():
        # the container/payload shape buckets are intended shape
        # polymorphism (one jit, one specialization per bucket), so they
        # belong IN the signature — without them a second bucket of the
        # same jit would read as a false retrace alarm
        c_b = pow2_bucket(p.keys.size)
        p_b = payload_bucket(p.payload.size)
        reg.note_call(
            f"decode:{rows}x{words}:c{c_b}:p{p_b}:a{a_b}:r{r_b}",
            "decode", _time.perf_counter() - t0,
            devobs.fingerprint(arrs))
    return out
