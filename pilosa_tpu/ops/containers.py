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

* **array** (type 0): ``count`` (word-slot, word-value) entries — payload
  is ``count`` u32 slot indices followed by ``count`` u32 word values.
  Chosen for sparse containers (fewer than 1024 non-zero words, where
  2 words/entry beats the bitmap's 2048).  Decodes by scatter.
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

Everything here runs through XLA (gather/scatter/mask ops the TPU VPU
executes at full lane width).  The hand-scheduled Pallas variant that
decodes containers HBM->VMEM tile-by-tile lives in ops/kernels.py behind
the same ``decode_block`` signature, selected by the
``container-kernels`` knob (``kernels.resolve()``); this module is the
``jnp`` backend — the kill switch — and the host-side pack/oracle layer
both backends share.
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

# Dense fragments beyond this many rows never compress: the decode
# scatter's flat int32 indices must stay below 2^31 (rows * SHARD_WORDS).
MAX_COMPRESSED_ROWS = (1 << 31) // SHARD_WORDS - 1


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (0 stays 0) — the shape-bucketing unit
    that keeps one compiled decode executable serving many fragments."""
    return 0 if n <= 0 else 1 << (int(n) - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class Packed:
    """One fragment's packed container stream (host arrays, built from
    the sparse word store without materialising the dense tensor)."""
    keys: np.ndarray      # int32[C] container ids (flat_word // 2048), sorted
    types: np.ndarray     # int32[C] TYPE_*
    counts: np.ndarray    # int32[C] entries (array) / words (bitmap) / runs
    offsets: np.ndarray   # int32[C] payload word offset
    payload: np.ndarray   # uint32[P]
    a_max: int            # largest array-container entry count
    r_max: int            # largest run-container run count

    @property
    def nbytes(self) -> int:
        return int(self.keys.nbytes + self.types.nbytes +
                   self.counts.nbytes + self.offsets.nbytes +
                   self.payload.nbytes)

    def type_histogram(self) -> dict[str, int]:
        t = self.types
        return {"array": int(np.count_nonzero(t == TYPE_ARRAY)),
                "bitmap": int(np.count_nonzero(t == TYPE_BITMAP)),
                "run": int(np.count_nonzero(t == TYPE_RUN))}


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
    input that decides compressed vs dense residency without packing."""
    if idx.size == 0:
        return 0
    _, cnt = np.unique(idx // CONTAINER_WORDS, return_counts=True)
    payload_words = int(np.minimum(2 * cnt, CONTAINER_WORDS).sum())
    return 4 * payload_words + 16 * cnt.size


def pack_words(idx: np.ndarray, val: np.ndarray) -> Packed:
    """Pack a fragment's sparse word store (sorted flat indices + word
    values, storage/fragment.py) into a container stream, choosing the
    cheapest form per container (the optimize heuristic of
    roaring.go:2232, word-granular)."""
    cid = idx // CONTAINER_WORDS
    uniq, start, cnt = np.unique(cid, return_index=True,
                                 return_counts=True)
    C = uniq.size
    keys = uniq.astype(np.int32)
    types = np.empty(C, dtype=np.int32)
    counts = np.empty(C, dtype=np.int32)
    offsets = np.empty(C, dtype=np.int32)
    parts: list[np.ndarray] = []
    a_max = r_max = 0
    for i in range(C):
        a, n = int(start[i]), int(cnt[i])
        w_off = (idx[a: a + n] % CONTAINER_WORDS).astype(np.uint32)
        w_val = val[a: a + n]
        ctype = -1
        dense = None
        # bit-run candidacy prefilter: every gap between non-adjacent
        # stored words forces a separate bit run, so the word-run count
        # lower-bounds the bit-run count — skip the unpackbits scan when
        # it already exceeds RUN_MAX
        if int(np.count_nonzero(np.diff(w_off.astype(np.int64)) != 1)) \
                + 1 <= RUN_MAX:
            dense = np.zeros(CONTAINER_WORDS, dtype=np.uint32)
            dense[w_off] = w_val
            starts_b, ends_b = _bit_runs(dense)
            nr = starts_b.size
            if nr <= RUN_MAX and 2 * nr < min(2 * n, CONTAINER_WORDS):
                ctype = TYPE_RUN
                pl = np.empty(2 * nr, dtype=np.uint32)
                pl[0::2] = starts_b
                pl[1::2] = ends_b
                counts[i] = nr
                r_max = max(r_max, nr)
        if ctype < 0:
            if n <= ARRAY_WORDS_MAX:
                ctype = TYPE_ARRAY
                pl = np.concatenate([w_off, w_val])
                counts[i] = n
                a_max = max(a_max, n)
            else:
                ctype = TYPE_BITMAP
                if dense is None:
                    dense = np.zeros(CONTAINER_WORDS, dtype=np.uint32)
                    dense[w_off] = w_val
                pl = dense
                counts[i] = CONTAINER_WORDS
        types[i] = ctype
        parts.append(pl)
    # payload order: bitmap containers first, so each one starts on a
    # CONTAINER_WORDS boundary and the TPU kernel (ops/kernels.py)
    # copies it with one tile-aligned VMEM load; array and run entries
    # are read word by word and need no alignment
    order = np.argsort(types != TYPE_BITMAP, kind="stable")
    sizes = np.array([parts[i].size for i in order], dtype=np.int64)
    offsets[order] = np.cumsum(sizes) - sizes
    payload = np.concatenate([parts[i] for i in order]) if parts \
        else np.zeros(0, dtype=np.uint32)
    return Packed(keys, types, counts, offsets, payload, a_max, r_max)


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
        elif t == TYPE_ARRAY:
            slots = p.payload[off: off + n].astype(np.int64)
            out[base + slots] = p.payload[off + n: off + 2 * n]
        else:  # TYPE_RUN
            pairs = p.payload[off: off + 2 * n].astype(np.int64)
            for s, e in pairs.reshape(n, 2):
                w0, w1 = s // WORD_BITS, (e - 1) // WORD_BITS
                for w in range(w0, w1 + 1):
                    lo = max(s - w * WORD_BITS, 0)
                    hi = min(e - w * WORD_BITS, WORD_BITS)
                    m = ((1 << hi) - 1) & ~((1 << lo) - 1)
                    out[base + w] |= np.uint32(m & 0xFFFFFFFF)
    return out.reshape(rows, words)


# ---------------------------------------------------------------------------
# Device decode.  Pure jnp — callable inside vmapped shard_map bodies
# (the decode fuses into the op's executable) or standalone via
# upload_decode (Fragment.device()'s compressed upload path).
# ---------------------------------------------------------------------------

def decode_block(keys, types, counts, offsets, payload, *, rows: int,
                 words: int = SHARD_WORDS, a_bucket: int = 0,
                 r_bucket: int = 0):
    """Decode one fragment's packed container stream to dense
    ``uint32[rows, words]`` on device.

    ``keys/types/counts/offsets``: int32[C] (padded entries use key -1 /
    type -1 — they decode to nothing).  ``payload``: uint32[P].
    ``a_bucket``/``r_bucket``: static per-bucket maxima of array entries
    and run counts; 0 compiles that container form out entirely (a
    sparse-only corpus pays no run-mask code, a run-only corpus no
    scatter).

    Each container computes its 2048-word dense tile (bitmap: payload
    gather; array: scatter of (slot, value) entries; run: OR of per-word
    range masks), selected by type; tiles then scatter into the flat
    dense output at ``key * CONTAINER_WORDS``.  Tile indices are unique
    by construction (one container per key, unique slots within one), so
    plain scatter-set is exact.
    """
    import jax
    import jax.numpy as jnp

    total = rows * words
    if keys.shape[0] == 0 or rows == 0:
        return jnp.zeros((rows, words), dtype=jnp.uint32)
    cw = CONTAINER_WORDS
    j = jnp.arange(cw, dtype=jnp.int32)

    def tile(key, typ, cnt, off):
        bm = payload.at[off + j].get(mode="fill", fill_value=0)
        t = jnp.where(typ == TYPE_BITMAP, bm, jnp.uint32(0))
        if a_bucket:
            e = jnp.arange(a_bucket, dtype=jnp.int32)
            slots = payload.at[off + e].get(
                mode="fill", fill_value=0).astype(jnp.int32)
            vals = payload.at[off + cnt + e].get(mode="fill",
                                                 fill_value=0)
            slots = jnp.where((e < cnt) & (typ == TYPE_ARRAY), slots, cw)
            t = t | jnp.zeros(cw, dtype=jnp.uint32).at[slots].set(
                vals, mode="drop")
        if r_bucket:
            r = jnp.arange(r_bucket, dtype=jnp.int32)
            valid = (r < cnt) & (typ == TYPE_RUN)
            rs = jnp.where(valid, payload.at[off + 2 * r].get(
                mode="fill", fill_value=0).astype(jnp.int32), 0)
            re = jnp.where(valid, payload.at[off + 2 * r + 1].get(
                mode="fill", fill_value=0).astype(jnp.int32), 0)
            base = j * WORD_BITS                       # [cw]
            lo = jnp.clip(rs[:, None] - base[None, :], 0, WORD_BITS)
            hi = jnp.clip(re[:, None] - base[None, :], 0, WORD_BITS)
            full = jnp.uint32(0xFFFFFFFF)
            mhi = jnp.where(hi == 0, jnp.uint32(0),
                            full >> (WORD_BITS - hi).astype(jnp.uint32))
            mlo = jnp.where(lo == 0, jnp.uint32(0),
                            full >> (WORD_BITS - lo).astype(jnp.uint32))
            t = t | jax.lax.reduce(mhi & ~mlo, np.uint32(0),
                                   jax.lax.bitwise_or, dimensions=(0,))
        return t

    tiles = jax.vmap(tile)(keys, types, counts, offsets)    # [C, cw]
    flat_idx = jnp.where(keys[:, None] < 0, total,
                         keys[:, None] * cw + j[None, :])
    flat = jnp.zeros(total, dtype=jnp.uint32).at[flat_idx].set(
        tiles, mode="drop")
    return flat.reshape(rows, words)


def pad_packed(p: Packed) -> tuple[np.ndarray, ...]:
    """Pad a Packed stream's arrays to their pow2 buckets (padding
    containers use key/type -1) — the per-fragment staging unit the
    compiled decode buckets expect."""
    cb = pow2_bucket(p.keys.size)
    pb = pow2_bucket(p.payload.size)
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
    return keys, types, counts, offsets, payload


@functools.lru_cache(maxsize=None)
def _decode_jit(rows: int, words: int, a_bucket: int, r_bucket: int,
                backend: str = "jnp"):
    import jax

    def _traced(*a, **k):
        # runs only while jax traces — the compile registry's exact
        # per-bucket compile detector (docs/observability.md)
        from ..utils import devobs
        devobs.COMPILES.mark_traced()
        from . import bitset, kernels
        dec = kernels.decode_block if backend == "pallas" else decode_block
        # the mirror is the device's word tile, [rows, 256, 128]
        return bitset.to_tile(dec(*a, **k))

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

    from . import kernels

    arrs = [jax.device_put(a, target) for a in pad_packed(p)]
    a_b, r_b = pow2_bucket(p.a_max), pow2_bucket(p.r_max)
    backend = kernels.backend_for(rows, pow2_bucket(p.payload.size),
                                  a_b, r_b)
    fn = _decode_jit(rows, words, a_b, r_b, backend)
    reg = devobs.COMPILES
    reg.begin_call()
    t0 = _time.perf_counter()
    out = fn(*arrs)
    if reg.traced():
        # the container/payload pow2 buckets are intended shape
        # polymorphism (one jit, one specialization per bucket), so they
        # belong IN the signature — without them a second bucket of the
        # same jit would read as a false retrace alarm.  The backend tag
        # splits the pallas and jnp executables the same way (a knob
        # flip is a new signature, not a retrace).
        c_b = pow2_bucket(p.keys.size)
        p_b = pow2_bucket(p.payload.size)
        reg.note_call(
            f"decode:{rows}x{words}:c{c_b}:p{p_b}:a{a_b}:r{r_b}"
            f":{backend}",
            "decode", _time.perf_counter() - t0,
            devobs.fingerprint(arrs))
    if backend == "pallas":
        tiles = rows * max(words // CONTAINER_WORDS, 1)
        devobs.LEDGER.record(
            sig=f"decode:{rows}x{words}:c{pow2_bucket(p.keys.size)}"
                f":p{pow2_bucket(p.payload.size)}:a{a_b}:r{r_b}:pallas",
            kind="decode", shards=1, shards_padded=1, batch_rows=rows,
            batch_rows_padded=rows, queue_s=0.0,
            dispatch_s=_time.perf_counter() - t0, decode_bytes=0,
            compiled=reg.traced(), kernel_launches=1, kernel_tiles=tiles)
    return out
