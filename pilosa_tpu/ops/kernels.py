"""Pallas container kernels: HBM->VMEM decode + fused bitwise-op/popcount
in one launch (docs/architecture.md "On native code and Pallas").

The compressed-residency layer (ops/containers.py) decodes packed
array/bitmap/run container streams to dense tiles with pure-jnp
gather/scatter — XLA schedules that decode through HBM-resident
temporaries bounded only by ``decode-workspace-mb``.  This module is the
hand-scheduled alternative: Pallas kernels that walk the same
key/type/count/offset/payload tables CONTAINER-TILE-BY-TILE, so each
2048-word dense tile is materialised in a VMEM block, consumed, and
overwritten by the next grid step instead of round-tripping through HBM.
Two kernels ship:

* ``decode_block`` — drop-in for ``containers.decode_block`` (same
  signature, same answer): grid over the fragment's ``rows x 16`` output
  container tiles, each step decoding one container into its (16, 128)
  VMEM block.
* ``fused_row_counts`` — decode + optional AND with a dense filter
  segment + per-row popcount accumulation in ONE kernel, so the decoded
  words never exist outside the tile at all (the TopN/Rows
  ``row_counts`` hot path, parallel/mesh_exec.py).

How a tile is decoded is shaped by what Mosaic lowers on a TPU (no
word-granular dynamic slice, no vector gather/scatter, no scalar reads
from VMEM):

* the per-tile type/count/offset tables are gathered by XLA outside the
  kernel and ride in as SCALAR-PREFETCH operands (SMEM), indexed by the
  grid position;
* a bitmap container is one tile-aligned ``pl.ds`` load from the
  payload held in VMEM as ``[P/128, 128]`` — ``containers.pack_words``
  lays bitmap containers first in the payload so every one starts on a
  2048-word boundary;
* array and run containers are scalar loops with a dynamic trip count
  (the container's own entry count, not its pow2 bucket) reading their
  entries from a second, scalar-prefetched copy of the payload in SMEM
  and selecting / OR-ing into the tile against a word-index iota.

Under ``vmap`` (every call site maps over the stacked shard axis) a
pallas_call with batched scalar-prefetch operands becomes one
``fori_loop`` over the fragments — jax's own batching rule.

Backend selection rides the ``container-kernels`` knob
(``CONTAINER_KERNELS``, set process-wide from the server config like
``DECODE_WORKSPACE_BYTES``): ``auto`` selects the Pallas kernels on a
TPU for every decode bucket whose footprint ``fits`` the chip's VMEM and
SMEM, and the jnp decode elsewhere; ``pallas`` forces the kernels for
every bucket (compiled on a TPU — an over-budget bucket is then the
compiler's error, not a quiet fallback — and executed through the
Pallas INTERPRETER off-TPU, so the whole path is differentially
testable in CPU tier-1); ``jnp`` is the kill switch restoring the jnp
path exactly.  The selected backend is part of every compressed
``Fragment.device_sig()`` (the kernel-backend axis), so the choice is
static per signature, identical on every trace of one executable, and a
knob flip rebuilds stacks and recompiles instead of replaying a
jnp-compiled program.
"""

from __future__ import annotations

import functools

from ..core import CONTAINER_WORDS, SHARD_WORDS, WORD_BITS
from .containers import TYPE_ARRAY, TYPE_BITMAP, TYPE_RUN

# Container-decode kernel backend: "auto" | "pallas" | "jnp".
# Process-wide, set from the server config (container-kernels) like
# fragment.COMPRESSED_RESIDENT; bench legs and tests flip it directly.
CONTAINER_KERNELS = "auto"

# One container's 2048 words as a VMEM tile: 16 sublanes x 128 lanes.
TILE_ROWS = CONTAINER_WORDS // 128    # 16
TILE_LANES = 128
TILES_PER_SHARD_ROW = SHARD_WORDS // CONTAINER_WORDS  # 16

# What one kernel launch may hold on chip, under the limits the v5e
# compiler enforces (libtpu 0.0.34, compiled for "TPU v5 lite"): 16 MiB
# of scoped VMEM by default and 1 MiB of SMEM.  VMEM holds the whole
# payload block — allocated once, its block index never changes: a
# 16 MiB payload compiles, a 32 MiB one fails "Scoped allocation with
# size 32.00M and limit 16.00M" — plus the double-buffered output and
# filter tiles.  SMEM holds the three per-tile tables and, when the
# bucket has array or run containers, the payload's scalar copy: a
# 2^17-word payload compiles, 2^18 fails "Ran out of memory in memory
# space smem. Used 1.01M of 1.00M".  Both budgets leave the compiler
# its own headroom.
VMEM_BUDGET_BYTES = 12 << 20
SMEM_BUDGET_BYTES = 768 << 10


@functools.lru_cache(maxsize=1)
def _platform() -> str:
    """Device platform this process compiles for (fixed per process —
    jax picks the backend once)."""
    import jax
    return jax.default_backend()


def resolve(mode: str | None = None) -> str:
    """Backend ("pallas" | "jnp") the knob value (default: the
    process-wide ``CONTAINER_KERNELS``) selects for buckets that fit the
    chip — what the server reports as ``kernel_backend``.  Per-bucket
    selection is ``backend_for``."""
    m = CONTAINER_KERNELS if mode is None else mode
    if m in ("jnp", "pallas"):
        return m
    # auto: kernels where they pay (TPU), jnp elsewhere — CPU tier-1
    # exercises the kernels only when a test/bench forces "pallas"
    return "pallas" if _platform() == "tpu" else "jnp"


def interpret_mode() -> bool:
    """Off-TPU the kernels run through the Pallas interpreter — same
    kernel logic, XLA:CPU execution — so tier-1 can differentially test
    the exact code path the TPU compiles.  On a TPU they always
    compile."""
    return _platform() != "tpu"


def fits(rows: int, payload_bucket: int, a_bucket: int,
         r_bucket: int) -> bool:
    """Whether one fragment's decode bucket fits the chip: payload block
    and tiles in VMEM, tables and the payload's scalar copy in SMEM."""
    p_bytes = max(payload_bucket, CONTAINER_WORDS) * 4
    vmem = p_bytes + 4 * CONTAINER_WORDS * 4
    smem = 3 * rows * TILES_PER_SHARD_ROW * 4
    if a_bucket or r_bucket:
        smem += p_bytes
    return vmem <= VMEM_BUDGET_BYTES and smem <= SMEM_BUDGET_BYTES


def backend_for(rows: int, payload_bucket: int, a_bucket: int,
                r_bucket: int) -> str:
    """The kernel-backend axis of a compressed ``Fragment.device_sig()``
    (storage/fragment.py): which decode the executables built for this
    bucket compile in.  ``auto`` leaves buckets that do not ``fits`` to
    jnp — statically, by signature; a forced ``pallas`` is never
    replaced."""
    if resolve() == "jnp":
        return "jnp"
    if CONTAINER_KERNELS == "auto" and not fits(
            rows, payload_bucket, a_bucket, r_bucket):
        return "jnp"
    return "pallas"


def sig_backend(sig) -> str:
    """Backend recorded in a compressed group signature ('z', rows, C,
    P, A, R, backend); signatures minted before the backend axis read as
    jnp (the decode they compiled)."""
    return sig[6] if len(sig) > 6 else "jnp"


def _tile_tables(keys, types, counts, offsets, tiles: int):
    """int32[tiles] type/count/offset of the container covering each
    output tile (type -1, count 0 where none does).  Keys are unique
    and padding rows carry key -1, so one drop-mode scatter inverts the
    container map; XLA runs this outside the kernel."""
    import jax.numpy as jnp
    C = keys.shape[0]
    idx = jnp.where(keys >= 0, keys, tiles).astype(jnp.int32)
    slot = jnp.full((tiles,), -1, dtype=jnp.int32).at[idx].set(
        jnp.arange(C, dtype=jnp.int32), mode="drop")
    live = slot >= 0
    ci = jnp.where(live, slot, 0)
    return (jnp.where(live, types[ci], -1),
            jnp.where(live, counts[ci], 0),
            jnp.where(live, offsets[ci], 0))


def _kernel_operands(keys, types, counts, offsets, payload, tiles: int,
                     a_bucket: int, r_bucket: int):
    """(scalar-prefetch operands, VMEM payload) of one launch: the three
    per-tile tables, the payload's SMEM copy when the bucket has array
    or run containers, and the payload as ``[P/128, 128]`` padded to at
    least one container tile so the bitmap load never leaves it."""
    import jax.numpy as jnp
    if payload.shape[0] < CONTAINER_WORDS:
        payload = jnp.zeros(CONTAINER_WORDS, dtype=jnp.uint32).at[
            :payload.shape[0]].set(payload)
    scalars = list(_tile_tables(keys, types, counts, offsets, tiles))
    if a_bucket or r_bucket:
        scalars.append(payload)
    return scalars, payload.reshape(-1, TILE_LANES)


def _container_tile(typ, cnt, off, pay_s, pay_v, a_bucket: int,
                    r_bucket: int):
    """One container's dense (TILE_ROWS, TILE_LANES) word tile — the
    per-grid-step body both kernels share.  ``typ``/``cnt``/``off`` are
    SMEM scalars, ``pay_s`` the payload's SMEM ref (None when the bucket
    has no array or run containers), ``pay_v`` its VMEM ref.  Mirrors
    containers.decode_block's per-container math (bitmap copy / array
    (slot, value) entries / run range masks); a_bucket/r_bucket of 0
    compile that form out.  Offsets are clamped into the payload: a
    stack staged while a write raced its signature may carry tables that
    point past the clamped payload (mesh_exec._place_packed_block), and
    the jnp decode fills such reads with zeros rather than faulting."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    P = pay_v.shape[0] * TILE_LANES
    is_bm = typ == TYPE_BITMAP
    row0 = jnp.where(
        is_bm, jnp.minimum(off, P - CONTAINER_WORDS) // TILE_LANES, 0)
    bm = pay_v[pl.ds(pl.multiple_of(row0, TILE_ROWS), TILE_ROWS), :]
    tile = jnp.where(is_bm, bm, jnp.uint32(0))
    shape = (TILE_ROWS, TILE_LANES)
    word = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * TILE_LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    def entry(i):
        return pay_s[jnp.minimum(i, P - 1)]

    if a_bucket:
        def a_body(e, tile):
            slot = entry(off + e).astype(jnp.int32)
            return jnp.where(word == slot, entry(off + cnt + e), tile)

        tile = jax.lax.fori_loop(
            0, jnp.where(typ == TYPE_ARRAY, cnt, 0), a_body, tile)
    if r_bucket:
        base = word * WORD_BITS
        full = jnp.uint32(0xFFFFFFFF)

        def below(n):
            # the n low bits set, n in [0, WORD_BITS]
            return jnp.where(
                n == 0, jnp.uint32(0),
                full >> (WORD_BITS - n).astype(jnp.uint32))

        def r_body(r, tile):
            rs = entry(off + 2 * r).astype(jnp.int32)
            re_ = entry(off + 2 * r + 1).astype(jnp.int32)
            lo = jnp.clip(rs - base, 0, WORD_BITS)
            hi = jnp.clip(re_ - base, 0, WORD_BITS)
            return tile | (below(hi) & ~below(lo))

        tile = jax.lax.fori_loop(
            0, jnp.where(typ == TYPE_RUN, cnt, 0), r_body, tile)
    return tile


def _step_tile(refs, ns: int, t, a_bucket: int, r_bucket: int):
    """The tile of grid position ``t`` from a kernel's refs: the
    ``ns`` scalar-prefetch refs (three tables, then the payload's SMEM
    copy when ns == 4) followed by the VMEM payload."""
    return _container_tile(
        refs[0][t], refs[1][t], refs[2][t], refs[3] if ns == 4 else None,
        refs[ns], a_bucket, r_bucket)


def decode_block(keys, types, counts, offsets, payload, *, rows: int,
                 words: int = SHARD_WORDS, a_bucket: int = 0,
                 r_bucket: int = 0):
    """Pallas drop-in for ``containers.decode_block``: decode one
    fragment's packed stream to dense ``uint32[rows, words]``, one
    container tile per grid step.  Same arguments, same answer."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if keys.shape[0] == 0 or rows == 0:
        return jnp.zeros((rows, words), dtype=jnp.uint32)
    tiles = rows * (words // CONTAINER_WORDS)
    scalars, pay_v = _kernel_operands(
        keys, types, counts, offsets, payload, tiles, a_bucket, r_bucket)
    ns = len(scalars)

    def kernel(*refs):
        refs[-1][...] = _step_tile(refs, ns, pl.program_id(0), a_bucket,
                                   r_bucket)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=ns, grid=(tiles,),
            # whole payload every step: fetched once per fragment
            in_specs=[pl.BlockSpec(pay_v.shape, lambda t, *_: (0, 0))],
            out_specs=pl.BlockSpec((TILE_ROWS, TILE_LANES),
                                   lambda t, *_: (t, 0))),
        out_shape=jax.ShapeDtypeStruct((tiles * TILE_ROWS, TILE_LANES),
                                       jnp.uint32),
        interpret=interpret_mode(),
        name="container_decode",
    )(*scalars, pay_v)
    return out.reshape(rows, words)


def fused_row_counts(keys, types, counts, offsets, payload, filt=None, *,
                     rows: int, words: int = SHARD_WORDS,
                     a_bucket: int = 0, r_bucket: int = 0):
    """Decode + optional AND-with-filter + per-row popcount in ONE
    kernel launch: int32[rows] set-bit counts of a packed fragment,
    optionally masked by a dense segment (the device's ``uint32[256,
    128]`` word tile: its sixteen (16, 128) container tiles are whole
    sublane-groups, blocked here without a copy).  The decoded
    words exist only as the grid step's VMEM tile — no dense
    ``[rows, words]`` temporary at all (the jnp path's decode output)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if keys.shape[0] == 0 or rows == 0:
        return jnp.zeros((rows,), dtype=jnp.int32)
    tpr = words // CONTAINER_WORDS
    scalars, pay_v = _kernel_operands(
        keys, types, counts, offsets, payload, rows * tpr, a_bucket,
        r_bucket)
    ns = len(scalars)
    half = TILE_ROWS // 2

    def kernel(*refs):
        out_ref = refs[-1]
        k = pl.program_id(1)
        tile = _step_tile(refs, ns, pl.program_id(0) * tpr + k, a_bucket,
                          r_bucket)
        if filt is not None:
            tile = tile & refs[ns + 1][...]
        pc = jax.lax.population_count(tile).astype(jnp.int32)

        # the row's (8, 128) accumulator block is revisited by its tpr
        # consecutive steps: zero on the first, add this tile's per-lane
        # popcounts (its two sublane halves folded) on each.  The
        # cross-lane sum waits for XLA outside the kernel.
        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
        out_ref[...] += pc[:half] + pc[half:]

    operands = [pay_v]
    in_specs = [pl.BlockSpec(pay_v.shape, lambda r, k, *_: (0, 0))]
    if filt is not None:
        # the filter segment's matching container tile rides in a
        # (16, 128) block indexed by the step's position within the row
        operands.append(filt.reshape(tpr * TILE_ROWS, TILE_LANES))
        in_specs.append(pl.BlockSpec((TILE_ROWS, TILE_LANES),
                                     lambda r, k, *_: (k, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=ns, grid=(rows, tpr), in_specs=in_specs,
            out_specs=pl.BlockSpec((None, half, TILE_LANES),
                                   lambda r, k, *_: (r, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, half, TILE_LANES),
                                       jnp.int32),
        interpret=interpret_mode(),
        name="container_row_counts",
    )(*scalars, *operands)
    return out.sum(axis=(1, 2))
