"""The Pallas container kernel: a compressed field's rows counted under
a filter where they lie, none decoded (docs/architecture.md "On native
code and Pallas").

The compressed-residency layer (ops/containers.py) keeps a fragment as a
packed container stream and decodes it with XLA — whole blocks for
bitmap containers, one scatter of single words for the array entries.
``TopN`` / ``Rows`` over a compressed field (the ``row_counts`` node,
parallel/mesh_exec.py ``_build``) would decode every row of it only to
count each under the same filter, so they do not decode at all:
``fused_row_counts`` counts each container form in place.

* Bitmap containers are the payload's first blocks: every 2048-word
  block is ANDed with the filter's tile of the container it holds and
  popcounted, one pass of XLA over the payload.
* Array entries are counted as ``popcount(value & filter[word])``, and
  fetching ``filter[word]`` for some hundred million entries a query is
  the one thing here XLA cannot do at speed (its gather fetches single
  words, about 5 ns each on a v5e).  The kernel ``container_row_counts``
  does: the entries come in 32 class streams, each entry in the SUBLANE
  its word has in the filter's ``(256, 128)`` tile and in the stream of
  its register group, eight of the tile's 32 vector registers
  (``containers.class_streams``), so for a block of one group's entries
  a walk over the group's 8 registers takes each register ONCE, gathers
  along its lanes by the entries' lane numbers (``take_along_axis``:
  the TPU's own dynamic lane gather) and keeps the entries whose
  register it is — 8 gathers a register of entries, where streams that
  mixed all 32 registers took 32.  The streams ascend, so a chunk of
  128 lanes spans few rows; per-row sums go to a ``(rows, 128)``
  accumulator that stays in VMEM over the grid, and XLA adds up its
  lanes.
* Run containers (rare) build their tiles as the decode does.

Under ``vmap`` (the call site maps over the stacked shard axis) jax's
batching rule puts the shard axis on the kernel's grid: a launch is ONE
pallas_call over (stacked shard, register group, block of entries),
pipelined across shards, whose steps widen where few filters leave the
unrolled walk room (``step_lanes``).  Its per-chunk row spans are
therefore an ordinary SMEM operand: a batched scalar prefetch would make
it a ``while`` loop of one call a fragment (PERF.md §6).

Backend selection rides the ``container-kernels`` knob
(``CONTAINER_KERNELS``, set process-wide from the server config like
``DECODE_WORKSPACE_BYTES``): ``auto`` selects the kernel on a TPU for
every field whose accumulator ``fits`` VMEM, and XLA's gather and
scatter-add elsewhere; ``pallas`` forces the kernel (compiled on a TPU,
executed through the Pallas INTERPRETER off-TPU, so the path is
differentially testable in CPU tier-1); ``jnp`` is the kill switch: no
Pallas kernel on any path.  The selected backend is part of every
compressed ``Fragment.device_sig()`` (the kernel-backend axis), so the
choice is static per signature, identical on every trace of one
executable, and a knob flip rebuilds stacks and recompiles instead of
replaying a jnp-compiled program.
"""

from __future__ import annotations

import functools

from ..core import CONTAINER_WORDS, SHARD_WORDS, WORD_TILE
from .containers import ARRAY_CLASSES, ARRAY_GROUPS, TYPE_BITMAP, TYPE_RUN

# Container kernel backend: "auto" | "pallas" | "jnp".
# Process-wide, set from the server config (container-kernels) like
# fragment.COMPRESSED_RESIDENT; bench legs and tests flip it directly.
CONTAINER_KERNELS = "auto"

TILE_LANES = WORD_TILE[1]               # 128
# The registers of a filter tile one group's streams name.
_GROUP_REGS = WORD_TILE[0] // ARRAY_CLASSES // ARRAY_GROUPS     # 8
# Lanes one grid step takes at one or two params rows, at most, and
# rows one iteration of its row loop adds up (PERF.md §6: the fastest
# on a v5e of the widths and unrollings timed).
STEP_LANES_MAX = 2304
ROWS_UNROLL = 4

# Params rows (padded) one fused count unrolls at most: each costs a
# filter tile in VMEM and a gather a register; a launch of more takes
# the decoding path.
FUSED_PARAMS_MAX = 8       # = nodes.UNROLL_ROWS_MAX: the filters come unrolled
# The kernel's accumulator, int32[params rows, rows, 128], stays in
# VMEM over a shard's blocks; it and the block of the filters' tiles a
# register group names (32 KiB a params row) are double-buffered, the
# next loading while the one before finishes, beside the blocks of
# entries: at this bound and 8 params rows 8 + 0.5 MiB + 0.25, under the
# 16 MiB of scoped VMEM the v5e compiler grants by default (1024 rows
# under 8 compile for a deviceless v5e: PERF.md §6).
VMEM_BUDGET_BYTES = 4 << 20


@functools.lru_cache(maxsize=1)
def _platform() -> str:
    """Device platform this process compiles for (fixed per process —
    jax picks the backend once)."""
    import jax
    return jax.default_backend()


def resolve(mode: str | None = None) -> str:
    """Backend ("pallas" | "jnp") the knob value (default: the
    process-wide ``CONTAINER_KERNELS``) selects for fields that fit the
    chip — what the server reports as ``kernel_backend``.  Per-field
    selection is ``backend_for``."""
    m = CONTAINER_KERNELS if mode is None else mode
    if m in ("jnp", "pallas"):
        return m
    # auto: the kernel where it pays (TPU), jnp elsewhere — CPU tier-1
    # exercises the kernel only when a test/bench forces "pallas"
    return "pallas" if _platform() == "tpu" else "jnp"


def interpret_mode() -> bool:
    """Off-TPU the kernel runs through the Pallas interpreter — same
    kernel logic, XLA:CPU execution — so tier-1 can differentially test
    the exact code path the TPU compiles.  On a TPU it always
    compiles."""
    return _platform() != "tpu"


def fits(rows: int) -> bool:
    """Whether a field of ``rows`` rows' accumulator fits VMEM at the
    most params rows a fused count takes."""
    return FUSED_PARAMS_MAX * rows * TILE_LANES * 4 <= VMEM_BUDGET_BYTES


def backend_for(rows: int) -> str:
    """The kernel-backend axis of a compressed ``Fragment.device_sig()``
    (storage/fragment.py): how the executables built over this fragment
    count its array entries.  ``auto`` leaves fields that do not
    ``fits`` to jnp — statically, by signature; a forced ``pallas`` is
    never replaced."""
    if resolve() == "jnp":
        return "jnp"
    if CONTAINER_KERNELS == "auto" and not fits(rows):
        return "jnp"
    return "pallas"


def sig_backend(sig) -> str:
    """Backend recorded in a compressed group signature ('z', rows, C,
    P, A, R, backend); signatures minted before the backend axis read as
    jnp."""
    return sig[6] if len(sig) > 6 else "jnp"


def step_lanes(lanes: int, b: int) -> int:
    """Lanes of the class streams one grid step of ``container_row_counts``
    takes, from the shapes alone: up to ``STEP_LANES_MAX`` at one or two
    params rows and half of it from four, where a wider step's unrolled
    walk (lanes / 128 x ``b`` x 8 gathers) spills; the largest whole
    number of registers' lanes that divides the stream, so no block is
    ragged and no lane is read twice or past the end."""
    chunks = lanes // TILE_LANES
    cap = min(chunks, STEP_LANES_MAX // (1 if b <= 2 else 2) // TILE_LANES)
    return TILE_LANES * max(d for d in range(1, cap + 1) if chunks % d == 0)


def kernel_steps(lanes: int, b: int) -> int:
    """Grid steps ``container_row_counts`` takes over one fragment's
    32 class streams of ``lanes`` lanes under ``b`` filters: a step a
    block of each register group's eight."""
    return ARRAY_GROUPS * (lanes // step_lanes(lanes, b))


def kernel_gathers(lanes: int, b: int) -> int:
    """Lane gathers ``container_row_counts`` makes over one fragment's
    streams of ``lanes`` lanes under ``b`` filters — steps x 128-lane
    chunks a step x the 8 registers of a group x ``b``: every chunk of a
    group's block walks the group's registers, a gather a register a
    filter."""
    return ARRAY_GROUPS * (lanes // TILE_LANES) * _GROUP_REGS * b


def _count_entries(filts, a_idx, a_val, rows: int, lanes: int | None = None):
    """int32[B, rows]: ``popcount(value & filter[word])`` of the array
    entries in the class streams ``a_idx`` / ``a_val`` ``[8 * G, L]``
    summed into their rows, under each of the filters' ``uint32[B, 256,
    128]`` word tiles — the kernel ``container_row_counts``, a grid step
    a block of ``step_lanes`` lanes of one register group's eight
    streams (``lanes`` overrides it, and G, the groups, comes from the
    streams' shape: scripts/container_kernel_bench.py times both).

    A step reads the block of the filters' tiles that holds its group's
    registers, and the walk over them is unrolled, every register taken
    whether the block has entries in it or not: a loop from the block's
    first register to its last read three times slower on the chip, for
    a loop of traced bounds is scheduled a step at a time (PERF.md §6:
    10.4 ms a 53-shard launch of the 128-row field against 3.1).
    For the same reason the loop over the rows the block spans adds up
    ``ROWS_UNROLL`` rows an iteration (the rows past the last add
    nothing, into the accumulator's spare rows): a group's stream
    crosses four times as many rows a block as a class's did, and a
    loop of one row an iteration took as long as the gathers it saved
    (PERF.md §6).  Its bounds ride in through SMEM, reduced by XLA
    beforehand, where a reduction to a scalar inside the kernel stalls
    it, as an ordinary operand, not a scalar prefetch: under the call
    site's ``vmap`` over the stacked shards jax then puts the shard axis
    on the grid, ``(shard, group, block)``, one pipelined call a launch,
    where a batched scalar prefetch is a ``while`` loop of one call a
    fragment (PERF.md §6)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = filts.shape[0]
    streams, L = a_idx.shape
    groups = streams // ARRAY_CLASSES
    regs = WORD_TILE[0] // ARRAY_CLASSES // groups  # registers a group
    tl = lanes or step_lanes(L, B)
    blocks = L // tl
    chunks = [slice(k * TILE_LANES, (k + 1) * TILE_LANES)
              for k in range(tl // TILE_LANES)]
    # the rows each block's entries span: [first, last + 1), reduced
    # within the (8, 128) tiles where they lie (a transpose that put a
    # block's classes together first made it a reduction across the
    # layout: 0.46 ms a 53-shard launch, PERF.md §6); a block of
    # padding alone spans [rows, 0), no row
    by_block = a_idx.reshape(groups, ARRAY_CLASSES, blocks, tl) >> 15
    held = by_block < rows                  # padding is above every row
    spans = jnp.stack([
        jnp.min(jnp.where(held, by_block, rows), axis=(1, 3)),
        jnp.max(jnp.where(held, by_block, -1), axis=(1, 3)) + 1]
    ).reshape(2, groups * blocks)
    spare = ROWS_UNROLL - 1                 # rows an iteration runs past

    def kernel(span_ref, f_ref, idx_ref, val_ref, out_ref):
        group, block = pl.program_id(0), pl.program_id(1)
        step = group * blocks + block

        @pl.when(step == 0)
        def _first():
            out_ref[...] = jnp.zeros_like(out_ref)

        idx = idx_ref[...]
        reg = (idx >> 10) & (regs - 1)
        lane = idx & (TILE_LANES - 1)
        got = [jnp.zeros((ARRAY_CLASSES, tl), dtype=jnp.uint32)] * B
        for r in range(regs):
            mine = reg == r
            for b in range(B):
                words = f_ref[b, r * ARRAY_CLASSES:(r + 1) * ARRAY_CLASSES, :]
                here = jnp.concatenate(
                    [jnp.take_along_axis(words, lane[:, c], axis=1)
                     for c in chunks], axis=1)
                got[b] = jnp.where(mine, here, got[b])
        # padding's value is 0: it adds nothing to any row
        val = val_ref[...]
        bits = [jax.lax.population_count(val & g).astype(jnp.int32)
                for g in got]
        row = idx >> 15
        lo, hi = span_ref[0, step], span_ref[1, step]

        def add(i, carry):
            for u in range(ROWS_UNROLL):
                r = lo + i * ROWS_UNROLL + u
                mine = row == r
                for b in range(B):
                    x = jnp.where(mine, bits[b], 0)
                    part = x[:, chunks[0]]
                    for c in chunks[1:]:
                        part = part + x[:, c]
                    out_ref[b, pl.ds(r, 1), :] += jnp.sum(
                        part, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, (hi - lo + spare) // ROWS_UNROLL, add, 0)

    out = pl.pallas_call(
        kernel,
        grid=(groups, blocks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((B, regs * ARRAY_CLASSES, TILE_LANES),
                               lambda g, i: (0, g, 0)),
                  pl.BlockSpec((ARRAY_CLASSES, tl), lambda g, i: (g, i)),
                  pl.BlockSpec((ARRAY_CLASSES, tl), lambda g, i: (g, i))],
        out_specs=pl.BlockSpec((B, rows + spare, TILE_LANES),
                               lambda g, i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, rows + spare, TILE_LANES),
                                       jnp.int32),
        interpret=interpret_mode(),
        name="container_row_counts",
    )(spans, filts, a_idx, a_val)
    return out[:, :rows].sum(axis=2)


def fused_row_counts(keys, types, counts, offsets, payload, a_idx, a_val,
                     filts=None, *, rows: int, words: int = SHARD_WORDS,
                     a_bucket: int = 0, r_bucket: int = 0,
                     backend: str = "jnp"):
    """int32[B, rows] set-bit counts of a packed fragment under each of
    ``filts`` (the device's ``uint32[B, 256, 128]`` word tiles; None: no
    filter, B = 1), with no row of it ever decoded: what ``TopN`` /
    ``Rows`` ask of a compressed field (parallel/mesh_exec.py
    ``_build``).  Each container form is counted where it lies (module
    docstring); ``backend`` says how the array entries are: ``pallas``
    by the kernel, ``jnp`` by XLA's gather and scatter-add — the same
    sums, for the kill switch and for what the kernel does not take."""
    import jax
    import jax.numpy as jnp
    from . import containers

    if filts is None:
        filts = jnp.full((1, words), 0xFFFFFFFF, dtype=jnp.uint32)
    B = filts.shape[0]
    if keys.shape[0] == 0 or rows == 0:
        return jnp.zeros((B, rows), dtype=jnp.int32)
    cw = CONTAINER_WORDS
    tpr = words // cw
    f_tiles = filts.reshape(B, tpr, cw)     # a filter, a container a row
    live = keys >= 0

    def popcount(x):
        return jax.lax.population_count(x).astype(jnp.int32)

    def by_row(at_rows, per):
        """``per`` ``[B, n]`` summed into the rows ``at_rows`` ``[n]``
        (``rows``: nowhere) — a small scatter."""
        return jnp.zeros((B, rows), dtype=jnp.int32).at[:, at_rows].add(
            per, mode="drop")

    # -- bitmap containers: block i of the payload holds the container
    # whose offset says so
    blocks = jnp.pad(payload, (0, (-payload.shape[0]) % cw)).reshape(-1, cw)
    nb = blocks.shape[0]
    out = jnp.zeros((B, rows), dtype=jnp.int32)
    if nb:
        held = jnp.full((nb,), -1, dtype=jnp.int32).at[
            jnp.where(live & (types == TYPE_BITMAP), offsets // cw,
                      nb)].set(keys, mode="drop")
        out = by_row(jnp.where(held >= 0, held // tpr, rows), jnp.sum(
            popcount(blocks[None] & f_tiles[:, held % tpr]), axis=2))
    if a_bucket:
        if backend == "pallas" and words == SHARD_WORDS \
                and B * rows * TILE_LANES * 4 <= VMEM_BUDGET_BYTES:
            out = out + _count_entries(
                filts.reshape((B,) + WORD_TILE), a_idx, a_val, rows)
        else:
            idx, val = a_idx.reshape(-1), a_val.reshape(-1)
            ok = idx < rows * words
            at = jnp.where(ok, idx % words, 0)
            bits = jnp.where(ok, popcount(
                val & filts.reshape(B, words)[:, at]), 0)
            out = out + by_row(jnp.where(ok, idx // words, rows), bits)
    if r_bucket:
        tiles = containers.run_tiles(types, counts, offsets, payload,
                                     r_bucket)
        out = out + by_row(
            jnp.where(live & (types == TYPE_RUN), keys // tpr, rows),
            jnp.sum(popcount(tiles[None] & f_tiles[:, keys % tpr]),
                    axis=2))
    return out
