"""Reducer nodes: the one definition of each reducer.

A ``plan.ReduceNode`` (kind, slotted plan, primary, extra) plus its
int32 params matrix ``[B, P]`` is the unit every layer passes: the
executor lowers a call or a batched call group to a node, a dispatch
batcher ticket carries one, and both ways of launching it — the
per-stage launcher (``MeshExecutor.reduce_async``: one node, one
executable a shape group, shard slices streamed under a budget) and the
whole-query program (``WholeQueryRunner.run``: every node of a request
in one executable) — trace the SAME per-shard body, read the same key
list and apply the same skip rule, all defined here.  A single call is
``B = 1``.

The six kinds:

* ``count``        — popcount of the plan's result per params row.
* ``segments``     — the plan's raw result per params row (bitmap calls).
* ``row_counts``   — per-row popcounts of the primary fragment under an
                     optional filter plan (TopN, Rows, MinRow/MaxRow).
                     ``extra`` = ("topn",) marks a top-n question
                     (``topn_extra``): its matrix carries each row's n as
                     the last column, and a launch that reduces every
                     shard itself may answer it by ``topn_walk``.
* ``bsi_sum``      — per-bit-slice popcounts of a BSI fragment under an
                     optional filter; the host does the 2^i weighting.
* ``bsi_minmax``   — the MSB-first extremum scan (no batch axis: the
                     first params row); ``extra`` = ("max",) | ("min",).
* ``group_counts`` — per-row popcounts under the intersection of
                     dynamically indexed prefix rows and an optional
                     filter (GroupBy); its matrix is the pair (prefix
                     row ids [C, Pk], filter params [P]) and ``extra`` =
                     (prefix keys..., padded C).

What a launch costs in program temporaries, and what it may cost, is a
property of these programs and lives beside them (``node_temp_rows``,
``batch_temp_bound``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import SHARD_WORDS
from ..executor.plan import eval_plan, plan_inputs
from ..ops import bitset, bsi

KINDS = ("count", "segments", "row_counts", "bsi_sum", "bsi_minmax",
         "group_counts")
# Kinds with a genuine batch axis: their tickets and programs fuse
# across concurrent requests (params concatenate along B).  bsi_minmax
# has none and group_counts' leading axis is the combo grid.
BATCH_KINDS = frozenset({"count", "segments", "row_counts", "bsi_sum"})
# Kinds whose outputs stay per shard (the host assembles them after the
# fetch); the others sum over the shards in the program.
PER_SHARD_KINDS = frozenset({"segments", "bsi_minmax"})


def sig_rows(shape) -> int:
    """Row count of a per-key group-signature entry — dense entries are
    the device shape (rows, 256, 128), compressed ones
    ('z', rows, C, P, A, R)."""
    return shape[1] if shape[0] == "z" else shape[0]


def node_keys(node) -> list[tuple[str, str]]:
    """The (field, view) key list a node stacks, primary first.  The
    ONLY definition: a shard schedule built from it prefetches and pins
    precisely the stacks the launch reads."""
    keys = [node.primary] if node.primary else []
    if node.kind == "group_counts":
        keys += node.extra[:-1]
    if node.plan is not None:
        keys += plan_inputs(node.plan)
    return list(dict.fromkeys(keys))


def participates(node, sig_map) -> bool:
    """Whether a shape group (``sig_map``: key -> signature, None where
    the fragment is absent in the whole group) contributes to a node —
    the one skip rule."""
    if node.kind in ("count", "segments"):
        # no fragment at all: the plan evaluates to empty
        return any(s is not None for s in sig_map.values())
    s0 = sig_map.get(node.primary)
    if s0 is None:
        return False
    if node.kind in ("bsi_sum", "bsi_minmax") and \
            sig_rows(s0) < bsi.OFFSET_ROW + 1:
        return False
    if node.kind == "group_counts":
        return all(sig_map.get(pk) is not None for pk in node.extra[:-1])
    return True


# Params rows a pass unrolls at most where a plan takes rows of a
# compressed fragment (and a fused count's filters: ops/kernels.py).
UNROLL_ROWS_MAX = 8


def plan_rows(plan, frags, mat):
    """The plan's result for every params row of ``mat``: [B, 256, 128].
    One ``vmap`` over the rows — but where the plan takes rows of a
    compressed fragment (``mesh_exec.PackedRows``) the few rows are
    unrolled: a row take decodes under a conditional on the launch's
    whole block, which stays a conditional only while the row id is not
    batched (under a batch axis both branches run, the scatter always)."""
    if mat.shape[0] <= UNROLL_ROWS_MAX and any(
            hasattr(f, "take_row") for f in frags.values()):
        return jnp.stack([eval_plan(plan, frags, mat[b])
                          for b in range(mat.shape[0])])
    return jax.vmap(lambda p: eval_plan(plan, frags, p))(mat)


def node_shard(node, mat, frags):
    """One reducer node's per-shard contribution, traced inside the
    vmapped per-shard pass of either launcher (decode has already
    produced dense [rows, 256, 128] fragments in ``frags``; a segment is
    one word tile, [256, 128]).  Counts accumulate in int32."""
    if node.kind in ("count", "segments"):
        segs = plan_rows(node.plan, frags, mat)
        if node.kind == "segments":
            return segs                                    # [B, 256, 128]
        return bitset.row_counts(segs)              # [B]
    frag = frags[node.primary]
    if node.kind == "row_counts":
        if node.plan is None:
            counts = bitset.row_counts(frag)
            return jnp.broadcast_to(counts,
                                    (mat.shape[0],) + counts.shape)
        masks = plan_rows(node.plan, frags, mat)
        return masked_counts(frag, masks)           # [B, rows]
    if node.kind == "bsi_sum":
        if node.plan is None:
            counts = bsi.sum_counts(frag, None)
            return jnp.broadcast_to(counts,
                                    (mat.shape[0],) + counts.shape)
        return jax.vmap(
            lambda p: bsi.sum_counts(frag, eval_plan(node.plan, frags,
                                                     p)))(
            mat)                                           # [B, 2, d+1]
    if node.kind == "bsi_minmax":
        filt = None
        if node.plan is not None:
            filt = eval_plan(node.plan, frags, mat[0])
        return bsi.min_max_bits(frag, filt,
                                want_max=node.extra[0] == "max")
    # group_counts: combos ride the leading axis of mat[0]
    rids, params = mat
    pk_list = node.extra[:-1]
    fseg = eval_plan(node.plan, frags, params) \
        if node.plan is not None else None

    def one_combo(rids_row):
        mask = None
        for j, pk in enumerate(pk_list):
            pfrag = frags[pk]
            rid = rids_row[j]
            if pfrag.shape[0] == 0:
                seg = jnp.zeros(pfrag.shape[1:], dtype=pfrag.dtype)
            else:
                seg = jnp.where(
                    rid < pfrag.shape[0],
                    jax.lax.dynamic_index_in_dim(
                        pfrag, jnp.minimum(rid, pfrag.shape[0] - 1),
                        axis=0, keepdims=False),
                    jnp.zeros_like(pfrag[0]))
            mask = seg if mask is None else mask & seg
        if fseg is not None:
            mask = fseg if mask is None else mask & fseg
        masked = frag if mask is None else frag & mask[None]
        return bitset.row_counts(masked)            # [rows]

    return jax.vmap(one_combo)(rids)                       # [C, rows]


def masked_counts(rows, masks):
    """Per-row popcounts of ``rows`` [k, 256, 128] under each of
    ``masks`` [B, 256, 128] on one shard: [B, k].  The one count body:
    the full pass calls it over a fragment's every row, ``topn_walk``
    over a block of them."""
    return bitset.row_counts(rows[None] & masks[:, None])


# -- a filtered TopN stops at the n-th count --------------------------------
#
# TopN(field, filter, n) keeps n of the field's R rows, and
# |row & filter| <= |row|: a row whose unfiltered total is below the
# n-th largest filtered count found so far cannot be among them
# (upstream's fragment.top: "heap with threshold pruning").  The totals
# are the stacked block's own (``row_totals``, kept with the block:
# mesh_exec ``MeshExecutor.walk_plan``), so the bound is exact for the
# very words the launch reads.  Rows are walked in blocks of
# ``walk_block_rows`` — whole (256, 128) tiles on an untiled major axis,
# so a block is a dynamic slice XLA reads in place — in descending order
# of each block's largest total, and the walk ends before the first
# block whose largest total is below the threshold.  Where rows are not
# stacked in order of their totals the order of blocks keeps the walk
# exact and only its grain is coarser.
#
# The params rows of a launch are unrolled, in the filter segments and
# in the block's counts: one popcount-reduce a row over the one slice,
# which XLA fuses into a single read of the block.  Under a batch axis
# (vmap over B) the compiler copies each block out before it counts it
# and reads whole filter stacks to take B rows of them (PR 36, traced on
# the chip: a B = 2 launch cost six B = 1 launches), so a launch of
# more than ``TOPN_WALK_ROWS`` params rows takes the full pass.

TOPN_EXTRA = ("topn",)
# Rows a block: measured on the chip (scripts/row_tile_bench.py, PR 36).
TOPN_BLOCK_ROWS = 8
# ...and never more blocks than this, so that a field of thousands of
# near-equal rows, which stops nowhere, pays a bounded number of loop
# steps (a threshold and an all-reduce each) beside its one pass.
TOPN_MAX_BLOCKS = 16
# Params rows (padded) a walked launch unrolls at most.
TOPN_WALK_ROWS = 8


def topn_extra(plan, n, ids, extras: bool = False) -> tuple:
    """``extra`` of the row_counts node of TopN(field, plan, n, ids):
    ``TOPN_EXTRA`` where the finisher is ``rank_counts(counts, n)`` with
    n > 0 over a filter — the top n by count, ties by row id — and ()
    where it reads every count: n = 0 (all rows), ``ids`` (named rows),
    tanimoto / attribute filters (``extras``), no filter (the counts are
    the totals).  Read off the call; nobody sets it."""
    return TOPN_EXTRA if plan is not None and n and n > 0 \
        and not ids and not extras else ()


def with_n(params, n: int):
    """A top-n node's params row: the plan's slots, then n."""
    return np.append(np.asarray(params, dtype=np.int32), np.int32(
        min(int(n), np.iinfo(np.int32).max)))


def walk_block_rows(rows: int) -> int:
    """Rows a block of the walk over a fragment of ``rows`` rows."""
    return min(rows, max(TOPN_BLOCK_ROWS, -(-rows // TOPN_MAX_BLOCKS)))


def row_totals(stack, axis_name):
    """Unfiltered per-row popcounts of a device-local stacked block
    [S_local, R, 256, 128], summed over its shards and the mesh: s32[R],
    inside a shard_map over ``axis_name``."""
    return jax.lax.psum(bitset.row_counts(stack).sum(axis=0), axis_name)


def walk_order(totals):
    """The walk's plan from a block's row totals s32[R]: (starts,
    bounds), s32[nb] each — the first row of every block of
    ``walk_block_rows(R)`` rows (the last block is moved back to end at
    R, so every block is whole and a row may be counted twice, to the
    same figure) in descending order of the block's largest total, and
    those largest totals."""
    R = totals.shape[0]
    k = walk_block_rows(R)
    starts = jnp.minimum(jnp.arange(-(-R // k), dtype=jnp.int32) * k, R - k)
    bounds = jax.vmap(lambda s: jnp.max(
        jax.lax.dynamic_slice_in_dim(totals, s, k)))(starts)
    order = jnp.argsort(-bounds, stable=True)
    return starts[order], bounds[order]


def nth_largest(counts, ns):
    """The ``ns[b]``-th largest of each row of ``counts`` [B, R] (>= 0),
    0 where a row has fewer than n entries: the count a row must reach
    to be among the top n."""
    R = counts.shape[1]
    ranked = jnp.sort(counts, axis=1)                      # ascending
    at = jnp.clip(R - ns, 0, R - 1)[:, None]
    return jnp.where(ns > R, 0,
                     jnp.take_along_axis(ranked, at, axis=1)[:, 0])


def block_counts(block, mask):
    """Counts of a block's k rows under one params row's filter
    segments, summed over the device's shards: [S_local, k, 256, 128]
    and [S_local, 256, 128] -> s32[k]."""
    return jax.vmap(lambda rows, m: masked_counts(rows, m[None])[0])(
        block, mask).sum(axis=0)


def topn_walk(stack, masks, ns, starts, bounds, axis_name):
    """Filtered per-row counts of a top-n node, as far as they can
    matter.  Inside the shard_map body, outside the per-shard vmap (the
    bound is global): ``stack`` is the device-local block
    [S_local, R, 256, 128], ``masks`` its filter segments, one
    [S_local, 256, 128] a params row, ``ns`` s32[B], (``starts``,
    ``bounds``) ``walk_order`` of the block's totals.  Returns (counts
    s32[B, R], exact for every row visited and 0 for the others; rows
    visited, one s32 for the launch: in a fused launch the walk goes on
    while any params row needs the next block).

    After each block its counts are global (summed over the shards,
    ``psum``ed), so every device holds the same threshold and makes the
    same number of steps.  The next block is read only if its largest
    total is >= max(t, 1) for some params row, t the n-th largest count
    so far (0 while fewer than n rows are non-zero): a total equal to t
    can tie the n-th place and win it on the lower row id, so it is
    read.  A block not visited is behind the ``while``: never read."""
    R = stack.shape[1]
    k = walk_block_rows(R)
    nb = starts.shape[0]
    ns = jnp.maximum(ns, 1)

    def cond(state):
        j, counts = state
        t = jnp.maximum(nth_largest(counts, ns), 1)
        # (both sides are evaluated: the index stays inside at j = nb)
        return (j < nb) & jnp.any(bounds[jnp.minimum(j, nb - 1)] >= t)

    def step(state):
        j, counts = state
        start = starts[j]
        block = jax.lax.dynamic_slice_in_dim(stack, start, k, axis=1)
        part = jnp.stack([block_counts(block, m) for m in masks])  # [B, k]
        part = jax.lax.psum(part, axis_name)
        return j + 1, jax.lax.dynamic_update_slice_in_dim(
            counts, part, start, axis=1)

    j, counts = jax.lax.while_loop(
        cond, step,
        (jnp.int32(0), jnp.zeros((len(masks), R), dtype=jnp.int32)))
    return counts, jnp.minimum(j * k, R)


def mat_rows(mat) -> int:
    """Leading (batch or combo) rows of a node's params matrix."""
    return mat[0].shape[0] if isinstance(mat, tuple) else mat.shape[0]


def pow2_rows(n: int) -> int:
    """The batch rows a launch of ``n`` rows is padded to: the next
    power of two, so arbitrary batch sizes reuse a bounded set of
    compiled programs."""
    return 1 << max(0, n - 1).bit_length()


def pad_pow2_rows(mat: np.ndarray, repeat: bool = True) -> np.ndarray:
    """Pad a params matrix's row count up to ``pow2_rows`` of it.
    ``repeat`` duplicates the last row (always in-range); otherwise zero
    rows (GroupBy combo grids)."""
    B = mat.shape[0]
    pad = pow2_rows(B)
    if pad == B:
        return mat
    if repeat:
        return np.concatenate([mat, np.repeat(mat[-1:], pad - B, axis=0)])
    return np.concatenate(
        [mat, np.zeros((pad - B,) + mat.shape[1:], mat.dtype)])


# -- what a launch costs in program temporaries, and what it may cost -------
#
# A batched executable materializes device temporaries beside its
# stacked inputs, per stacked shard of the device.  The per-stage
# launches state theirs in [SHARD_WORDS] u32 rows (128 KiB) a batch row
# (``node_temp_rows``, the one function the executor's batch chunking
# and the cross-query batcher's per-stage tickets share):
#
# * count / segments: one gather temp per params slot (measured: an
#   8-slot Intersect batch at B=16384 on one shard exhausts a 16 GB HBM
#   with 8 x 2 GB gather temps);
# * filtered row_counts: one [B, rows, W] masked temp, rows = the
#   fragment row count (BENCH_r07's small-RAM OOM gap);
# * filtered bsi_sum: the summed field's bit rows under the filter, so
#   its depth + 2 rows.
#
# A whole-query program asks the compiler instead: its launch reads
# ``memory_analysis().temp_size_in_bytes`` of the executable it is about
# to run, once per compiled shape, and walks the device's shards in
# blocks where that figure would pass the bound
# (parallel/wholequery.py ``run``).  ``batch_temp_bound`` is what a
# launch may cost: what a device has left — its ``bytes_limit`` less
# what the device budget counts resident on the fullest device, less
# BATCH_TEMP_MARGIN — with the ``batch-temp-mb`` knob (BATCH_TEMP_BYTES;
# process-wide, most recent Server wins) as a ceiling only.
BATCH_TEMP_BYTES = 4 << 30
# Device bytes the bound leaves free beside resident blocks and one
# launch's temporaries: outputs, params, the temporaries of launches
# still in flight at B = 1 (0.4-0.5 GB each at 176 stacked shards) and
# the allocator's own slack.
BATCH_TEMP_MARGIN = 1 << 30
ROW_BYTES = SHARD_WORDS * 4


def node_temp_rows(kind: str, plan, P: int, primary_rows: int = 0) -> int:
    """[SHARD_WORDS] u32 rows one batch row of a per-stage launch of
    node kind ``kind`` keeps per stacked shard.  ``plan`` is the slotted
    filter (None: a B-independent broadcast pass, 0), ``P`` its params
    slots, ``primary_rows`` the rows of the (field, view) reduced."""
    if kind in ("count", "segments"):
        return max(1, P)
    if plan is None:
        return 0
    return max(1, P, primary_rows)


@functools.cache
def device_bytes_limit() -> int | None:
    """The smallest ``bytes_limit`` over the local devices, where the
    backend reports one (the CPU does not).  Read once."""
    limits = [(d.memory_stats() or {}).get("bytes_limit")
              for d in jax.local_devices()]
    return min((b for b in limits if b), default=None)


def device_budget_bytes() -> int | None:
    """The device budget's limit where no ``device-budget-mb`` is given:
    a device's ``bytes_limit`` less what one launch may take beside the
    resident blocks — the batch-temp ceiling and its margin — so a budget
    that holds leaves ``batch_temp_bound`` at its ceiling.  None where the
    backend reports no memory (the CPU): the budget then only counts."""
    limit = device_bytes_limit()
    if limit is None:
        return None
    return max(0, limit - BATCH_TEMP_BYTES - BATCH_TEMP_MARGIN)


def batch_temp_bound() -> int:
    """What one launch's temporaries may cost now: what a device has
    left — one device's limit less what the fullest device holds, as
    the device budget counts it — with ``batch-temp-mb`` as a
    ceiling."""
    limit = device_bytes_limit()
    if limit is None:
        return BATCH_TEMP_BYTES
    from ..storage.membudget import DEFAULT_BUDGET
    free = limit - DEFAULT_BUDGET.resident_bytes_max_device \
        - BATCH_TEMP_MARGIN
    return max(0, min(BATCH_TEMP_BYTES, free))
