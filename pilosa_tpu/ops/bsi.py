"""Bit-sliced-index (BSI) kernels for integer fields.

Mirrors the reference layout exactly (fragment.go:90-93, field.go:1564-1647):
a BSI fragment tensor is ``uint32[2 + depth, SHARD_WORDS]`` on the host and
``uint32[2 + depth, 256, 128]`` on the device (ops/bitset.py
"Representation": every kernel here takes the tiled form, so a bit plane
``bsi_frag[OFFSET_ROW + i]`` is an offset into the mirror, not a gather) with

* row 0 — existence ("not null") bit per column     (bsiExistsBit)
* row 1 — sign bit (set = negative)                 (bsiSignBit)
* row 2+i — bit i of the magnitude, LSB first       (bsiOffsetBit + i)

All comparison/aggregation scans are O(depth) vector passes, the same
complexity as the reference's per-slice roaring scans (fragment.go:1111 sum,
:1147 min, :1189 max, :1288-1538 rangeEQ/LT/GT/Between) but each pass is a
fused popcount/bit-op over the dense segment.

Depth is static at trace time (it is the fragment's row count minus 2), so the
per-bit loops below unroll into straight-line XLA — no dynamic control flow.

64-bit-safe aggregation: device popcounts are int32 (each <= 2^20); the 2^i
weighting that would overflow is done host-side in Python ints (see
``weighted_sum``), keeping the device path free of int64 emulation.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .bitset import popcount_words, row_counts, word_bit_np

EXISTS_ROW = 0
SIGN_ROW = 1
OFFSET_ROW = 2


def depth_of(bsi_frag) -> int:
    return bsi_frag.shape[0] - OFFSET_ROW


def not_null(bsi_frag, filter_seg=None):
    """Columns with a value set (fragment.go:1269 notNull)."""
    seg = bsi_frag[EXISTS_ROW]
    if filter_seg is not None:
        seg = seg & filter_seg
    return seg


def _magnitude_compare(bsi_frag, pred_mag: int, candidates):
    """Classic bit-sliced comparison of per-column magnitudes against a
    constant, MSB->LSB (the loop structure of fragment.go:1349 rangeLT /
    :1436 rangeGT collapsed into one pass).

    Returns (lt, eq, gt) segments partitioning ``candidates`` by
    magnitude <, ==, > ``pred_mag``.
    """
    depth = depth_of(bsi_frag)
    eq = candidates
    lt = jnp.zeros_like(candidates)
    gt = jnp.zeros_like(candidates)
    for i in range(depth - 1, -1, -1):
        bit = bsi_frag[OFFSET_ROW + i]
        if (pred_mag >> i) & 1:
            lt = lt | (eq & ~bit)
            eq = eq & bit
        else:
            gt = gt | (eq & bit)
            eq = eq & ~bit
    if pred_mag >> depth:
        # Predicate magnitude exceeds representable range: everything is less.
        lt = lt | eq | gt
        eq = jnp.zeros_like(eq)
        gt = jnp.zeros_like(gt)
    return lt, eq, gt


def range_op(bsi_frag, op: str, value: int, filter_seg=None):
    """Signed comparison of every column's value against ``value``.

    op in {"eq","neq","lt","le","gt","ge"} — the executor lowers PQL
    conditions (pql/ast.go Condition) and Between to these plus intersections
    (fragment.go:1273 rangeOp dispatch).
    """
    exists = not_null(bsi_frag, filter_seg)
    sign = bsi_frag[SIGN_ROW]
    pos = exists & ~sign
    neg = exists & sign
    mag = abs(int(value))

    if value > 0:
        plt, peq, pgt = _magnitude_compare(bsi_frag, mag, pos)
        # every negative value is < a positive predicate
        lt = neg | plt
        eq = peq
        gt = pgt
    elif value == 0:
        plt, peq, pgt = _magnitude_compare(bsi_frag, 0, pos)
        # magnitude-0 columns with the sign bit set still hold value 0
        _, neg_zero, _ = _magnitude_compare(bsi_frag, 0, neg)
        eq = peq | neg_zero
        lt = neg & ~neg_zero
        gt = pgt
    else:
        nlt, neq_, ngt = _magnitude_compare(bsi_frag, mag, neg)
        # for negatives: larger magnitude -> smaller value
        lt = ngt
        eq = neq_
        gt = pos | nlt

    if op == "eq":
        return eq
    if op == "neq":
        return exists & ~eq
    if op == "lt":
        return lt
    if op == "le":
        return lt | eq
    if op == "gt":
        return gt
    if op == "ge":
        return gt | eq
    raise ValueError(f"unknown range op {op!r}")


def range_between(bsi_frag, lo: int, hi: int, filter_seg=None):
    """lo <= value <= hi (fragment.go:1461 rangeBetween)."""
    ge = range_op(bsi_frag, "ge", lo, filter_seg)
    le = range_op(bsi_frag, "le", hi, filter_seg)
    return ge & le


# -- dynamic-predicate variants ---------------------------------------------
# The predicate magnitude arrives as a traced bit vector instead of a Python
# int, so every query against the same field shape shares ONE compiled
# executable (the plan cache is keyed by call-tree shape, SURVEY §7) — the
# per-slice branch on the predicate bit becomes a select.

MAG_BITS = 63  # max magnitude bits of an int64 predicate


def _magnitude_compare_dyn(bsi_frag, mag_bits, candidates):
    """_magnitude_compare with the predicate's bits as a traced int32[63]
    vector (LSB first).  Bits at positions >= depth mean the predicate
    exceeds the representable range: everything is less."""
    depth = depth_of(bsi_frag)
    eq = candidates
    lt = jnp.zeros_like(candidates)
    gt = jnp.zeros_like(candidates)
    for i in range(depth - 1, -1, -1):
        bit = bsi_frag[OFFSET_ROW + i]
        b = mag_bits[i] > 0
        new_lt = jnp.where(b, lt | (eq & ~bit), lt)
        new_gt = jnp.where(b, gt, gt | (eq & bit))
        eq = jnp.where(b, eq & bit, eq & ~bit)
        lt, gt = new_lt, new_gt
    if depth < MAG_BITS:
        ovf = jnp.sum(mag_bits[depth:MAG_BITS]) > 0
        lt = jnp.where(ovf, lt | eq | gt, lt)
        eq = jnp.where(ovf, jnp.zeros_like(eq), eq)
        gt = jnp.where(ovf, jnp.zeros_like(gt), gt)
    return lt, eq, gt


def range_op_dyn(bsi_frag, op: str, sign: str, mag_bits, filter_seg=None):
    """range_op with a dynamic predicate: ``sign`` ("pos"|"zero"|"neg") is
    structural (it selects the code path), ``mag_bits`` is the traced
    magnitude bit vector."""
    exists = not_null(bsi_frag, filter_seg)
    sgn = bsi_frag[SIGN_ROW]
    pos = exists & ~sgn
    neg = exists & sgn

    if sign == "pos":
        plt, peq, pgt = _magnitude_compare_dyn(bsi_frag, mag_bits, pos)
        lt = neg | plt
        eq = peq
        gt = pgt
    elif sign == "zero":
        # predicate 0 needs no dynamic bits (the zero compare is static)
        plt, peq, pgt = _magnitude_compare(bsi_frag, 0, pos)
        _, neg_zero, _ = _magnitude_compare(bsi_frag, 0, neg)
        eq = peq | neg_zero
        lt = neg & ~neg_zero
        gt = pgt
    else:
        nlt, neq_, ngt = _magnitude_compare_dyn(bsi_frag, mag_bits, neg)
        lt = ngt
        eq = neq_
        gt = pos | nlt

    if op == "eq":
        return eq
    if op == "neq":
        return exists & ~eq
    if op == "lt":
        return lt
    if op == "le":
        return lt | eq
    if op == "gt":
        return gt
    if op == "ge":
        return gt | eq
    raise ValueError(f"unknown range op {op!r}")


def range_between_dyn(bsi_frag, lo_sign, lo_bits, hi_sign, hi_bits,
                      filter_seg=None):
    ge = range_op_dyn(bsi_frag, "ge", lo_sign, lo_bits, filter_seg)
    le = range_op_dyn(bsi_frag, "le", hi_sign, hi_bits, filter_seg)
    return ge & le


def sum_counts(bsi_frag, filter_seg=None):
    """Device half of Sum (fragment.go:1111): per-bit-slice popcounts split by
    sign.  Returns int32[2, depth+1]: row 0 = positive-side counts (count of
    filter&exists&~sign per magnitude bit, last entry = total positive count),
    row 1 = same for the negative side.  Host reconstructs the exact int sum
    via ``weighted_sum``."""
    exists = not_null(bsi_frag, filter_seg)
    sign = bsi_frag[SIGN_ROW]
    pos = exists & ~sign
    neg = exists & sign
    depth = depth_of(bsi_frag)
    slices = bsi_frag[OFFSET_ROW:OFFSET_ROW + depth]
    pos_counts = row_counts(slices & pos[None])
    neg_counts = row_counts(slices & neg[None])
    pos_total = jnp.sum(popcount_words(pos), dtype=jnp.int32)
    neg_total = jnp.sum(popcount_words(neg), dtype=jnp.int32)
    return jnp.stack([
        jnp.concatenate([pos_counts, pos_total[None]]),
        jnp.concatenate([neg_counts, neg_total[None]]),
    ])


def weighted_sum(counts: np.ndarray):
    """Host half of Sum: exact Python-int reconstruction.

    Returns (sum, count) like fragment.go:1111 (sum of values, number of
    non-null columns in the filter)."""
    counts = np.asarray(counts)
    depth = counts.shape[1] - 1
    pos = sum(int(counts[0, i]) << i for i in range(depth))
    neg = sum(int(counts[1, i]) << i for i in range(depth))
    total = int(counts[0, depth]) + int(counts[1, depth])
    return pos - neg, total


def min_max_bits(bsi_frag, filter_seg=None, want_max=False):
    """Device half of Min/Max (fragment.go:1147 min, :1189 max).

    Narrows the candidate set bit-by-bit from the MSB.  Returns
    (value_bits int32[depth], negative int32, count int32):
    the chosen magnitude bit per slice, whether the extremum is negative, and
    how many columns attain it.  Host reconstructs the Python int.
    """
    exists = not_null(bsi_frag, filter_seg)
    sign = bsi_frag[SIGN_ROW]
    pos = exists & ~sign
    neg = exists & sign
    pos_count = jnp.sum(popcount_words(pos), dtype=jnp.int32)
    neg_count = jnp.sum(popcount_words(neg), dtype=jnp.int32)

    if want_max:
        # max: prefer positives; among positives maximise magnitude, among
        # negatives (only if no positives) minimise magnitude.
        use_neg = pos_count == 0
        cand = jnp.where(use_neg, neg, pos)
        prefer_set = ~use_neg  # maximise magnitude iff positive side
    else:
        use_neg = neg_count > 0
        cand = jnp.where(use_neg, neg, pos)
        prefer_set = use_neg  # minimise value = maximise magnitude if negative

    depth = depth_of(bsi_frag)
    bits = []
    for i in range(depth - 1, -1, -1):
        slice_i = bsi_frag[OFFSET_ROW + i]
        with_bit = cand & slice_i
        without_bit = cand & ~slice_i
        n_with = jnp.sum(popcount_words(with_bit), dtype=jnp.int32)
        n_without = jnp.sum(popcount_words(without_bit), dtype=jnp.int32)
        # prefer_set: take the bit=1 branch when non-empty; else bit=0 branch.
        take_set = jnp.where(prefer_set, n_with > 0, n_without == 0)
        cand = jnp.where(take_set, with_bit, without_bit)
        bits.append(take_set.astype(jnp.int32))
    bits.reverse()
    n_att = jnp.sum(popcount_words(cand), dtype=jnp.int32)
    return jnp.stack(bits), use_neg.astype(jnp.int32), n_att


def reconstruct_min_max(bits, negative, count):
    """Host half of Min/Max: (value, count) from min_max_bits output.

    When the candidate set is empty (no non-null columns under the filter)
    the device bit pattern is meaningless; this returns (0, 0) and callers
    must treat count == 0 as "no value" (the reference returns an empty
    ValCount, executor.go:2995)."""
    if int(count) == 0:
        return 0, 0
    bits = np.asarray(bits)
    mag = sum(int(bits[i]) << i for i in range(bits.shape[0]))
    val = -mag if int(negative) else mag
    return val, int(count)


def pack_values(cols: np.ndarray, values: np.ndarray, depth: int,
                words: int) -> np.ndarray:
    """Host-side construction of a BSI fragment tensor from (column, value)
    pairs — the import path's equivalent of fragment.go:977 setValueBase."""
    out = np.zeros((OFFSET_ROW + depth, words), dtype=np.uint32)
    cols = np.asarray(cols, dtype=np.int64)
    values = np.asarray(values, dtype=np.int64)
    if values.size and int(np.abs(values).max()) >> depth:
        raise ValueError(
            f"value magnitude {int(np.abs(values).max())} does not fit in "
            f"depth={depth} bits; widen the fragment (the storage layer "
            f"auto-sizes depth like the reference's setValueBase grows "
            f"bitDepth, fragment.go:977)"
        )
    w, bit = word_bit_np(cols)
    np.bitwise_or.at(out[EXISTS_ROW], w, bit)
    negmask = values < 0
    if negmask.any():
        np.bitwise_or.at(out[SIGN_ROW], w[negmask], bit[negmask])
    mags = np.abs(values)
    for i in range(depth):
        sel = (mags >> i) & 1 > 0
        if sel.any():
            np.bitwise_or.at(out[OFFSET_ROW + i], w[sel], bit[sel])
    return out


def unpack_values(bsi_frag: np.ndarray):
    """Host-side extraction: (cols int64[], values int64[]) for set columns."""
    from .bitset import unpack_columns

    bsi_frag = np.asarray(bsi_frag)
    cols = unpack_columns(bsi_frag[EXISTS_ROW])
    if cols.size == 0:
        return cols, np.zeros(0, dtype=np.int64)
    depth = bsi_frag.shape[0] - OFFSET_ROW
    w, bit = word_bit_np(cols)
    vals = np.zeros(cols.shape, dtype=np.int64)
    for i in range(depth):
        vals |= ((bsi_frag[OFFSET_ROW + i, w] & bit) > 0).astype(np.int64) << i
    sign = (bsi_frag[SIGN_ROW, w] & bit) > 0
    vals[sign] = -vals[sign]
    return cols, vals
