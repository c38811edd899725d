"""Differential tests for BSI kernels against a naive dict oracle —
mirrors the reference's BSI coverage in fragment_internal_test.go
(SetValue/Sum/Min/Max/Range under every comparison op, negative values).

A BSI fragment is packed on the host as ``[2 + depth, W]`` words and handed
to the kernels as the device's word tile (``dev``); a segment they answer
comes back through ``host`` and is compared with the oracle's words bit for
bit (ops/bitset.py "Representation")."""

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.ops import bitset, bsi

WORDS = 256
NBITS = WORDS * 32
DEPTH = 16
WIDTHS = [256, 1024]    # two sublanes of a vector register; a whole one


def dev(x):
    """Host words [..., W] -> the device's tiled array."""
    return jnp.asarray(bitset.to_tile(np.asarray(x)))


def host(x):
    """A kernel's tiled segment -> host words [W]."""
    return bitset.from_tile(np.asarray(x))


def make(rng, n=300, lo=-5000, hi=5000, depth=DEPTH, words=WORDS):
    """(cols, vals, host fragment [2 + depth, words])."""
    cols = np.unique(rng.integers(0, words * 32, size=n))
    vals = rng.integers(lo, hi, size=cols.size)
    frag = bsi.pack_values(cols, vals, depth=depth, words=words)
    return cols, vals, frag


def words_where(cols, keep, words=WORDS):
    """The oracle's answer as host words: the columns whose value passes."""
    return bitset.pack_columns(cols[keep], words=words)


def mag_bits(value):
    """(sign, int32[63] magnitude bits): a traced predicate's two halves."""
    sign = "zero" if value == 0 else ("pos" if value > 0 else "neg")
    mag = abs(int(value))
    return sign, jnp.asarray([(mag >> i) & 1 for i in range(bsi.MAG_BITS)],
                             dtype=jnp.int32)


def test_pack_unpack_roundtrip(rng):
    cols, vals, frag = make(rng)
    c2, v2 = bsi.unpack_values(frag)
    assert np.array_equal(c2, cols)
    assert np.array_equal(v2, vals)


OPS = {
    "eq": lambda v, p: v == p,
    "neq": lambda v, p: v != p,
    "lt": lambda v, p: v < p,
    "le": lambda v, p: v <= p,
    "gt": lambda v, p: v > p,
    "ge": lambda v, p: v >= p,
}


@pytest.mark.parametrize("op", list(OPS))
@pytest.mark.parametrize("pred", [-70000, -4999, -123, -1, 0, 1, 57, 4999, 70000])
def test_range_op(rng, op, pred):
    cols, vals, frag = make(rng)
    got = host(bsi.range_op(dev(frag), op, pred))
    assert np.array_equal(got, words_where(cols, OPS[op](vals, pred)))


@pytest.mark.parametrize("words", WIDTHS)
@pytest.mark.parametrize("op", list(OPS))
@pytest.mark.parametrize("pred", [-70000, -123, 0, 57, 70000])
def test_range_op_dyn(rng, op, pred, words):
    """The traced-predicate form, the one the compiled programs run."""
    cols, vals, frag = make(rng, words=words)
    sign, bits = mag_bits(pred)
    got = host(bsi.range_op_dyn(dev(frag), op, sign, bits))
    assert got.shape == (words,)
    assert np.array_equal(got, words_where(cols, OPS[op](vals, pred), words))
    filt = words_where(cols, np.arange(cols.size) % 3 > 0, words)
    got = host(bsi.range_op_dyn(dev(frag), op, sign, bits, dev(filt)))
    keep = OPS[op](vals, pred) & (np.arange(cols.size) % 3 > 0)
    assert np.array_equal(got, words_where(cols, keep, words))


def test_range_op_zero_with_negative_zero_sign(rng):
    # A column whose magnitude is 0 but sign bit is set still holds value 0.
    frag = np.zeros((2 + 4, WORDS), dtype=np.uint32)
    frag[bsi.EXISTS_ROW, 0] = 0b1  # col 0 exists
    frag[bsi.SIGN_ROW, 0] = 0b1    # sign set, magnitude 0
    frag = dev(frag)
    assert set(bitset.unpack_columns(
        host(bsi.range_op(frag, "eq", 0))).tolist()) == {0}
    assert set(bitset.unpack_columns(
        host(bsi.range_op(frag, "lt", 0))).tolist()) == set()
    assert set(bitset.unpack_columns(
        host(bsi.range_op(frag, "gt", -1))).tolist()) == {0}


def test_range_between(rng):
    cols, vals, frag = make(rng)
    got = host(bsi.range_between(dev(frag), -100, 250))
    assert np.array_equal(
        got, words_where(cols, (-100 <= vals) & (vals <= 250)))


@pytest.mark.parametrize("words", WIDTHS)
@pytest.mark.parametrize("lo,hi", [(-100, 250), (0, 4999), (-70000, -1),
                                   (3, 3), (10, -10)])
def test_range_between_dyn(rng, lo, hi, words):
    cols, vals, frag = make(rng, words=words)
    got = host(bsi.range_between_dyn(dev(frag), *mag_bits(lo),
                                     *mag_bits(hi)))
    assert np.array_equal(
        got, words_where(cols, (lo <= vals) & (vals <= hi), words))


def test_sum(rng):
    cols, vals, frag = make(rng)
    s, n = bsi.weighted_sum(np.asarray(bsi.sum_counts(dev(frag))))
    assert s == int(vals.sum())
    assert n == cols.size


def test_sum_with_filter(rng):
    cols, vals, frag = make(rng)
    keep = cols[: cols.size // 2]
    filt = bitset.pack_columns(keep, words=WORDS)
    s, n = bsi.weighted_sum(
        np.asarray(bsi.sum_counts(dev(frag), dev(filt))))
    assert s == int(vals[: cols.size // 2].sum())
    assert n == keep.size


@pytest.mark.parametrize("words", WIDTHS)
def test_sum_counts_matches_numpy(rng, words):
    """Every per-plane count of the device half, against numpy's popcount
    of the host's planes under the same filter."""
    cols, vals, frag = make(rng, n=2000, words=words)
    filt = words_where(cols, vals % 2 == 0, words)
    got = np.asarray(bsi.sum_counts(dev(frag), dev(filt)))
    assert got.shape == (2, DEPTH + 1) and got.dtype == np.int32

    def pop(x):
        return np.unpackbits(
            np.ascontiguousarray(x).view(np.uint8), axis=-1).sum(axis=-1)

    exists = frag[bsi.EXISTS_ROW] & filt
    for side, mask in enumerate((exists & ~frag[bsi.SIGN_ROW],
                                 exists & frag[bsi.SIGN_ROW])):
        assert np.array_equal(got[side, :DEPTH],
                              pop(frag[bsi.OFFSET_ROW:] & mask[None, :]))
        assert got[side, DEPTH] == pop(mask)


@pytest.mark.parametrize("want_max", [False, True])
def test_min_max(rng, want_max):
    cols, vals, frag = make(rng)
    out = bsi.min_max_bits(dev(frag), want_max=want_max)
    val, cnt = bsi.reconstruct_min_max(*[np.asarray(x) for x in out])
    target = int(vals.max() if want_max else vals.min())
    assert val == target
    assert cnt == int((vals == target).sum())


@pytest.mark.parametrize("words", WIDTHS)
@pytest.mark.parametrize("want_max", [False, True])
def test_min_max_bits_match_numpy(rng, want_max, words):
    """The chosen magnitude bits themselves, under a filter."""
    cols, vals, frag = make(rng, n=2000, words=words)
    keep = vals % 3 == 0
    bits, neg, cnt = bsi.min_max_bits(
        dev(frag), dev(words_where(cols, keep, words)), want_max=want_max)
    target = int(vals[keep].max() if want_max else vals[keep].min())
    assert np.array_equal(
        np.asarray(bits), [(abs(target) >> i) & 1 for i in range(DEPTH)])
    assert int(neg) == int(target < 0)
    assert int(cnt) == int((vals[keep] == target).sum())


@pytest.mark.parametrize("case", [
    [5, 7, 9], [-5, -7, -9], [-5, 0, 5], [0], [-3, -3, 8],
])
def test_min_max_small(case):
    cols = np.arange(len(case))
    vals = np.array(case)
    frag = dev(bsi.pack_values(cols, vals, depth=8, words=WORDS))
    for want_max in (False, True):
        out = bsi.min_max_bits(frag, want_max=want_max)
        val, cnt = bsi.reconstruct_min_max(*[np.asarray(x) for x in out])
        target = max(case) if want_max else min(case)
        assert val == target, (case, want_max)
        assert cnt == case.count(target)


def test_min_max_with_filter(rng):
    cols = np.array([1, 2, 3, 4])
    vals = np.array([10, -20, 30, -40])
    frag = dev(bsi.pack_values(cols, vals, depth=8, words=WORDS))
    filt = dev(bitset.pack_columns(np.array([1, 3]), words=WORDS))
    out = bsi.min_max_bits(frag, filter_seg=filt, want_max=False)
    val, cnt = bsi.reconstruct_min_max(*[np.asarray(x) for x in out])
    assert (val, cnt) == (10, 1)
    out = bsi.min_max_bits(frag, filter_seg=filt, want_max=True)
    val, cnt = bsi.reconstruct_min_max(*[np.asarray(x) for x in out])
    assert (val, cnt) == (30, 1)


def test_pack_values_overflow_raises():
    with pytest.raises(ValueError):
        bsi.pack_values(np.array([0]), np.array([70000]), depth=16, words=WORDS)


def test_min_max_empty_returns_zero_count():
    frag = dev(np.zeros((2 + 4, WORDS), dtype=np.uint32))
    out = bsi.min_max_bits(frag, want_max=False)
    val, cnt = bsi.reconstruct_min_max(*[np.asarray(x) for x in out])
    assert (val, cnt) == (0, 0)
