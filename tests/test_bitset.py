"""Differential tests for the dense bitset kernels against a naive set-based
oracle — same strategy as the reference's roaring/naive.go + naive_test.go
(every container op checked against a []uint64 reimplementation).

Data is made on the host as ``[R, W]`` words, carried to the kernels as the
device's word tile ``[R, W // 128, 128]`` (``dev``) and back (``host``): what
a kernel answers is compared with the numpy reference on the host's words,
bit for bit (ops/bitset.py "Representation")."""

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.core import SHARD_WORDS, WORD_TILE
from pilosa_tpu.ops import bitset

WORDS = 256  # 8192-column mini-shard: fast on CPU, shape-polymorphic kernels
NBITS = WORDS * 32
# word counts the kernels are held to bit for bit: two sublanes of a vector
# register, one whole (8, 128) register, four registers
WIDTHS = [256, 1024, 4096]


def dev(x):
    """Host words [..., W] -> the device's tiled array."""
    return jnp.asarray(bitset.to_tile(np.asarray(x)))


def host(x):
    """A kernel's tiled result -> host words [..., W]."""
    return bitset.from_tile(np.asarray(x))


def rand_cols(rng, density=0.1, nbits=NBITS):
    n = int(nbits * density)
    return np.unique(rng.integers(0, nbits, size=n))


def seg_of(cols, words=WORDS):
    return bitset.pack_columns(cols, words=words)


def cols_of(seg):
    return set(bitset.unpack_columns(np.asarray(seg)).tolist())


def bits_of(x):
    """Host words [..., W] -> bool [..., W * 32], column order."""
    x = np.ascontiguousarray(x, dtype=np.uint32)
    return np.unpackbits(x.view(np.uint8), bitorder="little").reshape(
        x.shape[:-1] + (x.shape[-1] * 32,)).astype(bool)


def words_of(bits):
    """bool [..., W * 32] -> host words [..., W]."""
    return np.packbits(bits, axis=-1, bitorder="little").view(np.uint32)


def rand_words(rng, shape, density=0.3):
    return words_of(rng.random(shape[:-1] + (shape[-1] * 32,)) < density)


@pytest.fixture
def ab(rng):
    a = rand_cols(rng, 0.1)
    b = rand_cols(rng, 0.05)
    return a, b, seg_of(a), seg_of(b)


def test_tile_is_a_view_of_the_hosts_words():
    assert WORD_TILE == bitset.tile_shape(SHARD_WORDS) == (256, 128)
    x = np.arange(2 * SHARD_WORDS, dtype=np.uint32).reshape(2, SHARD_WORDS)
    t = bitset.to_tile(x)
    assert t.shape == (2,) + WORD_TILE and np.shares_memory(t, x)
    # word w of a row sits at [w >> 7, w & 127]
    assert t[1, 300 >> 7, 300 & 127] == x[1, 300]
    back = bitset.from_tile(t)
    assert back.shape == x.shape and np.shares_memory(back, x)
    with pytest.raises(ValueError):
        bitset.tile_shape(100)


def test_pack_unpack_roundtrip(rng):
    cols = rand_cols(rng)
    assert cols_of(seg_of(cols)) == set(cols.tolist())


def test_intersect(ab):
    a, b, sa, sb = ab
    assert cols_of(host(bitset.intersect(dev(sa), dev(sb)))) == set(a) & set(b)


def test_union(ab):
    a, b, sa, sb = ab
    assert cols_of(host(bitset.union(dev(sa), dev(sb)))) == set(a) | set(b)


def test_difference(ab):
    a, b, sa, sb = ab
    assert cols_of(host(bitset.difference(dev(sa), dev(sb)))) == \
        set(a) - set(b)


def test_xor(ab):
    a, b, sa, sb = ab
    assert cols_of(host(bitset.xor(dev(sa), dev(sb)))) == set(a) ^ set(b)


def test_union_many(rng):
    sets = [rand_cols(rng, 0.02) for _ in range(5)]
    stacked = np.stack([seg_of(c) for c in sets])
    expect = set()
    for c in sets:
        expect |= set(c.tolist())
    assert cols_of(host(bitset.union_many(dev(stacked)))) == expect


def test_count(ab):
    a, _, sa, _ = ab
    assert int(bitset.count(dev(sa))) == len(a)


def test_intersection_count(ab):
    a, b, sa, sb = ab
    assert int(bitset.intersection_count(dev(sa), dev(sb))) == \
        len(set(a) & set(b))


def test_count_range(rng):
    cols = rand_cols(rng)
    seg = dev(seg_of(cols))
    for start, end in [(0, NBITS), (100, 200), (31, 33), (32, 64), (5, 5),
                       (0, 1), (NBITS - 1, NBITS), (1000, 4097)]:
        expect = len([c for c in cols if start <= c < end])
        assert int(bitset.count_range(seg, start, end)) == expect, (start, end)


def test_flip(rng):
    cols = rand_cols(rng)
    start, end = 50, 7000
    got = cols_of(host(bitset.flip(dev(seg_of(cols)), start, end)))
    expect = set(cols.tolist()) ^ set(range(start, end))
    assert got == expect


def test_keep_range(rng):
    cols = rand_cols(rng)
    got = cols_of(host(bitset.keep_range(dev(seg_of(cols)), 33, 5000)))
    assert got == {c for c in cols if 33 <= c < 5000}


@pytest.mark.parametrize("n", [1, 7, 32, 33, 100])
def test_shift(rng, n):
    cols = rand_cols(rng)
    got = cols_of(host(bitset.shift(dev(seg_of(cols)), n)))
    expect = {c + n for c in cols if c + n < NBITS}
    assert got == expect


def test_row_counts(rng):
    frag = np.stack([seg_of(rand_cols(rng, d)) for d in (0.1, 0.01, 0.0)])
    counts = np.asarray(bitset.row_counts(dev(frag)))
    for i in range(3):
        assert counts[i] == len(cols_of(frag[i]))


def test_intersection_counts_matrix(rng):
    aset = [rand_cols(rng, 0.05) for _ in range(3)]
    bset = [rand_cols(rng, 0.05) for _ in range(4)]
    a = np.stack([seg_of(c) for c in aset])
    b = np.stack([seg_of(c) for c in bset])
    got = np.asarray(bitset.intersection_counts_matrix(dev(a), dev(b)))
    for i in range(3):
        for j in range(4):
            assert got[i, j] == len(set(aset[i]) & set(bset[j]))


# -- every kernel against its numpy reference, bit for bit, on host [R, W]
# -- data carried through the device tile and back --------------------------

@pytest.mark.parametrize("words", WIDTHS)
def test_row_counts_matches_numpy(rng, words):
    frag = rand_words(rng, (5, words))
    got = np.asarray(bitset.row_counts(dev(frag)))
    assert got.dtype == np.int32
    assert np.array_equal(got, bits_of(frag).sum(axis=-1))


@pytest.mark.parametrize("words", WIDTHS)
def test_intersection_count_matches_numpy(rng, words):
    a, b = rand_words(rng, (2, words))
    assert int(bitset.intersection_count(dev(a), dev(b))) == \
        int((bits_of(a) & bits_of(b)).sum())


@pytest.mark.parametrize("words", WIDTHS)
def test_intersection_counts_matrix_matches_numpy(rng, words):
    a = rand_words(rng, (3, words))
    b = rand_words(rng, (4, words))
    got = np.asarray(bitset.intersection_counts_matrix(dev(a), dev(b)))
    expect = (bits_of(a)[:, None, :] & bits_of(b)[None, :, :]).sum(axis=-1)
    assert got.shape == (3, 4) and np.array_equal(got, expect)


@pytest.mark.parametrize("words", WIDTHS)
def test_count_range_matches_numpy(rng, words):
    seg = rand_words(rng, (words,))
    nbits = words * 32
    for start, end in [(0, nbits), (127 * 32, 129 * 32), (4095, 4097),
                       (7, 7), (nbits - 1, nbits), (1, nbits - 1)]:
        assert int(bitset.count_range(dev(seg), start, end)) == \
            int(bits_of(seg)[start:end].sum()), (start, end)


@pytest.mark.parametrize("words", WIDTHS)
def test_flip_matches_numpy(rng, words):
    seg = rand_words(rng, (words,))
    # the range crosses a lane row of the tile (word 128) mid-word
    start, end = 4090, words * 32 - 3
    ref = bits_of(seg)
    ref[start:end] ^= True
    got = host(bitset.flip(dev(seg), start, end))
    assert got.shape == (words,) and np.array_equal(got, words_of(ref))
    keep = bits_of(seg)
    keep[:start] = False
    keep[end:] = False
    assert np.array_equal(host(bitset.keep_range(dev(seg), start, end)),
                          words_of(keep))


@pytest.mark.parametrize("n", [1, 31, 32, 33, 4096, 4097])
@pytest.mark.parametrize("words", WIDTHS)
def test_shift_matches_numpy(rng, words, n):
    """A carry crosses words in their linear order, so across the lane rows
    of the tile too (n = 4096 bits is exactly one lane row)."""
    frag = rand_words(rng, (2, words))
    ref = np.zeros_like(bits_of(frag))
    ref[:, n:] = bits_of(frag)[:, :-n]
    got = host(bitset.shift(dev(frag), n))
    assert got.shape == frag.shape and np.array_equal(got, words_of(ref))


@pytest.mark.parametrize("words", WIDTHS)
def test_set_clear_bits_match_numpy(rng, words):
    n_rows = 6
    base = rand_words(rng, (n_rows, words), density=0.05)
    rows = rng.integers(-1, n_rows, size=3000).astype(np.int32)   # -1: padding
    cols = rng.integers(0, words * 32, size=3000).astype(np.int32)
    named = np.zeros((n_rows, words * 32), dtype=bool)
    named[rows[rows >= 0], cols[rows >= 0]] = True
    got = host(bitset.set_bits(dev(base), jnp.asarray(rows),
                               jnp.asarray(cols)))
    assert np.array_equal(got, words_of(bits_of(base) | named))
    got = host(bitset.clear_bits(dev(base), jnp.asarray(rows),
                                 jnp.asarray(cols)))
    assert np.array_equal(got, words_of(bits_of(base) & ~named))


def test_kernels_at_shard_width(rng):
    """The shapes the system runs: a fragment mirror u32[R, 256, 128]."""
    frag = rand_words(rng, (3, SHARD_WORDS), density=0.02)
    d = dev(frag)
    assert d.shape == (3,) + WORD_TILE
    assert np.array_equal(np.asarray(bitset.row_counts(d)),
                          bits_of(frag).sum(axis=-1))
    ref = np.zeros_like(bits_of(frag))
    ref[:, 33:] = bits_of(frag)[:, :-33]
    assert np.array_equal(host(bitset.shift(d, 33)), words_of(ref))
    assert int(bitset.count_range(d[1], 4000, 1 << 19)) == \
        int(bits_of(frag)[1, 4000:1 << 19].sum())


def test_set_clear_bits(rng):
    frag = dev(np.zeros((4, WORDS), dtype=np.uint32))
    rows = np.array([0, 1, 3, 3, -1], dtype=np.int32)
    cols = np.array([5, 8191, 0, 77, 123], dtype=np.int32)
    frag = bitset.set_bits(frag, jnp.asarray(rows), jnp.asarray(cols))
    r, c = bitset.unpack_fragment(host(frag))
    assert set(zip(r.tolist(), c.tolist())) == {(0, 5), (1, 8191), (3, 0), (3, 77)}

    frag = bitset.clear_bits(
        frag, jnp.asarray(np.array([3, -1], np.int32)),
        jnp.asarray(np.array([77, 5], np.int32)))
    r, c = bitset.unpack_fragment(host(frag))
    assert set(zip(r.tolist(), c.tolist())) == {(0, 5), (1, 8191), (3, 0)}


def test_pack_fragment(rng):
    rows = np.array([0, 0, 2, 5])
    cols = np.array([1, 100, 1, 8000])
    frag = bitset.pack_fragment(rows, cols, n_rows=6, words=WORDS)
    r, c = bitset.unpack_fragment(frag)
    assert set(zip(r.tolist(), c.tolist())) == set(zip(rows.tolist(), cols.tolist()))


def test_set_bits_same_word_collision():
    # Regression: two positions in the same 32-bit word must both land.
    frag = dev(np.zeros((2, WORDS), dtype=np.uint32))
    rows = jnp.asarray(np.array([0, 0, 0, 1, 1], np.int32))
    cols = jnp.asarray(np.array([0, 1, 1, 31, 30], np.int32))
    frag = bitset.set_bits(frag, rows, cols)
    r, c = bitset.unpack_fragment(host(frag))
    assert set(zip(r.tolist(), c.tolist())) == {(0, 0), (0, 1), (1, 31), (1, 30)}


def test_clear_bits_same_word_collision():
    frag = dev(bitset.pack_fragment(
        np.array([0, 0, 0]), np.array([0, 1, 2]), n_rows=1, words=WORDS))
    frag = bitset.clear_bits(
        frag, jnp.asarray(np.array([0, 0], np.int32)),
        jnp.asarray(np.array([0, 1], np.int32)))
    r, c = bitset.unpack_fragment(host(frag))
    assert set(zip(r.tolist(), c.tolist())) == {(0, 2)}


def test_set_bits_padding_does_not_clobber():
    # Regression: a row==-1 padding entry must not race a real write to word 0.
    frag = dev(np.zeros((1, WORDS), dtype=np.uint32))
    rows = jnp.asarray(np.array([-1, 0], np.int32))
    cols = jnp.asarray(np.array([0, 0], np.int32))
    frag = bitset.set_bits(frag, rows, cols)
    r, c = bitset.unpack_fragment(host(frag))
    assert set(zip(r.tolist(), c.tolist())) == {(0, 0)}


def test_set_bits_random_vs_oracle(rng):
    n_rows = 8
    frag = dev(np.zeros((n_rows, WORDS), dtype=np.uint32))
    rows = rng.integers(0, n_rows, size=2000).astype(np.int32)
    cols = rng.integers(0, NBITS, size=2000).astype(np.int32)
    frag = bitset.set_bits(frag, jnp.asarray(rows), jnp.asarray(cols))
    expect = bitset.pack_fragment(rows, cols, n_rows=n_rows, words=WORDS)
    assert np.array_equal(host(frag), expect)
