"""Compressed-resident fragments (ops/containers.py): codec round-trip
for every container type at its boundary cardinalities, the device decode
against the host oracle, the density heuristic's dense fallback, and the
DIFFERENTIAL guarantee — a randomized query corpus executed with
compressed residency (including under eviction pressure) must return
results byte-identical to the dense-resident run.  A decode bug would
corrupt query results silently; the differential catches it as a
divergence."""

import jax
import numpy as np
import pytest

from conftest import over_budget_limit

from pilosa_tpu.core import CONTAINER_WORDS, SHARD_WIDTH, SHARD_WORDS, \
    WORD_TILE
from pilosa_tpu.ops.bitset import from_tile
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import containers
from pilosa_tpu.ops.containers import (
    ARRAY_WORDS_MAX, RUN_MAX, TYPE_ARRAY, TYPE_BITMAP, TYPE_RUN,
    pack_words, pad_packed, pow2_bucket, stream_bucket, unpack_packed,
    upload_decode,
)
from pilosa_tpu.storage import FieldOptions, Holder, fragment
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu.storage.membudget import DEFAULT_BUDGET, DeviceBudget

from test_differential import _norm, gen_query


def _store(dense_flat):
    """Sparse word store (sorted flat idx + values) of a flat dense
    uint32 array — the Fragment._idx/_val form pack_words takes."""
    idx = np.nonzero(dense_flat)[0].astype(np.int64)
    return idx, dense_flat[idx]


def _oracle(idx, val, rows):
    out = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
    out[idx] = val
    return out.reshape(rows, SHARD_WORDS)


def _roundtrip(idx, val, rows):
    """pack -> host unpack, pack -> device decode AND pack -> every row
    taken alone (and the row behind the last), all against the dense
    oracle."""
    import jax.numpy as jnp
    p = pack_words(idx, val)
    want = _oracle(idx, val, rows)
    np.testing.assert_array_equal(unpack_packed(p, rows), want)
    # the decoded mirror is the device's word tile; the host flattens it
    got = np.asarray(upload_decode(p, rows))
    assert got.shape == (rows,) + WORD_TILE
    np.testing.assert_array_equal(from_tile(got), want)
    arrs = [jnp.asarray(a) for a in pad_packed(p)]
    take = jax.jit(lambda *a: containers.decode_row(
        *a[:-1], a[-1], rows=rows, a_bucket=stream_bucket(p.a_len),
        r_bucket=pow2_bucket(p.r_max),
        has_array=containers.row_has_entries(a[5], a[-1])))
    for r in range(rows + 1):
        np.testing.assert_array_equal(
            np.asarray(take(*arrs, jnp.int32(r))),
            want[r] if r < rows else 0)
    return p


# -- codec round-trip at boundary cardinalities -----------------------------

def test_empty_roundtrip():
    p = _roundtrip(np.zeros(0, np.int64), np.zeros(0, np.uint32), 2)
    assert p.keys.size == 0 and p.nbytes == 0


def test_array_bitmap_threshold():
    """Exactly ARRAY_WORDS_MAX scattered words stay an array container;
    one more flips to bitmap (every-other-word spacing defeats the run
    form on both sides of the boundary)."""
    rows = 1
    for n, want_type in ((ARRAY_WORDS_MAX, TYPE_ARRAY),
                         (ARRAY_WORDS_MAX + 1, TYPE_BITMAP)):
        flat = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
        flat[np.arange(n) * 2] = 7
        idx, val = _store(flat)
        p = _roundtrip(idx, val, rows)
        assert int(p.types[0]) == want_type, n


def test_full_container_run():
    """A fully-set container is one run — the maximal-run boundary —
    and a full shard row packs to runs, not bitmaps."""
    rows = 1
    flat = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
    flat[:CONTAINER_WORDS] = 0xFFFFFFFF
    p = _roundtrip(*_store(flat), rows)
    assert int(p.types[0]) == TYPE_RUN and int(p.counts[0]) == 1
    flat[:] = 0xFFFFFFFF  # full row: every container one run
    p = _roundtrip(*_store(flat), rows)
    assert set(p.types.tolist()) == {TYPE_RUN}
    assert p.nbytes < rows * SHARD_WORDS * 4 // 100  # >100x on full rows


def test_run_max_boundary():
    """RUN_MAX two-word bit-runs keep the run form (2 payload words per
    run undercut the array's 2 per word); past RUN_MAX the container
    falls back (here: array — the words stay sparse)."""
    rows = 1
    for n_runs, want_type in ((RUN_MAX, TYPE_RUN),
                              (RUN_MAX + 1, TYPE_ARRAY)):
        flat = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
        # two full words per run, a zero word between runs
        starts = np.arange(n_runs) * 3
        flat[starts] = 0xFFFFFFFF
        flat[starts + 1] = 0xFFFFFFFF
        idx, val = _store(flat)
        p = _roundtrip(idx, val, rows)
        assert int(p.types[0]) == want_type, n_runs
        if want_type == TYPE_RUN:
            assert int(p.counts[0]) == n_runs


def test_mixed_forms_roundtrip(rng):
    """One fragment mixing all three forms + empty containers between."""
    rows = 4
    flat = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
    flat[rng.choice(CONTAINER_WORDS, 40, replace=False)] = \
        rng.integers(1, 1 << 32, size=40, dtype=np.uint32)   # array
    flat[2 * CONTAINER_WORDS: 3 * CONTAINER_WORDS] = \
        rng.integers(1, 1 << 32, size=CONTAINER_WORDS,
                     dtype=np.uint32)                         # bitmap
    flat[5 * CONTAINER_WORDS: 6 * CONTAINER_WORDS] = 0xFFFFFFFF  # run
    # partial-word run straddling a container boundary
    s = 9 * CONTAINER_WORDS * 32 + 13
    for b in range(s, s + 200):
        flat[b // 32] |= np.uint32(1) << (b % 32)
    p = _roundtrip(*_store(flat), rows)
    h = p.type_histogram()
    assert h["array"] >= 1 and h["bitmap"] >= 1 and h["run"] >= 1


def test_random_stores_roundtrip(rng):
    """Randomized corpora: sparse scatter, clustered ranges, and dense
    blocks, each packed and decoded back to the oracle."""
    rows = 3
    total = rows * SHARD_WORDS
    for _ in range(5):
        flat = np.zeros(total, dtype=np.uint32)
        n = int(rng.integers(0, 3000))
        flat[rng.choice(total, n, replace=False)] = rng.integers(
            1, 1 << 32, size=n, dtype=np.uint32)
        a = int(rng.integers(0, total - 500))
        flat[a: a + int(rng.integers(0, 500))] = 0xFFFFFFFF
        _roundtrip(*_store(flat), rows)


def test_estimate_upper_bounds_packed(rng):
    """estimate_packed_bytes (the no-pack heuristic input) never
    undercounts the real packed stream."""
    rows = 2
    total = rows * SHARD_WORDS
    for n in (0, 1, 100, 5000, 40000):
        flat = np.zeros(total, dtype=np.uint32)
        flat[rng.choice(total, n, replace=False)] = 1
        idx, val = _store(flat)
        assert containers.estimate_packed_bytes(idx) >= \
            pack_words(idx, val).nbytes


def test_decode_bucket_padding(rng):
    """pad_packed's pow2-bucket padding (key/type -1 rows, zero payload
    tail) decodes identically to the exact stream."""
    rows = 2
    flat = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
    flat[rng.choice(3 * CONTAINER_WORDS, 90, replace=False)] = 5
    idx, val = _store(flat)
    p = pack_words(idx, val)
    import jax.numpy as jnp
    padded = [jnp.asarray(a) for a in pad_packed(p)]
    assert padded[0].size == pow2_bucket(p.keys.size)
    assert padded[5].shape == (8, stream_bucket(p.a_len))
    got = np.asarray(containers.decode_block(
        *padded, rows=rows, a_bucket=stream_bucket(p.a_len),
        r_bucket=pow2_bucket(p.r_max)))
    np.testing.assert_array_equal(got, _oracle(idx, val, rows))


def test_class_streams_layout(rng):
    """An array entry lies in the stream of its word's sublane in the
    device's word tile, every stream ascends (padding included), and
    the streams hold exactly the array containers' words."""
    rows = 3
    flat = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
    flat[rng.choice(flat.size, 7000, replace=False)] = rng.integers(
        1, 1 << 32, 7000, dtype=np.uint64).astype(np.uint32)
    flat[SHARD_WORDS: SHARD_WORDS + 2 * CONTAINER_WORDS] |= 1   # bitmaps
    idx, val = _store(flat)
    p = pack_words(idx, val)
    assert p.a_len % containers.ARRAY_LANES == 0
    assert p.a_idx.shape == p.a_val.shape == (8, p.a_len)
    live = p.a_idx != containers.ARRAY_PAD
    for c in range(8):
        assert ((p.a_idx[c][live[c]] >> 7) & 7 == c).all()
        assert (np.diff(p.a_idx[c].astype(np.int64)) >= 0).all()
    in_array = ~np.isin(idx // CONTAINER_WORDS,
                        p.keys[p.types != TYPE_ARRAY])
    assert int(live.sum()) == int(in_array.sum()) == p.array_words
    np.testing.assert_array_equal(np.sort(p.a_idx[live]), idx[in_array])


def test_scatter_targets_ascend(rng):
    """The decoders' one scatter tells the compiler its indices ascend
    and are unique (a TPU otherwise takes the updates one by one; a CPU
    ignores the hint, so the claim is checked here): over whole streams,
    and over a row's slices, those moved back at a stream's end too."""
    import jax.numpy as jnp
    rows = 5
    flat = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
    flat[rng.choice(flat.size, 9000, replace=False)] = 1
    flat[4 * SHARD_WORDS + 100: 4 * SHARD_WORDS + 900: 3] = 1
    p = pack_words(*_store(flat))
    a_idx = jnp.asarray(pad_packed(p)[5])
    n = a_idx.shape[1]

    def ascends(idx, first, k):
        t, span, plane = containers._stream_targets(idx, first, k,
                                                    SHARD_WORDS)
        t = np.asarray(t).astype(np.int64).reshape(-1)
        assert t.min() >= 0 and t.max() < 8 * span
        assert (np.diff(t) > 0).all()

    ascends(a_idx, 0, rows)
    w = 256
    for r in range(rows + 1):
        below = np.asarray(a_idx) < r * SHARD_WORDS
        start = np.minimum(below.sum(axis=1), n - w)    # dynamic_slice's
        ascends(jnp.stack([a_idx[c, s: s + w]
                           for c, s in enumerate(start)]), r, 1)


def test_decode_row_scatters_only_when_told(rng):
    """``row_has_entries`` is true exactly for the rows that hold array
    entries in some fragment of the block, and a row take told there
    are none leaves them out: the bitmap containers alone."""
    import jax.numpy as jnp
    rows = 4
    flat = np.zeros(rows * SHARD_WORDS, dtype=np.uint32)
    flat[: SHARD_WORDS] = 3                            # row 0: bitmaps
    at = SHARD_WORDS + rng.choice(SHARD_WORDS, 500, replace=False)
    flat[at] = 9                                       # row 1: arrays
    flat[3 * SHARD_WORDS: 3 * SHARD_WORDS + CONTAINER_WORDS] = 5
    flat[3 * SHARD_WORDS + CONTAINER_WORDS + 17] = 1   # row 3: both
    idx, val = _store(flat)
    p = pack_words(idx, val)
    arrs = [jnp.asarray(a) for a in pad_packed(p)]
    block = jnp.stack([arrs[5], jnp.full_like(arrs[5],
                                              containers.ARRAY_PAD)])
    has = [bool(containers.row_has_entries(block, r)) for r in range(rows)]
    assert has == [False, True, False, True]
    want = _oracle(idx, val, rows)
    kw = dict(rows=rows, a_bucket=stream_bucket(p.a_len), r_bucket=0)
    for r in range(rows):
        bare = np.asarray(containers.decode_row(
            *arrs, r, has_array=jnp.bool_(False), **kw))
        full = np.asarray(containers.decode_row(*arrs, r, **kw))
        np.testing.assert_array_equal(full, want[r])
        assert (bare == want[r]).all() == (not has[r])


# -- density heuristic / fragment forms -------------------------------------

def test_device_form_heuristic():
    # 8 rows, 1 MiB dense: a limit the dense form does not fit
    budget = DeviceBudget(limit_bytes=1 << 19)
    f = Fragment(None, "i", "f", "standard", 0, budget=budget)
    f.bulk_import(np.arange(8), np.arange(8) * 1000)
    assert budget.dense_demand == f._cap_rows * SHARD_WORDS * 4
    assert f.device_form() == "compressed"
    assert f.packed_host().nbytes == f.device_nbytes()
    assert f.device_nbytes() < f._cap_rows * SHARD_WORDS * 4
    # no limit, or one the dense set fits (padding's eighth included):
    # the dense mirror pays no decode -> dense
    budget.limit_bytes = None
    assert f.device_form() == "dense"
    budget.limit_bytes = 64 << 20
    assert f.device_form() == "dense"
    budget.limit_bytes = budget.dense_demand
    assert f.device_form() == "compressed"
    budget.limit_bytes = 1 << 19
    # kill switch
    old = fragment.COMPRESSED_RESIDENT
    try:
        fragment.COMPRESSED_RESIDENT = False
        assert f.device_form() == "dense"
    finally:
        fragment.COMPRESSED_RESIDENT = old


def test_dense_data_stays_dense(rng):
    """A fragment dense enough that packing wins nothing must fall back
    to the dense form (all-bitmap streams are ~1x 'compression'): every
    cap row filled with random words — no zero words to drop, no runs."""
    budget = DeviceBudget(limit_bytes=1 << 16)  # no dense form fits it
    f = Fragment(None, "i", "f", "standard", 0, budget=budget)
    f.set_bit(0, 0)
    for row in range(f._cap_rows):
        f.set_row(row, rng.integers(1, 1 << 32, size=SHARD_WORDS,
                                    dtype=np.uint32))
    assert f.device_form() == "dense"
    assert f.device_sig() == (f.n_rows,) + WORD_TILE


def test_compressed_device_mirror_equals_dense():
    """Fragment.device()'s compressed upload path (ship packed, decode
    on device) produces the same mirror bytes as the dense upload."""
    budget = DeviceBudget(limit_bytes=1 << 19)  # under the 1 MiB dense form
    f = Fragment(None, "i", "f", "standard", 0, budget=budget)
    rng = np.random.default_rng(7)
    f.bulk_import(rng.integers(0, 6, 4000), rng.integers(0, SHARD_WIDTH, 4000))
    assert f.device_form() == "compressed"
    got = np.asarray(f.device())
    assert got.shape == (f.n_rows,) + WORD_TILE
    np.testing.assert_array_equal(from_tile(got), f.to_dense())


# -- differential: compressed-resident vs dense-resident --------------------

@pytest.fixture(scope="module")
def corpus():
    """16-shard index mixing sparse scatter (a, b), run-heavy clustered
    ranges (a row 11), BSI values (v), an emptied fragment (b row 5 set
    then cleared in shard 3), and existence — wide enough that the
    8-virtual-device mesh slices it under a tight budget."""
    rng = np.random.default_rng(99)
    h = Holder(None)
    idx = h.create_index("c")
    a = idx.create_field("a")
    b = idx.create_field("b")
    v = idx.create_field("v", FieldOptions(type="int", min=-500, max=500))
    n = 40_000
    cols = rng.integers(0, 16 * SHARD_WIDTH, size=n)
    a.import_bits(rng.integers(0, 10, size=n), cols)
    b.import_bits(rng.integers(0, 6, size=n), cols)
    # run-heavy: clustered contiguous ranges across every shard
    run_cols = np.concatenate([
        np.arange(s * SHARD_WIDTH + 1000, s * SHARD_WIDTH + 40_000)
        for s in range(16)])
    a.import_bits(np.full(run_cols.size, 11), run_cols)
    vcols = np.unique(cols[: n // 2])
    v.import_values(vcols, rng.integers(-500, 500, size=vcols.size))
    idx.add_existence(np.unique(np.concatenate([cols, run_cols])))
    # emptied fragment: set bits then clear them (empty packed stream)
    ecols = np.arange(3 * SHARD_WIDTH + 50, 3 * SHARD_WIDTH + 80)
    b.import_bits(np.full(30, 5), ecols)
    b.import_bits(np.full(30, 5), ecols, clear=True)
    return h


def _run_corpus(ex, queries):
    return [_norm(r) for q in queries for r in ex.execute("c", q)]


def test_compressed_differential(corpus):
    """The randomized corpus (plus run-heavy TopN and the emptied
    fragment's row) is byte-identical across dense-resident, compressed-
    resident, and compressed-under-eviction-pressure runs."""
    qrng = np.random.default_rng(1234)
    queries = [gen_query(qrng) for _ in range(4)]
    queries += ["TopN(a, n=3)", "Count(Row(a=11))", "Row(b=5)",
                "Count(Intersect(Row(a=11), Row(b=2)))"]
    ex = Executor(corpus, use_mesh=True)
    old = DEFAULT_BUDGET.limit_bytes
    try:
        # reference: dense-resident (compression never engages with no
        # budget limit)
        DEFAULT_BUDGET.limit_bytes = None
        want = _run_corpus(ex, queries)

        # compressed-resident: the largest budget the dense set does not
        # fit, ample for
        # the packed streams, so everything stays resident
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(corpus)
        DEFAULT_BUDGET.shrink_to_limit()
        assert _run_corpus(ex, queries) == want
        st = DEFAULT_BUDGET.stats()
        assert st["compressedBytes"] > 0, \
            "no packed stream ever registered: the differential " \
            "exercised only the dense path"
        assert st["compressedBytes"] < 16 * 16 * SHARD_WORDS * 4

        # tight budget (1 MiB over the mesh; the limit is one device's):
        # eviction + re-staging of packed stacks
        DEFAULT_BUDGET.limit_bytes = (1 << 20) // jax.device_count()
        DEFAULT_BUDGET.shrink_to_limit()
        ev0 = DEFAULT_BUDGET.evictions
        assert _run_corpus(ex, queries) == want
        assert DEFAULT_BUDGET.evictions > ev0, \
            "budget never evicted: pressure leg exercised nothing"
        assert DEFAULT_BUDGET.stats()["pinnedBytes"] == 0
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()


def test_retrace_keeps_layout(corpus):
    """Regression: re-tracing a cached executable at a new stacked group
    size must keep the layout it was compiled with.  Mixed-bucket
    fragments (some with run containers, some without) queried at
    growing then shrinking subset sizes force re-traces; a re-trace that
    read another group's layout decodes with the wrong container buckets
    (r_bucket=0 silently drops every run container — the a=11 run rows
    here)."""
    ex = Executor(corpus, use_mesh=True)
    old = DEFAULT_BUDGET.limit_bytes
    q = "Count(Intersect(Row(a=11), Row(a=2)))"
    try:
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(corpus)
        want = {}
        for size in (16, 2, 9, 16, 1):
            sl = list(range(size))
            got = ex.execute("c", q, shards=sl)[0]
            if size in want:
                assert got == want[size], \
                    f"subset {size} diverged after re-trace"
            want[size] = got
        # the full-size answer must match the sum of disjoint halves
        lo = ex.execute("c", q, shards=list(range(8)))[0]
        hi = ex.execute("c", q, shards=list(range(8, 16)))[0]
        assert want[16] == lo + hi
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()


def test_compressed_stats_surface(corpus):
    """Holder.container_stats counts forms without packing on demand,
    and sees all three container types on the mixed corpus once packs
    exist."""
    st0 = Holder(None).container_stats()
    assert st0 == {"array": 0, "bitmap": 0, "run": 0,
                   "compressedFragments": 0, "denseFragments": 0}
    ex = Executor(corpus, use_mesh=True)
    old = DEFAULT_BUDGET.limit_bytes
    try:
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(corpus)
        ex.execute("c", "Count(Union(Row(a=1), Row(a=11)))")
        st = corpus.container_stats()
        assert st["compressedFragments"] > 0
        assert st["array"] > 0 and st["run"] > 0
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()
