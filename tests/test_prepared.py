"""Differential tests for the prepared-statement cache (executor/prepared).

Every test drives the SAME query text through (a) a mesh executor whose
prepared cache serves repeats and (b) a fresh classic executor with the
cache disabled, asserting identical results — the analog of the kernel
suite's numpy-oracle differential strategy (SURVEY.md §5.2), applied to
the statement-cache layer where a stale or mis-guarded replay would be a
silent wrong answer.
"""

import numpy as np
import pytest

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.prepared import fingerprint
from pilosa_tpu.storage import FieldOptions, Holder


@pytest.fixture(scope="module")
def holder():
    rng = np.random.default_rng(3)
    h = Holder(None)
    idx = h.create_index("prep", track_existence=True)
    f = idx.create_field("f")
    n = 20_000
    rows = rng.integers(0, 16, size=n)
    cols = rng.integers(0, 2 * SHARD_WIDTH, size=n)
    f.import_bits(rows, cols)
    idx.add_existence(cols)
    v = idx.create_field("v", FieldOptions(type="int", min=-500, max=500))
    vcols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, size=5000))
    vvals = rng.integers(-500, 501, size=vcols.size)
    v.import_values(vcols, vvals)
    idx.add_existence(vcols)
    # second int field with a different base offset (min) — multi-group
    # Sum queries must keep each group's base (late-binding regression)
    w = idx.create_field("w", FieldOptions(type="int", min=1000, max=2000))
    w.import_values(vcols, rng.integers(1000, 2001, size=vcols.size))
    return h


@pytest.fixture(scope="module")
def cached(holder):
    return Executor(holder, use_mesh=True)


@pytest.fixture()
def classic(holder):
    ex = Executor(holder, use_mesh=True)
    ex.prepared = None  # same mesh engine, no statement cache
    return ex


def test_fingerprint_literals():
    from pilosa_tpu.executor.prepared import fingerprint_spans
    q = "Count(Row(f=14)) Row(v > -3) TopN(f, n=50, ids=[1,2])"
    t, vals = fingerprint(q)
    assert t == "Count(Row(f=?)) Row(v > ?) TopN(f, n=?, ids=[?,?])"
    assert vals == [14, -3, 50, 1, 2]
    assert len(fingerprint_spans(q)) == 5


def test_fingerprint_preserves_strings_timestamps_and_words():
    q = ("Row(f=7, from='2017-01-01T00:00', to=2018-06-02T11:30) "
         "Set('k9', f=3) Count(Row(g1=1a2b)) Row(x=1.5)")
    t, vals = fingerprint(q)
    assert "'2017-01-01T00:00'" in t
    assert "2018-06-02T11:30" in t
    assert "'k9'" in t
    assert "1a2b" in t
    assert "1.5" in t
    assert vals == [7, 3]


def _check(cached, classic, queries):
    """Same template, varying literals: first query populates the cache,
    the rest replay it; classic executor must agree on every one."""
    for q in queries:
        assert cached.execute("prep", q) == classic.execute("prep", q), q


def test_count_row_replay(cached, classic):
    _check(cached, classic,
           [f"Count(Row(f={r}))" for r in (1, 5, 0, 15, 9, 400)])
    assert cached.prepared.hits > 0


def test_multi_call_batch_replay(cached, classic):
    rng = np.random.default_rng(11)
    qs = []
    for _ in range(3):
        rows = rng.integers(0, 16, size=8)
        qs.append(" ".join(
            f"Count(Intersect(Row(f={a}), Row(f={b})))"
            for a, b in zip(rows[::2], rows[1::2])))
    _check(cached, classic, qs)


def test_bsi_regime_guards(cached, classic):
    # values crossing every _resolve_bsi branch: normal, clamp, fast-path
    # notnull, out-of-range empty, sign flip, zero
    vals = [5, -5, 0, 499, 500, 501, -499, -500, -501, 1000, -1000,
            2000, 100000]
    _check(cached, classic, [f"Count(Row(v > {x}))" for x in vals])
    _check(cached, classic, [f"Count(Row(v < {x}))" for x in vals])
    _check(cached, classic, [f"Count(Row(v == {x}))" for x in vals])
    _check(cached, classic, [f"Count(Row(v != {x}))" for x in vals])
    _check(cached, classic, [f"Count(Row(v >= {x}))" for x in vals])
    _check(cached, classic, [f"Count(Row(v <= {x}))" for x in vals])


def test_between_guards(cached, classic):
    pairs = [(0, 10), (-10, 10), (-500, 500), (-501, 501), (-2000, -600),
             (600, 2000), (5, 5), (490, 510), (-510, -490)]
    _check(cached, classic,
           [f"Count(Row({lo} <= v <= {hi}))" for lo, hi in pairs])
    _check(cached, classic,
           [f"Count(Row({lo} < v < {hi}))" for lo, hi in pairs])


def test_sum_and_topn_replay(cached, classic):
    _check(cached, classic,
           [f"Sum(Row(v > {x}), field=v)" for x in (0, 100, -100, 499)])
    _check(cached, classic,
           [f"TopN(f, Row(v > {x}), n=5)" for x in (0, 50, -50)])
    # structural literal (n) change -> equality guard miss -> still correct
    _check(cached, classic, ["TopN(f, Row(v > 10), n=3)"])


def test_row_id_beyond_capacity(cached, classic):
    _check(cached, classic, ["Count(Row(f=2))", "Count(Row(f=500000))"])


def test_epoch_invalidation(cached, classic, holder):
    q = "Count(Row(f=3))"
    assert cached.execute("prep", q) == classic.execute("prep", q)
    # DDL bumps the schema epoch; the entry must not be replayed stale
    holder.index("prep").create_field("tmp_epoch")
    holder.index("prep").delete_field("tmp_epoch")
    assert cached.execute("prep", q) == classic.execute("prep", q)


def test_writes_not_cached(cached, holder):
    q = "Set(999999, f=2)"
    cached.execute("prep", q)
    assert (("prep", fingerprint(q)[0]) not in
            [k for k, v in cached.prepared._entries.items()
             if not isinstance(v, str)])
    # the write actually landed and reads observe it
    assert cached.execute("prep", "Count(Row(f=2))")[0] == \
        cached.execute("prep", "Count(Row(f = 2))")[0]
    holder.field("prep", "f").clear_bit(2, 999999)


def test_mutation_invalidates_results_not_plan(cached, classic, holder):
    """A Set between two identical-template queries must be visible —
    the plan replays but the data path re-reads the fragments."""
    q = "Count(Row(f=6))"
    before = cached.execute("prep", q)[0]
    col = 3 * SHARD_WIDTH - 7  # within existing shards
    changed = holder.field("prep", "f").set_bit(6, col)
    after = cached.execute("prep", q)[0]
    assert after == before + (1 if changed else 0)
    assert cached.execute("prep", q) == classic.execute("prep", q)
    holder.field("prep", "f").clear_bit(6, col)


def test_conditional_both_bounds_dynamic(cached, classic):
    qs = ["Count(Row(4 <= v < 9))", "Count(Row(-3 <= v < 100))",
          "Count(Row(0 <= v < 1))"]
    _check(cached, classic, qs)


def test_chunked_batch_dispatch(holder, classic, monkeypatch):
    """A batch larger than the dispatch chunk must split into multiple
    padded power-of-two dispatches (bounding per-dispatch HBM gather
    temps) and still return per-call-exact results, on both the prepared
    and the classic grouped paths."""
    from pilosa_tpu.parallel import nodes

    # shrink the temp budget so chunking kicks in at tiny B: with P=2 and
    # 2 shards over the 8-device test mesh (1 stacked shard per device),
    # chunk = budget / (2*1*SHARD_WORDS*4) = 16 rows per dispatch
    monkeypatch.setattr(nodes, "BATCH_TEMP_BYTES", 2 * 2 * 32768 * 4 * 8)

    rng = np.random.default_rng(11)
    pairs = [(int(a), int(b))
             for a, b in zip(rng.integers(0, 16, size=21),
                             rng.integers(0, 16, size=21))]
    q = " ".join(f"Count(Intersect(Row(f={a}), Row(f={b})))"
                 for a, b in pairs)

    ex = Executor(holder, use_mesh=True)  # fresh prepared cache
    build = ex.execute("prep", q)          # miss -> prepare -> chunked run
    hit = ex.execute("prep", q)            # prepared-hit chunked run
    grouped = classic.execute("prep", q)   # classic grouped chunked run
    percall = [classic.execute("prep",
                               f"Count(Intersect(Row(f={a}), Row(f={b})))")[0]
               for a, b in pairs]
    assert build == hit == grouped == percall
    ex.close()


def test_batch_chunks_padding():
    from pilosa_tpu.executor.executor import _batch_chunks

    mat = np.arange(42, dtype=np.int64).reshape(21, 2)
    chunks = list(_batch_chunks(mat, n_shards=1))
    # default budget: no split at this size, padded to 32
    assert [(lo, n) for lo, n, _ in chunks] == [(0, 21)]
    assert chunks[0][2].shape == (32, 2)
    # padding repeats the last real row (always in-range row ids)
    assert (chunks[0][2][21:] == mat[20]).all()


def test_multi_group_sum_bases(cached, classic):
    """Two Sum groups with different base offsets in ONE query: each
    group's finalizer must use its own base (a free-variable _sum_fin
    late-bound across groups once computed every group with the last
    group's base)."""
    _check(cached, classic,
           ["Sum(Row(f=1), field=v) Sum(Row(f=2), field=v)"
            " Sum(Row(f=1), field=w) Sum(Row(f=2), field=w)",
            "Sum(Row(f=3), field=v) Sum(Row(f=4), field=v)"
            " Sum(Row(f=3), field=w) Sum(Row(f=4), field=w)"])


def test_topn_per_call_n_and_ids(cached, classic):
    """TopN calls sharing one group (same field, same filter shape) but
    different n / ids must keep their own values on the prepared path —
    the group key omits n/ids."""
    _check(cached, classic,
           ["TopN(f, n=2) TopN(f, n=5)",
            "TopN(f, n=3) TopN(f, n=7)",
            "TopN(f, ids=[1,2], n=0) TopN(f, ids=[3], n=0)"])
    # and sanity: the two calls really do return different lengths
    out = cached.execute("prep", "TopN(f, n=2) TopN(f, n=5)")
    assert len(out[0]) == 2 and len(out[1]) == 5
