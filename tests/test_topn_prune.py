"""A filtered TopN stops at the n-th count (ISSUE 36, parallel/nodes.py
``topn_walk``, docs/whole-query.md "The TopN walk").

The rule under test: a ``row_counts`` node that answers a top-n question
walks its primary's rows in blocks, in descending order of each block's
largest unfiltered total, and reads no block whose largest total is
below the n-th largest filtered count found so far.  The answer is the
full pass's and the numpy reference's, ties included; everything that
is not a top-n question, and every launch that does not reduce all of
its shards in one program over one dense shape group, takes the full
pass, and ``topnPrune.*`` says which happened."""

import threading

import jax
import numpy as np
import pytest

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.ingest.committer import GroupCommitter
from pilosa_tpu.parallel import default_mesh, nodes
from pilosa_tpu.parallel import wholequery as wholequery_mod
from pilosa_tpu.server.handler import serialize_result
from pilosa_tpu.storage import Holder, fragment
from pilosa_tpu.storage.membudget import DEFAULT_BUDGET

from test_observability import _req, make_server

N_SHARDS = 3
ROWS = 40               # of field d that hold bits
STACKED = 64            # rows of d's fragments (``_cap_rows`` of 40):
#                         eight blocks of TOPN_BLOCK_ROWS, three of them empty
POOL = 1500             # columns a shard that any row may hold
JOIN_S = 60


class Ref:
    """The numpy reference: the set columns of every (field, row)."""

    def __init__(self):
        self.bits: dict[tuple, np.ndarray] = {}

    def set(self, field, row, cols):
        key = (field, int(row))
        self.bits[key] = np.union1d(
            self.bits.get(key, np.zeros(0, dtype=np.int64)),
            np.asarray(cols, dtype=np.int64))

    def clear(self, field, row, cols):
        self.bits[(field, row)] = np.setdiff1d(self.bits[(field, row)], cols)

    def cols(self, field, row):
        return self.bits.get((field, row), np.zeros(0, dtype=np.int64))

    def filter(self, g_rows):
        out = self.cols("g", g_rows[0])
        for r in g_rows[1:]:
            out = np.intersect1d(out, self.cols("g", r))
        return out

    def counts(self, g_rows=None, field="d"):
        """{row: count under the filter} of a field, zeros left out."""
        filt = None if g_rows is None else self.filter(g_rows)
        out = {}
        for (fld, r), v in self.bits.items():
            if fld == field:
                c = v.size if filt is None else np.intersect1d(v, filt).size
                if c:
                    out[r] = int(c)
        return out

    def topn(self, g_rows, n, ids=None):
        counts = self.counts(g_rows)
        if ids is not None:
            counts = {r: c for r, c in counts.items() if r in ids}
        ranked = sorted(counts.items(), key=lambda rc: (-rc[1], rc[0]))
        return [{"id": r, "count": c} for r, c in ranked[: n or None]]

    def totals(self, rows=STACKED, field="d"):
        return np.asarray([self.cols(field, r).size for r in range(rows)])


def pool_cols(rng):
    """Column ids any row may hold: POOL a shard."""
    return np.concatenate([
        s * SHARD_WIDTH + rng.choice(SHARD_WIDTH, POOL, replace=False)
        for s in range(N_SHARDS)])


def row_sizes(kind, rng):
    """Bits of each of d's ROWS rows: geometric by row id, near-equal,
    or the geometric sizes dealt to the ids at random (rank order is
    not id order, and a block's rows are far apart in rank)."""
    geo = np.maximum((3000 * 0.72 ** np.arange(ROWS)).astype(int), 1)
    if kind == "geometric":
        return geo
    if kind == "uniform":
        return np.full(ROWS, 900) + rng.integers(0, 40, ROWS)
    return rng.permutation(geo)


def build(kind="geometric", seed=36, sizes=None):
    """Holder with index p: d (ROWS rows of ``sizes`` or ``kind``) and
    g (1: ~half the pool, 2: ~a twentieth, 3: ~a hundredth, 4: one
    column a shard; no row 9), every fragment of a field as wide as the
    others (one shape group)."""
    rng = np.random.default_rng(seed)
    pool = pool_cols(rng)
    h, ref = Holder(None), Ref()
    idx = h.create_index("p", track_existence=False)
    d, g = idx.create_field("d"), idx.create_field("g")
    sizes = row_sizes(kind, rng) if sizes is None else sizes
    for r, size in enumerate(sizes):
        cols = rng.choice(pool, min(int(size), pool.size), replace=False)
        if r == ROWS - 1:   # the widest row in every shard: one shape
            cols = np.union1d(cols, pool[::POOL][:N_SHARDS])
        ref.set("d", r, cols)
    for r, share in ((1, 0.5), (2, 0.05), (3, 0.01)):
        ref.set("g", r, pool[rng.random(pool.size) < share])
    ref.set("g", 4, pool[::POOL][:N_SHARDS])
    for (fld, r), cols in ref.bits.items():
        (d if fld == "d" else g).import_bits(
            np.full(cols.size, r, dtype=np.int64), cols)
    assert {fr.n_rows for *_x, fr in h.iter_fragments("p")
            if _x[1] == "d"} == {STACKED}
    return h, idx, ref


def prune_vars(ex):
    m = ex.mesh_exec
    return {"queries": m.topn_queries, "rowsVisited": m.topn_rows_visited,
            "rowsStacked": m.topn_rows_stacked, "fullScans": m.topn_full_scans}


def delta(after, before):
    return {k: after[k] - before[k] for k in after}


def answer(ex, q):
    return serialize_result(ex.execute("p", q))


def reckon_visited(totals, counts, n):
    """Rows the walk visits, reckoned from the unfiltered ``totals`` and
    the filtered ``counts`` (dense, by row id) with numpy alone."""
    rows = totals.size
    k = nodes.walk_block_rows(rows)
    starts = np.minimum(np.arange(-(-rows // k)) * k, rows - k)
    bounds = np.asarray([totals[s:s + k].max() for s in starts])
    order = np.argsort(-bounds, kind="stable")
    seen = np.zeros(rows, dtype=np.int64)
    visited = 0
    for j in order:
        t = np.sort(seen)[::-1][n - 1] if n <= rows else 0
        if bounds[j] < max(t, 1):
            break
        seen[starts[j]:starts[j] + k] = counts[starts[j]:starts[j] + k]
        visited += 1
    return min(visited * k, rows)


FILTERS = {"half": ("Row(g=1)", [1]), "sparse": ("Row(g=3)", [3]),
           "and": ("Intersect(Row(g=1), Row(g=2))", [1, 2])}


# -- the answer: pruned = full pass = reference ----------------------------

@pytest.fixture(scope="module", params=["geometric", "uniform", "shuffled"])
def field(request):
    h, idx, ref = build(request.param)
    walk = Executor(h, use_mesh=True)
    full = Executor(h, use_mesh=True, whole_query=False)
    yield request.param, ref, walk, full
    walk.close()
    full.close()
    h.close()


@pytest.mark.parametrize("n", [1, 3, 10, 100])
@pytest.mark.parametrize("filt", list(FILTERS))
def test_pruned_full_and_reference_agree(field, filt, n):
    kind, ref, walk, full = field
    pql, g_rows = FILTERS[filt]
    q = f"TopN(d, {pql}, n={n})"
    w0, f0 = prune_vars(walk), prune_vars(full)
    want = [ref.topn(g_rows, n)]
    assert answer(walk, q) == want
    assert answer(full, q) == want
    dw, df = delta(prune_vars(walk), w0), delta(prune_vars(full), f0)
    counts = np.zeros(STACKED, dtype=np.int64)
    for r, c in ref.counts(g_rows).items():
        counts[r] = c
    assert dw == {"queries": 1, "rowsStacked": STACKED, "fullScans": 0,
                  "rowsVisited": reckon_visited(ref.totals(), counts, n)}
    # the per-stage path reads every row and says so
    assert df == {"queries": 0, "rowsVisited": 0, "rowsStacked": 0,
                  "fullScans": 1}
    if kind == "geometric" and n <= 10 and filt == "half":
        assert dw["rowsVisited"] <= ROWS // 2   # the walk did stop


def test_served_path(tmp_path):
    """Over HTTP: the answer, and ``/debug/vars`` ``topnPrune``."""
    srv = make_server(tmp_path)
    try:
        port = srv.port
        _req(port, "POST", "/index/p", {})
        for fld in ("d", "g"):
            _req(port, "POST", f"/index/p/field/{fld}", {})
        _h, _idx, ref = build()
        _h.close()
        idx = srv.holder.index("p")
        for (fld, r), cols in ref.bits.items():
            idx.field(fld).import_bits(
                np.full(cols.size, r, dtype=np.int64), cols)
        before = _req(port, "GET", "/debug/vars")[0]["topnPrune"]
        for n, g in ((10, 1), (4, 2), (10, 1)):
            out, _ = _req(port, "POST", "/index/p/query",
                          f"TopN(d, Row(g={g}), n={n})")
            assert out["results"] == [ref.topn([g], n)]
        out, _ = _req(port, "POST", "/index/p/query",
                      "TopN(d, Row(g=1), n=0)")
        assert out["results"] == [ref.topn([1], 0)]
        after = _req(port, "GET", "/debug/vars")[0]["topnPrune"]
        d = delta(after, before)
        assert d["queries"] == 3 and d["fullScans"] == 0
        assert d["rowsStacked"] == 3 * STACKED
        assert 0 < d["rowsVisited"] < d["rowsStacked"]
    finally:
        srv.close()


# -- ties, short answers, empty filters --------------------------------------

def test_tie_with_a_total_equal_to_the_threshold():
    """Rows 17 and 20 (block 16..23, walked first) count 400 and 6 under
    the filter; row 3's TOTAL is 6, all of it under the filter, and no
    row of its block holds more: the block's bound equals the threshold
    of n = 2.  It must be read, and row 3 takes the second place from
    row 20 on the lower id."""
    pool = pool_cols(np.random.default_rng(36)).reshape(N_SHARDS, POOL)
    g1, rest = pool[:, :400].ravel(), pool[:, 400:].ravel()  # every shard
    h, ref = Holder(None), Ref()
    idx = h.create_index("p", track_existence=False)
    d, g = idx.create_field("d"), idx.create_field("g")
    rows = {r: rest[1000 + r:1001 + r] for r in range(ROWS)}   # one bit
    rows[17] = g1[:400]
    rows[20] = np.concatenate([g1[400:406], rest[:300]])
    rows[3] = g1[500:506]
    rows[ROWS - 1] = pool[:, -1]        # the widest row in every shard
    for r, cols in rows.items():
        ref.set("d", r, cols)
    ref.set("g", 1, g1)
    for (fld, r), cols in ref.bits.items():
        (d if fld == "d" else g).import_bits(
            np.full(cols.size, r, dtype=np.int64), cols)
    ex = Executor(h, use_mesh=True)
    try:
        totals = ref.totals()
        assert totals[3] == 6 and totals[:8].max() == 6   # the tie's bound
        assert ref.counts([1]) == {17: 400, 20: 6, 3: 6}
        v0 = prune_vars(ex)
        assert answer(ex, "TopN(d, Row(g=1), n=2)") == [
            [{"id": 17, "count": 400}, {"id": 3, "count": 6}]]
        d_ = delta(prune_vars(ex), v0)
        # block 16..23, then block 0..7 (bound 6 = t), and no other:
        # every other block's largest total is under 6
        assert d_ == {"queries": 1, "fullScans": 0, "rowsStacked": STACKED,
                      "rowsVisited": 2 * nodes.TOPN_BLOCK_ROWS}
    finally:
        ex.close()
        h.close()


def test_fewer_than_n_rows_and_empty_filters(field):
    _kind, ref, walk, full = field
    # g=4 holds a column a shard: fewer than n of d's rows are under it
    want = [ref.topn([4], 50)]
    assert 0 < len(want[0]) < ROWS
    assert answer(walk, "TopN(d, Row(g=4), n=50)") == want
    assert answer(full, "TopN(d, Row(g=4), n=50)") == want
    # no row 9 in g: an empty filter, an empty answer, from either path
    v0 = prune_vars(walk)
    assert answer(walk, "TopN(d, Row(g=9), n=10)") == [[]]
    assert answer(full, "TopN(d, Row(g=9), n=10)") == [[]]
    d = delta(prune_vars(walk), v0)
    assert d["queries"] == 1 and d["fullScans"] == 0


# -- what is not a top-n question takes the full pass ------------------------

NOT_TOPN = {
    "n=0": "TopN(d, Row(g=1), n=0)",
    "no-n": "TopN(d, Row(g=1))",
    "ids": "TopN(d, Row(g=1), n=3, ids=[1, 2, 30, 39])",
    "tanimoto": "TopN(d, Row(g=2), n=3, tanimotoThreshold=1)",
    "attr": 'TopN(d, Row(g=1), n=3, attrName="k", attrValues=["a"])',
    "no-filter": "TopN(d, n=3)",
    "rows": "Rows(d)",
    "minrow": "MinRow(field=d)",
    "maxrow": "MaxRow(field=d)",
}


@pytest.mark.parametrize("case", list(NOT_TOPN))
def test_not_a_topn_question_takes_the_full_pass(field, case):
    _kind, _ref, walk, full = field
    if case == "attr":
        walk.holder.field("p", "d").row_attrs.set_attrs(1, {"k": "a"})
    v0 = prune_vars(walk)
    got = answer(walk, NOT_TOPN[case])
    assert got == answer(full, NOT_TOPN[case])
    assert got != [[]] or case == "tanimoto"
    d = delta(prune_vars(walk), v0)
    assert d["queries"] == 0 and d["rowsVisited"] == 0
    assert d["fullScans"] == 0      # not a top-n question: not counted


# -- fused launches, meshes ---------------------------------------------------

def _together(ex, queries):
    out, errs = [None] * len(queries), [None] * len(queries)
    barrier = threading.Barrier(len(queries))

    def run(i):
        barrier.wait()
        try:
            out[i] = answer(ex, queries[i])
        except Exception as e:          # noqa: BLE001 — reported below
            errs[i] = e
    ts = [threading.Thread(target=run, args=(i,), daemon=True)
          for i in range(len(queries))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(JOIN_S)
    assert not any(t.is_alive() for t in ts), "a ticket's thread hangs"
    assert errs == [None] * len(queries), errs
    return out


@pytest.mark.parametrize("b", [2, 4])
def test_fused_launch_with_different_n_and_filters(b):
    """B tickets in one launch, each with its own n and filter: each
    row's threshold is its own, the walk goes on while any needs the
    next block, and every ticket gets its own exact answer."""
    h, idx, ref = build()
    ex = Executor(h, use_mesh=True, dispatch_batch=True,
                  dispatch_batch_max=b, dispatch_batch_window_us=20e6)
    try:
        asks = [(1, 1), (3, 12), (2, 3), (1, 25)][:b]
        warm = answer(ex, "TopN(d, Row(g=1), n=5)")
        assert warm == [ref.topn([1], 5)]
        fused0, v0 = ex.batcher.fused_launches, prune_vars(ex)
        got = _together(
            ex, [f"TopN(d, Row(g={g}), n={n})" for g, n in asks])
        assert got == [[ref.topn([g], n)] for g, n in asks]
        assert ex.batcher.fused_launches == fused0 + 1
        d = delta(prune_vars(ex), v0)
        assert d["queries"] == b and d["fullScans"] == 0
        # one figure a launch: the union's, the same for every ticket
        assert d["rowsVisited"] % b == 0
        counts = [np.zeros(STACKED, dtype=np.int64) for _ in asks]
        for c, (g, _n) in zip(counts, asks):
            for r, v in ref.counts([g]).items():
                c[r] = v
        assert d["rowsVisited"] // b == max(
            reckon_visited(ref.totals(), c, n)
            for c, (_g, n) in zip(counts, asks))
    finally:
        ex.close()
        h.close()


@pytest.mark.parametrize("calls", [3, nodes.TOPN_WALK_ROWS + 1])
def test_a_body_of_topn_calls(field, calls):
    """Same-shape TopN calls of one body share a node: up to
    ``TOPN_WALK_ROWS`` params rows (after padding) the walk unrolls
    them; a larger group takes the full pass, counted."""
    _kind, ref, walk, _full = field
    asks = [(1 + i % 3, 1 + i) for i in range(calls)]
    v0 = prune_vars(walk)
    got = answer(walk, " ".join(
        f"TopN(d, Row(g={g}), n={n})" for g, n in asks))
    assert got == [ref.topn([g], n) for g, n in asks]
    d = delta(prune_vars(walk), v0)
    walked = calls <= nodes.TOPN_WALK_ROWS
    assert d["queries"] == (calls if walked else 0)
    assert d["fullScans"] == (0 if walked else calls)


@pytest.mark.parametrize("devices", [1, 4, 8])
def test_meshes(devices):
    """One device, four, eight: the counts are global before the
    threshold is taken, so every device makes the same steps."""
    h, idx, ref = build("shuffled", seed=devices)
    ex = Executor(h, mesh=default_mesh(jax.devices()[:devices]))
    try:
        assert ex.mesh_exec.n_devices == devices
        v0 = prune_vars(ex)
        for g, n in ((1, 10), (2, 3), (3, 1)):
            assert answer(ex, f"TopN(d, Row(g={g}), n={n})") == \
                [ref.topn([g], n)]
        d = delta(prune_vars(ex), v0)
        assert d["queries"] == 3 and d["fullScans"] == 0
    finally:
        ex.close()
        h.close()


# -- the totals follow the block ----------------------------------------------

def _ingest(h, field, row, cols):
    com = GroupCommitter(h, flush_ms=0)     # inline flush per wait
    try:
        seq = com.submit("p", field, cols=np.asarray(cols),
                         rows=np.full(len(cols), row, dtype=np.int64))
        assert com.wait_flushed(seq)
    finally:
        com.close()


def test_set_through_the_overlay_and_clear():
    """A ``Set`` through the ingest overlay lifts a row the walk never
    visited into the top n, and a ``Clear`` drops one out: the plan was
    made from the old words, the block moved, and the next walk counts
    the totals anew."""
    h, idx, ref = build()
    ex = Executor(h, use_mesh=True)
    try:
        q = "TopN(d, Row(g=1), n=3)"
        v0 = prune_vars(ex)
        assert answer(ex, q) == [ref.topn([1], 3)]
        assert delta(prune_vars(ex), v0)["rowsVisited"] < 30
        me = ex.mesh_exec
        blk, = [b for k, b in me._blocks.items() if k[1] == ("d", "standard")]
        assert blk.walk is not None and blk.walk[0] is blk.arrays
        # row 33 sits in a block of tiny totals; give it every g=1 column
        g1 = ref.cols("g", 1)
        _ingest(h, "d", 33, g1)
        ref.set("d", 33, g1)
        assert ref.topn([1], 3)[0] == {"id": 33, "count": int(g1.size)}
        assert answer(ex, q) == [ref.topn([1], 3)]
        # the overlay rewrote the block's arrays in place of a re-stage
        blk2, = [b for k, b in me._blocks.items()
                 if k[1] == ("d", "standard")]
        assert blk2 is blk and blk.walk[0] is blk.arrays
        # and a Clear of the leader's bits under the filter drops it out
        lead = ref.topn([1], 3)[1]["id"]        # row 0, behind row 33
        gone = np.intersect1d(ref.cols("d", lead), g1)
        for col in gone:
            assert idx.field("d").clear_bit(lead, int(col))
        ref.clear("d", lead, gone)
        assert all(p["id"] != lead for p in ref.topn([1], 3))
        assert answer(ex, q) == [ref.topn([1], 3)]
        d = delta(prune_vars(ex), v0)
        assert d["queries"] == 3 and d["fullScans"] == 0
    finally:
        ex.close()
        h.close()


@pytest.mark.parametrize("resident", ["dense-streamed", "compressed"])
def test_under_a_forced_budget(resident, monkeypatch):
    """Under a device budget the stacks are no one dense block.  Held
    dense, the working set streams in shard slices: the whole-query
    program is not taken and the per-stage launcher reads every row of
    every slice.  Held compressed, it fits and the program is taken,
    but a compressed stack is decoded a shard at a time: the full pass.
    Either way the answer is the same and a full scan is counted."""
    if resident == "dense-streamed":
        monkeypatch.setattr(fragment, "COMPRESSED_RESIDENT", False)
    h, idx, ref = build()
    ex = Executor(h, mesh=default_mesh(jax.devices()[:1]))
    old = DEFAULT_BUDGET.limit_bytes
    try:
        q = "TopN(d, Row(g=1), n=5)"
        DEFAULT_BUDGET.limit_bytes = None
        v0 = prune_vars(ex)
        assert answer(ex, q) == [ref.topn([1], 5)]
        assert delta(prune_vars(ex), v0)["queries"] == 1
        v0, fb0 = prune_vars(ex), ex.wq_fallbacks
        # d alone is 3 shards x 64 rows x 128 KiB = 24 MB dense
        DEFAULT_BUDGET.limit_bytes = 12 << 20
        DEFAULT_BUDGET.shrink_to_limit()
        assert answer(ex, q) == [ref.topn([1], 5)]
        d = delta(prune_vars(ex), v0)
        assert d["queries"] == 0 and d["fullScans"] == 1
        assert ex.wq_fallbacks - fb0 == (resident == "dense-streamed")
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()
        h.close()


# -- a block not visited is not read -------------------------------------------

def test_a_block_not_visited_is_behind_a_while(monkeypatch):
    """The lowered program: the primary's stack is read by a dynamic
    slice of one block inside a ``while``; no op counts all of its rows
    at once (the full pass's ``popcnt`` over [S, R, 256, 128]) and no
    ``select`` stands where the loop's condition does."""
    h, idx, ref = build()
    ex = Executor(h, use_mesh=True)
    seen = []
    read = wholequery_mod._InstrumentedWhole.temp_bytes

    def spy(self, local, mats, flat):
        seen.append((self, mats, flat))
        return read(self, local, mats, flat)

    monkeypatch.setattr(wholequery_mod._InstrumentedWhole, "temp_bytes", spy)
    try:
        assert answer(ex, "TopN(d, Row(g=1), n=3)") == [ref.topn([1], 3)]
        (fn, mats, flat), = seen
        text = fn.fn.lower(mats, *flat).as_text()
        s_local = flat[0].shape[0] // ex.mesh_exec.n_devices
        k = nodes.walk_block_rows(STACKED)
        whole = f"tensor<{s_local}x{STACKED}x256x128xui32>"
        block = f"tensor<{s_local}x{k}x256x128xui32>"
        assert "stablehlo.while" in text
        pops = [ln for ln in text.splitlines() if "stablehlo.popcnt" in ln]
        assert pops and not any(f"x{STACKED}x256x128xui32>" in ln
                                for ln in pops)
        assert any(f"x{k}x256x128xui32>" in ln for ln in pops)
        slices = [ln for ln in text.splitlines()
                  if "stablehlo.dynamic_slice" in ln and whole in ln]
        assert slices and all(block in ln for ln in slices)
        # the n column rides in the matrix: [B, P + 1]
        assert mats[0].shape == (1, 2) and mats[0][0, -1] == 3
    finally:
        ex.close()
        h.close()
