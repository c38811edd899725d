"""Whole-query pjit programs (docs/whole-query.md): a read request
compiles to ONE XLA computation over the mesh.

The load-bearing guarantees tested here:

* Differential: whole-query results are byte-identical to the legacy
  per-stage path across a mixed corpus — nested Intersect/Union/Not/
  Shift, BSI ranges, time-quantum views, TopN, GroupBy, Min/Max — in
  dense-resident, compressed-resident, and eviction-pressure legs.
* One launch: a `Count(Intersect(...))`-class request is ONE device
  launch (verified by the launch ledger), and a mixed multi-call
  request is STILL one launch where the legacy path takes several.
* Fallbacks are loud: unsupported shapes reroute with the
  `wholequery.fallback` counter and a structured log event naming the
  unsupported node; the "error" policy raises instead.
* The kill switch (`whole-query = false`) restores the legacy path
  exactly.
* Re-trace regression (the PR 7 class): re-tracing the cached program
  at a new stacked bucket keeps its frozen layouts/schedule.
"""

import threading

import jax
import numpy as np
import pytest

from conftest import over_budget_limit

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import ExecutionError
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
from pilosa_tpu.utils import devobs

N_SHARDS = 20


@pytest.fixture(scope="module")
def corpus():
    """20-shard index mixing ragged set fields (a, b — different max
    rows per shard so stacking splits into multiple shape groups), a
    BSI field (v), run-heavy clustered ranges (a row 11), a
    time-quantum field (t), existence, and a shard with no fragments at
    all (bits only in shards 0..17; shards 18-19 stay empty —
    and wide enough that the 8-virtual-device mesh must slice it under
    a tight budget, forcing the streaming fallback leg)."""
    rng = np.random.default_rng(99)
    h = Holder(None)
    idx = h.create_index("w")
    a = idx.create_field("a")
    b = idx.create_field("b")
    v = idx.create_field("v", FieldOptions(type="int", min=-500, max=500))
    t = idx.create_field("t", FieldOptions(type="time",
                                           time_quantum="YMD"))
    n = 30_000
    cols = rng.integers(0, 18 * SHARD_WIDTH, size=n)
    a.import_bits(rng.integers(0, 10, size=n), cols)
    b.import_bits(rng.integers(0, 6, size=n), cols)
    # ragged rows: high row ids only in the first shards -> the stacked
    # shape signature differs between shard groups
    ragged = rng.integers(0, 3 * SHARD_WIDTH, size=2000)
    a.import_bits(rng.integers(20, 25, size=2000), ragged)
    # run-heavy clustered ranges (compressed residency's run form)
    run_cols = np.concatenate([
        np.arange(s * SHARD_WIDTH + 1000, s * SHARD_WIDTH + 30_000)
        for s in range(18)])
    a.import_bits(np.full(run_cols.size, 11), run_cols)
    vcols = np.unique(cols[: n // 2])
    v.import_values(vcols, rng.integers(-500, 500, size=vcols.size))
    from datetime import datetime
    tcols = np.unique(cols[: n // 4])
    t.import_bits(np.full(tcols.size, 2), tcols,
                  timestamps=[datetime(2017, 5, 15)] * tcols.size)
    idx.add_existence(np.unique(np.concatenate([cols, ragged, run_cols])))
    return h


QUERIES = [
    "Count(Intersect(Row(a=1), Row(b=2)))",
    "Count(Union(Row(a=0), Not(Row(b=3)), Shift(Row(a=2), n=5)))",
    "Row(a=3)",
    "Difference(Row(a=11), Row(b=1))",
    "Count(Row(-200 < v < 200))",
    "Sum(Row(v > 17), field=v)",
    "Sum(field=v)",
    "Min(field=v) Max(Row(a=2), field=v)",
    "TopN(a, Row(b=1), n=3)",
    "TopN(a, n=4)",
    "Rows(a)",
    "MinRow(field=a) MaxRow(field=a)",
    "GroupBy(Rows(b), Rows(a), Row(v > 0))",
    "Row(t=2, from=2017-01-01T00:00, to=2017-12-31T00:00)",
    "Count(Row(t=2, from=2017-05-01T00:00, to=2017-06-01T00:00))",
    "Count(Row(a=1)) Count(Row(a=7)) Sum(Row(a=1), field=v) "
    "TopN(b, Row(a=4), n=2) Row(b=0)",
]


def _norm(r):
    if hasattr(r, "columns"):
        return ("row", tuple(int(c) for c in r.columns()))
    if isinstance(r, list):
        return tuple(_norm(x) for x in r)
    return r


def _run_corpus(ex, queries=QUERIES):
    return [_norm(r) for q in queries for r in ex.execute("w", q)]


# legs 2/3 rerun a representative subset (one query per reducer kind):
# compressed layouts and the pressure fallback recompile every program
# shape, and 16 shapes x 2 extra legs of XLA compiles is tier-1 budget,
# not coverage
SUBSET = [QUERIES[0], QUERIES[3], QUERIES[5], QUERIES[7], QUERIES[8],
          QUERIES[12], QUERIES[15]]


def test_differential_three_legs(corpus):
    """Whole-query results byte-identical to the legacy path in
    dense-resident, compressed-resident, and eviction-pressure legs.
    Under eviction pressure the over-budget requests fall back (the
    streaming slice planner owns them) — the fallback must be counted
    AND still byte-identical."""
    legacy = Executor(corpus, use_mesh=True, whole_query=False)
    wq = Executor(corpus, use_mesh=True)
    old = DEFAULT_BUDGET.limit_bytes
    try:
        # dense-resident
        DEFAULT_BUDGET.limit_bytes = None
        want = _run_corpus(legacy)
        assert _run_corpus(wq) == want
        assert wq.wq_requests > 0
        want_sub = _run_corpus(legacy, SUBSET)

        # compressed-resident: the largest budget the dense set does not
        # fit, ample for
        # the packed stacks, which stay staged
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(corpus)
        DEFAULT_BUDGET.shrink_to_limit()
        assert _run_corpus(wq, SUBSET) == want_sub
        assert DEFAULT_BUDGET.stats()["compressedBytes"] > 0, \
            "compressed leg never staged a packed stream"

        # eviction pressure: a tight budget (1 MiB over the mesh; the
        # limit is one device's) forces the streaming planner
        DEFAULT_BUDGET.limit_bytes = (1 << 20) // jax.device_count()
        DEFAULT_BUDGET.shrink_to_limit()
        ev0 = DEFAULT_BUDGET.evictions
        fb0 = wq.wq_fallbacks
        assert _run_corpus(wq, SUBSET) == want_sub
        assert DEFAULT_BUDGET.evictions > ev0, \
            "pressure leg never evicted"
        assert wq.wq_fallbacks > fb0, \
            "over-budget requests should fall back to the streaming path"
        assert DEFAULT_BUDGET.stats()["pinnedBytes"] == 0
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        legacy.close()
        wq.close()


def test_single_launch_per_request(corpus):
    """Acceptance: a Count(Intersect(...)) read query executes as ONE
    launch (ledger-verified, kind wholequery), and a mixed Count + Sum
    + TopN + bitmap request is STILL one launch while the legacy path
    takes one per reducer stage."""
    wq = Executor(corpus, use_mesh=True, whole_query_fallback="error")
    legacy = Executor(corpus, use_mesh=True, whole_query=False)
    mixed = ("Count(Intersect(Row(a=1), Row(b=2))) Sum(Row(a=1), field=v)"
             " TopN(b, Row(a=4), n=2) Row(b=0)")
    try:
        # warm both paths (compiles + stacks), then count launches
        wq.execute("w", "Count(Intersect(Row(a=8), Row(b=5)))")
        wq.execute("w", mixed)
        before = devobs.LEDGER.launches_total
        wq.execute("w", "Count(Intersect(Row(a=1), Row(b=2)))")
        assert devobs.LEDGER.launches_total - before == 1
        entry = devobs.LEDGER.snapshot()["entries"][-1]
        assert entry["kind"] == "wholequery"
        # shards 18-19 hold no fragments: only the 18
        # fragment-bearing shards reach the device
        assert entry["shards"] == 18

        before = devobs.LEDGER.launches_total
        wq.execute("w", mixed)
        assert devobs.LEDGER.launches_total - before == 1

        legacy.execute("w", mixed)  # warm
        before = devobs.LEDGER.launches_total
        legacy.execute("w", mixed)
        assert devobs.LEDGER.launches_total - before > 1, \
            "legacy path should take one launch per reducer stage"
    finally:
        wq.close()
        legacy.close()


def test_kill_switch_restores_legacy(corpus):
    ex = Executor(corpus, use_mesh=True, whole_query=False)
    try:
        before = devobs.LEDGER.launches_total
        ex.execute("w", "Count(Row(a=1))")
        assert ex.wq_requests == 0 and ex.wq_fallbacks == 0
        kinds = {e["kind"] for e in devobs.LEDGER.snapshot()["entries"]
                 [-(devobs.LEDGER.launches_total - before):]}
        assert "wholequery" not in kinds
    finally:
        ex.close()


class _CaptureLog:
    def __init__(self):
        self.events = []

    def event(self, name, **fields):
        self.events.append((name, fields))


def test_fallback_counted_and_logged(corpus):
    """An unsupported node falls back with the counter, a structured
    log event naming the node, and /debug/vars-visible state — and the
    'error' policy raises instead of silently rerouting."""
    ex = Executor(corpus, use_mesh=True)
    log = _CaptureLog()
    ex.logger = log
    try:
        fb0 = ex.wq_fallbacks
        # Options() carries per-call shard overrides: fallback matrix
        out = ex.execute("w", "Options(Row(a=1), shards=[0, 1])")
        assert ex.wq_fallbacks == fb0 + 1
        assert ex.wq_last_fallback.startswith("options")
        names = [n for n, _ in log.events]
        assert "wholequery.fallback" in names
        _, fields = log.events[-1]
        assert fields["node"] == "options"
        # answers still correct through the legacy path
        legacy = Executor(corpus, use_mesh=True, whole_query=False)
        try:
            want = legacy.execute("w", "Options(Row(a=1), shards=[0, 1])")
            assert _norm(out[0]) == _norm(want[0])
        finally:
            legacy.close()
    finally:
        ex.close()

    strict = Executor(corpus, use_mesh=True,
                      whole_query_fallback="error")
    try:
        with pytest.raises(ExecutionError, match="whole-query fallback"):
            strict.execute("w", "Options(Row(a=1), shards=[0])")
    finally:
        strict.close()


def test_groupby_and_minmax_join_or_fall_back(corpus):
    """group_counts and bsi_minmax either ride the whole-query program
    (counted as requests, single launch) or fall back cleanly with the
    counter — no silent slow paths."""
    ex = Executor(corpus, use_mesh=True)
    try:
        # small grid GroupBy and Min/Max JOIN the path
        r0, fb0 = ex.wq_requests, ex.wq_fallbacks
        ex.execute("w", "GroupBy(Rows(b), Rows(a))")
        ex.execute("w", "Min(field=v) Max(field=v)")
        assert ex.wq_requests == r0 + 2 and ex.wq_fallbacks == fb0
        # a Rows child with args needs Rows execution: clean fallback
        fb0 = ex.wq_fallbacks
        ex.execute("w", "GroupBy(Rows(b, limit=3), Rows(a))")
        assert ex.wq_fallbacks == fb0 + 1
        assert ex.wq_last_fallback.startswith("group_counts")
    finally:
        ex.close()


def test_retrace_keeps_results(corpus):
    """PR 7-style regression: growing/shrinking shard subsets re-trace
    the cached whole-query program at new stacked buckets; the re-trace
    must keep its frozen layouts/schedule (answers stable per subset,
    full set equals the sum of disjoint halves)."""
    ex = Executor(corpus, use_mesh=True, whole_query_fallback="error")
    old = DEFAULT_BUDGET.limit_bytes
    q = "Count(Intersect(Row(a=11), Row(a=2)))"
    try:
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(corpus)
        want = {}
        for size in (20, 2, 9, 20, 1):
            got = ex.execute("w", q, shards=list(range(size)))[0]
            if size in want:
                assert got == want[size], \
                    f"subset {size} diverged after re-trace"
            want[size] = got
        lo = ex.execute("w", q, shards=list(range(10)))[0]
        hi = ex.execute("w", q, shards=list(range(10, 20)))[0]
        assert want[20] == lo + hi
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()


CROSSING = ["Count(Intersect(Row(a=11), Row(a=2)))",
            "Sum(Row(v > 17), field=v)",
            "TopN(a, Row(b=1), n=3)"]


@pytest.mark.parametrize("blocked", [False, True], ids=["whole", "blocked"])
@pytest.mark.parametrize("q", CROSSING, ids=["count", "sum", "topn"])
def test_bucket_steps_with_a_cached_program(corpus, monkeypatch, q, blocked):
    """A cached whole-query program outlives its shard bucket: on one
    device 16 stacked shards grow to 24 at the 17th (a step 16 does not
    divide), shrink back, and pass 8 and 16 again.  Whole, the program
    re-traces over whatever its arrays hold; walked in shard blocks
    (the bound forced under the compiler's figure for it), every bucket
    has its own program and blocks.  Exact against the legacy path at
    every size, and never a fallback."""
    import jax
    from pilosa_tpu.parallel import nodes
    from pilosa_tpu.parallel.mesh_exec import default_mesh
    legacy = Executor(corpus, use_mesh=True, whole_query=False)
    ex = Executor(corpus, mesh=default_mesh(jax.devices()[:1]),
                  whole_query_fallback="error")
    mesh = ex.mesh_exec
    try:
        first = ex.execute("w", q, shards=list(range(16)))[0]
        assert mesh.stacked_per_device(16) == 16
        assert mesh.stacked_per_device(17) == 24
        if blocked:
            (fn,) = [f for k, f in mesh._cache.items()
                     if k[0] == "wholequery"]
            (temp,) = fn._temps.values()
            monkeypatch.setattr(nodes, "BATCH_TEMP_BYTES", temp // 3)
        splits = mesh.temp_splits
        for size in (16, 17, 20, 16, 9, 8, 17):
            shards = list(range(size))
            assert _norm(ex.execute("w", q, shards=shards)[0]) == \
                _norm(legacy.execute("w", q, shards=shards)[0]), size
        assert _norm(first) == \
            _norm(legacy.execute("w", q, shards=list(range(16)))[0])
        keys = [k for k in mesh._cache if k[0] == "wholequery"]
        walked = {k[5][1:] for k in keys if len(k) > 5}
        if blocked:
            assert mesh.temp_splits >= splits + 7
            # a blocked program is keyed by its buckets (one a shape
            # group: set field a is ragged), and its blocks divide them
            assert len({local for local, _ in walked}) >= 2
            for local, blocks in walked:
                assert all(n % b == 0 for n, b in zip(local, blocks))
                assert any(b < n for n, b in zip(local, blocks))
        else:
            assert mesh.temp_splits == splits and not walked
            assert len(keys) == 1
    finally:
        ex.close()
        legacy.close()


def test_fused_wholequery_tickets(corpus):
    """Concurrent same-shape requests fuse in the dispatch batcher: the
    batched parameter axis rides ONE compiled program (docs/batching.md
    composition), with per-ticket slices byte-identical to solo runs."""
    ex = Executor(corpus, use_mesh=True, dispatch_batch=True,
                  dispatch_batch_window_us=50_000)
    try:
        want = {i: ex.execute("w", f"Count(Row(a={i}))")[0]
                for i in range(8)}
        f0 = ex.batcher.fused_launches
        results: dict = {}
        barrier = threading.Barrier(8)

        def worker(i):
            barrier.wait()
            results[i] = ex.execute("w", f"Count(Row(a={i}))")[0]

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == want
        assert ex.batcher.fused_launches > f0, \
            "concurrent whole-query tickets never fused"
    finally:
        ex.close()


def test_config_knobs(monkeypatch, tmp_path):
    from pilosa_tpu.server.server import Config
    assert Config().whole_query is True
    assert Config().whole_query_fallback == "legacy"
    monkeypatch.setenv("PILOSA_TPU_WHOLE_QUERY", "false")
    monkeypatch.setenv("PILOSA_TPU_WHOLE_QUERY_FALLBACK", "error")
    cfg = Config.from_env()
    assert cfg.whole_query is False
    assert cfg.whole_query_fallback == "error"
    monkeypatch.delenv("PILOSA_TPU_WHOLE_QUERY")
    monkeypatch.delenv("PILOSA_TPU_WHOLE_QUERY_FALLBACK")
    toml = tmp_path / "c.toml"
    toml.write_text('whole-query = false\n'
                    'whole-query-fallback = "error"\n')
    cfg = Config.from_toml(str(toml))
    assert cfg.whole_query is False
    assert cfg.whole_query_fallback == "error"


def test_debug_vars_section(corpus):
    """The executor's /debug/vars wholeQuery section reflects requests
    and fallbacks (wired by the handler; asserted here at the executor
    surface the handler reads)."""
    ex = Executor(corpus, use_mesh=True)
    try:
        ex.execute("w", "Count(Row(a=1))")
        ex.execute("w", "Options(Row(a=1), shards=[0])")
        assert ex.wq_requests >= 1
        assert ex.wq_fallbacks >= 1
        assert ex.wq_last_fallback
    finally:
        ex.close()


# -- the device shape of a row: a (256, 128) word tile ----------------------
# (ops/bitset.py "Representation"): every stacked argument a program takes
# is u32[S, R, 256, 128], a fragment inside the per-shard pass
# u32[R, 256, 128]; the host flattens a fetched segment to [W].

NODE_QUERIES = {
    "count": "Count(Intersect(Row(a=1), Row(b=2)))",
    "segments": "Row(a=3)",
    "row_counts": "TopN(a, Row(b=1), n=3)",
    "bsi_sum": "Sum(Row(v > 17), field=v)",
    "bsi_minmax": "Min(Row(a=2), field=v)",
    "group_counts": "GroupBy(Rows(b), Rows(a))",
}


@pytest.mark.parametrize("kind", list(NODE_QUERIES))
def test_node_takes_tiled_stacks(corpus, monkeypatch, kind):
    """One ``nodes.node_shard`` kind a case: the program's stacked arguments
    are rank 4 with trailing (256, 128), the fragments its per-shard
    pass sees rank 3 with the same tile, and the answer is the legacy
    path's."""
    from pilosa_tpu.core import SHARD_WORDS, WORD_TILE
    from pilosa_tpu.parallel import nodes, wholequery as wq
    stacked, seen = [], []
    real_over, real_node = wq._over_shards, nodes.node_shard

    def over(per_shard, arrs, block):
        stacked.extend(a.shape for a in arrs)
        return real_over(per_shard, arrs, block)

    def node_shard(node, mat, frags):
        seen.append((node.kind, [f.shape for f in frags.values()]))
        return real_node(node, mat, frags)

    monkeypatch.setattr(wq, "_over_shards", over)
    monkeypatch.setattr(nodes, "node_shard", node_shard)
    # a fresh executor traces its programs anew: the spies see them
    ex = Executor(corpus, use_mesh=True, whole_query_fallback="error")
    legacy = Executor(corpus, use_mesh=True, whole_query=False)
    q = NODE_QUERIES[kind]
    try:
        got = ex.execute("w", q)
        assert [_norm(r) for r in got] == \
            [_norm(r) for r in legacy.execute("w", q)]
        assert kind in {k for k, _ in seen}
        assert stacked and all(
            len(s) == 4 and s[-2:] == WORD_TILE for s in stacked), stacked
        assert all(len(s) == 3 and s[1:] == WORD_TILE
                   for _, shapes in seen for s in shapes), seen
        if kind == "segments":
            # the client gets the host's [W] words, the fragment's own
            segs = got[0].segments
            assert set(segs) == set(range(18))   # 18-19 hold no bits
            for shard, seg in segs.items():
                assert isinstance(seg, np.ndarray)
                assert seg.shape == (SHARD_WORDS,)
                fr = corpus.fragment("w", "a", "standard", shard)
                want = fr.row(3) if fr is not None and fr.n_rows > 3 \
                    else np.zeros(SHARD_WORDS, dtype=np.uint32)
                assert np.array_equal(seg, want), shard
    finally:
        ex.close()
        legacy.close()


def test_overlay_sets_exactly_the_named_words(rng):
    """An ingest overlay on a tiled block [S, R, 256, 128] ORs exactly
    the words (member, row, word) named — across the tile's lane rows
    (word 127 | 128) — and leaves every other word as it was."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from pilosa_tpu.core import SHARD_WORDS, WORD_TILE
    from pilosa_tpu.ops import bitset
    from pilosa_tpu.parallel.mesh_exec import MeshExecutor, SHARD_AXIS
    mesh = MeshExecutor()
    try:
        n_s, n_r = 2 * mesh.n_devices, 3
        host = np.zeros((n_s, n_r, SHARD_WORDS), dtype=np.uint32)
        host[:, :, ::97] = rng.integers(
            1, 1 << 32, size=host[:, :, ::97].shape, dtype=np.uint32)
        block = jax.device_put(
            bitset.to_tile(host), NamedSharding(mesh.mesh, P(SHARD_AXIS)))
        assert block.shape == (n_s, n_r) + WORD_TILE
        words = np.array([0, 97, 127, 128, 129, 255, 256, 4095, 4096,
                          SHARD_WORDS - 1] * 3)
        member = rng.integers(0, n_s, size=words.size).astype(np.int32)
        rows = rng.integers(0, n_r, size=words.size)
        # one flat (row, word) index a member: dedupe as merge_chunks does
        _, keep = np.unique(
            np.stack([member, rows, words]), axis=1, return_index=True)
        member, rows, words = member[keep], rows[keep], words[keep]
        vals = rng.integers(1, 1 << 32, size=words.size, dtype=np.uint32)
        out = mesh._overlay_stack(block, member,
                                  rows * SHARD_WORDS + words, vals)
        assert out.shape == block.shape
        want = host.copy()
        want[member, rows, words] |= vals
        assert np.array_equal(bitset.from_tile(np.asarray(out)), want)
    finally:
        mesh.close()


def test_compressed_beside_dense_in_one_launch(rng, monkeypatch):
    """A compressed (``decode_block``) entry and a dense one in the same
    launch: both reach the per-shard pass as [R, 256, 128] fragments,
    and the count is numpy's."""
    from pilosa_tpu.core import SHARD_WORDS, WORD_TILE
    from pilosa_tpu.parallel import wholequery as wq
    n_shards = 4
    h = Holder(None)
    idx = h.create_index("m")
    sparse = idx.create_field("sparse")
    dense = idx.create_field("dense")
    cols = rng.integers(0, n_shards * SHARD_WIDTH, size=6000)
    sparse.import_bits(rng.integers(0, 4, size=cols.size), cols)
    dense.import_bits(np.zeros(n_shards, dtype=np.int64),
                      np.arange(n_shards) * SHARD_WIDTH)
    want = 0
    for shard in range(n_shards):
        fr = h.fragment("m", "dense", "standard", shard)
        for row in range(fr._cap_rows):     # no zero word: stays dense
            fr.set_row(row, rng.integers(1, 1 << 32, size=SHARD_WORDS,
                                         dtype=np.uint32))
        both = fr.row(0) & h.fragment("m", "sparse", "standard",
                                      shard).row(1)
        want += int(np.unpackbits(both.view(np.uint8)).sum())
    layouts, shapes = [], []
    from pilosa_tpu.parallel import mesh_exec
    real = mesh_exec._unpack_frags

    def unpack(layout, arrays, rows_of=(), stacked=None):
        out = real(layout, arrays, rows_of, stacked)
        layouts.append([n for _, n, _ in layout])
        shapes.extend(f.shape for f in out.values())
        return out

    monkeypatch.setattr(mesh_exec, "_unpack_frags", unpack)
    old = DEFAULT_BUDGET.limit_bytes
    ex = Executor(h, use_mesh=True, whole_query_fallback="error")
    try:
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(h)
        DEFAULT_BUDGET.shrink_to_limit()
        forms = {h.fragment("m", f, "standard", 0).device_form()
                 for f in ("sparse", "dense")}
        assert forms == {"compressed", "dense"}
        got = ex.execute(
            "m", "Count(Intersect(Row(sparse=1), Row(dense=0)))")[0]
        assert got == want
        # one launch decoded the packed entry (5 tables) beside the
        # dense one (1 array), and both came out tiled
        assert layouts and all(sorted(l) == [1, 7] for l in layouts)
        assert all(len(s) == 3 and s[1:] == WORD_TILE for s in shapes)
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()
