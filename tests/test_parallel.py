"""Placement + mesh execution tests.

Placement mirrors cluster_internal_test.go (TestCluster_Partition /
partitionNodes); mesh execution runs real shard_map over the 8 virtual CPU
devices from conftest and must agree with the per-shard executor."""

import threading

import jax
import numpy as np
import pytest

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel import (
    JmpHasher, MeshExecutor, ModHasher, Placement, default_mesh, jump_hash,
)
from pilosa_tpu.parallel.nodes import KINDS
from pilosa_tpu.pql import parse
from pilosa_tpu.storage import FieldOptions, Holder, fragment
from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
from pilosa_tpu.utils import devobs

from test_differential import _norm


# -- placement --------------------------------------------------------------

def test_jump_hash_properties():
    # deterministic, in range, monotone-consistency on bucket growth
    for key in [0, 1, 12345, 2**63]:
        for n in [1, 2, 7, 100]:
            b = jump_hash(key, n)
            assert 0 <= b < n
            assert jump_hash(key, n) == b
    # jump-hash consistency: growing n only moves keys to the NEW bucket
    moved_elsewhere = 0
    for key in range(1000):
        b5, b6 = jump_hash(key, 5), jump_hash(key, 6)
        if b5 != b6:
            assert b6 == 5
    # roughly 1/6 of keys move
    moved = sum(jump_hash(k, 5) != jump_hash(k, 6) for k in range(6000))
    assert 500 < moved < 1500


def test_partition_stability():
    p = Placement(["a", "b", "c"], replica_n=1)
    # partition is a pure function of (index, shard)
    assert p.partition("i", 0) == p.partition("i", 0)
    assert p.partition("i", 0) != p.partition("other", 0) or True
    parts = {p.partition("i", s) for s in range(100)}
    assert len(parts) > 50  # well spread over 256 partitions


def test_replication_ring():
    p = Placement(["n0", "n1", "n2", "n3"], replica_n=2, hasher=ModHasher())
    owners = p.partition_nodes(1)
    assert owners == ["n1", "n2"]  # ring successors
    owners = p.partition_nodes(3)
    assert owners == ["n3", "n0"]  # wraps
    # replica_n capped at node count
    p2 = Placement(["x"], replica_n=3)
    assert p2.partition_nodes(0) == ["x"]


def test_owned_and_grouped_shards():
    p = Placement(["n0", "n1", "n2"], replica_n=2)
    shards = list(range(20))
    by_node = p.shards_by_node("i", shards)
    assert sorted(s for lst in by_node.values() for s in lst) == shards
    # every shard owned by exactly replica_n nodes
    for s in shards:
        owners = [n for n in p.nodes if p.owns_shard(n, "i", s)]
        assert len(owners) == 2
        assert p.primary("i", s) == p.shard_nodes("i", s)[0]


# -- mesh execution ---------------------------------------------------------

N_SHARDS = 11  # deliberately not a multiple of 8 devices


@pytest.fixture
def loaded(tmp_path):
    h = Holder(None)
    idx = h.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    rng = np.random.default_rng(9)
    cols = rng.integers(0, N_SHARDS * SHARD_WIDTH, size=5000)
    rows = rng.integers(0, 8, size=5000)
    f.import_bits(rows, cols)
    v.import_values(cols, rng.integers(0, 1000, size=5000))
    idx.add_existence(cols)
    return h, rows, cols


def test_mesh_matches_pershard(loaded):
    h, rows, cols = loaded
    assert len(jax.devices()) == 8  # conftest virtual mesh
    plain = Executor(h)
    meshy = Executor(h, use_mesh=True)
    for q in ["Count(Row(f=1))",
              "Count(Intersect(Row(f=1), Row(f=2)))",
              "Count(Union(Row(f=0), Row(f=3), Row(f=7)))",
              "Count(Not(Row(f=1)))",
              "Count(Row(v > 500))"]:
        assert plain.execute("i", q) == meshy.execute("i", q), q


def test_mesh_bitmap_segments(loaded):
    h, rows, cols = loaded
    plain = Executor(h)
    meshy = Executor(h, use_mesh=True)
    a = plain.execute("i", "Union(Row(f=1), Row(f=4))")[0]
    b = meshy.execute("i", "Union(Row(f=1), Row(f=4))")[0]
    assert np.array_equal(a.columns(), b.columns())
    assert set(a.segments) == set(b.segments)


def test_mesh_sum_with_filter(loaded):
    h, _, _ = loaded
    plain = Executor(h)
    meshy = Executor(h, use_mesh=True)
    assert plain.execute("i", "Sum(Row(f=1), field=v)") == \
        meshy.execute("i", "Sum(Row(f=1), field=v)")


def test_mesh_empty_and_missing_fragments(loaded):
    h, _, _ = loaded
    meshy = Executor(h, use_mesh=True)
    # field exists but row beyond data
    assert meshy.execute("i", "Count(Row(f=500))") == [0]
    # difference touching missing fragments in some shards
    out = meshy.execute("i", "Count(Difference(Row(f=1), Row(f=1)))")
    assert out == [0]


def test_mesh_executor_cache(loaded):
    h, _, _ = loaded
    me = Executor(h, use_mesh=True)
    me.execute("i", "Count(Row(f=1))")
    n = len(me.mesh_exec._cache)
    me.execute("i", "Count(Row(f=1))")
    assert len(me.mesh_exec._cache) == n


def test_stacks_register_with_device_budget(loaded):
    """Stacked shard blocks account against the DeviceBudget and evict as
    one unit (r3 advisor: stacks bypassed the budget entirely)."""
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    h, _, _ = loaded
    me = Executor(h, use_mesh=True)
    me.execute("i", "Count(Row(f=1))")
    # (no global resident_bytes delta check: GC finalizers of earlier
    # tests' executors may unregister concurrently)
    sc = me.mesh_exec._stack_cache
    assert len(sc) == 1
    ckey = next(iter(sc))
    # the unit registered is the (field, view)'s block the entry reads
    (blk,) = sc[ckey][4]
    key = blk.skey
    assert key in DEFAULT_BUDGET._entries
    nbytes = DEFAULT_BUDGET._entries[key][0]
    assert nbytes == blk.nbytes > 0
    assert me.mesh_exec.stack_block_bytes() == nbytes
    # budget eviction drops the block and the stack-cache entry over it
    DEFAULT_BUDGET._entries[key][1]()
    assert ckey not in sc and not me.mesh_exec._blocks
    DEFAULT_BUDGET.unregister(key)
    # close() unregisters whatever remains
    me.execute("i", "Count(Row(f=1))")
    (blk,) = sc[ckey][4]
    assert blk.skey != key and blk.skey in DEFAULT_BUDGET._entries
    me.close()
    assert blk.skey not in DEFAULT_BUDGET._entries


def test_server_config_sets_device_budget(tmp_path):
    from pilosa_tpu.server import Config, Server
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    old = DEFAULT_BUDGET.limit_bytes
    try:
        srv = Server(Config(data_dir=str(tmp_path), bind="localhost:0",
                            device_budget_mb=256))
        assert DEFAULT_BUDGET.limit_bytes == 256 << 20
        srv.httpd.server_close()
    finally:
        DEFAULT_BUDGET.limit_bytes = old


def test_global_mesh_executor(loaded):
    """multihost.global_mesh: a mesh over every process device drives the
    same executor path (single process here; multi-process differs only
    in where jax.devices() live)."""
    from pilosa_tpu.parallel import multihost
    h, _, _ = loaded
    mesh = multihost.global_mesh()
    assert mesh.devices.size == 8
    me = Executor(h, mesh=mesh)
    plain = Executor(h)
    q = "Count(Intersect(Row(f=1), Row(f=2)))"
    assert me.execute("i", q) == plain.execute("i", q)
    lo, hi = multihost.process_shard_slice(10)
    assert (lo, hi) == (0, 10)
    with pytest.raises(ValueError):
        multihost.init_distributed("localhost:1", 0, 0)
    with pytest.raises(ValueError):
        multihost.init_distributed("localhost:1", 2, 5)


def test_plan_cache_keyed_by_shape(loaded):
    """Distinct row ids and BSI predicate values must share ONE compiled
    executable — literals are runtime params, not baked constants
    (SURVEY §7: plan cache keyed by call tree shape).  A recompile per
    distinct query value would cost seconds each on TPU."""
    h, _, _ = loaded
    me = Executor(h, use_mesh=True)
    me.execute("i", "Count(Row(f=1))")
    n = len(me.mesh_exec._cache)
    for q in ["Count(Row(f=2))", "Count(Row(f=7))", "Count(Row(f=999))"]:
        me.execute("i", q)
    assert len(me.mesh_exec._cache) == n, "row id recompiled the plan"
    me.execute("i", "Count(Row(v > 10))")
    n = len(me.mesh_exec._cache)
    for q in ["Count(Row(v > 500))", "Count(Row(v > 3))"]:
        me.execute("i", q)
    assert len(me.mesh_exec._cache) == n, "BSI value recompiled the plan"
    # per-shard compiler shares executables the same way
    plain = Executor(h)
    plain.execute("i", "Count(Intersect(Row(f=1), Row(f=2)))")
    n = len(plain.compiler._cache)
    plain.execute("i", "Count(Intersect(Row(f=3), Row(f=4)))")
    assert len(plain.compiler._cache) == n
    # correctness across the shared executable
    assert plain.execute("i", "Count(Row(f=2))") == \
        me.execute("i", "Count(Row(f=2))")


def test_mesh_topn_rows_minmax_match_pershard(loaded):
    """The round-3 reducers (row_counts, bsi_sum, bsi_min_max,
    group_counts) must agree with the per-shard host loop on every
    aggregation call (VERDICT r2: 'route the remaining reducers through
    the mesh')."""
    h, _, _ = loaded
    plain = Executor(h)
    meshy = Executor(h, use_mesh=True)
    for q in ["TopN(f, n=3)",
              "TopN(f)",
              "TopN(f, Row(f=2), n=2)",
              "Min(field=v)", "Max(field=v)",
              "Min(Row(f=1), field=v)", "Max(Row(f=1), field=v)",
              "MinRow(field=f)", "MaxRow(field=f)",
              "Rows(f)", "Rows(f, limit=3)", "Rows(f, previous=2)",
              "GroupBy(Rows(f))",
              "GroupBy(Rows(f), limit=4)"]:
        assert plain.execute("i", q) == meshy.execute("i", q), q


def test_mesh_groupby_two_fields_and_filter():
    h = Holder(None)
    idx = h.create_index("i")
    a = idx.create_field("a")
    b = idx.create_field("b")
    g = idx.create_field("g")
    rng = np.random.default_rng(3)
    cols = rng.integers(0, 3 * SHARD_WIDTH, size=3000)
    a.import_bits(rng.integers(0, 3, size=3000), cols)
    b.import_bits(rng.integers(0, 4, size=3000), cols)
    g.import_bits(rng.integers(0, 2, size=3000), cols)
    idx.add_existence(cols)
    plain = Executor(h)
    meshy = Executor(h, use_mesh=True)
    for q in ["GroupBy(Rows(a), Rows(b))",
              "GroupBy(Rows(a), Rows(b), Row(g=1))",
              "GroupBy(Rows(a), Rows(b), limit=5)"]:
        assert plain.execute("i", q) == meshy.execute("i", q), q


def test_mesh_groupby_single_executable():
    """Every combo of a GroupBy must share one compiled executable —
    prefix row ids are dynamic args, not baked constants (a recompile per
    combo would dwarf the query)."""
    h = Holder(None)
    idx = h.create_index("i")
    a = idx.create_field("a")
    b = idx.create_field("b")
    rng = np.random.default_rng(5)
    cols = rng.integers(0, 2 * SHARD_WIDTH, size=2000)
    a.import_bits(rng.integers(0, 6, size=2000), cols)
    b.import_bits(rng.integers(0, 6, size=2000), cols)
    idx.add_existence(cols)
    meshy = Executor(h, use_mesh=True)
    meshy.execute("i", "GroupBy(Rows(a), Rows(b))")  # 36 combos
    n_compiled = len(meshy.mesh_exec._cache)
    meshy.execute("i", "GroupBy(Rows(a), Rows(b))")
    assert len(meshy.mesh_exec._cache) == n_compiled
    # 6x6 combos but only O(1) executables: Rows row_counts (1 per field,
    # same shapes may share) + 1 group_counts
    assert n_compiled <= 4


def test_mesh_negative_bsi_values():
    h = Holder(None)
    idx = h.create_index("i")
    v = idx.create_field("v", FieldOptions(type="int", min=-500, max=500))
    rng = np.random.default_rng(11)
    cols = rng.integers(0, 2 * SHARD_WIDTH, size=1000)
    vals = rng.integers(-500, 500, size=1000)
    v.import_values(cols, vals)
    idx.add_existence(cols)
    plain = Executor(h)
    meshy = Executor(h, use_mesh=True)
    for q in ["Sum(field=v)", "Min(field=v)", "Max(field=v)",
              "Count(Row(v < 0))", "Count(Row(v >< [-100, 100]))"]:
        assert plain.execute("i", q) == meshy.execute("i", q), q


def test_mesh_mixed_write_read_query_sequential(loaded):
    """Batched grouping must NOT reorder dispatch around writes: a read
    after a write in the same multi-call query sees the write (the
    reference executes calls sequentially, executor.go:113)."""
    h, _, _ = loaded
    me = Executor(h, use_mesh=True)
    before = me.execute("i", "Count(Row(f=1))")[0]
    out = me.execute(
        "i", "Set(999999, f=1) Count(Row(f=1)) Count(Row(f=2))")
    assert out[0] is True
    assert out[1] == before + 1  # read AFTER the write sees the new bit
    # read-only multi-call queries still batch (single fetch)
    out2 = me.execute("i", "Count(Row(f=1)) Count(Row(f=1))")
    assert out2[0] == out2[1] == before + 1


def test_mesh_stack_cache_bounded(loaded):
    """The placed-stack cache is LRU-bounded so stale shard sets don't pin
    device memory forever."""
    h, _, _ = loaded
    me = Executor(h, use_mesh=True)
    me.mesh_exec.stack_cache_max = 2
    me.execute("i", "Count(Row(f=1))")
    me.execute("i", "Count(Row(v > 3))")
    me.execute("i", "Count(Intersect(Row(f=1), Row(v > 2)))")
    me.execute("i", "TopN(f, n=1)")
    assert len(me.mesh_exec._stack_cache) <= 2
    # evicted entries re-place transparently with correct results
    plain = Executor(h)
    assert plain.execute("i", "Count(Row(f=1))") == \
        me.execute("i", "Count(Row(f=1))")


def test_mesh_stack_cache_invalidation(loaded):
    """Placed shard-stacks are reused across queries and rebuilt when a
    fragment mirror changes (a write), so results never go stale."""
    h, _, _ = loaded
    me = Executor(h, use_mesh=True)
    before = me.execute("i", "Count(Row(f=1))")[0]
    token0 = {k: v[0] for k, v in me.mesh_exec._stack_cache.items()}
    me.execute("i", "Count(Row(f=2))")  # same shape, repeat gather
    for k, v in me.mesh_exec._stack_cache.items():
        assert v[0] == token0[k]  # reused, not re-placed
    # write invalidates: new mirror -> new stack -> fresh result
    f = h.field("i", "f")
    free_col = 0
    assert f.set_bit(1, free_col) or True
    after = me.execute("i", "Count(Row(f=1))")[0]
    oracle = Executor(h).execute("i", "Count(Row(f=1))")[0]
    assert after == oracle
    assert after >= before


def test_mesh_single_shard(tmp_path):
    h = Holder(None)
    idx = h.create_index("i")
    idx.field("_exists")  # noqa
    f = idx.create_field("f")
    f.set_bit(1, 42)
    meshy = Executor(h, use_mesh=True)
    assert meshy.execute("i", "Count(Row(f=1))") == [1]
    res = meshy.execute("i", "Row(f=1)")[0]
    assert res.columns().tolist() == [42]


# -- one body per reducer: the per-stage launcher ---------------------------
# Every node kind of parallel/nodes.py, launched per stage
# (MeshExecutor.reduce_async) resident and streamed, against the
# whole-query program over the same body and the host reference.

STAGE_SHARDS = 16       # two mesh-width slices on the 8-device test mesh
STAGE_QUERIES = {
    "count": "Count(Intersect(Row(a=1), Row(b=2)))",
    "segments": "Union(Row(a=3), Row(b=1))",
    "row_counts": "TopN(a, Row(b=1), n=3)",
    "bsi_sum": "Sum(Row(v > 17), field=v)",
    "bsi_minmax": "Min(Row(a=2), field=v)",
    "group_counts": "GroupBy(Rows(b), Rows(a))",
}
# the same shapes three at a time: Count, TopN and Sum batch into one
# launch at B = 3 (padded to 4), the others launch a call each
STAGE_TRIPLES = {
    "count": " ".join(f"Count(Intersect(Row(a={i}), Row(b={i})))"
                      for i in (1, 2, 3)),
    "segments": "Union(Row(a=3), Row(b=1)) Union(Row(a=4), Row(b=2)) "
                "Union(Row(a=5), Row(b=3))",
    "row_counts": " ".join(f"TopN(a, Row(b={i}), n=3)" for i in (1, 2, 3)),
    "bsi_sum": " ".join(f"Sum(Row(v > {i}), field=v)" for i in (17, 400, 9)),
    "bsi_minmax": "Min(Row(a=2), field=v) Min(Row(a=3), field=v) "
                  "Min(Row(a=4), field=v)",
    "group_counts": "GroupBy(Rows(b), Rows(a)) GroupBy(Rows(b), Rows(a)) "
                    "GroupBy(Rows(b), Rows(a))",
}


@pytest.fixture(scope="module")
def staged():
    h = Holder(None)
    idx = h.create_index("p")
    a = idx.create_field("a")
    b = idx.create_field("b")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    rng = np.random.default_rng(33)
    n = 30_000
    cols = rng.integers(0, STAGE_SHARDS * SHARD_WIDTH, size=n)
    a.import_bits(rng.integers(0, 10, size=n), cols)
    b.import_bits(rng.integers(0, 4, size=n), cols)
    vcols = np.unique(cols[: n // 2])
    v.import_values(vcols, rng.integers(0, 1000, size=vcols.size))
    idx.add_existence(cols)
    yield h
    h.close()


def _launched(since: int) -> list[dict]:
    """The launch ledger's entries after its ``since``-th launch."""
    n = devobs.LEDGER.launches_total - since
    return devobs.LEDGER.snapshot()["entries"][-n:] if n else []


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["resident", "streamed"])
@pytest.mark.parametrize("kind", KINDS)
def test_per_stage_equals_whole_query_and_host(staged, monkeypatch, kind,
                                               streamed):
    """The per-stage answer (``whole_query=False``: one launch of the
    node a shape group and shard slice) is the whole-query program's and
    the ``use_mesh=False`` reference's, with the working set resident and
    under a device budget that streams it in two slices; every launch
    it makes is named by the node kind."""
    # the dense form: compressed residency would fit the budget whole
    monkeypatch.setattr(fragment, "COMPRESSED_RESIDENT", False)
    q = STAGE_QUERIES[kind]
    host = Executor(staged)
    whole = Executor(staged, use_mesh=True, whole_query_fallback="error")
    stage = Executor(staged, use_mesh=True, whole_query=False)
    old = DEFAULT_BUDGET.limit_bytes
    try:
        DEFAULT_BUDGET.limit_bytes = None
        want = _norm(host.execute("p", q))
        assert _norm(whole.execute("p", q)) == want
        whole.close()
        if streamed:
            # each query stacks a or v: 16 shards x 16 rows x 128 KiB,
            # 32 MiB over the mesh; the limit is one device's share
            DEFAULT_BUDGET.limit_bytes = (12 << 20) // jax.device_count()
            DEFAULT_BUDGET.shrink_to_limit()
        # the text replays a prepared template where there is one (a
        # call group of one row), the parsed query takes the call's own
        # lowering: both launch the node at B = 1
        assert _norm(stage.execute("p", q)) == want
        since = devobs.LEDGER.launches_total
        assert _norm(stage.execute("p", parse(q))) == want
        entries = _launched(since)
        assert {e["kind"] for e in entries} == {kind}
        assert {e["slices"] for e in entries} == {2 if streamed else 1}
        # one row; a GroupBy's leading axis is its prefix combos (b: 4)
        rows = 4 if kind == "group_counts" else 1
        assert all(e["batchRows"] == rows for e in entries)
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        for ex in (host, whole, stage):
            ex.close()


def _stage_kinds(ex) -> list[str]:
    return [k[0] for k in ex.mesh_exec._cache]


def test_lone_and_batched_count_share_one_executable(staged):
    """A lone Count is B = 1 of the program a three-call query runs at
    B = 3: one ``count`` executable, not a plain and a batched one."""
    ex = Executor(staged, use_mesh=True, whole_query=False)
    host = Executor(staged)
    try:
        lone = "Count(Intersect(Row(a=4), Row(b=0)))"
        assert ex.execute("p", lone) == host.execute("p", lone)
        assert _stage_kinds(ex) == ["count"]
        since = devobs.LEDGER.launches_total
        triple = STAGE_TRIPLES["count"]
        assert ex.execute("p", triple) == host.execute("p", triple)
        assert _stage_kinds(ex) == ["count"]
        # (the call group's chunk is padded to a power of two before
        # it becomes a ticket: the ledger sees the ticket's four rows)
        (entry,) = _launched(since)
        assert (entry["kind"], entry["batchRowsPadded"]) == ("count", 4)
    finally:
        ex.close()
        host.close()


def test_executable_kinds_are_the_node_kinds(staged):
    """After every node kind has run per stage at B = 1 and B = 3 and
    through the whole-query program, no executable's kind is outside the
    six node kinds, ``wholequery``, ``walkplan`` and ``overlay``."""
    stage = Executor(staged, use_mesh=True, whole_query=False)
    whole = Executor(staged, use_mesh=True)
    host = Executor(staged)
    try:
        for kind in KINDS:
            for q in (STAGE_QUERIES[kind], STAGE_TRIPLES[kind]):
                want = _norm(host.execute("p", q))
                assert _norm(stage.execute("p", q)) == want, q
                assert _norm(whole.execute("p", q)) == want, q
        assert set(_stage_kinds(stage)) == set(KINDS)
        # (``walkplan``: the row totals a filtered TopN's walk is planned
        # from, one small program a stacked block shape: nodes.topn_walk)
        assert set(_stage_kinds(whole)) == {"wholequery", "walkplan"}
        # an ingest flush overlays the resident stack (docs/ingest.md)
        staged.field("p", "a").set_bit(1, 5)
        assert stage.execute("p", "Count(Row(a=1))") == \
            host.execute("p", "Count(Row(a=1))")
        assert set(_stage_kinds(stage)) <= set(KINDS) | {"overlay"}
    finally:
        for ex in (stage, whole, host):
            ex.close()


def test_single_call_and_call_group_fuse_into_one_launch(staged):
    """A single call (B = 1) and a batched call group (B = 3) of one
    node, submitted together, are one ticket kind: they fuse into one
    launch (one row and the group's chunk of three padded to four: five,
    padded to eight)."""
    ex = Executor(staged, use_mesh=True, whole_query=False,
                  dispatch_batch_window_us=500_000)
    host = Executor(staged)
    lone = "Count(Intersect(Row(a=4), Row(b=0)))"
    triple = STAGE_TRIPLES["count"]
    got: dict = {}
    barrier = threading.Barrier(2)

    def client(q):
        barrier.wait()
        got[q] = ex.execute("p", q)

    try:
        threads = [threading.Thread(target=client, args=(q,))
                   for q in (lone, triple)]
        since = devobs.LEDGER.launches_total
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert got == {q: host.execute("p", q) for q in (lone, triple)}
        assert (ex.batcher.fused_launches, ex.batcher.single_launches) \
            == (1, 0)
        (entry,) = _launched(since)
        assert (entry["kind"], entry["tickets"], entry["batchRows"],
                entry["batchRowsPadded"]) == ("count", 2, 5, 8)
    finally:
        ex.close()
        host.close()
