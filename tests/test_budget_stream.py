"""Out-of-core shard streaming: pin-aware budget eviction, the host-side
dense staging cache, residency-aware slice scheduling with prefetch, and
the budgeted-eviction DIFFERENTIAL guarantee — a query corpus run under a
budget small enough to force evictions (and streaming) mid-batch must
return results identical to the unbudgeted run.  A pinning bug would
corrupt in-flight buffers silently; the differential catches it as a
divergence."""

import numpy as np
import pytest

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import _batch_chunks
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.storage import fragment
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu.storage.membudget import (
    DEFAULT_BUDGET, HOST_STAGE_BUDGET, DeviceBudget,
)

from test_differential import _norm, gen_query


# -- pin-aware eviction (unit) ----------------------------------------------

def test_pinned_entry_never_evicted():
    b = DeviceBudget(limit_bytes=100)
    dropped = []
    b.register(("a",), 60, lambda: dropped.append("a"))
    assert b.pin(("a",))
    # over budget, but the only candidate is pinned: admitted over-limit
    b.register(("b",), 60, lambda: dropped.append("b"))
    assert dropped == []
    assert b.resident_bytes == 120
    assert b.stats()["pinnedBytes"] == 60
    # unpinned again: LRU order (a, then b) drains normally
    b.unpin(("a",))
    b.register(("c",), 50, lambda: dropped.append("c"))
    assert dropped == ["a", "b"]
    assert b.resident_bytes == 50
    assert b.evictions == 2


def test_eviction_prefers_unpinned_coldest():
    b = DeviceBudget(limit_bytes=100)
    dropped = []
    b.register(("cold",), 40, lambda: dropped.append("cold"))
    b.register(("pinned",), 40, lambda: dropped.append("pinned"))
    b.pin(("pinned",))
    b.touch(("cold",))  # cold is now MRU, pinned is LRU
    b.register(("new",), 40, lambda: dropped.append("new"))
    # pinned (LRU) skipped; cold (unpinned, though warmer) evicted
    assert dropped == ["cold"]


def test_pin_unknown_key_and_counters():
    b = DeviceBudget(limit_bytes=None)
    assert not b.pin(("nope",))
    b.unpin(("nope",))  # no-op
    b.register(("x",), 10, lambda: None)
    b.register(("x",), 30, lambda: None)  # re-register accumulates uploads
    b.note_prefetch(True)
    b.note_prefetch(False)
    s = b.stats()
    assert s["uploadBytes"] == 40
    assert s["prefetchHits"] == 1 and s["prefetchMisses"] == 1
    # pins survive a re-register (an in-flight user still holds the key)
    b.pin(("x",))
    b.register(("x",), 50, lambda: None)
    assert b.stats()["pinnedBytes"] == 50


# -- the limit is one device's (PERF.md PR 35) --------------------------------

def test_limit_is_held_against_the_fullest_device():
    """An entry spread over four devices counts a quarter against the
    limit and the tenant quota, and whole in ``resident_bytes`` and
    ``uploadBytes``; one on a single device counts whole in both, so with
    one device nothing moved."""
    b = DeviceBudget(limit_bytes=100, tenant_quota_bytes=60)
    dropped = []
    b.register(("wide",), 200, lambda: dropped.append("wide"),
               device_bytes=50, devices=4, tenant="t")
    b.register(("one",), 40, lambda: dropped.append("one"), tenant="u")
    assert dropped == []            # 50 + 40 a device, 240 in all
    s = b.stats()
    assert (s["residentBytes"], s["residentBytesMaxDevice"],
            s["devices"], s["uploadBytes"]) == (240, 90, 4, 240)
    assert b.resident_bytes == 240 and b.resident_bytes_max_device == 90
    assert s["tenantBytes"] == {"t": 50, "u": 40}   # under the quota
    b.register(("more",), 20, lambda: dropped.append("more"))
    assert dropped == ["wide"]      # 90 + 20 > 100: the coldest goes
    s = b.stats()
    assert (s["residentBytes"], s["residentBytesMaxDevice"],
            s["devices"], s["evictedBytes"]) == (60, 60, 1, 200)
    assert s["tenantBytes"] == {"u": 40}
    # one device: the two ledgers are one, as before
    one = DeviceBudget(limit_bytes=100)
    one.register(("a",), 60, lambda: None)
    one.register(("b",), 30, lambda: None)
    s = one.stats()
    assert s["residentBytes"] == s["residentBytesMaxDevice"] == 90
    assert s["devices"] == 1 and s["peakBytes"] == 90


@pytest.mark.parametrize("n_devices", [1, 4])
def test_block_registers_what_the_fullest_device_holds(wide, n_devices):
    """A stacked block over four devices registers a quarter of its
    bytes a device, read off the array's own shards; over one device,
    all of them: ``blockBytes`` = ``residentBytes`` either way."""
    import jax
    from pilosa_tpu.parallel.mesh_exec import default_mesh
    ex = Executor(wide, mesh=default_mesh(jax.devices()[:n_devices]))
    me = ex.mesh_exec
    me._budget = DeviceBudget()
    try:
        assert ex.execute("w", "Count(Row(f=1))")[0] > 0
        with me._sc_lock:
            (blk,) = me._blocks.values()
        assert blk.nbytes == blk.arrays.nbytes == 16 * 16 * (128 << 10)
        assert blk.devices == n_devices
        assert blk.device_bytes * n_devices == blk.nbytes
        s = me._budget.stats()
        assert s["residentBytes"] == me.stack_block_bytes() == blk.nbytes
        assert s["residentBytesMaxDevice"] == blk.device_bytes
        assert s["uploadBytes"] == blk.nbytes and s["devices"] == n_devices
    finally:
        ex.close()


def test_batch_temp_bound_is_of_one_device(monkeypatch):
    """The taxi-1b-mesh4 cell's figures: 16.9 GB a chip, 11.58 GB of
    stacks over four chips.  A quarter of them is on each, so the bound
    is the 4 GiB ceiling (12.9 GB are free); held against the sum, as
    before this PR, it was 4.27 GB, under the ceiling, and one more
    field would have made it 0.  On one chip the same stacks leave what
    they always left."""
    from pilosa_tpu.parallel import nodes
    limit, resident = 16_900_000_000, 11_580_000_000
    monkeypatch.setattr(nodes, "device_bytes_limit", lambda: limit)
    for devices, bound in ((4, nodes.BATCH_TEMP_BYTES),
                           (1, limit - resident - nodes.BATCH_TEMP_MARGIN)):
        budget = DeviceBudget()
        budget.register(("stacks",), resident, lambda: None,
                        device_bytes=resident // devices, devices=devices)
        monkeypatch.setattr("pilosa_tpu.storage.membudget.DEFAULT_BUDGET",
                            budget)
        assert nodes.batch_temp_bound() == bound
    assert bound == 4_246_258_176 < nodes.BATCH_TEMP_BYTES


# -- filter-less chunk fix (r5 advisor) -------------------------------------

def test_filterless_group_dispatches_single_chunk():
    mat = np.zeros((40000, 3), dtype=np.int32)
    chunks = list(_batch_chunks(mat, n_shards=0))
    assert [(lo, n) for lo, n, _ in chunks] == [(0, 40000)]
    assert chunks[0][2].shape[0] == 65536  # padded to pow2
    # with a filter (n_shards > 0) the cap still applies
    assert len(list(_batch_chunks(mat, n_shards=1))) > 1


# -- host staging cache -----------------------------------------------------

def test_staged_dense_caches_until_mutation():
    # a device budget the dense set does not fit (4 rows, 512 KiB):
    # while it fits nothing ever re-uploads, so staged_dense
    # deliberately skips caching
    f = Fragment(None, "i", "f", "standard", 0,
                 budget=DeviceBudget(limit_bytes=1 << 18))
    f.bulk_import(np.array([0, 1, 2]), np.array([5, 6, 7]))
    d1 = f.staged_dense()
    d2 = f.staged_dense()
    assert d1 is d2  # served from the stage cache
    assert (d1 == f.to_dense()).all()
    f.set_bit(3, 9)  # gen bump invalidates
    d3 = f.staged_dense()
    assert d3 is not d1
    assert (d3 == f.to_dense()).all()
    # budget eviction drops the cached expansion; next call rebuilds
    key = ("stage", id(f))
    assert key in HOST_STAGE_BUDGET._entries
    HOST_STAGE_BUDGET._entries[key][1]()
    assert f._stage is None
    assert (f.staged_dense() == f.to_dense()).all()
    f._drop_stage()
    assert key not in HOST_STAGE_BUDGET._entries


def test_staged_dense_disabled_at_zero_limit():
    old = HOST_STAGE_BUDGET.limit_bytes
    try:
        HOST_STAGE_BUDGET.limit_bytes = 0
        f = Fragment(None, "i", "f", "standard", 0,
                     budget=DeviceBudget(limit_bytes=1 << 18))
        f.bulk_import(np.array([0]), np.array([1]))
        assert f.staged_dense() is not f.staged_dense()
        assert f._stage is None
    finally:
        HOST_STAGE_BUDGET.limit_bytes = old


def test_staged_dense_transient_under_unlimited_device_budget():
    # nothing can evict -> no re-upload to accelerate -> no cache growth
    f = Fragment(None, "i", "f", "standard", 0)  # DEFAULT_BUDGET, no limit
    old = DEFAULT_BUDGET.limit_bytes
    try:
        DEFAULT_BUDGET.limit_bytes = None
        f.bulk_import(np.array([0]), np.array([1]))
        assert f.staged_dense() is not f.staged_dense()
        assert f._stage is None
    finally:
        DEFAULT_BUDGET.limit_bytes = old


# -- residency-aware slicing ------------------------------------------------

@pytest.fixture
def wide(rng):
    """16-shard index: wide enough that the 8-virtual-device test mesh
    can split it into two mesh-width slices."""
    h = Holder(None)
    idx = h.create_index("w", track_existence=False)
    f = idx.create_field("f")
    n = 40_000
    f.import_bits(rng.integers(0, 10, size=n),
                  rng.integers(0, 16 * SHARD_WIDTH, size=n))
    return h


def test_shard_schedule_slices_and_orders_by_residency(wide, monkeypatch):
    # this test exercises the DENSE slicing machinery; compressed
    # residency would shrink the working set under the budget and
    # (correctly) stop carving slices — pin the dense form
    monkeypatch.setattr(fragment, "COMPRESSED_RESIDENT", False)
    ex = Executor(wide, use_mesh=True)
    me = ex.mesh_exec
    shards = list(range(16))
    keys = [("f", "standard")]
    old = DEFAULT_BUDGET.limit_bytes
    try:
        # unlimited budget: one slice, identical to the unsliced path
        DEFAULT_BUDGET.limit_bytes = None
        assert me.shard_schedule(wide, "w", [keys], shards).slices == \
            [shards]
        # 16 shards x 16 rows x 128KB = 32MB working set, 4MB a device
        # of the mesh's eight; a budget of 1.5MB a device (12MB over
        # the mesh) must carve mesh-width slices
        per_device = (12 << 20) // me.n_devices
        DEFAULT_BUDGET.limit_bytes = per_device
        sched = me.shard_schedule(wide, "w", [keys], shards)
        assert sched.slices == [shards[:8], shards[8:]]
        assert sched.max_slice_len == 8
        # stage the SECOND slice; the next schedule drains it first
        me._placed_groups(keys, wide, "w", shards[8:])
        sched = me.shard_schedule(wide, "w", [keys], shards)
        assert sched.slices == [shards[8:], shards[:8]]
        # streamed execution over the schedule equals the unbudgeted run
        want = None
        for limit in (None, per_device):
            DEFAULT_BUDGET.limit_bytes = limit
            got = ex.execute("w", "Count(Union(Row(f=1), Row(f=3)))")
            if want is None:
                want = got
            assert got == want
        assert DEFAULT_BUDGET.stats()["prefetchHits"] + \
            DEFAULT_BUDGET.stats()["prefetchMisses"] > 0
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()


# -- budgeted-eviction differential ----------------------------------------

def test_budgeted_run_matches_unbudgeted(wide, rng):
    """The differential query corpus under a budget that forces eviction
    (and streaming) mid-batch returns results identical to the
    unbudgeted run — pinned entries are never popped mid-dispatch."""
    h = wide
    idx = h.indexes["w"]
    b = idx.create_field("b")
    v = idx.create_field("v", FieldOptions(type="int", min=-500, max=500))
    n = 30_000
    cols = rng.integers(0, 16 * SHARD_WIDTH, size=n)
    b.import_bits(rng.integers(0, 6, size=n), cols)
    vcols = np.unique(cols[: n // 2])
    v.import_values(vcols, rng.integers(-500, 500, size=vcols.size))
    idx.add_existence(cols)

    # the differential grammar references fields a/b/v; alias a -> f
    qrng = np.random.default_rng(4321)
    queries = [gen_query(qrng).replace("Row(a=", "Row(f=")
               .replace("Rows(a", "Rows(f").replace("TopN(a", "TopN(f")
               for _ in range(12)]
    batches = []
    i = 0
    while i < len(queries):
        take = int(qrng.integers(1, 4))
        batches.append(" ".join(queries[i: i + take]))
        i += take

    ex = Executor(h, use_mesh=True)
    old = DEFAULT_BUDGET.limit_bytes
    try:
        DEFAULT_BUDGET.limit_bytes = None
        want = [_norm(r) for bt in batches for r in ex.execute("w", bt)]
        # under the whole set's compressed bytes, 2.0 MB now that a
        # (field, view) is resident once however many key lists read it
        # and a key's compressed fragments are one shape group: 1 MB
        # over the mesh, and the limit is one device's
        DEFAULT_BUDGET.limit_bytes = (1 << 20) // ex.mesh_exec.n_devices
        DEFAULT_BUDGET.shrink_to_limit()
        ev0 = DEFAULT_BUDGET.evictions
        got = [_norm(r) for bt in batches for r in ex.execute("w", bt)]
        assert got == want
        assert DEFAULT_BUDGET.evictions > ev0, \
            "budget never evicted: the differential exercised nothing"
        assert DEFAULT_BUDGET.stats()["pinnedBytes"] == 0, \
            "pins leaked past their dispatch"
    finally:
        DEFAULT_BUDGET.limit_bytes = old
        ex.close()
