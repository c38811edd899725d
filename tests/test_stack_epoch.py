"""The device epoch (docs/ingest.md "Device epoch"): a mesh stack-cache
hit that walks no fragment, and whole-query params that ride with the
launch.

The rule under test: an entry of ``MeshExecutor._stack_cache`` carries
the device epoch read BEFORE the walk that validated it; while a later
lookup reads the same epoch, the entry is served without looking at a
fragment (``stackCache.fastHits``), and anything that can move an input
of ``_stack_token`` moves the epoch, so the next lookup walks
(``stackCache.walks``) and never serves a stale stack.  Whoever adds an
input to ``_stack_token`` adds its bump and a case to ``MUTATIONS``."""

import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from conftest import over_budget_limit

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.ingest.committer import GroupCommitter
from pilosa_tpu.ops import kernels
from pilosa_tpu.parallel import default_mesh
from pilosa_tpu.parallel import mesh_exec as mesh_exec_mod
from pilosa_tpu.parallel import wholequery as wholequery_mod
from pilosa_tpu.storage import Holder
from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
from pilosa_tpu.utils import devobs

from test_observability import _req, make_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_SHARDS = 3
QUERY = "Count(Intersect(Row(f=1), Row(g=1)))"
TOPN = "TopN(f, Row(g=1), n=3)"
KEYS = [("f", "standard"), ("g", "standard")]


class Ref:
    """The numpy reference: the set columns of every (field, row)."""

    def __init__(self):
        self.bits: dict[tuple, np.ndarray] = {}

    def set(self, field, rows, cols):
        rows, cols = np.asarray(rows), np.asarray(cols)
        for r in np.unique(rows):
            key = (field, int(r))
            self.bits[key] = np.union1d(
                self.bits.get(key, np.zeros(0, dtype=np.int64)),
                cols[rows == r])

    def clear(self, field, row, col):
        self.bits[(field, row)] = np.setdiff1d(self.bits[(field, row)],
                                               [col])

    def drop_field(self, field):
        self.bits = {k: v for k, v in self.bits.items() if k[0] != field}

    def drop_shard(self, field, shard):
        for k, v in self.bits.items():
            if k[0] == field:
                self.bits[k] = v[v // SHARD_WIDTH != shard]

    def cols(self, field, row):
        return self.bits.get((field, row), np.zeros(0, dtype=np.int64))

    def count(self, g_row=1):
        return int(np.intersect1d(self.cols("f", 1),
                                  self.cols("g", g_row)).size)

    def topn(self, n):
        g1 = self.cols("g", 1)
        counts = {r: int(np.intersect1d(v, g1).size)
                  for (fld, r), v in self.bits.items() if fld == "f"}
        ranked = sorted(((c, -r) for r, c in counts.items() if c),
                        reverse=True)
        return [(-r, c) for c, r in ranked[:n]]


def build(holder=None, index="e", g_shards=N_SHARDS):
    """f over every shard; g over the first ``g_shards`` only, so a g
    fragment can still appear under a shard the index already has."""
    rng = np.random.default_rng(29)
    h = holder if holder is not None else Holder(None)
    idx = h.create_index(index)
    f, g = idx.create_field("f"), idx.create_field("g")
    ref = Ref()
    n = 6000
    cols = rng.integers(0, N_SHARDS * SHARD_WIDTH, size=n)
    f_rows = rng.integers(0, 4, size=n)
    f.import_bits(f_rows, cols)
    ref.set("f", f_rows, cols)
    gcols = cols[cols < g_shards * SHARD_WIDTH]
    g_rows = rng.integers(0, 3, size=gcols.size)
    g.import_bits(g_rows, gcols)
    ref.set("g", g_rows, gcols)
    idx.add_existence(np.unique(cols))
    return h, idx, ref


@pytest.fixture
def loaded():
    h, idx, ref = build()
    ex = Executor(h, use_mesh=True)
    yield h, idx, ref, ex
    ex.close()


@pytest.fixture
def knobs():
    """Restore the process-wide state a case flips."""
    from pilosa_tpu.storage import fragment
    saved = (DEFAULT_BUDGET.limit_bytes, kernels.CONTAINER_KERNELS,
             fragment.QUARANTINE_SEEN)
    yield
    (DEFAULT_BUDGET.limit_bytes, kernels.CONTAINER_KERNELS,
     fragment.QUARANTINE_SEEN) = saved


def topn(ex):
    return [(p.id, p.count) for p in ex.execute("e", TOPN)[0]]


def counters(ex):
    return ex.mesh_exec.stack_fast_hits, ex.mesh_exec.stack_walks


def free_col(ref, shard=0):
    """A column of ``shard`` set in g row 1 but not in f row 1: setting
    it in f row 1 moves the query's answer by one."""
    g1 = ref.cols("g", 1)
    g1 = g1[g1 // SHARD_WIDTH == shard]
    return int(np.setdiff1d(g1, ref.cols("f", 1))[0])


def entry(ex, index="e"):
    key = (index, tuple(KEYS), tuple(range(N_SHARDS)))
    return ex.mesh_exec._stack_cache[key]


# -- (a) the hit walks nothing ------------------------------------------------

def test_repeat_query_walks_no_fragment(loaded, monkeypatch):
    h, _idx, ref, ex = loaded
    assert ex.execute("e", QUERY) == [ref.count()]
    assert topn(ex) == ref.topn(3)
    calls = []
    inside = []
    real_place = ex.mesh_exec._place_groups
    real_fragment = h.fragment

    def place(*a):
        inside.append(1)
        try:
            return real_place(*a)
        finally:
            inside.pop()

    def fragment(*a):
        if inside:
            calls.append(a)
        return real_fragment(*a)

    monkeypatch.setattr(ex.mesh_exec, "_place_groups", place)
    monkeypatch.setattr(h, "fragment", fragment)
    fh0, wk0 = counters(ex)
    for _ in range(3):
        assert ex.execute("e", QUERY) == [ref.count()]
    fh1, wk1 = counters(ex)
    assert calls == []
    assert (fh1 - fh0, wk1 - wk0) == (3, 0)
    # another program over its own warm stack: TopN's row_counts
    assert topn(ex) == ref.topn(3)
    # ... and the streaming scheduler's residency signal takes the same
    # fast check
    assert ex.mesh_exec._is_resident(KEYS, h, "e", list(range(N_SHARDS)))
    assert calls == []


# -- (b) one case per token input ---------------------------------------------

def _set(h, idx, ref, ex):
    col = free_col(ref)
    assert idx.field("f").set_bit(1, col)
    ref.set("f", [1], [col])


def _clear(h, idx, ref, ex):
    col = int(np.intersect1d(ref.cols("f", 1), ref.cols("g", 1))[0])
    assert idx.field("f").clear_bit(1, col)
    ref.clear("f", 1, col)


def _bulk_import(h, idx, ref, ex):
    cols = np.setdiff1d(ref.cols("g", 1), ref.cols("f", 1))[:50]
    idx.field("f").import_bits(np.ones(cols.size, dtype=np.int64), cols)
    ref.set("f", np.ones(cols.size, dtype=np.int64), cols)


def _row_growth(h, idx, ref, ex):
    fr = h.fragment("e", "f", "standard", 0)
    row = fr.n_rows + 3     # past _cap_rows: the stacked shape changes
    col = free_col(ref)
    assert idx.field("f").set_bit(row, col)
    assert fr.n_rows > row
    ref.set("f", [row], [col])
    # the answer has to move too, or a stale stack would pass
    assert idx.field("f").set_bit(1, col)
    ref.set("f", [1], [col])


def _ingest(h, ref, cols):
    com = GroupCommitter(h, flush_ms=0)     # inline flush per wait
    try:
        seq = com.submit("e", "f", rows=np.ones(len(cols), dtype=np.int64),
                         cols=np.asarray(cols))
        assert com.wait_flushed(seq)
    finally:
        com.close()
    ref.set("f", np.ones(len(cols), dtype=np.int64), cols)


def _ingest_flush(h, idx, ref, ex):
    _ingest(h, ref, [free_col(ref)])
    assert sum(fr.delta_bytes() for *_x, fr in h.iter_fragments("e")) > 0


def _journal_fold(h, idx, ref, ex):
    _ingest(h, ref, [free_col(ref)])
    assert ex.execute("e", QUERY) == [ref.count()]  # overlay applied
    for *_x, fr in h.iter_fragments("e"):
        with fr._lock:
            fr._fold_journal_locked()


def _quarantine(h, idx, ref, ex):
    h.fragment("e", "f", "standard", 1)._enter_quarantine(
        "test", persist=False)
    ref.drop_shard("f", 1)


def _new_shard_fragment(h, idx, ref, ex):
    # g had no fragment under the last shard; the index had the shard
    assert h.fragment("e", "g", "standard", N_SHARDS - 1) is None
    f1 = ref.cols("f", 1)
    col = int(f1[f1 // SHARD_WIDTH == N_SHARDS - 1][0])
    assert idx.field("g").set_bit(1, col)
    ref.set("g", [1], [col])


def _field_recreated(h, idx, ref, ex):
    idx.delete_field("g")
    ref.drop_field("g")
    g = idx.create_field("g")
    cols = ref.cols("f", 1)[::2]
    g.import_bits(np.ones(cols.size, dtype=np.int64), cols)
    ref.set("g", np.ones(cols.size, dtype=np.int64), cols)


def _device_budget(h, idx, ref, ex):
    # what server.py does for device-budget-mb: under a limit their
    # dense set does not fit (the largest such) these sparse fragments
    # turn compressed-resident, a new signature
    DEFAULT_BUDGET.limit_bytes = over_budget_limit(h)


def _container_kernels(h, idx, ref, ex):
    # what server.py does for container-kernels
    kernels.CONTAINER_KERNELS = "jnp" \
        if kernels.CONTAINER_KERNELS != "jnp" else "auto"


# a fold re-anchors the device forms and a knob re-shapes them; neither
# moves an answer, so their cases check the walk and the rebuilt stack
SAME_ANSWER = {"journal-fold", "device-budget-mb", "container-kernels"}
MUTATIONS = {
    "set": _set, "clear": _clear, "bulk-import": _bulk_import,
    "row-growth": _row_growth, "ingest-flush": _ingest_flush,
    "journal-fold": _journal_fold, "quarantine": _quarantine,
    "new-shard-fragment": _new_shard_fragment,
    "field-recreated": _field_recreated,
    "device-budget-mb": _device_budget,
    "container-kernels": _container_kernels,
}


@pytest.mark.parametrize("case", list(MUTATIONS))
def test_token_input_moves_the_epoch(case, knobs):
    g_shards = N_SHARDS - 1 if case == "new-shard-fragment" else N_SHARDS
    h, idx, ref = build(g_shards=g_shards)
    if case == "container-kernels":
        # compressed signatures: the largest budget the dense set
        # does not fit
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(h)
    ex = Executor(h, use_mesh=True)
    try:
        assert ex.execute("e", QUERY) == [ref.count()]
        fh0, wk0 = counters(ex)
        assert ex.execute("e", QUERY) == [ref.count()]
        assert counters(ex) == (fh0 + 1, wk0)       # engaged before it
        before, token0, out0 = ref.count(), entry(ex)[0], entry(ex)[1]
        MUTATIONS[case](h, idx, ref, ex)
        assert case in SAME_ANSWER or ref.count() != before
        wk1 = counters(ex)[1]
        assert ex.execute("e", QUERY) == [ref.count()]
        assert counters(ex)[1] == wk1 + 1           # it walked
        if case == "ingest-flush":
            # the overlay was applied to the resident stack, which was
            # not rebuilt: same token, same groups, newer ingest epochs
            assert entry(ex)[0] == token0 and entry(ex)[1] is out0
            assert any(entry(ex)[2])
        elif case != "container-kernels":
            assert entry(ex)[0] != token0
        assert topn(ex) == ref.topn(3)
        # ... and the entry it left is served fast again
        fh2, wk2 = counters(ex)
        assert ex.execute("e", QUERY) == [ref.count()]
        assert counters(ex) == (fh2 + 1, wk2)
    finally:
        ex.close()


# -- (c) the order: the epoch is read before the walk -------------------------

@pytest.mark.parametrize("when", ["before-walk", "after-walk"])
def test_write_between_epoch_read_and_store(loaded, monkeypatch, when):
    """A write that lands inside a lookup, after the epoch was read.
    Before the walk, the walk sees it and rebuilds; after the walk, this
    lookup answers without it (the write was not acknowledged when the
    lookup began).  Either way the entry keeps the epoch read at the
    top, older than the write's bump, so the next lookup walks again.
    An epoch read after the walk would stamp the old stack as current
    and serve it for ever."""
    h, idx, ref, ex = loaded
    assert ex.execute("e", QUERY) == [ref.count()]
    # a spurious bump (the token stays): the lookup walks and finds its
    # entry current
    h.fragment("e", "f", "standard", 0)._bump_device_epoch()
    real = ex.mesh_exec._stack_token
    old = ref.count()
    col = free_col(ref)

    def stack_token(*a):
        monkeypatch.setattr(ex.mesh_exec, "_stack_token", real)
        if when == "before-walk":
            idx.field("f").set_bit(1, col)
        out = real(*a)
        if when == "after-walk":
            idx.field("f").set_bit(1, col)
        return out

    monkeypatch.setattr(ex.mesh_exec, "_stack_token", stack_token)
    got = ex.execute("e", QUERY)
    ref.set("f", [1], [col])
    assert got == ([ref.count()] if when == "before-walk" else [old])
    assert entry(ex)[3] != h.device_epoch("e", KEYS)
    wk = counters(ex)[1]
    assert ex.execute("e", QUERY) == [ref.count()]
    assert counters(ex)[1] == wk + 1
    assert entry(ex)[3] == h.device_epoch("e", KEYS)


# -- (d) the served path ------------------------------------------------------

@pytest.mark.parametrize("n_devices", [1, 8])
def test_served_write_is_read_back(tmp_path, monkeypatch, n_devices):
    devices = jax.devices()[:n_devices]
    monkeypatch.setattr(mesh_exec_mod, "default_mesh",
                        lambda d=None: default_mesh(d or devices))
    srv = make_server(tmp_path)
    try:
        assert srv.api.executor.mesh_exec.n_devices == n_devices
        _h, _idx, ref = build(srv.holder)
        port = srv.port

        def query(pql):
            return _req(port, "POST", "/index/e/query", pql)[0]["results"]

        def stack_cache():
            return _req(port, "GET", "/debug/vars")[0]["stackCache"]

        assert query(QUERY) == [ref.count()]
        for _ in range(4):
            col = free_col(ref)
            sc0 = stack_cache()
            assert query(f"Set({col}, f=1)") == [True]    # acknowledged
            ref.set("f", [1], [col])
            assert query(QUERY) == [ref.count()]
            assert query(QUERY.replace("g=1", "g=2")) == [ref.count(2)]
            top = query(TOPN)[0]
            assert [(p["id"], p["count"]) for p in top] == ref.topn(3)
            sc1 = stack_cache()
            # the write sent the first Count to the walk; the second,
            # over the same stack, was served by the epoch alone
            assert sc1["walks"] > sc0["walks"]
            assert sc1["fastHits"] > sc0["fastHits"]
    finally:
        srv.close()


def test_writers_and_readers_share_the_epoch(loaded):
    """More threads than cores, a short switch interval, three seconds:
    every thread reads its own acknowledged writes back and never sees
    the count fall, whichever of them stamped the entry last."""
    h, idx, ref, ex = loaded
    base = ref.count()
    free = np.setdiff1d(ref.cols("g", 1), ref.cols("f", 1))
    n_threads = 2 * (os.cpu_count() or 4)
    lanes = np.array_split(free[:n_threads * 12], n_threads)
    deadline = time.monotonic() + 3.0
    errors, acked = [], []

    def worker(cols):
        try:
            seen, mine = base, 0
            for i, col in enumerate(cols):
                if time.monotonic() > deadline:
                    break
                if i % 2 == 0:      # every other read follows no write
                    assert idx.field("f").set_bit(1, int(col))
                    mine += 1
                    acked.append(col)
                got = ex.execute("e", QUERY)[0]
                assert got >= max(seen, base + mine), (got, seen, mine)
                seen = got
        except Exception as e:      # reported by the asserting thread
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(lane,))
               for lane in lanes]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert len(acked) >= n_threads
    assert ex.execute("e", QUERY) == [base + len(acked)]
    assert ex.mesh_exec.stack_walks > 1


# -- (e) eviction -------------------------------------------------------------

def test_budget_eviction_drops_the_entry(loaded, knobs):
    h, _idx, ref, ex = loaded
    assert ex.execute("e", QUERY) == [ref.count()]
    assert len(ex.mesh_exec._stack_cache) == 1
    DEFAULT_BUDGET.limit_bytes = 1      # every stack is over it
    DEFAULT_BUDGET.shrink_to_limit()
    assert len(ex.mesh_exec._stack_cache) == 0
    DEFAULT_BUDGET.limit_bytes = None
    wk = counters(ex)[1]
    assert ex.execute("e", QUERY) == [ref.count()]
    assert counters(ex)[1] == wk + 1
    assert len(ex.mesh_exec._stack_cache) == 1


# -- (f) params ride with the launch ------------------------------------------

@pytest.mark.parametrize("n_devices", [1, 8])
def test_host_params_ride_with_the_launch(monkeypatch, n_devices):
    h, _idx, ref = build()
    mesh = default_mesh(jax.devices()[:n_devices])
    ex = Executor(h, mesh=mesh)
    plain = Executor(h)
    seen = []
    real_call = wholequery_mod._InstrumentedWhole.__call__

    def call(self, mats, *flat, _launch_meta=None):
        seen.append(jax.tree_util.tree_leaves(mats))
        out = real_call(self, mats, *flat, _launch_meta=_launch_meta)
        for o in out:
            if o.sharding.is_fully_replicated:
                assert o.sharding.device_set == set(mesh.devices.flat)
        return out

    monkeypatch.setattr(wholequery_mod._InstrumentedWhole, "__call__", call)
    queries = [QUERY, TOPN,
               "Count(Union(Row(f=0), Row(g=2)))"]
    try:
        first = [ex.execute("e", q) for q in queries]
        assert first == [plain.execute("e", q) for q in queries]
        assert first[0] == [ref.count()] and topn(ex) == ref.topn(3)
        totals = devobs.COMPILES.totals()
        n_exec = len(ex.mesh_exec._cache)
        # other rows of the same programs: new params, nothing compiled
        again = ["Count(Intersect(Row(f=2), Row(g=0)))",
                 "TopN(f, Row(g=2), n=3)",
                 "Count(Union(Row(f=3), Row(g=1)))"]
        assert [ex.execute("e", q) for q in again] == \
            [plain.execute("e", q) for q in again]
        after = devobs.COMPILES.totals()
        assert (after["compiles"], after["retraces"]) == \
            (totals["compiles"], totals["retraces"])
        assert len(ex.mesh_exec._cache) == n_exec
        assert ex.wq_fallbacks == 0 and len(seen) == 7
        # what the program was handed: host matrices, int32, no
        # device_put of their own
        for leaves in seen:
            assert leaves and all(
                type(m) is np.ndarray and m.dtype == np.int32
                for m in leaves)
    finally:
        ex.close()
        plain.close()


# -- the counters' plumbing ---------------------------------------------------

def test_stack_fast_hit_share_reads_the_two_counters():
    """benchmark/layer_metrics/stack_fast_hit_share.json is data for the
    accepted ``vars_ratio`` reader and has its BENCHMARK.json entry."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        from readers import vars_ratio
    finally:
        sys.path.pop(0)
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           "stack_fast_hit_share.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "vars_ratio"
    before = {"stackCache": {"entries": 2, "fastHits": 100, "walks": 10}}
    after = {"stackCache": {"entries": 2, "fastHits": 397, "walks": 13}}
    ctx = {"spans": {"trace": {"before": before, "after": after, "n": 250}}}
    assert vars_ratio.read(spec, ctx) == pytest.approx(100 * 297 / 300)
    # a program without the counters (the parent): nothing, no raise
    bare = {"stackCache": {"entries": 2, "executables": 3}}
    ctx = {"spans": {"trace": {"before": bare, "after": bare, "n": 250}}}
    assert vars_ratio.read(spec, ctx) is None
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # additions come last and the sixteen metrics accepted before this
    # one keep their places, as does this one after them (a later PR
    # appends; benchmark/tests/test_layer_metrics_spans.py pins the
    # list and may not be edited by the PR that adds one)
    assert [m["name"] for m in bench["per_layer"][:16]] == [
        "handler_ms_mean", "prepared_hit_share", "wq_fallback_share",
        "launches_per_query", "compiles_in_window",
        "upload_bytes_per_query", "kernels_roofline", "device_idle_share",
        "handler_self_ms_mean", "plan_ms_mean", "ticket_wait_ms_mean",
        "dispatcher_ms_per_query", "place_ms_per_query",
        "enqueue_ms_per_query", "scatter_ms_per_query", "fetch_ms_mean"]
    entry_ = bench["per_layer"][16]
    assert entry_ == {
        "name": "stack_fast_hit_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "stack and place",
        "moves": "qps", "workloads": [w["name"] for w in bench["workloads"]]}


# -- a (field, view)'s rows are resident once -----------------------------------

def test_programs_share_a_fields_stacked_block():
    """Two key lists over one field stack it once (the second entry
    takes the first's block), ``stackCache.blockBytes`` counts it once,
    and an ingest flush journaled after the block was stacked reaches
    both entries — the one that overlays it and the one that finds the
    block already overlaid."""
    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ingest import GroupCommitter
    from pilosa_tpu.storage import Holder
    h = Holder(None)
    idx = h.create_index("s")
    idx.create_field("f")
    idx.create_field("g")
    rng = np.random.default_rng(5)
    cols = rng.integers(0, 3 * SHARD_WIDTH, 4000)
    rows_f, rows_g = rng.integers(0, 6, 4000), rng.integers(0, 3, 4000)
    h.index("s").field("f").import_bits(rows_f, cols)
    h.index("s").field("g").import_bits(rows_g, cols)
    f1, g1 = set(cols[rows_f == 1].tolist()), set(cols[rows_g == 1].tolist())
    queries = ("Count(Row(f=1))", "Count(Intersect(Row(g=1), Row(f=1)))")
    ex = Executor(h, use_mesh=True)
    com = GroupCommitter(h, flush_ms=0)
    mesh = ex.mesh_exec
    shards = [0, 1, 2]
    try:
        one = mesh._placed_groups([("f", "standard")], h, "s", shards)
        alone = mesh.stack_block_bytes()
        two = mesh._placed_groups([("g", "standard"), ("f", "standard")],
                                  h, "s", shards)
        assert two[0][1][1] is one[0][1][0]           # f: the same block
        assert mesh.stack_block_bytes() == alone + two[0][1][0].nbytes
        assert [ex.execute("s", q)[0] for q in queries] == \
            [len(f1), len(g1 & f1)]
        # new columns in row 1 of f and g, journaled, not re-staged
        fresh = set((np.arange(7) * 1000 + 17).tolist())
        for field in ("f", "g"):
            seq = com.submit("s", field, rows=np.ones(7, dtype=np.int64),
                             cols=np.array(sorted(fresh)))
            com.wait_flushed(seq)
        assert sum(fr.delta_bytes()
                   for *_x, fr in h.iter_fragments("s")) > 0
        assert [ex.execute("s", q)[0] for q in queries] == \
            [len(f1 | fresh), len((g1 | fresh) & (f1 | fresh))]
        one = mesh._placed_groups([("f", "standard")], h, "s", shards)
        two = mesh._placed_groups([("g", "standard"), ("f", "standard")],
                                  h, "s", shards)
        assert two[0][1][1] is one[0][1][0]           # still one block
        assert [ex.execute("s", q)[0] for q in queries] == \
            [len(f1 | fresh), len((g1 | fresh) & (f1 | fresh))]
    finally:
        ex.close()


@pytest.fixture
def two_fields():
    """Index ``s`` over three shards with set fields f and g, one
    executor, and f's and g's budget keys read off its blocks."""
    from pilosa_tpu.core import SHARD_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage import Holder
    h = Holder(None)
    idx = h.create_index("s")
    rng = np.random.default_rng(6)
    cols = rng.integers(0, 3 * SHARD_WIDTH, 3000)
    for name, n_rows in (("f", 6), ("g", 3)):
        idx.create_field(name).import_bits(
            rng.integers(0, n_rows, 3000), cols)
    ex = Executor(h, use_mesh=True)
    yield h, ex, ex.mesh_exec
    ex.close()


F, G = ("f", "standard"), ("g", "standard")
KEY_LISTS = {"f": [F], "gf": [G, F], "g": [G]}


def _registered(mesh) -> int:
    """What the device budget counts for this executor's blocks."""
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    return sum(e[0] for k, e in DEFAULT_BUDGET._entries.items()
               if k[:2] == ("block", id(mesh)))


@pytest.mark.parametrize("leave", ["trim", "evict", "write", "close"])
def test_the_budget_counts_each_block_once(two_fields, leave):
    """The block is the budget's unit: registered once however many
    entries read it, counted while ANY entry reads it, and gone from
    the budget exactly when no entry does — after the entry that
    placed it is trimmed, after the budget evicts it (every reader
    goes), after a write displaces it, after close()."""
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    h, ex, mesh = two_fields
    shards = [0, 1, 2]
    for keys in KEY_LISTS.values():
        mesh._placed_groups(keys, h, "s", shards)
    blocks = {b.bkey[1]: b for b in mesh._blocks.values()}
    assert set(blocks) == {F, G} and len(mesh._stack_cache) == 3
    both = blocks[F].nbytes + blocks[G].nbytes
    assert _registered(mesh) == mesh.stack_block_bytes() == both
    if leave == "trim":
        # the entries that placed f and g go; a new list reads both
        mesh.stack_cache_max = 1
        mesh._placed_groups([F, G], h, "s", shards)
        assert [ck[1] for ck in mesh._stack_cache] == [(F, G)]
        assert _registered(mesh) == mesh.stack_block_bytes() == both
        # ... and a list that reads g alone drops f with its last reader
        mesh._placed_groups(KEY_LISTS["g"], h, "s", shards)
        assert set(mesh._blocks) == {blocks[G].bkey}
        assert _registered(mesh) == blocks[G].nbytes
    elif leave == "evict":
        DEFAULT_BUDGET._entries[blocks[F].skey][1]()   # as eviction does
        DEFAULT_BUDGET.unregister(blocks[F].skey)
        assert [ck[1] for ck in mesh._stack_cache] == [(G,)]
        assert _registered(mesh) == mesh.stack_block_bytes() == \
            blocks[G].nbytes
    elif leave == "write":
        h.index("s").field("f").set_bit(1, 5)
        out = mesh._placed_groups(KEY_LISTS["f"], h, "s", shards)
        now = {b.bkey[1]: b for b in mesh._blocks.values()}
        assert now[F] is not blocks[F] and now[G] is blocks[G]
        assert now[F].arrays is out[0][1][0]
        assert blocks[F].skey not in DEFAULT_BUDGET._entries
        # [g, f] read the displaced block: gone, rebuilt from the new
        assert [ck[1] for ck in mesh._stack_cache] == [(G,), (F,)]
        assert _registered(mesh) == mesh.stack_block_bytes() == both
    else:
        ex.close()
        assert _registered(mesh) == 0 and not mesh._blocks


def test_a_slice_pins_its_blocks(two_fields):
    """``_pin_stack`` pins every block of the slice's stack and names
    them for the unpin; a stack that is not cached pins nothing."""
    from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
    h, ex, mesh = two_fields
    mesh._placed_groups(KEY_LISTS["gf"], h, "s", [0, 1, 2])
    pinned = mesh._pin_stack(KEY_LISTS["gf"], "s", [0, 1, 2])
    assert sorted(pinned) == sorted(b.skey for b in mesh._blocks.values())
    assert all(DEFAULT_BUDGET._entries[k][2] == 1 for k in pinned)
    for k in pinned:
        DEFAULT_BUDGET.unpin(k)
    assert all(DEFAULT_BUDGET._entries[k][2] == 0 for k in pinned)
    assert mesh._pin_stack(KEY_LISTS["f"], "s", [0, 1]) == []
