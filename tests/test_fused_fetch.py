"""A fused launch's results cross to the host once (ISSUE 34,
parallel/fetch.py, docs/batching.md "Scatter and fetch"): the scatter
hands every ticket views of ONE shared host copy and enqueues nothing
on the device; per-shard kinds keep their device slice a ticket; a lone
ticket's path is what it was."""

import sys
import threading
import types

import jax
import numpy as np
import pytest
from jax._src import array as jax_array
from jax._src import dispatch as jax_dispatch

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor import executor as executor_mod
from pilosa_tpu.parallel import fetch
from pilosa_tpu.parallel.batcher import DispatchBatcher
from pilosa_tpu.parallel.fetch import HostView, SharedFetch, fetch_parts
from pilosa_tpu.server.handler import serialize_result
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.utils import devobs

N = 4
JOIN_S = 60

# one query a ticket, by the reducer kind its launch runs
QUERIES = {
    "count": lambda k: f"Count(Intersect(Row(f={k}), Row(f={k + 3})))",
    "bsi_sum": lambda k: f"Sum(Row(v > {97 * k + 5}), field=v)",
    "row_counts": lambda k: f"TopN(f, Row(f={k}), n=4)",
    "segments": lambda k: f"Row(f={k})",
    "segments+count": lambda k: f"Row(f={k}) Count(Row(f={k + 1}))",
}
REDUCED = ("count", "bsi_sum", "row_counts")


@pytest.fixture(scope="module")
def holder():
    rng = np.random.default_rng(34)
    h = Holder(None)
    idx = h.create_index("b", track_existence=False)
    f = idx.create_field("f")
    f.import_bits(rng.integers(0, 40, size=6000),
                  rng.integers(0, 3 * SHARD_WIDTH, size=6000))
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    cols = np.unique(rng.integers(0, 3 * SHARD_WIDTH, size=900))
    v.import_values(cols, rng.integers(0, 1000, size=cols.size))
    yield h
    h.close()


@pytest.fixture(scope="module")
def lone(holder):
    """The answers of launches that fuse nothing."""
    ex = Executor(holder, use_mesh=True, dispatch_batch=False)

    def answer(q):
        return serialize_result(ex.execute("b", q))
    yield answer
    ex.close()


def _fusing(holder, n, **kw):
    """An executor whose dispatcher holds its tickets until ``n`` wait:
    ``n`` threads, one query each, make one fused launch."""
    return Executor(holder, use_mesh=True, dispatch_batch=True,
                    dispatch_batch_max=n, dispatch_batch_window_us=20e6,
                    **kw)


def _together(ex, queries):
    """Each query from a thread of its own, at once: (answers, errors)
    by position; no thread may outlast JOIN_S."""
    out, errs = [None] * len(queries), [None] * len(queries)
    barrier = threading.Barrier(len(queries))

    def run(i):
        barrier.wait()
        try:
            out[i] = serialize_result(ex.execute("b", queries[i]))
        except Exception as e:
            errs[i] = e
    ts = [threading.Thread(target=run, args=(i,), daemon=True)
          for i in range(len(queries))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(JOIN_S)
    assert not any(t.is_alive() for t in ts), "a ticket's thread hangs"
    return out, errs


def _counts():
    return devobs.FETCHES.transfers, devobs.FETCHES.shared_tickets


@pytest.fixture
def scatter_calls(monkeypatch):
    """What the dispatcher thread asked of jax inside ``_scatter``:
    eager slices of a device array and eagerly dispatched primitives."""
    calls = {"getitem": 0, "primitive": 0, "scatters": 0}
    inside = threading.local()
    scatter = DispatchBatcher._scatter
    getitem = jax_array.ArrayImpl.__getitem__
    apply_primitive = jax_dispatch.apply_primitive

    def _scatter(self, *a, **kw):
        inside.flag = True
        calls["scatters"] += 1
        try:
            return scatter(self, *a, **kw)
        finally:
            inside.flag = False

    def _getitem(self, idx):
        if getattr(inside, "flag", False):
            calls["getitem"] += 1
        return getitem(self, idx)

    def _apply_primitive(prim, *args, **params):
        if getattr(inside, "flag", False):
            calls["primitive"] += 1
        return apply_primitive(prim, *args, **params)

    monkeypatch.setattr(DispatchBatcher, "_scatter", _scatter)
    monkeypatch.setattr(jax_array.ArrayImpl, "__getitem__", _getitem)
    monkeypatch.setattr(jax_dispatch, "apply_primitive", _apply_primitive)
    return calls


# -- (a), (b), (f): reduced kinds, both ticket surfaces --------------------

@pytest.mark.parametrize("whole_query", [True, False],
                         ids=["whole-query", "per-stage"])
@pytest.mark.parametrize("kind", REDUCED)
def test_fused_launch_is_one_transfer(holder, lone, scatter_calls, kind,
                                      whole_query):
    """N tickets of one fused launch: the lone launches' answers, ONE
    transfer, N - 1 tickets served from it, and a scatter that makes no
    jax call."""
    queries = [QUERIES[kind](k) for k in range(N)]
    want = [lone(q) for q in queries]
    ex = _fusing(holder, N, whole_query=whole_query)
    try:
        t0, s0 = _counts()
        got, errs = _together(ex, queries)
        t1, s1 = _counts()
        assert errs == [None] * N
        assert got == want
        assert ex.batcher.fused_launches == 1
        assert ex.batcher.single_launches == 0
        assert (t1 - t0, s1 - s0) == (1, N - 1)
        assert scatter_calls == {"getitem": 0, "primitive": 0,
                                 "scatters": 1}
    finally:
        ex.close()


# -- (c): many threads, one launch -----------------------------------------

def test_32_threads_resolve_one_launch_with_one_transfer(holder, lone):
    n = 32
    queries = [QUERIES["count"](k) for k in range(n)]
    want = [lone(q) for q in queries]
    ex = _fusing(holder, n)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t0, s0 = _counts()
        got, errs = _together(ex, queries)
        t1, s1 = _counts()
    finally:
        sys.setswitchinterval(old)
        ex.close()
    assert errs == [None] * n
    assert got == want
    assert ex.batcher.fused_launches == 1
    assert (t1 - t0, s1 - s0) == (1, n - 1)


def test_shared_fetch_under_contention(monkeypatch):
    """64 threads ask one SharedFetch at once, the interpreter switching
    as often as it can: one ``device_get``, every view its own rows."""
    n = 64
    gets = []
    shim = types.SimpleNamespace(
        device_get=lambda arrs: gets.append(1) or jax.device_get(arrs))
    monkeypatch.setattr(fetch, "jax", shim)
    table = np.arange(n * 3, dtype=np.int32).reshape(n, 3)
    shared = SharedFetch([jax.numpy.asarray(table)])
    views = [HostView(shared, 0, i, 1) for i in range(n)]
    got = [None] * n
    barrier = threading.Barrier(n)

    def run(i):
        barrier.wait()
        (got[i],) = fetch_parts([views[i]])
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    t0, s0 = _counts()
    try:
        ts = [threading.Thread(target=run, args=(i,), daemon=True)
              for i in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(JOIN_S)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    t1, s1 = _counts()
    assert len(gets) == 1
    assert (t1 - t0, s1 - s0) == (1, n - 1)
    for i in range(n):
        assert got[i].shape == (1, 3)
        assert (got[i] == table[i:i + 1]).all()


# -- (d): a fetch that raises ----------------------------------------------

def test_a_failed_fetch_is_every_tickets_exception(holder, monkeypatch):
    ex = _fusing(holder, N)
    boom = RuntimeError("the transfer failed")

    def device_get(arrs):
        raise boom
    try:
        # compile first, so that only the fetch is at stake below
        _together(ex, [QUERIES["count"](k) for k in range(N)])
        monkeypatch.setattr(fetch, "jax",
                            types.SimpleNamespace(device_get=device_get))
        t0, s0 = _counts()
        got, errs = _together(
            ex, [QUERIES["count"](k + 7) for k in range(N)])
        t1, s1 = _counts()
    finally:
        ex.close()
    assert got == [None] * N
    assert all(e is boom for e in errs), errs
    # the one attempt is counted; nobody was served
    assert (t1 - t0, s1 - s0) == (1, 0)


# -- (e): per-shard kinds keep a device slice a ticket ---------------------

@pytest.mark.parametrize("whole_query", [True, False],
                         ids=["whole-query", "per-stage"])
def test_fused_segments_keep_a_device_slice_a_ticket(
        holder, lone, scatter_calls, whole_query):
    queries = [QUERIES["segments"](k) for k in range(N)]
    want = [lone(q) for q in queries]
    ex = _fusing(holder, N, whole_query=whole_query)
    try:
        t0, s0 = _counts()
        got, errs = _together(ex, queries)
        t1, s1 = _counts()
        assert errs == [None] * N
        assert got == want
        assert ex.batcher.fused_launches == 1
        # nothing is shared: each ticket fetches its own rows
        assert (t1 - t0, s1 - s0) == (N, 0)
        assert scatter_calls["scatters"] == 1
        assert scatter_calls["getitem"] >= N
        assert scatter_calls["getitem"] % N == 0    # a slice a part
    finally:
        ex.close()


def test_a_program_of_both_takes_each_node_by_its_kind(
        holder, lone, scatter_calls, monkeypatch):
    """A ``Row`` and a ``Count`` in one request, fused: the segments
    node keeps a device slice a ticket, the count node is fetched
    once."""
    queries = [QUERIES["segments+count"](k) for k in range(N)]
    want = [lone(q) for q in queries]
    ex = _fusing(holder, N)
    seen = []
    monkeypatch.setattr(
        executor_mod, "fetch_parts", lambda parts: (
            seen.append([type(p) for p in parts]) or fetch_parts(parts)))
    try:
        t0, s0 = _counts()
        got, errs = _together(ex, queries)
        t1, s1 = _counts()
    finally:
        ex.close()
    assert errs == [None] * N
    assert got == want
    assert ex.batcher.fused_launches == 1
    # a transfer a ticket for its segments, one for the launch's counts
    assert (t1 - t0, s1 - s0) == (N + 1, N - 1)
    assert scatter_calls["scatters"] == 1
    assert scatter_calls["getitem"] >= N
    assert len(seen) == N
    for kinds in seen:
        assert HostView in kinds
        assert any(issubclass(k, jax.Array) for k in kinds)


# -- (g): a lone ticket ----------------------------------------------------

@pytest.mark.parametrize("kind", REDUCED + ("segments",))
def test_a_lone_ticket_fetches_its_own_device_parts(
        holder, lone, scatter_calls, monkeypatch, kind):
    ex = Executor(holder, use_mesh=True, dispatch_batch=True,
                  dispatch_batch_window_us=100)
    q = QUERIES[kind](5)
    want = lone(q)
    seen = []
    monkeypatch.setattr(
        executor_mod, "fetch_parts", lambda parts: (
            seen.append(list(parts)) or fetch_parts(parts)))
    try:
        t0, s0 = _counts()
        got = serialize_result(ex.execute("b", q))
        t1, s1 = _counts()
    finally:
        ex.close()
    assert got == want
    assert ex.batcher.single_launches == 1
    assert ex.batcher.fused_launches == 0
    assert scatter_calls["scatters"] == 0
    assert (t1 - t0, s1 - s0) == (1, 0)
    (parts,) = seen
    assert parts and all(isinstance(p, jax.Array) for p in parts)
