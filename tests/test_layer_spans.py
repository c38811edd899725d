"""Layer spans (ISSUE 26, docs/observability.md "Layer spans"): one name
per layer boundary of the served path, on the profiler's clock and in
``/debug/vars`` ``timings``; stable program names; eager compiles in the
compile registry's count; ``scripts/trace_gaps.py``'s arithmetic."""

import glob
import os
import signal
import sys
import threading
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pilosa_tpu.parallel.mesh_exec import SHARD_AXIS, MeshExecutor
from pilosa_tpu.parallel.nodes import KINDS
from pilosa_tpu.parallel.wholequery import (PROGRAM_NAME_NODES,
                                            _InstrumentedWhole, program_name)
from pilosa_tpu.utils import devobs
from pilosa_tpu.utils.stats import StatsClient
from pilosa_tpu.utils.tracing import GLOBAL_TRACER, layer_span

from test_observability import _req, make_server

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
import trace_gaps  # noqa: E402

QUERIES = ["Count(Row(f=1))", "Count(Intersect(Row(f=1), Row(f=2)))",
           "TopN(f, Row(f=1), n=2)", "Sum(Row(f=1), field=v)"]
TIMINGS = ("http.query", "http.query.self", "query.plan", "query.fetch",
           "query.wait", "query.exec", "dispatch.idle", "dispatch.window",
           "dispatch.round", "dispatch.scatter", "dispatch.ticket_wait")
N_SERIAL, N_THREADS, N_EACH = 6, 4, 5


@contextmanager
def limit(seconds: int):
    """Fail, rather than hang, past ``seconds`` (the tests run on the
    worker's main thread)."""
    def _late(signum, frame):
        raise TimeoutError(f"no end after {seconds} s")
    old = signal.signal(signal.SIGALRM, _late)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _query(port, q, index="ls"):
    return _req(port, "POST", f"/index/{index}/query", q)[0]["results"]


def _burst(port, n_threads=N_THREADS, n_each=N_EACH):
    """The same template from several threads at once, so that launches
    fuse (the server's coalescing window is long)."""
    def run(k):
        for i in range(n_each):
            _query(port, f"Count(Row(f={1 + (i + k) % 2}))")
    ts = [threading.Thread(target=run, args=(k,)) for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()


def _settle(srv):
    """Wait until the handler threads have booked the requests already
    answered (a reply is on the wire before ``_observe`` hands the
    request's timings over, its spans' included) and the dispatcher its
    round."""
    stats = srv.api.stats
    b = srv.api.executor.batcher
    sent = stats.count_value("query")
    for _ in range(400):
        # the dispatcher hands a round's timings over once it has ended
        if stats.timing_totals("http.query")[0] >= sent \
                and not b._round_stats.pending:
            break
        time.sleep(0.005)


def _totals(srv) -> dict:
    """Count and sum of every layer timing, the ledger's seconds and the
    batcher's launch counts — read in process, at one instant, once
    ``_settle``."""
    stats = srv.api.stats
    b = srv.api.executor.batcher
    _settle(srv)
    out = {name: stats.timing_totals(name) for name in TIMINGS}
    out["ledger"] = devobs.LEDGER.aggregates()
    out["fused"] = b.fused_launches
    out["tickets"] = b.batch_size_hist.snapshot()["sum"]
    out["t"] = time.perf_counter()
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A server with a long coalescing window, a small index, and the
    totals before and after N served queries."""
    srv = make_server(tmp_path_factory.mktemp("layer_spans"),
                      dispatch_batch_window_us=20000.0)
    port = srv.port
    _req(port, "POST", "/index/ls", {})
    _req(port, "POST", "/index/ls/field/f", {})
    _req(port, "POST", "/index/ls/field/v",
         {"options": {"type": "int", "min": 0, "max": 1000}})
    _query(port, "Set(1, f=1) Set(2, f=1) Set(3, f=2) Set(1048577, f=1) "
                 "Set(1048577, f=2) Set(1, v=7) Set(1048577, v=30)")
    for q in QUERIES:                       # compile outside the deltas
        _query(port, q)
    _burst(port)
    before = _totals(srv)
    for i in range(N_SERIAL):
        _query(port, QUERIES[i % len(QUERIES)])
    _burst(port)
    after = _totals(srv)
    yield {"srv": srv, "port": port, "before": before, "after": after,
           "n": N_SERIAL + N_THREADS * N_EACH}
    srv.close()


def _delta(served, name):
    (c0, s0), (c1, s1) = served["before"][name], served["after"][name]
    return c1 - c0, s1 - s0


def _ledger(served, key):
    return served["after"]["ledger"][key] - served["before"]["ledger"][key]


@pytest.mark.parametrize("name", TIMINGS + ("dispatch.place",
                                            "dispatch.enqueue"))
def test_span_counts_what_its_boundary_implies(served, name):
    n = served["n"]
    rounds = _delta(served, "dispatch.round")[0]
    launches = _ledger(served, "launches")
    assert 1 <= launches <= n
    if name == "dispatch.place":
        # the ledger's total, not a timing: mesh_exec and wholequery hold
        # no stats client
        assert _ledger(served, "placeSecondsTotal") > 0
        return
    if name == "dispatch.enqueue":
        assert _ledger(served, "dispatchSecondsTotal") > 0
        # every launch also reports the wait its tickets had
        assert _ledger(served, "queueSecondsTotal") > 0
        return
    count, total = _delta(served, name)
    assert total > 0
    if name in ("http.query", "http.query.self", "query.plan",
                "query.fetch", "query.exec"):
        assert count == n
    elif name == "query.wait":
        # one a ticket, on the request thread that submitted it
        assert count == n
        assert count == served["after"]["tickets"] \
            - served["before"]["tickets"]
    elif name == "dispatch.ticket_wait":
        # one ticket a request on the whole-query path, each counted
        # once where its launch begins
        assert count == n
        assert count == served["after"]["tickets"] \
            - served["before"]["tickets"]
    elif name == "dispatch.round":
        assert launches <= count <= n
    elif name == "dispatch.window":
        assert count == rounds
    elif name == "dispatch.idle":
        assert 1 <= count <= rounds + 1
    elif name == "dispatch.scatter":
        fused = served["after"]["fused"] - served["before"]["fused"]
        assert fused >= 1           # the burst did coalesce
        assert count == fused


def test_sums_nest(served):
    wall = served["after"]["t"] - served["before"]["t"]
    parts = _ledger(served, "placeSecondsTotal") \
        + _ledger(served, "dispatchSecondsTotal") \
        + _delta(served, "dispatch.scatter")[1]
    busy = _delta(served, "dispatch.round")[1]
    assert 0 < parts <= busy <= wall
    assert 0 < _delta(served, "http.query.self")[1] \
        <= _delta(served, "http.query")[1]
    # a ticket's wait ends before its request does; the request
    # thread's wait holds the dispatcher's for it
    assert _delta(served, "dispatch.ticket_wait")[1] \
        <= _delta(served, "query.wait")[1] \
        <= _delta(served, "http.query")[1]


def test_dispatcher_time_is_partitioned(served):
    """Every instant of the dispatcher thread lies in one of idle, window
    and round: over a stretch that begins and ends just after a round,
    the three sums make up the wall."""
    srv, port = served["srv"], served["port"]
    _query(port, QUERIES[0])
    a = _totals(srv)
    deadline = time.perf_counter() + 1.0
    while time.perf_counter() < deadline:
        _burst(port, n_threads=3, n_each=2)
        time.sleep(0.05)
    _query(port, QUERIES[0])
    b = _totals(srv)
    wall = b["t"] - a["t"]
    summed = sum(b[k][1] - a[k][1] for k in
                 ("dispatch.idle", "dispatch.window", "dispatch.round"))
    assert abs(summed - wall) <= 0.05 * wall, (summed, wall)


@pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")
def test_profiler_trace_holds_the_spans(served, tmp_path):
    from jax.profiler import ProfileData
    port = served["port"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with limit(60):
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for q in QUERIES:
                _query(port, q)
            _burst(port, n_threads=2, n_each=2)
            time.sleep(0.05)        # an idle stretch that ends in the trace
            _query(port, QUERIES[0])
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        by_line: dict = {}
        roots, enqueues, scatters, jits = [], [], [], set()
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("dispatch."):
                        by_line.setdefault(i, set()).add(e.name)
                    if e.name == "dispatch.enqueue":
                        enqueues.append(dict(e.stats))
                    elif e.name == "dispatch.scatter":
                        scatters.append(dict(e.stats))
                    elif e.name == "http.query":
                        roots.append(dict(e.stats))
                    elif e.name.startswith("PjitFunction(ptpu_"):
                        jits.add(e.name)
    # one dispatcher thread: its spans share one host line
    (names,) = by_line.values()
    assert {"dispatch.enqueue", "dispatch.idle", "dispatch.round",
            "dispatch.window", "dispatch.place"} <= names
    assert roots and all(len(r["trace"]) == 16 for r in roots)
    assert len({r["trace"] for r in roots}) == len(roots)
    assert enqueues and all(
        {"kind", "sig", "rows", "rows_padded", "tickets", "compiled"}
        <= set(e) for e in enqueues)
    # one scatter a fused launch, tagged with the tickets it resolved
    fused = [e for e in enqueues if int(e["tickets"]) > 1]
    assert fused and len(scatters) == len(fused)
    assert sorted(int(e["tickets"]) for e in scatters) \
        == sorted(int(e["tickets"]) for e in fused)
    assert any(j.startswith("PjitFunction(ptpu_wq_") for j in jits)
    # the arithmetic half reads the same spans
    host = trace_gaps.host_lines(path)
    spans = trace_gaps.host_spans(host)
    assert {n for n, _, _ in spans} == names
    assert trace_gaps.overlap_ns(spans, trace_gaps.PARTITION) == 0
    # a request thread a line, though every Python thread's line has one
    # name: no two of a line's requests overlap, and the burst's two
    # threads are two lines
    lines = trace_gaps.request_lines(host)
    assert len(lines) >= 2
    assert sum(1 for spans in lines.values() for name, _, _ in spans
               if name == "http.query") == len(roots)
    for spans in lines.values():
        assert trace_gaps.overlap_ns(spans, ("http.query",)) == 0


def test_scatter_holds_no_dispatch_lock(served, monkeypatch):
    """``dispatch.scatter`` hands out views of one shared fetch and
    enqueues nothing, so the collective-launch lock — only the
    dispatcher thread launches in a burst of tickets — is free while
    the span is open."""
    from pilosa_tpu.parallel import batcher, mesh_exec
    held = []

    class Watched(batcher.HostView):
        __slots__ = ()

        def __init__(self, *a):
            held.append(mesh_exec._DISPATCH_LOCK.locked())
            super().__init__(*a)

    monkeypatch.setattr(batcher, "HostView", Watched)
    b = served["srv"].api.executor.batcher
    before = b.fused_launches
    with limit(60):
        _burst(served["port"])
    assert b.fused_launches > before
    assert held and not any(held)


@pytest.mark.parametrize("kind", KINDS)
def test_program_name_is_the_kind(kind):
    """The lowered module is ``jit_ptpu_<kind>`` whatever the executor,
    the plan or the shapes: one persistent-cache key per program."""
    texts = []
    for n in (8, 16):
        mesh = MeshExecutor()

        def block_fn(params, x):
            return jax.lax.psum(jnp.sum(x) + params[0], SHARD_AXIS)

        key = mesh._plan_key(kind, f"plan-{n}", (("f", "standard"),),
                             ((n, 4),))
        ex = mesh._jit_shard_map(key, block_fn, (P(), P(SHARD_AXIS)), P())
        assert ex.fn.__name__ == f"ptpu_{kind}"
        texts.append(ex.fn.lower(
            np.zeros(1, np.int32), np.zeros((n, 4), np.int32)).as_text())
        mesh.close()
    assert all(t.startswith(f"module @jit_ptpu_{kind} ") for t in texts)


def test_whole_query_program_names(served):
    from pilosa_tpu.executor.plan import ReduceNode
    assert program_name((ReduceNode("count", None),)) == "ptpu_wq_count"
    assert program_name((ReduceNode("row_counts", None, ("f", "standard")),
                         ReduceNode("bsi_sum", None, ("v", "bsi")))) \
        == "ptpu_wq_row_counts_bsi_sum"
    many = (ReduceNode("count", None),) * (PROGRAM_NAME_NODES + 3)
    assert program_name(many) == "ptpu_wq_" + "_".join(
        ["count"] * PROGRAM_NAME_NODES) + f"_n{PROGRAM_NAME_NODES + 3}"
    # the programs the served queries compiled carry those names
    cache = served["srv"].api.executor.mesh_exec._cache
    names = {e.fn.__name__ for e in cache.values()
             if isinstance(e, _InstrumentedWhole)}
    assert {"ptpu_wq_count", "ptpu_wq_row_counts",
            "ptpu_wq_bsi_sum"} <= names


def test_backend_compiles_count_an_eager_compile():
    """``devobs.COMPILES`` sees every executable jax builds, the eager
    ones of no instrumented boundary included."""
    devobs.COMPILES.listen()
    devobs.COMPILES.listen()            # idempotent
    before = devobs.COMPILES.totals()
    x = jnp.arange(7 * 11 * 13, dtype=jnp.int32)  # a shape nothing else has
    jax.lax.dynamic_slice(x, (3,), (5,)).block_until_ready()
    after = devobs.COMPILES.totals()
    assert after["backendCompiles"] >= before["backendCompiles"] + 1
    assert after["compiles"] == before["compiles"]
    # and counts each once, however often listen() was called
    again = devobs.COMPILES.totals()["backendCompiles"]
    jax.lax.dynamic_slice(x, (3,), (6,)).block_until_ready()
    assert devobs.COMPILES.totals()["backendCompiles"] == again + 1


def test_layer_span_primitive():
    stats = StatsClient()
    with layer_span("a.b", stats, rows=3) as s:
        s.tag(compiled=False)
        time.sleep(0.002)
    count, total = stats.timing_totals("a.b")
    assert count == 1 and total >= 0.002
    with layer_span("a.c"):             # the annotation alone
        pass
    assert stats.timing_totals("a.c") == (0, 0.0)


def test_no_annotation_is_made_while_no_profiler_records(tmp_path):
    """With tracing off a span holds no ``TraceAnnotation``; under a
    profiler it does, and so does every ``Tracer.span``."""
    assert layer_span("a.b")._ann is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        span = layer_span("a.b", rows=3)
        assert span._ann is not None
        with span as s, GLOBAL_TRACER.span("root"):
            s.tag(compiled=True)
    finally:
        jax.profiler.stop_trace()
    assert layer_span("a.b")._ann is None


def test_the_ledger_is_the_sink_of_place_spans_alone():
    before = devobs.LEDGER.aggregates()["placeSecondsTotal"]
    with layer_span("dispatch.place", devobs.LEDGER):
        time.sleep(0.002)
    assert devobs.LEDGER.aggregates()["placeSecondsTotal"] >= before + 0.002
    with pytest.raises(ValueError, match="dispatch.scatter"):
        with layer_span("dispatch.scatter", devobs.LEDGER):
            pass


@pytest.mark.parametrize("client", ["expvar", "statsd", "nop"])
def test_timings_takes_a_rounds_observations_at_once(client):
    """``StatsClient.timings``: the dispatcher's once-a-round hand-over
    reads like as many ``timing`` calls, in every client."""
    from pilosa_tpu.utils.stats import NopStatsClient, StatsdClient

    class Sock:
        sent = []

        def sendto(self, payload, addr):
            self.sent.append(payload)

    sock = Sock()
    stats = {"expvar": StatsClient, "nop": NopStatsClient,
             "statsd": lambda: StatsdClient(sock=sock)}[client]()
    stats.timings([("dispatch.round", 0.5), ("dispatch.ticket_wait", 0.25),
                   ("dispatch.ticket_wait", 0.75)])
    stats.timings([])
    want = {"nop": ((0, 0.0), (0, 0.0))}.get(client, ((1, 0.5), (2, 1.0)))
    assert (stats.timing_totals("dispatch.round"),
            stats.timing_totals("dispatch.ticket_wait")) == want
    assert len(sock.sent) == (3 if client == "statsd" else 0)


def test_a_prepared_miss_plans_twice_and_a_hit_once(served):
    """``query.plan`` is a plain block in ``prepared.attempt`` and another
    round parse and translate: a template's first request passes both."""
    srv, port = served["srv"], served["port"]

    def plans():
        _settle(srv)
        return srv.api.stats.timing_totals("query.plan")[0]

    c0 = plans()
    _query(port, "Count(Union(Row(f=1), Row(f=2)))")    # built, then run
    c1 = plans()
    _query(port, "Count(Union(Row(f=2), Row(f=1)))")    # replayed
    c2 = plans()
    _query(port, "Set(9, f=1)")         # no template: attempt, then parse
    c3 = plans()
    assert (c1 - c0, c2 - c1, c3 - c2) == (1, 1, 2)


# -- scripts/trace_gaps.py: the arithmetic on hand-made intervals ----------

def test_trace_gaps_flatten_and_attribute():
    spans = [
        ("dispatch.idle", 0, 100),
        ("dispatch.window", 100, 120),
        ("dispatch.round", 120, 300),
        ("dispatch.place", 130, 150),
        ("dispatch.enqueue", 150, 200),
        ("dispatch.scatter", 250, 290),
        ("dispatch.idle", 300, 400),
    ]
    segs = trace_gaps.flatten(list(reversed(spans)))
    assert segs == [
        ("dispatch.idle", 0, 100),
        ("dispatch.window", 100, 120),
        (trace_gaps.ROUND_REST, 120, 130),
        ("dispatch.place", 130, 150),
        ("dispatch.enqueue", 150, 200),
        (trace_gaps.ROUND_REST, 200, 250),
        ("dispatch.scatter", 250, 290),
        (trace_gaps.ROUND_REST, 290, 300),
        ("dispatch.idle", 300, 400),
    ]
    assert trace_gaps.overlap_ns(
        spans, ("dispatch.idle", "dispatch.window", "dispatch.round")) == 0
    assert trace_gaps.overlap_ns(
        spans + [("dispatch.round", 90, 110)],
        ("dispatch.idle", "dispatch.window", "dispatch.round")) == 20
    gaps = [("a -> b", 50, 60),         # idle 50, window 10
            ("b -> c", 240, 20),        # rest 10, scatter 10
            ("c -> d", 390, 30)]        # idle 10, nothing 20
    totals, per_gap = trace_gaps.attribute(gaps, segs)
    assert totals == {"dispatch.idle": 60, "dispatch.window": 10,
                      trace_gaps.ROUND_REST: 10, "dispatch.scatter": 10,
                      trace_gaps.UNNAMED: 20}
    assert per_gap[1] == ("b -> c", 20, {trace_gaps.ROUND_REST: 10,
                                         "dispatch.scatter": 10})
    assert trace_gaps.attribute([], segs) == ({}, [])
    assert trace_gaps.attribute(gaps[:1], []) == (
        {trace_gaps.UNNAMED: 60}, [("a -> b", 60, {trace_gaps.UNNAMED: 60})])


def test_trace_gaps_reads_host_spans_from_event_tuples():
    """The dispatcher's spans are the ``dispatch.*`` events of the line
    that holds its partition: a request thread's own launch (a kind the
    batcher does not take) stays on the request thread's line."""
    lines = {
        (3, "python"): [("dispatch.idle", 10.0, 15.0),
                        ("np.asarray(jax.Array)", 11.0, 12.0),
                        ("dispatch.round", 15.0, 20.0),
                        ("dispatch.enqueue", 16.0, 19.0)],
        (5, "python"): [("http.query", 0.0, 30.0),
                        ("dispatch.enqueue", 12.0, 14.0)],
    }
    assert trace_gaps.host_spans(lines) == [
        ("dispatch.idle", 10.0, 15.0), ("dispatch.round", 15.0, 20.0),
        ("dispatch.enqueue", 16.0, 19.0)]
    assert trace_gaps.request_lines(lines) == {
        (5, "python"): lines[(5, "python")]}
