"""The request thread is a partition (docs/observability.md "Layer
spans"): every instant of a served query's ``http.query`` lies in one of
``http.query.self``, ``query.plan``, ``query.wait``, ``query.fetch`` and
``query.exec``, handed to the stats client in one call a request; and
``/debug/vars`` → ``hostThreads``, each host thread's CPU time by role
(docs/observability.md "Host threads"); and ``scripts/trace_gaps.py``'s
request-thread arithmetic."""

import http.client
import os
import sys
import threading
import time

import pytest

from pilosa_tpu.utils import hostthreads
from pilosa_tpu.utils.hostthreads import HOST_THREADS, ROLES, RoleTotals
from pilosa_tpu.utils.stats import StatsClient
from pilosa_tpu.utils.tracing import RequestTimings, request_span

from test_observability import _req, make_server

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
import trace_gaps  # noqa: E402

PARTS = ("http.query.self", "query.plan", "query.wait", "query.fetch",
         "query.exec")
# prepared replays (Count / Sum / TopN templates) and classic requests
# (a Row result, or a template the prepared cache refuses)
PREPARED = ["Count(Row(f=1))", "Count(Intersect(Row(f=1), Row(f=2)))",
            "TopN(f, Row(f=1), n=2)", "Sum(Row(f=1), field=v)",
            "Count(Row(f=1)) Sum(Row(f=2), field=v)"]
CLASSIC = ["Row(f=1)", "Count(Row(f=1)) Row(f=2)", "Set(5, f=2)"]


def _query(port, q):
    return _req(port, "POST", "/index/rp/query", q)[0]["results"]


class _Recorder:
    """Wraps a live stats client's ``timing`` and ``timings``: every
    call from a thread other than the dispatcher's, with what it
    carried."""

    def __init__(self, stats, monkeypatch):
        self.calls = []
        self._lock = threading.Lock()
        for kind in ("timing", "timings"):
            real = getattr(stats, kind)
            monkeypatch.setattr(stats, kind, self._wrap(kind, real))

    def _wrap(self, kind, real):
        def call(*args, **kwargs):
            if threading.current_thread().name != "ptpu-dispatch":
                with self._lock:
                    self.calls.append((kind, args))
            return real(*args, **kwargs)
        return call

    def take(self):
        with self._lock:
            out, self.calls = self.calls, []
        return out


def _settle(srv, n_queries):
    """Wait for the handler threads to book the queries answered: the
    reply is on the wire before the request's timings are handed
    over."""
    stats = srv.api.stats
    for _ in range(400):
        if stats.timing_totals("http.query")[0] >= n_queries:
            return
        time.sleep(0.005)
    raise AssertionError("requests not booked")


@pytest.fixture(scope="module", params=[True, False],
                ids=["whole-query", "per-stage"])
def served(request, tmp_path_factory):
    srv = make_server(tmp_path_factory.mktemp("partition"),
                      whole_query=request.param)
    port = srv.port
    _req(port, "POST", "/index/rp", {})
    _req(port, "POST", "/index/rp/field/f", {})
    _req(port, "POST", "/index/rp/field/v",
         {"options": {"type": "int", "min": 0, "max": 1000}})
    _query(port, "Set(1, f=1) Set(2, f=1) Set(3, f=2) Set(1048577, f=1) "
                 "Set(1048577, f=2) Set(1, v=7) Set(1048577, v=30)")
    for q in PREPARED + CLASSIC:         # compile, and build the templates
        _query(port, q)
    yield srv
    srv.close()


def _served_queries(srv, monkeypatch, queries):
    """Serve ``queries`` one after another; the calls their request
    threads made to the stats client (once every query served before
    them is booked, so no earlier request's hand-over is among them)."""
    stats = srv.api.stats
    _settle(srv, stats.count_value("query"))
    rec = _Recorder(stats, monkeypatch)
    sent = stats.timing_totals("http.query")[0]
    for q in queries:
        _query(srv.port, q)
    _settle(srv, sent + len(queries))
    return rec.take()


@pytest.mark.parametrize("path", ["prepared", "classic"])
def test_the_five_segments_make_up_each_request(served, monkeypatch, path):
    queries = PREPARED if path == "prepared" else CLASSIC
    stats = served.api.stats
    hits0 = stats.count_value("query.prepared.hit")
    calls = _served_queries(served, monkeypatch, queries)
    hits = stats.count_value("query.prepared.hit") - hits0
    if path == "prepared":
        assert hits == len(queries)
    else:
        assert hits == 0
    requests = [args[0] for kind, args in calls if kind == "timings"
                and any(p[0] == "http.query" for p in args[0])]
    assert len(requests) == len(queries)
    for pairs in requests:
        seconds: dict = {}
        for name, s, *_ in pairs:
            seconds[name] = seconds.get(name, 0.0) + s
        assert seconds["query.exec"] >= 0
        assert seconds["http.query.self"] >= 0
        total = sum(seconds.get(name, 0.0) for name in PARTS)
        assert abs(seconds["http.query"] - total) <= 1e-6, seconds
        # every request planned and fetched
        assert seconds.get("query.plan", 0) > 0
        assert "query.fetch" in seconds


def test_one_wait_a_ticket(served):
    """``query.wait`` counts once for each ticket a request thread
    submits: one a whole-query request, one a call group per stage."""
    srv = served
    stats, b = srv.api.stats, srv.api.executor.batcher
    sent = stats.timing_totals("http.query")[0]
    w0 = stats.timing_totals("query.wait")[0]
    t0 = b.batch_size_hist.snapshot()["sum"]
    for q in PREPARED:
        _query(srv.port, q)
    _settle(srv, sent + len(PREPARED))
    waits = stats.timing_totals("query.wait")[0] - w0
    tickets = b.batch_size_hist.snapshot()["sum"] - t0
    assert waits == tickets
    assert waits >= len(PREPARED)
    if not srv.config.whole_query:
        # the last query's two call groups are two tickets
        assert waits > len(PREPARED)


def test_one_timings_call_a_served_query(served, monkeypatch):
    """A request's timings reach the stats client in one ``timings``
    call, so its thread takes the stats lock for timings at most twice
    on the prepared path (that call, and the ``query.prepared`` timer)
    — seven times before: ``http.request``, ``http.query``,
    ``http.query.self``, the tenant's, ``query.prepared``,
    ``query.plan`` and ``query.fetch`` each took it."""
    calls = _served_queries(served, monkeypatch, PREPARED)
    kinds = [kind for kind, _ in calls]
    assert kinds.count("timings") == len(PREPARED)
    assert len(kinds) <= 2 * len(PREPARED) < 7 * len(PREPARED)


def test_request_span_falls_back_to_its_stats_outside_a_request():
    stats = StatsClient()
    with request_span("query.wait", stats, kind="count"):
        pass
    assert stats.timing_totals("query.wait")[0] == 1
    rt = RequestTimings()
    token = rt.install()
    try:
        with request_span("query.wait", stats):
            time.sleep(0.001)
        with request_span("query.fetch", stats):
            pass
    finally:
        rt.uninstall(token)
    assert stats.timing_totals("query.wait")[0] == 1     # held back
    assert [p[0] for p in rt.pending] == ["query.wait", "query.fetch"]
    spans = sum(p[1] for p in rt.pending)
    assert rt.exec_seconds(spans + 0.5) == pytest.approx(0.5)
    assert rt.exec_seconds(0.0) == 0.0
    rt.timing("http.query", 1.0, exemplar="abc")
    rt.flush(stats)
    assert rt.pending == []
    assert stats.timing_totals("query.wait")[0] == 2
    assert stats.timing_totals("http.query") == (1, 1.0)


# -- hostThreads ---------------------------------------------------------------

def test_host_threads_over_http(served):
    port = served.port
    first, _ = _req(port, "GET", "/debug/vars")
    for q in PREPARED:
        _query(port, q)
    second, _ = _req(port, "GET", "/debug/vars")
    a, b = first["hostThreads"], second["hostThreads"]
    assert set(b["roles"]) == set(ROLES)
    assert b["wallSeconds"] > a["wallSeconds"] and b["readSeconds"] > 0
    # the request thread reading /debug/vars and the dispatcher
    assert b["roles"]["request"]["threads"] >= 1
    assert b["roles"]["dispatcher"]["threads"] == 1
    for role in ROLES:
        assert b["roles"][role]["cpuSeconds"] >= \
            a["roles"][role]["cpuSeconds"]
        if b["source"] == "schedstat":
            assert b["roles"][role]["runqueueSeconds"] >= \
                a["roles"][role]["runqueueSeconds"] >= 0
        else:
            assert b["roles"][role]["runqueueSeconds"] is None
    assert b["roles"]["request"]["cpuSeconds"] > \
        a["roles"]["request"]["cpuSeconds"]


def test_a_closed_connection_stays_in_request(served):
    """A keep-alive connection's thread works, is read while alive, then
    ends: its CPU stays with ``request`` once it is gone."""
    conn = http.client.HTTPConnection("localhost", served.port, timeout=60)
    try:
        for q in PREPARED * 2:
            conn.request("POST", "/index/rp/query", q.encode())
            conn.getresponse().read()
        alive = HOST_THREADS.snapshot()["roles"]["request"]
    finally:
        conn.close()
    for _ in range(400):
        gone = HOST_THREADS.snapshot()["roles"]["request"]
        if gone["threads"] < alive["threads"]:
            break
        time.sleep(0.005)
    assert gone["threads"] < alive["threads"]
    assert gone["cpuSeconds"] >= alive["cpuSeconds"]
    assert HOST_THREADS.snapshot()["roles"]["request"]["cpuSeconds"] >= \
        gone["cpuSeconds"]


def test_host_threads_under_concurrent_churn():
    """Threads that register, work and retire while snapshots are taken:
    no snapshot fails, and the role's CPU never goes backwards."""
    ht = hostthreads.HostThreads()
    stop = threading.Event()

    def conn():
        ht.register("request")
        try:
            sum(i * i for i in range(20000))
        finally:
            ht.retire()

    def churn():
        while not stop.is_set():
            ts = [threading.Thread(target=conn) for _ in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=10)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    workers = [threading.Thread(target=churn)
               for _ in range(2 * (os.cpu_count() or 1))]
    try:
        for w in workers:
            w.start()
        seen = []
        deadline = time.monotonic() + 1.5
        while time.monotonic() < deadline:
            seen.append(ht.snapshot()["roles"]["request"]["cpuSeconds"])
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert len(seen) > 1 and seen == sorted(seen)
    final = ht.snapshot()["roles"]["request"]
    assert final["threads"] == 0 and final["cpuSeconds"] >= seen[-1] > 0


def test_role_totals_arithmetic():
    t = RoleTotals()
    # first sight: whole figures count
    out = t.observe({1: ("request", 100, 10), 2: ("native", 50, 5)})
    assert out["request"] == [1, 100, 10] and out["native"] == [1, 50, 5]
    assert out["python"] == [0, 0, 0] and out["dispatcher"] == [0, 0, 0]
    # deltas accumulate; a thread gone leaves its last figures
    out = t.observe({1: ("request", 160, 12)})
    assert out["request"] == [1, 160, 12] and out["native"] == [0, 50, 5]
    # a connection thread retires with its final figures (above what a
    # read saw) and lives on a moment as a plain python thread
    t.retire(1, "request", 170, 13)
    out = t.observe({1: ("python", 175, 13)})
    assert out["request"] == [0, 170, 13]
    assert out["python"] == [1, 5, 0]
    # a retire that read older figures than a snapshot keeps the larger
    t.observe({3: ("request", 40, 4)})
    t.retire(3, "request", 30, 3)
    out = t.observe({})
    assert out["request"] == [0, 210, 17] and out["python"] == [0, 5, 0]
    # a thread first seen as python that registers: what it counted
    # stays with python, the rest goes to request
    t.observe({4: ("python", 20, 1)})
    out = t.observe({4: ("request", 50, 3)})
    assert out["python"] == [0, 25, 1] and out["request"] == [1, 240, 19]
    # counters that go backwards are another thread with the same id
    out = t.observe({4: ("request", 8, 1)})
    assert out["request"] == [1, 248, 20]
    # a retire of a thread no read saw counts whole
    t.retire(5, "request", 7, 0)
    assert t.observe({4: ("request", 8, 1)})["request"] == [1, 255, 20]


def test_role_totals_never_go_backwards():
    t = RoleTotals()
    seq = [
        {1: ("python", 10, 0), 2: ("native", 5, 1)},
        {1: ("request", 20, 1), 2: ("native", 9, 1), 3: ("python", 4, 0)},
        {2: ("native", 12, 2), 3: ("dispatcher", 6, 0)},
        {3: ("python", 9, 0), 6: ("request", 2, 0)},
        {},
    ]
    last = {r: (0, 0) for r in ROLES}
    for readings in seq:
        out = t.observe(readings)
        for r in ROLES:
            assert out[r][1] >= last[r][0] and out[r][2] >= last[r][1]
            last[r] = (out[r][1], out[r][2])
    # everything any thread did is somewhere
    assert sum(v[0] for v in last.values()) == 20 + 12 + 9 + 2


def test_stat_fallback_reads_ticks(tmp_path, monkeypatch):
    """Where ``schedstat`` is absent: utime + stime of ``stat``, in clock
    ticks, and no runqueue figure."""
    task = tmp_path / "77"
    task.mkdir()
    (task / "stat").write_bytes(
        b"77 (a (b) c) S 1 1 1 0 -1 4194560 5 0 0 0 30 12 0 0 20 0 1 0")
    monkeypatch.setattr(hostthreads, "TASKS", str(tmp_path))
    cpu, rq = hostthreads._stat(77)
    assert cpu == int(42 * hostthreads._TICK_NS) and rq == 0


def test_gil_held_read_reads_what_python_reads(tmp_path):
    """The libc reader (the GIL held through the read) returns a file's
    bytes as Python's own read does, and raises OSError where there is
    no file."""
    read = hostthreads._libc_reader()
    assert read is not None
    f = tmp_path / "stat"
    f.write_bytes(b"77 (x) S 1 2 3\n")
    assert read(str(f)) == hostthreads._py_read(str(f)) == f.read_bytes()
    tid = threading.get_native_id()
    assert read(f"/proc/self/task/{tid}/stat").startswith(
        f"{tid} (".encode())
    with pytest.raises(OSError):
        read(str(tmp_path / "gone"))


# -- scripts/trace_gaps.py: the request threads ------------------------------

def test_trace_gaps_request_threads():
    # host_lines' form: one line a thread, keyed by (index, name) —
    # every Python thread's line is named alike
    host = {
        (1, "python3"): [("http.query", 0.0, 100.0),
                         ("query.plan", 10.0, 20.0),
                         ("query.wait", 20.0, 70.0),
                         ("query.fetch", 70.0, 90.0),
                         ("executor.execute", 5.0, 95.0)],
        (2, "python3"): [("http.query", 40.0, 140.0),
                         ("query.wait", 50.0, 80.0)],
        (3, "python3"): [("dispatch.idle", 0.0, 100.0)],
        (4, "python3"): [("query.wait", 0.0, 10.0)],    # no http.query
    }
    lines = trace_gaps.request_lines(host)
    assert sorted(lines) == [(1, "python3"), (2, "python3")]
    segs = {line: trace_gaps.flatten(spans,
                                     {"http.query": trace_gaps.QUERY_REST})
            for line, spans in lines.items()}
    rest = trace_gaps.QUERY_REST
    assert segs[(1, "python3")] == [
        (rest, 0.0, 10.0), ("query.plan", 10.0, 20.0),
        ("query.wait", 20.0, 70.0), ("query.fetch", 70.0, 90.0),
        (rest, 90.0, 100.0)]
    gaps = [("a -> b", 30.0, 20.0),     # line 1 waits; 2 outside, then in
            ("b -> c", 85.0, 10.0)]     # 1 fetch 5, rest 5; 2 rest
    totals, per_gap = trace_gaps.threads_in(gaps, segs)
    assert totals == {"query.wait": 20.0, trace_gaps.OUTSIDE: 10.0,
                      "query.fetch": 5.0, rest: 10.0 + 5.0 + 10.0}
    assert per_gap[0] == {"query.wait": 1.0, trace_gaps.OUTSIDE: 0.5,
                          rest: 0.5}
    assert per_gap[1] == {"query.fetch": 0.5, rest: 1.5}
    assert trace_gaps.threads_in(gaps, {}) == ({}, [{}, {}])
