"""Cross-query dynamic batching (parallel/batcher.py, docs/batching.md):
differential correctness under concurrency, the lone ticket's launch,
queued-deadline drop-out, knob plumbing, and the client-abort stat."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.server.handler import serialize_result
from pilosa_tpu.server.server import Config, Server
from pilosa_tpu.storage import FieldOptions, Holder
from pilosa_tpu.utils.deadline import DeadlineExceeded, QueryContext


@pytest.fixture(scope="module")
def corpus_holder():
    rng = np.random.default_rng(11)
    h = Holder(None)
    idx = h.create_index("b", track_existence=False)
    f = idx.create_field("f")
    f.import_bits(rng.integers(0, 32, size=4000),
                  rng.integers(0, 3 * SHARD_WIDTH, size=4000))
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    cols = np.unique(rng.integers(0, 3 * SHARD_WIDTH, size=800))
    v.import_values(cols, rng.integers(0, 1000, size=cols.size))
    yield h
    h.close()


def _mixed_corpus(n):
    out = []
    for i in range(n):
        out += [
            f"Count(Row(f={i % 32}))",
            f"Row(f={(i * 5) % 32})",
            f"Count(Intersect(Row(f={i % 32}), Row(f={(i + 3) % 32})))",
            f"TopN(f, Row(f={(i + 1) % 32}), n=4)",
            f"Sum(Row(v > {(i * 83) % 1000}), field=v)",
        ]
    return out


def _run_threaded(ex, queries, n_threads):
    """Execute the corpus from n_threads concurrent clients; results are
    serialized to JSON text so comparison is byte-level."""
    out = [None] * len(queries)
    errs = []
    barrier = threading.Barrier(n_threads)

    def worker(k):
        barrier.wait()
        for i in range(k, len(queries), n_threads):
            try:
                out[i] = json.dumps(
                    serialize_result(ex.execute("b", queries[i])))
            except Exception as e:  # surfaced below, not swallowed
                errs.append((queries[i], repr(e)))
    ts = [threading.Thread(target=worker, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs[:3]
    return out


def test_batched_vs_off_byte_identical(corpus_holder):
    """The acceptance differential: a mixed Count/Row/Intersect/TopN/Sum
    corpus from >=8 concurrent threads is byte-identical between
    dispatch-batch on and off — and the on-run actually fused."""
    queries = _mixed_corpus(16)
    ex_on = Executor(corpus_holder, use_mesh=True, dispatch_batch=True,
                     dispatch_batch_window_us=20000)
    ex_off = Executor(corpus_holder, use_mesh=True, dispatch_batch=False)
    try:
        got = _run_threaded(ex_on, queries, 8)
        want = _run_threaded(ex_off, queries, 8)
        assert got == want
        assert ex_on.batcher.fused_launches > 0, \
            "8 concurrent threads never fused a launch"
        # off-mode batcher is pure delegation: no dispatcher activity
        assert ex_off.batcher.fused_launches == 0
        assert ex_off.batcher.single_launches == 0
    finally:
        ex_on.close()
        ex_off.close()


def test_solo_query_is_one_unfused_launch(corpus_holder):
    """A lone ticket launches alone, at its own rows (B = 1 of the
    program a pack would run): singleton launches, no fused ones."""
    ex = Executor(corpus_holder, use_mesh=True, dispatch_batch=True,
                  dispatch_batch_window_us=100)
    try:
        [n] = ex.execute("b", "Count(Row(f=3))")
        ex_off = Executor(corpus_holder, use_mesh=True,
                          dispatch_batch=False)
        try:
            assert ex.execute("b", "Count(Row(f=3))") == \
                ex_off.execute("b", "Count(Row(f=3))")
        finally:
            ex_off.close()
        assert ex.batcher.single_launches >= 1
        assert ex.batcher.fused_launches == 0
        hist = ex.batcher.batch_size_hist.snapshot()
        assert hist["le_1"] == hist["count"]  # every batch was size 1
    finally:
        ex.close()


def test_expired_ticket_dropped_before_launch(corpus_holder):
    """A ticket whose deadline expires while queued in the batch window
    is dropped BEFORE the fused launch (DeadlineExceeded to its waiter),
    while a healthy ticket sharing the window still gets its answer."""
    ex = Executor(corpus_holder, use_mesh=True, dispatch_batch=True,
                  dispatch_batch_window_us=300_000)  # 0.3 s window
    try:
        ex.execute("b", "Count(Row(f=1))")  # warm compiles (solo)
        results, errors = [], []

        def doomed():
            # budget far shorter than the window: expires while queued
            try:
                ex.execute("b", "Count(Row(f=2))",
                           ctx=QueryContext(0.05))
            except DeadlineExceeded as e:
                errors.append(str(e))

        def healthy():
            results.append(ex.execute("b", "Count(Row(f=2))")[0])

        t1 = threading.Thread(target=doomed)
        t2 = threading.Thread(target=healthy)
        t1.start()
        t2.start()
        t1.join(timeout=30)
        t2.join(timeout=30)
        assert errors and "deadline" in errors[0]
        off = Executor(corpus_holder, use_mesh=True, dispatch_batch=False)
        try:
            assert results == [off.execute("b", "Count(Row(f=2))")[0]]
        finally:
            off.close()
        assert ex.batcher.expired_drops >= 1
        # the doomed ticket is absent from the launch: whatever batch ran
        # carried only the healthy query
        hist = ex.batcher.batch_size_hist.snapshot()
        assert hist["le_inf"] == 0 and hist["count"] >= 1
    finally:
        ex.close()


def test_queued_expiry_maps_to_504_via_server(tmp_path):
    """End to end: with a batch window longer than the query budget, the
    queued expiry surfaces as HTTP 504 (the deadline drop-out satellite)."""
    srv = Server(Config(data_dir=str(tmp_path / "d"), bind="localhost:0",
                        anti_entropy_interval=0,
                        dispatch_batch_window_us=400_000))
    try:
        srv.open()

        def post(path, body, timeout=60):
            req = urllib.request.Request(
                f"http://localhost:{srv.port}{path}", method="POST",
                data=body.encode())
            try:
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    return resp.status, resp.read()
            except urllib.error.HTTPError as e:
                return e.code, e.read()

        assert post("/index/dl", "{}")[0] == 200
        assert post("/index/dl/field/f", "{}")[0] == 200
        # writes don't ride the batcher; the timed read below does
        assert post("/index/dl/query", "Set(1, f=1)")[0] == 200
        code, body = post("/index/dl/query?timeout=0.05",
                          "Count(Row(f=1))")
        assert code == 504, body
        assert b"deadline" in body
        snap = json.loads(urllib.request.urlopen(
            f"http://localhost:{srv.port}/debug/vars",
            timeout=30).read())
        assert snap["dispatchBatcher"]["expiredDrops"] >= 1
        assert snap["counts"]["dispatch.expired_drop"] >= 1
    finally:
        srv.close()


def test_knob_plumbing_env_and_debug_vars(tmp_path, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_BATCH", "false")
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_BATCH_MAX", "7")
    monkeypatch.setenv("PILOSA_TPU_DISPATCH_BATCH_WINDOW_US", "123")
    cfg = Config.from_env()
    assert cfg.dispatch_batch is False
    assert cfg.dispatch_batch_max == 7
    assert cfg.dispatch_batch_window_us == 123.0
    monkeypatch.delenv("PILOSA_TPU_DISPATCH_BATCH")
    srv = Server(Config(data_dir=str(tmp_path / "k"), bind="localhost:0",
                        anti_entropy_interval=0, dispatch_batch_max=7,
                        dispatch_batch_window_us=123))
    try:
        srv.open()
        b = srv.api.executor.batcher
        assert b.enabled and b.max_batch == 7
        snap = json.loads(urllib.request.urlopen(
            f"http://localhost:{srv.port}/debug/vars",
            timeout=30).read())
        assert snap["dispatchBatcher"]["maxBatch"] == 7
        assert snap["dispatchBatcher"]["windowUs"] == 123.0
        # /metrics carries the batch-size histogram + window-wait summary
        text = urllib.request.urlopen(
            f"http://localhost:{srv.port}/metrics",
            timeout=30).read().decode()
        assert "pilosa_tpu_dispatch_batch_size_bucket" in text
        assert "pilosa_tpu_dispatch_window_wait_seconds_count" in text
    finally:
        srv.close()


def test_client_abort_counted_not_traced(tmp_path, capfd):
    """A client that disconnects mid-response yields an http.client_abort
    stat, not a traceback (the BrokenPipeError satellite)."""
    import http.client

    srv = Server(Config(data_dir=str(tmp_path / "a"), bind="localhost:0",
                        anti_entropy_interval=0))
    try:
        srv.open()

        def post(path, body):
            conn = http.client.HTTPConnection("localhost", srv.port,
                                              timeout=30)
            conn.request("POST", path, body=body.encode())
            resp = conn.getresponse()
            resp.read()
            conn.close()
            return resp.status

        assert post("/index/ab", "{}") == 200
        assert post("/index/ab/field/f", "{}") == 200
        assert post("/index/ab/query", " ".join(
            f"Set({c}, f=0)" for c in range(500))) == 200
        # ask for a large response and slam the socket before reading it
        import socket
        for _ in range(3):
            s = socket.create_connection(("localhost", srv.port),
                                         timeout=30)
            q = b"Row(f=0)"
            s.sendall(b"POST /index/ab/query HTTP/1.1\r\n"
                      b"Host: localhost\r\n"
                      b"Content-Length: " + str(len(q)).encode() +
                      b"\r\n\r\n" + q)
            # reset instead of FIN: pending response data -> RST/EPIPE in
            # the handler's write path
            s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         __import__("struct").pack("ii", 1, 0))
            s.close()
        deadline = time.monotonic() + 10
        aborts = 0
        while time.monotonic() < deadline:
            aborts = srv.stats.snapshot()["counts"].get(
                "http.client_abort", 0)
            if aborts >= 1:
                break
            time.sleep(0.05)
        assert aborts >= 1, "client abort was never counted"
        err = capfd.readouterr().err
        assert "BrokenPipeError" not in err
        assert "ConnectionResetError" not in err
    finally:
        srv.close()
