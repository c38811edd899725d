"""HTTP server integration tests — the in-process harness of the reference
(test/pilosa.go test.Command: real server, ephemeral port) driving the real
HTTP surface (server/handler_test.go coverage)."""

import base64
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.server.server import Config, Server


@pytest.fixture
def srv(tmp_path):
    cfg = Config(data_dir=str(tmp_path / "data"), bind="localhost:0")
    s = Server(cfg)
    s.open()
    yield s
    s.close()


def call(srv, method, path, body=None, ctype="application/json", raw=False):
    url = f"http://localhost:{srv.port}{path}"
    data = body if isinstance(body, (bytes, type(None))) else \
        json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", ctype)
    with urllib.request.urlopen(req) as resp:
        payload = resp.read()
    if raw:
        return payload
    return json.loads(payload) if payload.strip() else {}


def call_err(srv, method, path, body=None):
    try:
        call(srv, method, path, body)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())
    raise AssertionError("expected HTTP error")


def test_home_version_info_status(srv):
    assert "message" in call(srv, "GET", "/")
    assert call(srv, "GET", "/version")["version"]
    assert call(srv, "GET", "/info")["shardWidth"] == 1 << 20
    st = call(srv, "GET", "/status")
    assert st["state"] == "NORMAL"
    assert len(st["nodes"]) == 1


def test_ddl_and_query_lifecycle(srv):
    assert call(srv, "POST", "/index/i", {}) == {}
    assert call(srv, "POST", "/index/i/field/f", {}) == {}
    # schema reflects both
    schema = call(srv, "GET", "/schema")["indexes"]
    assert schema[0]["name"] == "i"
    assert schema[0]["fields"][0]["name"] == "f"
    # write + read via PQL
    out = call(srv, "POST", "/index/i/query", b"Set(2, f=10)")
    assert out["results"] == [True]
    out = call(srv, "POST", "/index/i/query", b"Row(f=10)")
    assert out["results"][0]["columns"] == [2]
    out = call(srv, "POST", "/index/i/query", b"Count(Row(f=10))")
    assert out["results"] == [1]
    # DELETE
    assert call(srv, "DELETE", "/index/i/field/f") == {}
    assert call(srv, "DELETE", "/index/i") == {}
    assert call(srv, "GET", "/schema")["indexes"] == []


def test_errors(srv):
    code, body = call_err(srv, "POST", "/index/i/query", b"Row(f=1)")
    assert code == 400
    assert "index not found" in body["error"]
    call(srv, "POST", "/index/i", {})
    code, body = call_err(srv, "POST", "/index/i", {})
    assert code == 409
    code, body = call_err(srv, "GET", "/index/nope")
    assert code == 404
    code, body = call_err(srv, "POST", "/index/i/query", b"Row(f=")
    assert code == 400
    assert "parse error" in body["error"]
    # unknown path / wrong method
    code, _ = call_err(srv, "POST", "/definitely-not-a-route")
    assert code == 404
    code, _ = call_err(srv, "DELETE", "/schema")
    assert code == 405


def test_import_and_export(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/field/f/import", {
        "rowIDs": [1, 1, 2], "columnIDs": [10, 20, 10]})
    out = call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    assert out["results"] == [2]
    csv = call(srv, "GET", "/export?index=i&field=f&shard=0",
               raw=True).decode()
    assert set(csv.strip().split("\n")) == {"1,10", "1,20", "2,10"}


def test_import_values_and_sum(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/v",
         {"options": {"type": "int", "min": 0, "max": 1000}})
    call(srv, "POST", "/index/i/field/v/import", {
        "columnIDs": [1, 2, 3], "values": [10, 20, 30]})
    out = call(srv, "POST", "/index/i/query", b"Sum(field=v)")
    assert out["results"][0] == {"value": 60, "count": 3}


def test_import_roaring(srv):
    from pilosa_tpu.storage.roaring_io import pack_roaring

    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    blob = pack_roaring(np.array([0, 0, 3]), np.array([5, 9, 100]))
    call(srv, "POST", "/index/i/field/f/import-roaring/0", blob,
         ctype="application/octet-stream")
    out = call(srv, "POST", "/index/i/query", b"Row(f=0)")
    assert out["results"][0]["columns"] == [5, 9]
    out = call(srv, "POST", "/index/i/query", b"Row(f=3)")
    assert out["results"][0]["columns"] == [100]
    # JSON-wrapped views variant
    blob2 = pack_roaring(np.array([7]), np.array([42]))
    call(srv, "POST", "/index/i/field/f/import-roaring/1", {
        "views": {"": base64.b64encode(blob2).decode()}})
    out = call(srv, "POST", "/index/i/query", b"Row(f=7)")
    assert out["results"][0]["columns"] == [(1 << 20) + 42]


def test_schema_roundtrip(srv):
    schema = {"indexes": [{
        "name": "myidx",
        "options": {"keys": False, "trackExistence": True},
        "fields": [
            {"name": "a", "options": {"type": "set"}},
            {"name": "b", "options": {"type": "int", "min": -5, "max": 5}},
        ],
    }]}
    call(srv, "POST", "/schema", schema)
    got = call(srv, "GET", "/schema")["indexes"]
    assert got[0]["name"] == "myidx"
    assert {f["name"] for f in got[0]["fields"]} == {"a", "b"}
    # idempotent
    call(srv, "POST", "/schema", schema)


def test_persistence_across_restart(tmp_path):
    cfg = Config(data_dir=str(tmp_path / "data"), bind="localhost:0")
    s = Server(cfg)
    s.open()
    call(s, "POST", "/index/i", {})
    call(s, "POST", "/index/i/field/f", {})
    call(s, "POST", "/index/i/query", b"Set(7, f=3)")
    s.close()

    s2 = Server(cfg)
    s2.open()
    out = call(s2, "POST", "/index/i/query", b"Row(f=3)")
    assert out["results"][0]["columns"] == [7]
    s2.close()


def test_metrics_and_debug_vars(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/query", b"Set(1, f=1)")
    text = call(srv, "GET", "/metrics", raw=True).decode()
    assert "pilosa_tpu_query" in text
    call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    snap = call(srv, "GET", "/debug/vars")
    assert snap["counts"]["query"] >= 1
    # phase-level attribution (r3 verdict #10): exec/fetch timings,
    # budget + cache state
    assert snap["timings"]["query.exec"]["count"] >= 1
    assert snap["timings"]["query.fetch"]["count"] >= 1
    assert "residentBytes" in snap["deviceBudget"]
    assert snap["preparedCache"]["misses"] + \
        snap["preparedCache"]["hits"] >= 1
    assert snap["stackCache"]["executables"] >= 1


def test_options_column_attrs_over_http(srv):
    call(srv, "POST", "/index/oi", {})
    call(srv, "POST", "/index/oi/field/f", {})
    call(srv, "POST", "/index/oi/query",
         b'Set(7, f=1) SetColumnAttrs(7, city="pdx") '
         b'SetRowAttrs(f, 1, kind="x")')
    out = call(srv, "POST", "/index/oi/query",
               b"Options(Row(f=1), columnAttrs=true)")
    assert out["columnAttrs"] == [{"id": 7, "attrs": {"city": "pdx"}}]
    assert out["results"][0]["attrs"] == {"kind": "x"}
    out = call(srv, "POST", "/index/oi/query",
               b"Options(Row(f=1), excludeRowAttrs=true, "
               b"excludeColumns=true)")
    assert out["results"][0]["columns"] == []
    assert "attrs" not in out["results"][0]


def test_pprof_and_runtime_stats(srv):
    threads = call(srv, "GET", "/debug/pprof/threads", raw=True).decode()
    assert "thread " in threads and "handler.py" in threads
    prof = call(srv, "GET", "/debug/pprof/profile?seconds=0.2",
                raw=True).decode()
    assert prof == "" or " " in prof.splitlines()[0]
    srv.collect_runtime_stats()
    snap = call(srv, "GET", "/debug/vars")
    assert snap["gauges"]["runtime.rss_bytes"] > 0
    assert snap["gauges"]["runtime.threads"] >= 1


def test_pprof_profile_validates_and_serializes(srv):
    """?seconds must be validated (garbage was an unhandled 500) and only
    one profile may run at a time (409 for the second) — r4 advisor."""
    code, body = call_err(srv, "GET", "/debug/pprof/profile?seconds=abc")
    assert code == 400 and "seconds" in body["error"]

    import threading
    results = []

    def profile():
        try:
            call(srv, "GET", "/debug/pprof/profile?seconds=1", raw=True)
            results.append(200)
        except urllib.error.HTTPError as e:
            results.append(e.code)

    threads = [threading.Thread(target=profile) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(results) == [200, 409]
    # the lock is released: a fresh profile succeeds
    call(srv, "GET", "/debug/pprof/profile?seconds=0.1", raw=True)


def test_column_attrs_deduped_across_calls(srv):
    """Multiple Options(columnAttrs=true) calls over the same column must
    emit ONE top-level entry (reference's deduplicated ColumnAttrSets) —
    r4 advisor."""
    call(srv, "POST", "/index/ca", {})
    call(srv, "POST", "/index/ca/field/f", {})
    call(srv, "POST", "/index/ca/query",
         b'Set(7, f=1) Set(7, f=2) SetColumnAttrs(7, city="pdx")')
    out = call(srv, "POST", "/index/ca/query",
               b"Options(Row(f=1), columnAttrs=true) "
               b"Options(Row(f=2), columnAttrs=true)")
    assert out["columnAttrs"] == [{"id": 7, "attrs": {"city": "pdx"}}]


def test_gcnotify_gauges(srv):
    """gcnotify.go parity: GC cycle counts and pause totals surface as
    runtime gauges."""
    import gc

    from pilosa_tpu.utils.gcnotify import global_notifier
    before = global_notifier().snapshot()["collections"][2]
    gc.collect()
    srv.collect_runtime_stats()
    snap = call(srv, "GET", "/debug/vars")
    assert snap["gauges"]["runtime.gc_collections_gen2"] >= before + 1
    assert "runtime.gc_pause_ms_gen2" in snap["gauges"]


def test_diagnostics_reporting(srv):
    """diagnostics.go parity, inverted default: OFF unless the operator
    configures an endpoint; the payload carries anonymized scale info."""
    import http.server
    import threading

    assert srv.diagnostics._thread is None  # default: no reporting loop
    call(srv, "POST", "/index/di", {})
    call(srv, "POST", "/index/di/field/f", {})
    got = {}

    class Sink(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            got["body"] = json.loads(
                self.rfile.read(int(self.headers["Content-Length"])))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    sink = http.server.HTTPServer(("localhost", 0), Sink)
    threading.Thread(target=sink.handle_request, daemon=True).start()
    srv.diagnostics.endpoint = \
        f"http://localhost:{sink.server_address[1]}/d"
    assert srv.diagnostics.report_once()
    assert got["body"]["numIndexes"] >= 1
    assert got["body"]["version"]
    assert "uptimeSeconds" in got["body"]
    sink.server_close()


def test_statsd_client_emits_datagrams():
    import socket
    from pilosa_tpu.utils.stats import StatsdClient, make_stats_client

    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("localhost", 0))
    recv.settimeout(2)
    port = recv.getsockname()[1]
    st = StatsdClient("localhost", port)
    st.count("query", 2)
    st.with_tags("index:i").gauge("shards", 5)
    got = {recv.recvfrom(1024)[0].decode() for _ in range(2)}
    assert "query:2|c" in got
    assert "shards:5|g|#index:i" in got
    # in-process snapshot stays live for /debug/vars + /metrics
    assert st.snapshot()["counts"]["query"] == 2
    assert st.snapshot()["gauges"]["shards{index:i}"] == 5
    assert isinstance(make_stats_client("statsd", f"localhost:{port}"),
                      StatsdClient)
    recv.close()


def test_shards_max_and_fragment_nodes(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/query",
         b"Set(1, f=1)Set(3145729, f=1)")  # shards 0 and 3
    out = call(srv, "GET", "/internal/shards/max")
    assert out["standard"]["i"] == 3
    nodes = call(srv, "GET", "/internal/fragment/nodes?index=i&shard=0")
    assert nodes[0]["id"] == "node0"


def test_topn_groupby_over_http(srv):
    call(srv, "POST", "/index/i", {})
    call(srv, "POST", "/index/i/field/f", {})
    call(srv, "POST", "/index/i/field/g", {})
    call(srv, "POST", "/index/i/field/f/import", {
        "rowIDs": [0, 0, 0, 1], "columnIDs": [1, 2, 3, 1]})
    call(srv, "POST", "/index/i/field/g/import", {
        "rowIDs": [5, 5], "columnIDs": [1, 2]})
    out = call(srv, "POST", "/index/i/query", b"TopN(f, n=1)")
    assert out["results"][0] == [{"id": 0, "count": 3}]
    out = call(srv, "POST", "/index/i/query", b"GroupBy(Rows(f), Rows(g))")
    assert out["results"][0] == [
        {"group": [{"field": "f", "rowID": 0},
                   {"field": "g", "rowID": 5}], "count": 2},
        {"group": [{"field": "f", "rowID": 1},
                   {"field": "g", "rowID": 5}], "count": 1},
    ]


def test_body_size_limit(tmp_path):
    """POST bodies above max-body-mb get 413 without buffering; a garbage
    Content-Length gets 400 (both previously crashed or buffered
    unbounded)."""
    import http.client

    cfg = Config(data_dir=str(tmp_path / "bl"), bind="localhost:0",
                 max_body_mb=1, max_body_internal_mb=4)
    s = Server(cfg)
    s.open()
    try:
        code, err = call_err(s, "POST", "/index/big/query",
                             b"x" * ((1 << 20) + 1))
        assert code == 413 and "exceeds limit" in err["error"]
        # a claimed-huge Content-Length is rejected without reading
        conn = http.client.HTTPConnection("localhost", s.port, timeout=10)
        conn.putrequest("POST", "/index/big/query")
        conn.putheader("Content-Length", str(50 << 30))
        conn.endheaders()
        resp = conn.getresponse()
        assert resp.status == 413
        conn.close()
        # garbage Content-Length -> 400 AND the connection closes (any
        # in-flight body bytes would desync the keep-alive stream)
        conn = http.client.HTTPConnection("localhost", s.port, timeout=10)
        conn.putrequest("POST", "/index/big/query")
        conn.putheader("Content-Length", "banana")
        conn.endheaders()
        resp = conn.getresponse()
        assert resp.status == 400
        resp.read()
        conn.close()
        # a normal-size request still works
        assert call(s, "POST", "/index/big", {}) == {}
        # the INTERNAL plane gets its own OPT-IN ceiling (roaring import
        # fan-out and resize fragment copies can exceed the public cap):
        # a body over the public limit but under max-body-internal-mb is
        # read and routed (404 here — no cluster routes registered),
        # while one over the internal ceiling still gets 413
        code, err = call_err(s, "POST", "/internal/bogus",
                             b"x" * ((1 << 20) + 1))
        assert code == 404 and "exceeds limit" not in err["error"]
        code, err = call_err(s, "POST", "/internal/bogus",
                             b"x" * ((4 << 20) + 1))
        assert code == 413 and "exceeds limit" in err["error"]
    finally:
        s.close()

    # 0 = unlimited (device-budget-mb convention)
    cfg0 = Config(data_dir=str(tmp_path / "bl0"), bind="localhost:0",
                  max_body_mb=0)
    s0 = Server(cfg0)
    s0.open()
    try:
        code, err = call_err(s0, "POST", "/index/big/query",
                             b"Count(Row(f=1)) " * 200_000)  # ~3 MB
        assert code == 400  # parses (index missing), not 413
        assert "exceeds" not in err["error"]
    finally:
        s0.close()
