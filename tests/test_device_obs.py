"""Device-runtime observability (ISSUE 8, docs/observability.md "Device
runtime"): the compile registry's retrace red flag on a forced re-trace
of a cached executable (the PR 7 regression corpus), launch-ledger ring
bounds + padding-ratio math, the time-series ring's sampling/wrap/
interval math under a fake clock, the new /debug surfaces (served,
probe-excluded), and the /metrics round-trip of the new families through
the PR 5 Prometheus parser."""

import json
import urllib.request

import pytest

from conftest import over_budget_limit

from pilosa_tpu.core import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.server.server import Config, Server
from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
from pilosa_tpu.utils import devobs
from pilosa_tpu.utils.devobs import CompileRegistry, LaunchLedger
from pilosa_tpu.utils.timeseries import TimeSeriesRing

from test_containers import corpus  # noqa: F401 — PR 7 regression corpus
from test_observability import _parse_prometheus, _req, make_server


class _EventLogger:
    """Collects Logger.event calls (the structured retrace lines)."""

    def __init__(self):
        self.events = []

    def event(self, name, **fields):
        self.events.append((name, fields))


# -- compile registry -------------------------------------------------------

def test_compile_registry_unit():
    reg = CompileRegistry()
    log = _EventLogger()
    reg.logger = log
    # first compile of a signature: counted, not a retrace
    reg.begin_call()
    assert not reg.traced()
    reg.mark_traced()
    assert reg.traced()
    assert reg.note_call("count:abc", "count", 0.5, "8x4:int32") is False
    t = reg.totals()
    assert t["compiles"] == 1 and t["retraces"] == 0
    # an un-traced call records nothing (the caller gates on traced())
    reg.begin_call()
    assert not reg.traced()
    # second compile of the SAME signature: retrace — log event carries
    # the fingerprint diff
    reg.begin_call()
    reg.mark_traced()
    assert reg.note_call("count:abc", "count", 0.25, "16x4:int32") is True
    t = reg.totals()
    assert t["compiles"] == 2 and t["retraces"] == 1
    assert t["compileSecondsTotal"] == pytest.approx(0.75)
    assert log.events == [("device.retrace", {
        "sig": "count:abc", "kind": "count", "compiles": 2,
        "compileS": 0.25, "prevShapes": "8x4:int32",
        "shapes": "16x4:int32"})]
    (entry,) = reg.snapshot()["entries"]
    assert entry["compiles"] == 2
    assert entry["lastFingerprint"] == "16x4:int32"
    assert entry["lastCompileWall"] > 0


def test_compile_registry_entry_bound():
    reg = CompileRegistry()
    reg.MAX_ENTRIES = 4
    for i in range(10):
        reg.begin_call()
        reg.mark_traced()
        reg.note_call(f"sig{i}", "count", 0.01, "fp")
    snap = reg.snapshot()
    assert len(snap["entries"]) == 4          # LRU-bounded
    assert snap["compiles"] == 10             # totals keep counting
    assert [e["sig"] for e in snap["entries"]] == \
        ["sig6", "sig7", "sig8", "sig9"]


def test_forced_retrace_fires_counter_and_event(corpus):  # noqa: F811
    """The acceptance gate: re-running the PR 7 retrace corpus (growing
    then shrinking shard subsets re-trace cached executables at new
    stacked group sizes) increments device retraces, emits the
    structured log event with the signature diff, and lands in the
    registry with compiles > 1."""
    ex = Executor(corpus, use_mesh=True)
    old_limit = DEFAULT_BUDGET.limit_bytes
    log = _EventLogger()
    old_logger = devobs.COMPILES.logger
    devobs.COMPILES.logger = log
    before = devobs.COMPILES.totals()
    q = "Count(Intersect(Row(a=11), Row(a=2)))"
    try:
        DEFAULT_BUDGET.limit_bytes = over_budget_limit(corpus)
        want = {}
        for size in (16, 2, 9, 16, 1):
            sl = list(range(size))
            got = ex.execute("c", q, shards=sl)[0]
            if size in want:
                assert got == want[size]
            want[size] = got
    finally:
        DEFAULT_BUDGET.limit_bytes = old_limit
        devobs.COMPILES.logger = old_logger
        ex.close()
    after = devobs.COMPILES.totals()
    assert after["retraces"] > before["retraces"], \
        "forced re-trace never reached the retrace counter"
    retraces = [f for n, f in log.events if n == "device.retrace"]
    assert retraces, "no structured device.retrace event emitted"
    # the signature diff IS the red flag: the re-trace changed shapes
    assert all(f["prevShapes"] != f["shapes"] for f in retraces)
    assert any(e["compiles"] > 1
               for e in devobs.COMPILES.snapshot()["entries"])


# -- launch ledger ----------------------------------------------------------

def test_launch_ledger_ring_bound_and_padding_math():
    led = LaunchLedger(size=4)
    for i in range(10):
        # 3 real shard rows padded to a 4-bucket, single query row:
        # 3 actual units, 1 padded unit per launch
        led.record(sig=f"s{i}", kind="count", shards=3, shards_padded=4,
                   batch_rows=1, batch_rows_padded=1, queue_s=0.001,
                   dispatch_s=0.002, decode_bytes=100, compiled=(i == 0))
    snap = led.snapshot()
    assert snap["launches"] == 10
    assert len(snap["entries"]) == 4          # ring bound
    assert [e["sig"] for e in snap["entries"]] == ["s6", "s7", "s8", "s9"]
    # golden padding math: 10 x (3 actual, 1 padded) -> 25% waste
    assert snap["rowsActual"] == 30 and snap["rowsPadded"] == 10
    assert snap["paddingWasteRatio"] == pytest.approx(0.25)
    assert led.padding_waste_ratio() == pytest.approx(0.25)
    assert snap["decodePeakBytes"] == 100
    assert snap["decodeBytesTotal"] == 1000
    assert snap["launchS"]["count"] == 10

    # query-axis padding counts too: 2 tickets fused to 3 rows padded
    # to 4 over an exact 8-shard bucket -> 8 padded units of 32
    led2 = LaunchLedger(size=4)
    led2.record(sig="f", kind="count", shards=8, shards_padded=8,
                batch_rows=3, batch_rows_padded=4, queue_s=0.0,
                dispatch_s=0.001, decode_bytes=0, compiled=False,
                tickets=2)
    assert led2.aggregates()["rowsActual"] == 24
    assert led2.aggregates()["rowsPadded"] == 8
    assert led2.aggregates()["paddingWasteRatio"] == pytest.approx(0.25)

    # resize keeps the newest entries
    led.resize(2)
    assert [e["sig"] for e in led.snapshot()["entries"]] == ["s8", "s9"]


def test_launch_ledger_populates_on_query(corpus):  # noqa: F811
    before = devobs.LEDGER.launches_total
    ex = Executor(corpus, use_mesh=True)
    try:
        ex.execute("c", "Count(Row(a=2))", shards=list(range(3)))
    finally:
        ex.close()
    assert devobs.LEDGER.launches_total > before
    entry = devobs.LEDGER.snapshot()["entries"][-1]
    assert entry["kind"] in ("count", "wholequery")
    assert entry["shards"] == 3
    # 3 shards bucket-pad to the 8-device mesh width
    assert entry["shardsPadded"] == 8
    assert entry["dispatchS"] > 0


# -- time-series ring -------------------------------------------------------

def test_timeseries_ring_fake_clock():
    clock = [100.0]
    ring = TimeSeriesRing(interval_s=5.0, window_s=20.0,
                          now_fn=lambda: clock[0])
    assert ring.capacity == 5                  # ceil(20/5) + 1
    assert ring.sample({"v": 1}) is True       # first sample always lands
    assert ring.sample({"v": 2}) is False      # same instant: gated
    clock[0] += 2.0
    assert ring.sample({"v": 3}) is False      # under the interval: gated
    clock[0] += 2.6                            # 4.6 >= 0.9 * 5: slack
    assert ring.sample({"v": 4}) is True
    for i in range(10):                        # wrap the ring
        clock[0] += 5.0
        assert ring.sample({"v": 10 + i}) is True
    snap = ring.snapshot()
    assert snap["samplesTotal"] == 12
    assert len(snap["samples"]) == 5           # bounded
    assert [s["v"] for s in snap["samples"]] == [15, 16, 17, 18, 19]
    # inter-sample math is monotonic-clock based and covers the window
    assert snap["coveredS"] == pytest.approx(20.0)
    assert snap["samples"][-1]["uptimeS"] == pytest.approx(54.6)
    # force bypasses the cadence gate (epoch marks)
    assert ring.sample({"v": 99}, force=True) is True


# -- served surfaces --------------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://localhost:{port}{path}", timeout=30) as resp:
        return resp.read(), dict(resp.headers)


def test_debug_surfaces_served_and_probe_excluded(tmp_path):
    srv = make_server(tmp_path, timeseries_interval=0.05,
                      timeseries_window=0.5)
    p = srv.port
    try:
        _req(p, "POST", "/index/i", {})
        _req(p, "POST", "/index/i/field/f", {})
        _req(p, "POST", "/index/i/query", "Count(Row(f=1))")
        # post-request accounting runs AFTER the response is sent
        # (handler._observe in the finally block); poll until all three
        # requests above have landed or the late increment would read
        # as a probe-exclusion leak below
        import time
        deadline = time.perf_counter() + 5
        while time.perf_counter() < deadline:
            hist0 = srv.stats.snapshot()["timings"]["http.request"]["count"]
            if hist0 >= 3:
                break
            time.sleep(0.01)
        body, _ = _get(p, "/debug/compiles")
        comp = json.loads(body)
        assert comp["compiles"] > 0 and "entries" in comp
        body, _ = _get(p, "/debug/launches")
        lau = json.loads(body)
        assert lau["launches"] > 0 and lau["entries"]
        assert 0.0 <= lau["paddingWasteRatio"] <= 1.0
        # sampler thread fills the ring on its own cadence
        import time
        deadline = time.perf_counter() + 5
        while time.perf_counter() < deadline:
            ts = json.loads(_get(p, "/debug/timeseries")[0])
            if len(ts["samples"]) >= 3:
                break
            time.sleep(0.02)
        assert ts["intervalS"] == 0.05
        assert len(ts["samples"]) >= 3
        sample = ts["samples"][-1]
        for field in ("hbmResidentBytes", "hbmCompressedBytes",
                      "admissionInUse", "batcherQueued", "compilesDelta",
                      "retracesDelta", "evictionsDelta",
                      "httpQueriesDelta"):
            assert field in sample, f"time-series sample lacks {field}"
        body, headers = _get(p, "/debug/dashboard")
        assert headers["Content-Type"].startswith("text/html")
        assert b"/debug/timeseries" in body
        # /debug/vars carries the summary sections the cli top reads
        v, _ = _req(p, "GET", "/debug/vars")
        assert v["device"]["compiles"]["compiles"] > 0
        assert v["timeseries"]["samplesTotal"] >= 3
        # all of the above is background traffic: the edge histograms
        # must not have moved (probe/debug exclusion, PR 5 discipline)
        hist1 = srv.stats.snapshot()["timings"]["http.request"]["count"]
        assert hist1 == hist0, "debug traffic leaked into http.request"
    finally:
        srv.close()


def test_retrace_visible_at_debug_compiles(tmp_path):
    """Server-side acceptance: two queries whose shard subsets bucket to
    different stacked shapes re-trace one cached executable, and the
    retrace shows at /debug/compiles and as device_retraces_total at
    /metrics."""
    srv = make_server(tmp_path)
    p = srv.port
    try:
        _req(p, "POST", "/index/rt", {})
        _req(p, "POST", "/index/rt/field/f", {})
        # one bit in each of 16 shards: subsets of <= 8 shards bucket to
        # the 8-device mesh width, the full set to 16
        _req(p, "POST", "/index/rt/field/f/import",
             {"rowIDs": [1] * 16,
              "columnIDs": [s * SHARD_WIDTH for s in range(16)]})
        before = json.loads(_get(p, "/debug/compiles")[0])
        shards = ",".join(str(s) for s in range(16))
        _req(p, "POST", f"/index/rt/query?shards={shards}",
             "Count(Row(f=1))")
        _req(p, "POST", "/index/rt/query?shards=0", "Count(Row(f=1))")
        after = json.loads(_get(p, "/debug/compiles")[0])
        assert after["retraces"] > before["retraces"]
        assert any(e["compiles"] > 1 for e in after["entries"])
        text = _get(p, "/metrics")[0].decode()
        _, samples = _parse_prometheus(text)
        assert samples[("pilosa_tpu_device_retraces_total",
                        frozenset())] >= 1
    finally:
        srv.close()


def test_metrics_device_families_round_trip(tmp_path):
    srv = make_server(tmp_path)
    p = srv.port
    try:
        _req(p, "POST", "/index/i", {})
        _req(p, "POST", "/index/i/field/f", {})
        for _ in range(2):
            _req(p, "POST", "/index/i/query", "Count(Row(f=1))")
        text = _get(p, "/metrics")[0].decode()
        types, samples = _parse_prometheus(text)
        flat = {n: v for (n, ls), v in samples.items() if not ls}
        assert flat["pilosa_tpu_device_compiles_total"] >= 1
        assert flat["pilosa_tpu_device_retraces_total"] >= 0
        assert flat["pilosa_tpu_device_launches_total"] >= 1
        assert 0.0 <= flat["pilosa_tpu_device_padding_waste_ratio"] <= 1.0
        assert "pilosa_tpu_device_decode_workspace_peak_bytes" in flat
        assert flat["pilosa_tpu_device_decode_workspace_limit_bytes"] > 0
        # the launch ledger's own histogram families parse as proper
        # cumulative Prometheus histograms
        fam = "pilosa_tpu_device_launch_seconds"
        assert types[fam] == "histogram"
        buckets = [v for (n, ls), v in samples.items()
                   if n == f"{fam}_bucket"]
        assert max(buckets) == samples[(f"{fam}_count", frozenset())]
        assert samples[(f"{fam}_count", frozenset())] >= 1
    finally:
        srv.close()


# -- cli top ----------------------------------------------------------------

def test_cli_top_renders_summary(tmp_path, capsys):
    from pilosa_tpu import cli
    srv = make_server(tmp_path, timeseries_interval=0.05)
    p = srv.port
    try:
        _req(p, "POST", "/index/i", {})
        _req(p, "POST", "/index/i/field/f", {})
        _req(p, "POST", "/index/i/query", "Count(Row(f=1))")
        rc = cli.main(["top", "-host", f"localhost:{p}",
                       "--count", "2", "--interval", "0.1"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "qps" in out and "hbm" in out and "retraces" in out
        assert out.count("pilosa-tpu top @") == 2
    finally:
        srv.close()
