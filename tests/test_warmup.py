"""Warm-start subsystem (pilosa_tpu/warmup/, docs/warmup.md): the
CRC-framed signature corpus's crash safety (every-length truncation,
every-byte corruption — load never raises, never returns garbage),
recorder fold/seed/flush/compaction, the compile-cache LRU prune and
where the cache lives (the environment's directory left alone, else one
fixed path shared by every Server and every process), the
coordinator's degrade-to-cold guarantees (corrupt/empty/stale corpus,
replay errors, expired budget all still reach READY), and a real
Server warm restart: prepared templates rebuilt, zero retraces during
replay, EXPLAIN flipping plan compile cold -> warm."""

import json
import os
import subprocess
import sys
import time

import pytest

from pilosa_tpu.warmup import (CorpusRecorder, SignatureCorpus,
                               compile_cache, prune, top_n,
                               WarmupCoordinator)
from pilosa_tpu.warmup.corpus import (CORPUS_MAGIC, SCHEMA_VERSION,
                                      _frame)
from pilosa_tpu.warmup.replayer import PHASE_READY, PHASE_WARMING

from test_observability import _req, make_server


def _rec(index="i", template="Count(Row(f=?))", query="Count(Row(f=1))",
         hits=1, **kw):
    rec = {"v": SCHEMA_VERSION, "index": index, "template": template,
           "query": query, "sig": "wholequery:abc", "fp": "fp1",
           "hits": hits, "lastUsed": 100.0, "compileS": 0.5}
    rec.update(kw)
    return rec


def _write_corpus(path, records):
    c = SignatureCorpus(str(path))
    c.open()
    c.append(records)
    c.close()


# -- corpus frame discipline -------------------------------------------------


def test_append_read_load_latest_wins(tmp_path):
    path = tmp_path / "signatures.log"
    recs = [_rec(hits=1), _rec(template="Row(g=?)", query="Row(g=2)",
                               hits=3),
            _rec(hits=7, query="Count(Row(f=9))")]  # same key as recs[0]
    _write_corpus(path, recs)
    assert SignatureCorpus.read(str(path)) == recs
    folded = SignatureCorpus.load(str(path))
    assert set(folded) == {("i", "Count(Row(f=?))"), ("i", "Row(g=?)")}
    # latest frame for a key wins (each frame is a full snapshot)
    assert folded[("i", "Count(Row(f=?))")]["hits"] == 7
    assert folded[("i", "Count(Row(f=?))")]["query"] == "Count(Row(f=9))"


def test_every_length_truncation_recovers(tmp_path):
    """Any kill -9 mid-write leaves a prefix; every prefix must load
    without raising and yield only records that were actually written."""
    path = tmp_path / "signatures.log"
    recs = [_rec(template=f"t{i}(?)", query=f"t{i}(1)", hits=i + 1)
            for i in range(3)]
    _write_corpus(path, recs)
    data = path.read_bytes()
    for cut in range(len(data) + 1):
        path.write_bytes(data[:cut])
        got = SignatureCorpus.read(str(path))
        assert got == recs[:len(got)]  # valid prefix, in order
        # and a fresh open() truncates the torn tail durably
        c = SignatureCorpus(str(path))
        c.open()
        c.close()
        assert SignatureCorpus.read(str(path)) == got
    path.write_bytes(data)
    assert len(SignatureCorpus.load(str(path))) == 3


def test_every_byte_corruption_recovers(tmp_path):
    """Flipping any single byte must never raise and must never invent
    a record: every loaded record equals one that was written."""
    path = tmp_path / "signatures.log"
    recs = [_rec(template=f"t{i}(?)", query=f"t{i}(1)", hits=i + 1)
            for i in range(3)]
    _write_corpus(path, recs)
    data = bytearray(path.read_bytes())
    for i in range(len(data)):
        corrupted = bytearray(data)
        corrupted[i] ^= 0xFF
        path.write_bytes(bytes(corrupted))
        for got in (SignatureCorpus.read(str(path)),
                    list(SignatureCorpus.load(str(path)).values())):
            for rec in got:
                assert rec in recs


def test_wrong_magic_resets_empty(tmp_path):
    path = tmp_path / "signatures.log"
    path.write_bytes(b"NOTMAGIC" + b"junk" * 10)
    c = SignatureCorpus(str(path))
    c.open()  # garbage prefix -> rewritten empty, not refused
    c.append([_rec()])
    c.close()
    assert len(SignatureCorpus.load(str(path))) == 1


def test_bad_records_dropped_not_fatal(tmp_path):
    path = tmp_path / "signatures.log"
    good = _rec()
    stale = _rec(template="old(?)")
    stale["v"] = SCHEMA_VERSION + 1          # stale schema version
    missing = {"v": SCHEMA_VERSION, "index": "i"}  # missing keys
    with open(path, "wb") as f:
        f.write(CORPUS_MAGIC)
        f.write(_frame(json.dumps(good).encode()))
        f.write(_frame(b"[1, 2, 3]"))         # CRC-valid, not a dict
        f.write(_frame(b"{not json"))         # CRC-valid, not JSON
        f.write(_frame(json.dumps(stale).encode()))
        f.write(_frame(json.dumps(missing).encode()))
    folded = SignatureCorpus.load(str(path))
    assert list(folded.values()) == [good]


def test_load_missing_and_empty_file(tmp_path):
    assert SignatureCorpus.load(str(tmp_path / "absent.log")) == {}
    (tmp_path / "empty.log").write_bytes(b"")
    assert SignatureCorpus.load(str(tmp_path / "empty.log")) == {}


def test_compact_rewrites_to_survivors(tmp_path):
    path = tmp_path / "signatures.log"
    c = SignatureCorpus(str(path))
    c.open()
    for i in range(40):
        c.append([_rec(template="hot(?)", query="hot(1)", hits=i)])
    big = path.stat().st_size
    c.compact([_rec(template="hot(?)", query="hot(1)", hits=39)])
    assert path.stat().st_size < big
    assert c.frames_appended == 1
    # the handle survives compaction: appends still land
    c.append([_rec(template="new(?)", query="new(2)")])
    c.close()
    assert set(SignatureCorpus.load(str(path))) == {
        ("i", "hot(?)"), ("i", "new(?)")}


def test_top_n_ranks_hits_then_recency():
    a = _rec(template="a(?)", hits=5, lastUsed=1.0)
    b = _rec(template="b(?)", hits=5, lastUsed=9.0)
    c = _rec(template="c(?)", hits=50, lastUsed=0.0)
    assert top_n([a, b, c], 2) == [c, b]
    assert top_n([a, b, c], 0) == []


# -- recorder ----------------------------------------------------------------


def test_recorder_note_flush_and_seed(tmp_path):
    path = tmp_path / "signatures.log"
    corpus = SignatureCorpus(str(path))
    corpus.open()
    rec = CorpusRecorder(keep_n=8)
    rec.note_sig("wholequery:deadbeef")
    rec.note("i", "Count(Row(f=1))")
    rec.note("i", "Count(Row(f=2))")  # same template, staged sig consumed
    rec.flush(corpus)
    corpus.close()
    folded = SignatureCorpus.load(str(path))
    (key, stored), = folded.items()
    assert key == ("i", "Count(Row(f=?))")
    assert stored["hits"] == 2
    assert stored["sig"] == "wholequery:deadbeef"
    assert stored["query"] == "Count(Row(f=2))"  # latest sample text

    # restart: seeding carries the hit count, new traffic adds to it
    rec2 = CorpusRecorder(keep_n=8)
    rec2.seed(folded)
    rec2.note("i", "Count(Row(f=3))")
    assert rec2.snapshot()["templates"] == 1
    corpus2 = SignatureCorpus(str(path))
    corpus2.open()
    rec2.flush(corpus2)
    corpus2.close()
    assert SignatureCorpus.load(str(path))[key]["hits"] == 3


def test_recorder_compacts_when_log_outgrows_bound(tmp_path):
    path = tmp_path / "signatures.log"
    corpus = SignatureCorpus(str(path))
    corpus.open()
    rec = CorpusRecorder(keep_n=2)
    for i in range(2 * rec.COMPACT_FACTOR + 3):
        rec.note(f"idx{i}", "Count(Row(f=1))")
        rec.flush(corpus)
    # the log was rewritten to the keep_n survivor set at least once
    assert corpus.frames_appended <= rec.keep_n * rec.COMPACT_FACTOR
    corpus.close()
    assert len(SignatureCorpus.read(str(path))) <= \
        rec.keep_n * rec.COMPACT_FACTOR + 1


# -- compile cache -----------------------------------------------------------


# One process: argv[1] Servers in a row, each on its own mkdtemp data
# dir, each compiling the same query.  Prints which cache directory each
# Server reports, what jax itself is configured with, and every
# directory prune was called on.
_CACHE_WORKER = r'''
import json, sys, tempfile
import jax
import pilosa_tpu.warmup as warmup
from pilosa_tpu.server.server import Config, Server
pruned = []
real_prune = warmup.prune
warmup.prune = lambda d, mb: (pruned.append(d), real_prune(d, mb))[1]
# jax records this event exactly where it writes a cache entry
written = []
jax.monitoring.register_event_listener(
    lambda event, **kw: written.append(event)
    if event == "/jax/compilation_cache/cache_misses" else None)
dirs = []
for _ in range(int(sys.argv[1])):
    s = Server(Config(data_dir=tempfile.mkdtemp(prefix="ptpu-cc-"),
                      bind="localhost:0", timeseries_interval=0,
                      metric_poll_interval=0, anti_entropy_interval=0,
                      compile_cache_mb=int(sys.argv[2])))
    s.open()
    s.api.create_index("ci")
    s.api.create_field("ci", "f")
    s.api.query("ci", "Set(1, f=1) Set(2, f=1)")
    assert s.api.query("ci", "Count(Row(f=1))") == [2]
    dirs.append(s._compile_cache_dir)
    s.close()
print(json.dumps({"dirs": dirs, "pruned": pruned, "written": len(written),
                  "jax_dir": jax.config.jax_compilation_cache_dir}))
'''


def _cache_worker(n_servers, cache_mb, env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_WORKER, str(n_servers),
         str(cache_mb)],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_environment_cache_dir_is_left_alone(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program uses that
    directory, sets none in code and never prunes it: a file far over
    compile-cache-mb survives a Server's start-up and shutdown."""
    cc = tmp_path / "env-cache"
    cc.mkdir()
    old = cc / "somebody-elses-entry"
    old.write_bytes(b"x" * (2 << 20))
    os.utime(old, (100.0, 100.0))
    out = _cache_worker(1, 1, env_dir=str(cc))
    assert out["dirs"] == [str(cc)] and out["jax_dir"] == str(cc)
    assert out["pruned"] == []
    assert old.exists()
    assert len(list(cc.iterdir())) > 1, "nothing was cached there"


def test_fixed_cache_dir_shared_by_servers_and_processes():
    """Without the variable, Servers on different mkdtemp data dirs all
    land on the one fixed directory in the checkout — the directory is
    part of jax's cache key — and a second process compiling the same
    query writes no entry to it (its own writes are counted, not the
    directory's files: other tests' servers fill the same directory
    meanwhile)."""
    fixed = compile_cache.DEFAULT_DIR
    assert os.path.basename(fixed) == ".compile-cache"
    out = _cache_worker(2, 256)
    assert out["dirs"] == [fixed, fixed] and out["jax_dir"] == fixed
    assert out["pruned"] and set(out["pruned"]) == {fixed}
    files = set(os.listdir(fixed))
    assert files
    again = _cache_worker(1, 256)
    assert again["dirs"] == [fixed]
    assert again["written"] == 0
    assert files <= set(os.listdir(fixed))


def test_prune_removes_oldest_first(tmp_path):
    files = []
    for i in range(4):
        p = tmp_path / f"entry{i}"
        p.write_bytes(b"x" * 1024 * 1024)  # 1 MB each
        os.utime(p, (100.0 + i, 100.0 + i))
        files.append(p)
    out = prune(str(tmp_path), 2)
    assert out["removed"] == 2 and out["files"] == 2
    assert not files[0].exists() and not files[1].exists()
    assert files[2].exists() and files[3].exists()
    # 0 = unbounded: nothing removed
    assert prune(str(tmp_path), 0)["removed"] == 0
    # missing dir never raises
    assert prune(str(tmp_path / "absent"), 1)["removed"] == 0


# -- coordinator (stub executor) ---------------------------------------------


class _StubExecutor:
    def __init__(self, fail_on=()):
        self.calls = []
        self.fail_on = set(fail_on)

    def execute(self, index, query):
        self.calls.append((index, query))
        if query in self.fail_on:
            raise RuntimeError("index dropped")
        return [0]


def _wait_ready(co, timeout=10.0):
    t0 = time.monotonic()
    while co.warming() and time.monotonic() - t0 < timeout:
        time.sleep(0.01)
    assert not co.warming()


def test_coordinator_cold_without_corpus(tmp_path):
    ex = _StubExecutor()
    co = WarmupCoordinator(ex, str(tmp_path / "signatures.log"))
    assert co.open() is False          # nothing to warm
    assert co.status()["phase"] == PHASE_READY
    co.start()
    co.close()
    assert ex.calls == []


def test_coordinator_disabled_by_top_n_zero(tmp_path):
    path = tmp_path / "signatures.log"
    _write_corpus(path, [_rec()])
    co = WarmupCoordinator(_StubExecutor(), str(path), top_n=0)
    assert co.open() is False
    co.close()


def test_coordinator_replays_top_n_then_ready(tmp_path):
    path = tmp_path / "signatures.log"
    _write_corpus(path, [_rec(template=f"t{i}(?)", query=f"t{i}(1)",
                              hits=10 - i) for i in range(5)])
    ex = _StubExecutor()
    co = WarmupCoordinator(ex, str(path), top_n=3, budget_s=30.0)
    flipped = []
    co.on_ready = lambda: flipped.append(True)
    assert co.open() is True
    assert co.status()["phase"] == PHASE_WARMING
    co.start()
    _wait_ready(co)
    st = co.status()
    assert st["planned"] == 3 and st["replayed"] == 3
    assert st["errors"] == 0 and st["skipped"] == 0
    # replay order is traffic rank: hottest first
    assert [q for _, q in ex.calls] == ["t0(1)", "t1(1)", "t2(1)"]
    assert flipped == [True]
    co.close()


def test_coordinator_replay_error_degrades_not_fails(tmp_path):
    path = tmp_path / "signatures.log"
    _write_corpus(path, [_rec(template="bad(?)", query="bad(1)", hits=9),
                         _rec(template="ok(?)", query="ok(1)", hits=1)])
    co = WarmupCoordinator(_StubExecutor(fail_on={"bad(1)"}), str(path))
    assert co.open() is True
    co.start()
    _wait_ready(co)
    st = co.status()
    assert st["errors"] == 1 and st["replayed"] == 1
    assert st["phase"] == PHASE_READY
    co.close()


def test_coordinator_budget_expiry_skips_remainder(tmp_path):
    path = tmp_path / "signatures.log"
    _write_corpus(path, [_rec(template=f"t{i}(?)", query=f"t{i}(1)")
                         for i in range(4)])
    co = WarmupCoordinator(_StubExecutor(), str(path), budget_s=0.0)
    assert co.open() is True
    co.start()
    _wait_ready(co)
    st = co.status()
    assert st["skipped"] == st["planned"] == 4
    assert st["replayed"] == 0 and st["phase"] == PHASE_READY
    co.close()


def test_coordinator_corrupt_corpus_cold_start(tmp_path):
    path = tmp_path / "signatures.log"
    path.write_bytes(os.urandom(512))  # garbage: wrong magic
    co = WarmupCoordinator(_StubExecutor(), str(path))
    assert co.open() is False          # cold start, never a crash
    assert co.status()["corpusEntries"] == 0
    co.start()
    co.close()
    # and the rewritten-empty log is usable going forward
    co.recorder.note("i", "Count(Row(f=1))")


# -- server end-to-end -------------------------------------------------------


@pytest.mark.slow
def test_server_warm_restart_end_to_end(tmp_path):
    """The full loop: serve -> corpus flushed on close -> restart enters
    warming -> replay through the real executor rebuilds prepared
    templates with zero retraces -> READY; EXPLAIN reports plan compile
    warm for post-restart traffic."""
    from pilosa_tpu.utils.devobs import COMPILES

    s = make_server(tmp_path, timeseries_interval=0,
                    metric_poll_interval=0)
    p = s.port
    _req(p, "POST", "/index/wi", {})
    _req(p, "POST", "/index/wi/field/f", {})
    _req(p, "POST", "/index/wi/query",
         "".join(f"Set({c}, f={r})" for r in range(3) for c in range(40)))
    for _ in range(3):
        out, _ = _req(p, "POST", "/index/wi/query", "Count(Row(f=1))")
        assert out["results"] == [40]
    st1, _ = _req(p, "GET", "/status")
    assert st1["phase"] == "ready" and st1["warming"] is False
    s.close()  # final flush writes the corpus

    s2 = make_server(tmp_path, timeseries_interval=0,
                     metric_poll_interval=0)
    try:
        assert s2.warmup.open.__self__ is s2.warmup  # sanity: wired
        t0 = time.monotonic()
        while s2.warmup.warming() and time.monotonic() - t0 < 60:
            time.sleep(0.02)
        st = s2.warmup.status()
        assert st["phase"] == "ready"
        assert st["replayed"] >= 1 and st["errors"] == 0
        assert st["retracesDuringWarm"] == 0
        # prepared template survived the restart (rebuilt by replay)
        prep = s2.api.executor.prepared
        assert prep is not None and len(prep._entries) >= 1
        # post-warm traffic does not compile: the replay already did
        before = COMPILES.totals()
        out, _ = _req(s2.port, "POST", "/index/wi/query?explain=true",
                      "Count(Row(f=1))")
        assert out["results"] == [40]
        after = COMPILES.totals()
        assert after["compiles"] == before["compiles"]
        plan = out["explain"]["plan"]
        assert plan and plan[0].get("compile") == "warm"
        # warmup surfaces at /debug/vars
        dv, _ = _req(s2.port, "GET", "/debug/vars")
        assert dv["warmup"]["phase"] == "ready"
        assert dv["warmup"]["replayed"] == st["replayed"]
    finally:
        s2.close()


def test_status_reports_warming_not_ready(tmp_path):
    """While the coordinator is warming, /status must say so (probes
    treat warming as not-READY) without ever claiming DOWN."""
    s = make_server(tmp_path, timeseries_interval=0,
                    metric_poll_interval=0)
    try:
        class _Stuck:
            def warming(self):
                return True

            def status(self):
                return {"phase": "warming"}

        s.api.warmup = _Stuck()
        st, _ = _req(s.port, "GET", "/status")
        assert st["warming"] is True and st["phase"] == "warming"
        assert st["nodes"][0]["state"] == "WARMING"
    finally:
        s.api.warmup = s.warmup
        s.close()


def test_cluster_local_warming_state(tmp_path):
    from pilosa_tpu.parallel.cluster import (Cluster, NODE_READY,
                                             NODE_WARMING)
    from pilosa_tpu.storage import Holder

    h = Holder(str(tmp_path / "h"))
    c = Cluster("node0", ["localhost:1", "localhost:2"], holder=h)
    c.set_local_warming(True)
    me = c.by_id["node0"]
    assert me.state == NODE_WARMING
    c.set_local_warming(False)
    assert me.state == NODE_READY
