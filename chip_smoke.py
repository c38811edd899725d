#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip, at real size.

The quickest standing proof that pilosa-tpu still starts on a TPU: one
process opens a ``Server`` the way ``python -m pilosa_tpu server`` does
(every option at its default), waits for READY, and from then on talks
to it only over HTTP on localhost from plain client threads.  It loads
BASELINE.json config 5 (index ``ssb1b``: 954 shards of 2^20 columns,
``seg`` 4 rows at ~25 % fill, ``metric`` 8 rows at ~12.5 %, ~1.5 GB dense
on the device) and config 4 (``bsi64``: 64 shards, a BSI int field and a
set field) through the bulk routes users have, sends a few requests of
every kind the hot path has, and compares every answer with a plain
numpy oracle over the same generated words.  Each query shape is sent
again with other literals and must compile nothing.  Then the same
TopN/Count pair runs over budget: dense-streamed, and over the sparse
variant held compressed on the device.

    python chip_smoke.py                         # on a TPU; 0 = pass
    python chip_smoke.py --rehearsal --shards 8  # on the CPU; never a pass

Exit codes: 0 pass (TPU only) · 1 a phase failed (its name is printed) ·
2 jax found no TPU · 10 a CPU rehearsal ran to the end (not a pass).
The last line of stdout is one JSON object, printed only when every
phase ran: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

N_SHARDS = 954            # ~1.0 B columns (954 * 2^20)
MIN_SHARDS = 256          # a cut below this is not the deployment
BSI_SHARDS = 64
BSI_VALUES = 1_000_000
SHARD_WORDS = 32768       # uint32 words per 2^20-column shard row
SEG_ROWS, METRIC_ROWS = 4, 8
ROWS = SEG_ROWS + METRIC_ROWS
LOADERS = 8               # client threads of the bulk load
BURST = 32                # concurrent single-call requests

EXIT_FAILED, EXIT_NO_TPU, EXIT_REHEARSAL = 1, 2, 10


def shard_words(seed: int, shard: int, sparse: bool) -> np.ndarray:
    """One shard's [12, SHARD_WORDS] uint32 block (seg rows 0-3, then
    metric rows 0-7) — bench.py build_config5's generator, one rng per
    (seed, shard) so loader threads and the oracle agree without
    sharing a stream.  ``sparse``: ~1.5 % of words kept plus one
    256-word fully-set range per row (array + long-run containers)."""
    rng = np.random.default_rng([seed, int(sparse), shard])
    a = rng.integers(0, 1 << 32, size=(ROWS, SHARD_WORDS), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, size=(ROWS, SHARD_WORDS), dtype=np.uint32)
    words = a & b                                   # ~25 % fill
    words[SEG_ROWS:] &= np.roll(b[SEG_ROWS:], 7, axis=1)  # ~12.5 %
    if sparse:
        words *= rng.random((ROWS, SHARD_WORDS)) < 0.015
        starts = rng.integers(0, SHARD_WORDS - 256, size=ROWS)
        for r in range(ROWS):
            words[r, starts[r]: starts[r] + 256] = 0xFFFFFFFF
    return words


def cache_census(cache_dir: str | None) -> dict:
    """{module name: entries} of a jax compilation cache directory
    (an entry is ``<module>-<key>-cache``).  ``jit_traced`` and
    ``jit_traced_body`` are the server's compiled programs;
    ``jit_dynamic_slice`` are the eager per-ticket slices of a fused
    launch's results, one per padded batch size a burst happened to
    reach — the only entries whose number depends on the clock."""
    out: dict = {}
    if cache_dir and os.path.isdir(cache_dir):
        for name in os.listdir(cache_dir):
            mod = name.rsplit("-", 2)[0]
            out[mod] = out.get(mod, 0) + 1
    return out


def popcount(words: np.ndarray) -> int:
    return int(np.bitwise_count(words).sum(dtype=np.int64))


class Client:
    """Plain HTTP client of one server: a keep-alive connection per
    thread, JSON in and out.  No jax."""

    def __init__(self, port: int):
        self.port = port
        self._local = threading.local()

    # the server drops a keep-alive connection idle for 120 s; a client
    # redials long before that rather than retrying a request
    IDLE_REDIAL_S = 30.0

    def connection(self) -> http.client.HTTPConnection:
        conn = getattr(self._local, "conn", None)
        now = time.monotonic()
        if conn is not None and \
                now - self._local.used > self.IDLE_REDIAL_S:
            conn.close()
            conn = None
        if conn is None:
            conn = self._local.conn = http.client.HTTPConnection(
                "localhost", self.port, timeout=600)
        self._local.used = now
        return conn

    def request(self, method: str, path: str, body=None,
                ctype: str = "application/json"):
        conn = self.connection()
        if isinstance(body, (dict, list)):
            body = json.dumps(body).encode()
        elif isinstance(body, str):
            body = body.encode()
        conn.request(method, path, body=body,
                     headers={"Content-Type": ctype} if body else {})
        resp = conn.getresponse()
        data = resp.read()
        if resp.status != 200:
            raise RuntimeError(
                f"{method} {path} -> {resp.status}: {data[:2000]!r}")
        return json.loads(data) if data.strip() else {}

    def query(self, index: str, pql: str) -> list:
        return self.request("POST", f"/index/{index}/query", pql,
                            ctype="text/plain")["results"]


class Smoke:
    def __init__(self, args, device: dict, rehearsal: bool):
        self.seed = args.seed
        self.n_shards = args.shards
        self.device = device
        self.rehearsal = rehearsal
        self.phase = "start"
        self.t0 = time.monotonic()
        self.tmp = tempfile.mkdtemp(prefix="ptpu-chip-smoke-")
        self.srv = None
        self.client = None
        # oracle state: the generated words, and bsi64's columns
        self.W: np.ndarray | None = None
        self.bsi: dict = {}

    # -- reporting ---------------------------------------------------------

    def begin(self, phase: str):
        self.phase = phase
        print(f"[{time.monotonic() - self.t0:7.1f}s] phase {phase}",
              flush=True)

    def report(self, **kv):
        print(json.dumps({"phase": self.phase, **kv}), flush=True)

    def check(self, ok: bool, what: str):
        if not ok:
            raise AssertionError(f"{self.phase}: {what}")

    # -- server ------------------------------------------------------------

    def open_server(self, **overrides):
        """A Config built the way cli.cmd_server builds it (environment,
        then the command line's overrides); everything else default."""
        from pilosa_tpu.server.server import Config, Server
        cfg = Config.from_env(data_dir=self.tmp, bind="localhost:0",
                              **overrides)
        self.srv = Server(cfg)
        self.srv.open()
        self.client = Client(self.srv.port)
        deadline = time.monotonic() + 300
        while True:
            nodes = self.client.request("GET", "/status")["nodes"]
            if all(n["state"] == "READY" for n in nodes):
                break
            self.check(time.monotonic() < deadline,
                       f"server never reported READY: {nodes}")
            time.sleep(0.05)
        dev = self.client.request("GET", "/debug/vars")["device"]
        served = {"platform": dev["platform"], "kind": dev["deviceKind"],
                  "count": dev["deviceCount"]}
        self.check(served == self.device,
                   f"/debug/vars names {served}, jax {self.device}")
        mesh = self.srv.api.executor.mesh_exec.n_devices
        self.check(mesh == self.device["count"],
                   f"mesh of {mesh} over {self.device['count']} devices")
        return cfg

    def close_server(self):
        if self.srv is not None:
            self.srv.close()
            self.srv = None

    def debug_vars(self) -> dict:
        return self.client.request("GET", "/debug/vars")

    # -- load --------------------------------------------------------------

    def load_ssb(self, index: str, sparse: bool):
        """The 1B-column index over POST .../import-roaring/{shard}, raw
        roaring bodies from LOADERS client threads; the generated words
        stay as the oracle's."""
        from pilosa_tpu.storage.roaring_io import pack_roaring_words
        c = self.client
        c.request("POST", f"/index/{index}",
                  {"options": {"trackExistence": False}})
        for f in ("seg", "metric"):
            c.request("POST", f"/index/{index}/field/{f}", {})
        W = np.empty((self.n_shards, ROWS, SHARD_WORDS), dtype=np.uint32)

        def load(shard: int) -> int:
            words = shard_words(self.seed, shard, sparse)
            W[shard] = words
            sent = 0
            for field, block in (("seg", words[:SEG_ROWS]),
                                 ("metric", words[SEG_ROWS:])):
                body = pack_roaring_words(block)
                c.request(
                    "POST",
                    f"/index/{index}/field/{field}/import-roaring/{shard}",
                    body, ctype="application/octet-stream")
                sent += len(body)
            return sent

        t0 = time.monotonic()
        with ThreadPoolExecutor(LOADERS) as pool:
            sent = sum(pool.map(load, range(self.n_shards)))
        self.W = W
        self.report(index=index, sparse=sparse, shards=self.n_shards,
                    columns=self.n_shards << 20,
                    roaring_bytes_sent=sent,
                    set_bits=popcount(W),
                    load_s=round(time.monotonic() - t0, 1))

    def load_bsi(self):
        """Config 4: 64 shards, ``v`` int [0, 1e6] over POST .../import
        (columnIDs + values) and the 8-row ``seg`` over import-roaring.
        Existence tracking stays at the API's default (on): Not() needs
        it."""
        from pilosa_tpu.storage.roaring_io import pack_roaring
        c = self.client
        width = 1 << 20
        rng = np.random.default_rng([self.seed, 4])
        cols = np.unique(rng.integers(0, BSI_SHARDS * width,
                                      size=BSI_VALUES))
        vals = rng.integers(0, 1_000_000, size=cols.size)
        seg = rng.integers(0, 8, size=cols.size)
        c.request("POST", "/index/bsi64", {})
        c.request("POST", "/index/bsi64/field/v",
                  {"options": {"type": "int", "min": 0, "max": 1_000_000}})
        c.request("POST", "/index/bsi64/field/seg", {})

        def load(shard: int):
            m = (cols >> 20) == shard
            c.request("POST", "/index/bsi64/field/v/import",
                      {"columnIDs": cols[m].tolist(),
                       "values": vals[m].tolist()})
            c.request(
                "POST", f"/index/bsi64/field/seg/import-roaring/{shard}",
                pack_roaring(seg[m], cols[m] - shard * width),
                ctype="application/octet-stream")

        with ThreadPoolExecutor(LOADERS) as pool:
            list(pool.map(load, range(BSI_SHARDS)))
        self.bsi = {"cols": cols, "vals": vals, "seg": seg}
        self.report(index="bsi64", shards=BSI_SHARDS, values=int(cols.size))

    # -- the numpy oracle ----------------------------------------------------

    def o_topn(self, a: int, b: int, n: int = 5) -> list:
        """bench.py oracle_topn5, over all shards at once."""
        mask = self.W[:, a] & self.W[:, b]
        counts = [popcount(self.W[:, SEG_ROWS + m] & mask)
                  for m in range(METRIC_ROWS)]
        order = sorted(range(METRIC_ROWS), key=lambda m: (-counts[m], m))
        return [{"id": m, "count": counts[m]} for m in order[:n]
                if counts[m] > 0]

    # -- query phases --------------------------------------------------------

    def compile_totals(self) -> dict:
        from pilosa_tpu.utils import devobs
        return devobs.COMPILES.totals()

    def shape(self, name: str, index: str, sends: list):
        """One query shape: ``sends`` is [(pql, expected results), ...]
        with different literals.  Every answer must equal the oracle's;
        every send after the first must compile nothing."""
        v0 = self.debug_vars()
        rows = []
        for i, (pql, want) in enumerate(sends):
            c0 = self.compile_totals()
            got = self.client.query(index, pql)
            c1 = self.compile_totals()
            self.check(got == want,
                       f"{name} send {i}: {pql!r} answered {got!r}, "
                       f"oracle {want!r}")
            compiles = c1["compiles"] - c0["compiles"]
            retraces = c1["retraces"] - c0["retraces"]
            if i > 0:
                self.check(compiles == 0 and retraces == 0,
                           f"{name} send {i} ({pql!r}): {compiles} "
                           f"compiles, {retraces} retraces — a repeat "
                           f"with new literals must compile nothing")
            rows.append({"compiles": compiles, "retraces": retraces,
                         "compile_s": round(
                             c1["compileSecondsTotal"]
                             - c0["compileSecondsTotal"], 2)})
        v1 = self.debug_vars()
        wq0, wq1 = v0["wholeQuery"], v1["wholeQuery"]
        fallbacks = wq1["fallbacks"] - wq0["fallbacks"]
        self.report(
            shape=name, sends=rows, equal=True,
            launches=v1["device"]["launches"]["launches"]
            - v0["device"]["launches"]["launches"],
            whole_query_programs=wq1["requests"] - wq0["requests"],
            whole_query_fallbacks=fallbacks,
            fallback_node=wq1["lastFallback"] if fallbacks else None)

    def ssb_sends(self, kind: str, literals: list) -> list:
        W = self.W
        out = []
        for lit in literals:
            if kind == "count_row":
                (a,) = lit
                out.append((f"Count(Row(seg={a}))",
                            [popcount(W[:, a])]))
            elif kind == "count_intersect":
                a, b = lit
                out.append((
                    f"Count(Intersect(Row(seg={a}), Row(seg={b})))",
                    [popcount(W[:, a] & W[:, b])]))
            elif kind == "topn":
                a, b = lit
                out.append((
                    f"TopN(metric, Intersect(Row(seg={a}), "
                    f"Row(seg={b})), n=5)", [self.o_topn(a, b)]))
            elif kind == "counts":
                # one body of len(lit) same-shape calls: the params
                # batch axis the dispatch batcher also fuses along
                out.append((
                    " ".join(f"Count(Row(seg={a}))" for a in lit),
                    [popcount(W[:, a]) for a in lit]))
            elif kind == "multi":
                a, b, m = lit
                out.append((
                    f"Count(Row(seg={a})) Count(Row(metric={m})) "
                    f"TopN(metric, Intersect(Row(seg={a}), "
                    f"Row(seg={b})), n=5)",
                    [popcount(W[:, a]),
                     popcount(W[:, SEG_ROWS + m]),
                     self.o_topn(a, b)]))
        return out

    def bsi_sends(self, kind: str, literals: list) -> list:
        cols, vals, seg = (self.bsi[k] for k in ("cols", "vals", "seg"))
        out = []
        for lit in literals:
            if kind == "union_not":
                a, b, x = lit
                want = int(((seg == a) | (vals > x) | (seg != b)).sum())
                out.append((
                    f"Count(Union(Row(seg={a}), Row(v > {x}), "
                    f"Not(Row(seg={b}))))", [want]))
            elif kind == "sum_gt":
                (x,) = lit
                m = vals > x
                out.append((f"Sum(Row(v > {x}), field=v)",
                            [{"value": int(vals[m].sum()),
                              "count": int(m.sum())}]))
            elif kind == "between":
                lo, hi = lit
                out.append((f"Count(Row({lo} < v < {hi}))",
                            [int(((vals > lo) & (vals < hi)).sum())]))
            elif kind == "groupby":
                (limit,) = lit
                counts = np.bincount(seg, minlength=8)
                want = [{"group": [{"field": "seg", "rowID": r}],
                         "count": int(counts[r])}
                        for r in range(8) if counts[r]][:limit]
                out.append((f"GroupBy(Rows(seg), limit={limit})", [want]))
        return out

    def shapes(self) -> list:
        """(name, index, kind, three literal sets) of every query shape
        sent one request at a time: two sets for the resident sends, the
        third for the same traffic returning after the restart.  The
        counts_xN bodies cover every size a fused launch can pad to (a
        power of two up to the burst): how many concurrent requests
        fuse is a matter of timing, and with these compiled first, what
        a burst compiles does not depend on the clock."""
        rng = np.random.default_rng([self.seed, 9])
        out = [
            ("topn", "ssb1b", "topn", [(0, 2), (1, 3), (2, 0)]),
            ("count_row", "ssb1b", "count_row", [(0,), (3,), (2,)]),
            ("count_intersect", "ssb1b", "count_intersect",
             [(0, 1), (2, 3), (1, 2)]),
            ("multi_call", "ssb1b", "multi",
             [(0, 1, 2), (3, 2, 5), (1, 0, 7)]),
            ("union_not", "bsi64", "union_not",
             [(1, 2, 900_000), (5, 0, 750_000), (3, 6, 500_000)]),
            ("sum_gt", "bsi64", "sum_gt",
             [(500_000,), (123_456,), (777_000,)]),
            ("between", "bsi64", "between",
             [(250_000, 750_000), (1_000, 40_000), (400_000, 410_000)]),
            ("groupby", "bsi64", "groupby", [(5,), (7,), (3,)]),
        ]
        n = 2
        while n <= BURST:
            out.append((f"counts_x{n}", "ssb1b", "counts",
                        [tuple(int(r) for r in rng.integers(0, 4, n))
                         for _ in range(3)]))
            n *= 2
        return out

    def sends(self, index: str, kind: str, literals: list) -> list:
        return self.bsi_sends(kind, literals) if index == "bsi64" \
            else self.ssb_sends(kind, literals)

    def burst(self, rows: list) -> dict:
        """BURST concurrent single-call requests, one per client
        thread, released together so the dispatch batcher can fuse
        them.  Returns the batcher's fused/single launch deltas."""
        W = self.W
        want = {r: popcount(W[:, r]) for r in set(rows)}
        gate = threading.Barrier(len(rows))
        dial = threading.Lock()
        b0 = self.debug_vars()["dispatchBatcher"]

        def one(r: int):
            # connect one at a time (the stdlib server listens with a
            # backlog of 5), then send together
            with dial:
                self.client.connection().connect()
            gate.wait(timeout=60)
            return r, self.client.query("ssb1b", f"Count(Row(seg={r}))")

        with ThreadPoolExecutor(len(rows)) as pool:
            answers = list(pool.map(one, rows))
        for r, got in answers:
            self.check(got == [want[r]],
                       f"burst Count(Row(seg={r})) answered {got}, "
                       f"oracle {want[r]}")
        b1 = self.debug_vars()["dispatchBatcher"]
        return {"fused": b1["fusedLaunches"] - b0["fusedLaunches"],
                "single": b1["singleLaunches"] - b0["singleLaunches"]}

    # -- the run ---------------------------------------------------------------

    def run(self):
        from pilosa_tpu import native
        from pilosa_tpu.storage.membudget import DEFAULT_BUDGET
        from pilosa_tpu.utils import devobs

        self.begin("native")
        live = native.fingerprint_live()
        self.report(native_fingerprint_scanner=live,
                    cc=shutil.which("cc"))
        self.check(live or shutil.which("cc") is None,
                   "cc exists but native/_fingerprint did not build+load")

        self.begin("open")
        cfg = self.open_server()
        cache_dir = self.srv._compile_cache_dir
        census0 = cache_census(cache_dir)
        self.report(
            compile_cache_dir=cache_dir,
            from_environment=bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            compile_cache_files_before=sum(census0.values()),
            compile_cache_entries_before=census0,
            use_mesh=cfg.use_mesh, whole_query=cfg.whole_query,
            dispatch_batch=cfg.dispatch_batch,
            compressed_resident=cfg.compressed_resident,
            container_kernels=cfg.container_kernels,
            device_budget_mb=cfg.device_budget_mb)

        self.begin("load")
        self.load_ssb("ssb1b", sparse=False)
        self.load_bsi()

        # ---- resident: every query kind, twice, against the oracle ----
        shapes = self.shapes()
        self.begin("resident.topn")
        name, index, kind, lits = shapes[0]
        self.shape(name, index, self.sends(index, kind, lits[:2]))
        # evidence the chip holds the index: the budget's resident bytes
        # and each device's own count, against the dense bytes expected
        dense = self.n_shards * ROWS * SHARD_WORDS * 4
        resident = DEFAULT_BUDGET.stats()["residentBytes"]
        import jax
        stats = [d.memory_stats() or {} for d in jax.devices()]
        in_use = [m.get("bytes_in_use") for m in stats]
        self.report(expected_dense_bytes=dense,
                    budget_resident_bytes=resident,
                    device_bytes_in_use=in_use,
                    device_peak_bytes_in_use=[
                        m.get("peak_bytes_in_use") for m in stats])
        self.check(resident >= dense,
                   f"budget holds {resident} B < dense {dense} B")
        if not self.rehearsal:
            self.check(all(b is not None for b in in_use),
                       "a device reports no memory_stats")
            self.check(sum(in_use) >= dense,
                       f"devices hold {sum(in_use)} B < dense {dense} B")
            self.check(max(in_use) <= 2 * min(in_use),
                       f"per-device bytes_in_use differ by more than "
                       f"2x: {in_use}")

        self.begin("resident.shapes")
        for name, index, kind, lits in shapes[1:]:
            self.shape(name, index, self.sends(index, kind, lits[:2]))

        self.begin("resident.burst")
        rng = np.random.default_rng([self.seed, 10])
        c0 = self.compile_totals()
        first = self.burst([int(r) for r in rng.integers(0, 4, BURST)])
        c1 = self.compile_totals()
        again = self.burst([int(r) for r in rng.integers(0, 4, BURST)])
        c2 = self.compile_totals()
        self.report(
            requests=BURST, equal=True, first=first, again=again,
            compiles_first=c1["compiles"] - c0["compiles"],
            compiles_again=c2["compiles"] - c1["compiles"],
            retraces=c2["retraces"] - c0["retraces"])
        self.check(c2["compiles"] == c0["compiles"]
                   and c2["retraces"] == c0["retraces"],
                   "the bursts compiled a program")
        self.check(first["fused"] + again["fused"] > 0,
                   "two bursts of concurrent requests never fused")

        self.begin("resident.write")
        # a clear bit of seg row 1, set over the wire, read back
        shard = self.n_shards - 1
        word = int(np.flatnonzero(self.W[shard, 1] != 0xFFFFFFFF)[0])
        bit = int(np.flatnonzero(
            ~np.unpackbits(self.W[shard, 1, word:word + 1].view(np.uint8),
                           bitorder="little").astype(bool))[0])
        col = (shard << 20) + word * 32 + bit
        before = popcount(self.W[:, 1])
        changed = self.client.query("ssb1b", f"Set({col}, seg=1)")
        self.W[shard, 1, word] |= np.uint32(1 << bit)
        got = self.client.query("ssb1b", "Count(Row(seg=1))")
        self.report(set_column=col, changed=changed, count_before=before,
                    count_after=got[0])
        self.check(changed == [True] and got == [before + 1],
                   f"Set({col}, seg=1) -> {changed}, then Count "
                   f"{got} (oracle {before + 1})")

        self.begin("resident.summary")
        v = self.debug_vars()
        census1 = cache_census(cache_dir)
        self.report(
            launches=v["device"]["launches"]["launches"],
            whole_query_programs=v["wholeQuery"]["requests"],
            whole_query_fallbacks=v["wholeQuery"]["fallbacks"],
            last_fallback=v["wholeQuery"]["lastFallback"] or None,
            compiles=v["device"]["compiles"]["compiles"],
            retraces=v["device"]["compiles"]["retraces"],
            compile_seconds=round(
                v["device"]["compiles"]["compileSecondsTotal"], 2),
            compile_cache_dir=cache_dir,
            compile_cache_files_before=sum(census0.values()),
            compile_cache_files_after=sum(census1.values()),
            compile_cache_entries_after=census1)
        self.check(v["device"]["compiles"]["retraces"] == 0,
                   "retraces on the resident path")

        # ---- over budget, same process: half the dense working set ----
        self.begin("budget.reopen")
        self.close_server()
        budget_mb = max(dense // 2 >> 20, 1)
        cfg = self.open_server(device_budget_mb=budget_mb)
        warm = self.debug_vars()["warmup"]
        self.report(device_budget_mb=cfg.device_budget_mb,
                    dense_working_set_mb=dense >> 20,
                    warm_replay={k: warm[k] for k in (
                        "planned", "replayed", "skipped", "errors",
                        "elapsedS", "retracesDuringWarm")})

        # the same traffic returns after the restart.  The warm replay
        # before READY stops at warmup-budget-s, so how far it got
        # depends on the clock; after this, what the process has
        # compiled does not
        self.begin("budget.returning_traffic")
        for name, index, kind, lits in shapes:
            self.shape(name, index, self.sends(index, kind, lits[2:]))

        self.begin("budget.dense_streamed")
        ev0 = DEFAULT_BUDGET.evictions
        self.shape("topn_streamed", "ssb1b",
                   self.ssb_sends("topn", [(1, 2), (0, 3)]))
        self.shape("count_streamed", "ssb1b",
                   self.ssb_sends("count_intersect", [(1, 3), (0, 2)]))
        st = DEFAULT_BUDGET.stats()
        evictions = DEFAULT_BUDGET.evictions - ev0
        self.report(evictions=evictions, limit_mb=budget_mb,
                    resident_mb=st["residentBytes"] >> 20)
        self.check(evictions > 0,
                   "the over-budget dense leg evicted nothing")

        self.begin("budget.load_sparse")
        self.W = None
        self.load_ssb("ssb1b_sparse", sparse=True)

        self.begin("budget.compressed")
        k0 = devobs.LEDGER.kernel_launches_total
        self.shape("topn_compressed", "ssb1b_sparse",
                   self.ssb_sends("topn", [(0, 2), (1, 3)]))
        self.shape("count_compressed", "ssb1b_sparse",
                   self.ssb_sends("count_intersect", [(0, 1), (2, 3)]))
        st = DEFAULT_BUDGET.stats()
        holder = self.srv.holder
        sigs: dict = {}
        for field in ("seg", "metric"):
            for shard in range(self.n_shards):
                sig = holder.fragment("ssb1b_sparse", field, "standard",
                                      shard).device_sig()
                sigs[sig] = sigs.get(sig, 0) + 1
        from pilosa_tpu.ops import kernels
        launches = devobs.LEDGER.kernel_launches_total - k0
        self.report(kernel_backend=kernels.resolve(),
                    interpreted=kernels.interpret_mode(),
                    kernel_launches=launches,
                    compressed_bytes=st["compressedBytes"],
                    fragment_signatures={str(k): n
                                         for k, n in sigs.items()})
        self.check(st["compressedBytes"] > 0,
                   "no fragment went resident compressed")
        self.check(all(sig[0] == "z" for sig in sigs),
                   f"sparse fragments not compressed: {sigs}")
        if not self.rehearsal:
            self.check(not kernels.interpret_mode(),
                       "container kernels ran interpreted on a TPU")
        if any(kernels.sig_backend(sig) == "pallas" for sig in sigs):
            self.check(launches > 0,
                       "pallas signatures but no kernel launch")
        self.close_server()

        self.begin("cache")
        census2 = cache_census(cache_dir)
        self.report(
            compile_cache_dir=cache_dir,
            entries_at_open=census0, entries_at_close=census2,
            added_by_this_run={m: n - census0.get(m, 0)
                               for m, n in census2.items()
                               if n != census0.get(m, 0)})

    def cleanup(self):
        if self.srv is not None:
            try:
                self.srv.close()
            # a failed run's close is best effort: the phase that failed
            # is already reported
            except Exception as e:
                print(f"close after failure: {e!r}", file=sys.stderr)
        shutil.rmtree(self.tmp, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--shards", type=int, default=N_SHARDS,
                    help="shards of the 1B-column index (a cut of scale, "
                         f"printed under 'reduced'; not below {MIN_SHARDS} "
                         "outside a rehearsal)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on whatever jax finds (the CPU); says so, "
                         "and is never a pass")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"jax {jax.__version__} platform: {device['platform']} "
          f"device_kind: {device['kind']} count: {device['count']}",
          flush=True)
    rehearsal = device["platform"] != "tpu"
    if rehearsal and not args.rehearsal:
        print("no TPU: jax found only "
              f"{device['platform']}; nothing was loaded or run",
              file=sys.stderr)
        return EXIT_NO_TPU
    if args.rehearsal:
        rehearsal = True
        print(f"REHEARSAL on {device['platform']}: checks the script, "
              "says nothing about the chip, and is never a pass",
              flush=True)
    elif args.shards < MIN_SHARDS:
        print(f"--shards {args.shards} < {MIN_SHARDS}", file=sys.stderr)
        return EXIT_FAILED
    reduced = [] if args.shards == N_SHARDS else [
        f"ssb1b shards {N_SHARDS} -> {args.shards}"]
    print(json.dumps({"shards": args.shards, "seed": args.seed,
                      "reduced": reduced, "rehearsal": rehearsal}),
          flush=True)

    smoke = Smoke(args, device, rehearsal)
    try:
        smoke.run()
    except Exception:
        import traceback
        traceback.print_exc()
        print(f"FAILED in phase {smoke.phase}", flush=True)
        return EXIT_FAILED
    finally:
        smoke.cleanup()
    print(f"[{time.monotonic() - smoke.t0:7.1f}s] all phases ran",
          flush=True)
    if rehearsal:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": device}), flush=True)
        return EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
