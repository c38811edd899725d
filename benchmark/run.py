#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent refuses anything but a TPU, opens one ``Server`` as
``python -m pilosa_tpu server`` does (every option at its default),
generates the configuration's columns from ``--seed``, fills the
fragments, builds the reference's cube, warms every shape the mix uses,
then drives ``POST /index/{index}/query`` from child processes that never
import jax (``loadgen.py``) in a closed loop for ``--seconds``.  Every
answer returned in the window is compared with the reference after the
window closes.  The last line of stdout is the one result object.

    JAX_PLATFORMS=cpu python benchmark/run.py --workload taxi.count-year-pcount \\
        --seed 1 --seconds 2 --trace 1 --rehearsal --shards 2

is the CPU rehearsal: it checks the harness, prints no result line and
exits 10, never 0.

Exit codes: 0 a result line was printed · 1 the run failed · 2 no TPU (or
fewer chips than the cell asks for) · 10 a rehearsal ran to its end.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()     # set-up is counted from here

import argparse
import importlib
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import datagen
import loader
import oracle
import serving
import trace_reduce
import traffic

EXIT_FAILED, EXIT_NO_TPU, EXIT_REHEARSAL = 1, 2, 10
WARM_LOOP_S = 1.0           # closed loop before the window, not measured
WARM_LOOPS = 6              # at most, until one builds no executable
WARM_PASSES = 4             # passes of every shape until none compiles
TRACE_S = 3.0               # seconds of the steady window under the profiler
LOAD_CHECK_ROWS = 16        # rows of a set field read back before timing
LATE_ANSWER_S = 60.0        # an answer is waited for this long past the close


def log(msg: str):
    print(f"[{time.monotonic() - T_PROCESS:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} {name!r}")


# -- the load generator's processes ------------------------------------------


class Load:
    """The mix's clients, spread over child processes (``loadgen.py``)."""

    def __init__(self, port: int, path: str, requests, processes: int):
        n = max(1, min(processes, len(requests.sequences)))
        self.children = []
        # the child gets the least environment: it has no use for jax's
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        for p in range(n):
            child = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "loadgen.py")],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                env=env)
            self.children.append(child)
            self._send(child, {"port": port, "path": path,
                               "bodies": requests.pql,
                               "sequences": requests.sequences[p::n]})
        for child in self.children:
            if not self._recv(child).get("ready"):
                raise RuntimeError("a load generator did not come up")

    @staticmethod
    def _send(child, obj: dict):
        child.stdin.write(json.dumps(obj) + "\n")
        child.stdin.flush()

    @staticmethod
    def _recv(child) -> dict:
        line = child.stdout.readline()
        if not line:
            raise RuntimeError(
                f"a load generator exited with {child.wait()}")
        return json.loads(line)

    def start(self, start: float, end: float):
        for child in self.children:
            self._send(child, {"start": start, "end": end})

    def collect(self) -> list:
        """Every request of the phase: (start, end, status, request,
        answer text), once each client's last answer has come."""
        out = []
        for child in self.children:
            res = self._recv(child)
            for t in res["threads"]:
                out.extend(zip(t["start"], t["end"], t["status"],
                               t["request"],
                               (res["answers"][a] for a in t["answer"])))
        return out

    def close(self):
        for child in self.children:
            try:
                self._send(child, {"quit": True})
                child.stdin.close()
            except OSError:
                pass
        for child in self.children:
            try:
                child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
            child.stdout.close()


# -- set-up --------------------------------------------------------------------


def check_load(client, cfg: dict, row_stats: dict, seed: int, shards: int):
    """Read every field back over HTTP before anything is timed: the
    count of up to LOAD_CHECK_ROWS rows of a set field, the sum and count
    of an int field, against the generated bits."""
    import numpy as np
    index = cfg["index"]["name"]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    for f in cfg["fields"]:
        bits = row_stats[f["name"]][0]
        if f["type"] == "int":
            base = loader.field_base(f)
            n = int(bits[loader.EXISTS_ROW])
            value = base * n + sum(
                int(b) << i for i, b in enumerate(bits[loader.OFFSET_ROW:]))
            got = client.query(index, f"Sum(field={f['name']})")
            want = [{"value": value, "count": n}]
        else:
            rows = np.sort(rng.permutation(f["rows"])[:LOAD_CHECK_ROWS])
            got = client.query(index, " ".join(
                f"Count(Row({f['name']}={r}))" for r in rows))
            want = [int(bits[r]) for r in rows]
        if got != want:
            raise RuntimeError(
                f"load check of {f['name']}: server {got}, generated {want}")
        if f["type"] != "int" and int(bits.sum()) != shards << 20:
            raise RuntimeError(f"{f['name']}: {int(bits.sum())} bits in "
                               f"{shards << 20} columns")


class CompileLog:
    """Every executable jax builds or loads from its cache in this
    process, as jax's own monitoring reports it — the program's counters
    (``devobs.COMPILES``) do not see the eager ones."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if event == self.EVENT:
            self.events.append((time.monotonic(), kw.get("fun_name", "?"),
                                duration))

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] <= t1]


def warm_shapes(client, cfg: dict, requests, cube, compiles) -> int:
    """Send every template alone and in bodies of 2, 4, ... calls up to
    the mix's clients — the sizes a fused launch of concurrent requests
    pads to — until a whole pass compiles nothing.  Each answer is held to
    the reference.  Returns the passes it took."""
    index = cfg["index"]["name"]
    by_template: dict = {}
    for i, t in enumerate(requests.template):
        by_template.setdefault(t, []).append(i)
    sizes, n = [1], 2
    while n <= int(requests.mix["clients"]):
        sizes.append(n)
        n *= 2
    for attempt in range(1, WARM_PASSES + 1):
        before = len(compiles.events)
        for t, ids in sorted(by_template.items()):
            for size in sizes:
                pick = [ids[(attempt * 7 + k) % len(ids)]
                        for k in range(size)]
                got = client.query(index, " ".join(
                    requests.pql[i] for i in pick))
                want = [requests.expected(i, cube) for i in pick]
                if got != want:
                    raise RuntimeError(
                        f"warm-up of template {t} x{size}: server "
                        f"{str(got)[:300]}, reference {str(want)[:300]}")
        if attempt > 1 and len(compiles.events) == before:
            return attempt
    return WARM_PASSES


# -- the window ----------------------------------------------------------------


def traced_span(client, trace_dir: str, start: float, seconds: float):
    """About TRACE_S seconds of the steady window under the profiler,
    /debug/vars read at both ends."""
    import jax
    length = min(TRACE_S, seconds / 3)
    time.sleep(max(0.0, start + min(TRACE_S, seconds / 3) - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = client.debug_vars()
    t0 = time.monotonic()
    time.sleep(length)
    t1 = time.monotonic()
    after = client.debug_vars()
    jax.profiler.stop_trace()
    return {"t0": t0, "t1": t1, "before": before, "after": after}


def percentile(values: list, q: float) -> float:
    """The value at rank ceil(q n) of the sorted values."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def judge(records: list, requests, cube, t_start: float, t_end: float):
    """Every request sent in the window against the reference.  Returns
    (sent, per-request ok flags, counts)."""
    sent = [r for r in records if t_start <= r[0] < t_end]
    expected: dict = {}
    ok, failed, wrong, late = [], 0, 0, 0
    for start, end, status, req, text in sent:
        if status != 200:
            failed += 1
            ok.append(False)
            continue
        if req not in expected:
            expected[req] = requests.expected(req, cube)
        try:
            got = json.loads(text)["results"]
        except (ValueError, KeyError, TypeError):
            got = None
        good = got == [expected[req]]
        wrong += not good
        late += good and end > t_end
        ok.append(good)
    return sent, ok, {"failed": failed, "wrong": wrong, "late": late}


def run_cell(args, cell: dict, bench: dict, device: dict) -> dict:
    cfg = datagen.load_json("configs", cell["config"])
    mix = datagen.load_json("traffic", cell["traffic"])
    shards = args.shards or int(cfg["shards"])
    tmp = tempfile.mkdtemp(prefix="ptpu-bench-")
    srv = load = None
    compiles = CompileLog()
    try:
        srv, client = serving.open_server(os.path.join(tmp, "data"), device)
        log(f"server READY on port {srv.port}; compile cache "
            f"{srv._compile_cache_dir}")
        loader.create_schema(client, cfg)
        cube = oracle.Cube(cfg, mix)
        row_stats = loader.load(srv.holder, cfg, args.seed, shards, cube)
        log(f"loaded {shards} shards of {cfg['name']}")
        check_load(client, cfg, row_stats, args.seed, shards)
        log("every field read back equal")
        requests = traffic.Requests(cfg, mix, args.seed)
        log(f"{len(requests.pql)} distinct requests drawn")
        passes = warm_shapes(client, cfg, requests, cube, compiles)
        log(f"shapes warm after {passes} passes")
        load = Load(srv.port, f"/index/{cfg['index']['name']}/query",
                    requests, int(mix.get("processes", 1)))
        # the closed loop itself, unmeasured, until a whole loop builds
        # no executable: which sizes concurrent requests fuse to is the
        # clock's to decide, and the eager slices of a fused launch's
        # results compile where no multi-call body reaches
        for loop in range(1, WARM_LOOPS + 1):
            now = time.monotonic()
            load.start(now + 0.05, now + 0.05 + WARM_LOOP_S)
            warm = load.collect()
            built = compiles.between(now, time.monotonic())
            log(f"warm loop {loop}: {len(warm)} requests, "
                f"{len(built)} executables built")
            if not built:
                break

        spans: dict = {}
        window_before = client.debug_vars() if args.trace else None
        t_start = time.monotonic() + 0.1
        t_end = t_start + args.seconds
        setup_s = t_start - T_PROCESS
        load.start(t_start, t_end)
        log(f"window open after {setup_s:.2f} s of set-up")
        trace_dir = os.path.join(tmp, "trace")
        if args.trace:
            spans["trace"] = traced_span(client, trace_dir, t_start,
                                         args.seconds)
        records = load.collect()
        log(f"window closed: {len(records)} requests")
        if args.trace:
            spans["window"] = {"before": window_before,
                               "after": client.debug_vars()}
        peak = serving.memory_peak_bytes()
        load.close()
        load = None
        srv.close()
        srv = None
        written = sum(os.path.getsize(os.path.join(d, f))
                      for d, _, files in os.walk(tmp) for f in files)

        sent, ok, counts = judge(records, requests, cube, t_start, t_end)
        n_ok_in_window = sum(1 for r, good in zip(sent, ok)
                             if good and r[1] <= t_end)
        slowest = 1000.0 * (args.seconds + LATE_ANSWER_S)
        lat = [1000.0 * (r[1] - r[0]) if good else slowest
               for r, good in zip(sent, ok)]
        end_to_end = {
            "qps": n_ok_in_window / args.seconds,
            "p95_ms": percentile(lat, 0.95) if lat else slowest,
            "setup_s": setup_s,
        }
        in_window = compiles.between(t_start, t_end)
        good_lat = sorted(l for l, good in zip(lat, ok) if good)
        diagnostics = {
            "late_answers": counts["late"],
            "bytes_left_in_tmp": written,
            "latency_ms": {q: percentile(good_lat, f) if good_lat else None
                           for q, f in (("p50", 0.5), ("p95", 0.95),
                                        ("p99", 0.99), ("max", 1.0))},
            "executables_built_in_window": [[n, d] for _, n, d in in_window],
        }
        compared = {
            "wrong_answers": {"value": counts["wrong"], "limit": 0},
            "failed_requests": {"value": counts["failed"], "limit": 0},
            "answers_compared": {"value": len(sent) - counts["failed"],
                                 "at_least": 1},
        }
        if args.control:
            stale = judge(records, requests, cube.stale(), t_start, t_end)[2]
            compared["control_stale_read_wrong_answers"] = {
                "value": stale["wrong"], "control": True}
        correct = counts["wrong"] == 0 and counts["failed"] == 0 \
            and len(sent) > counts["failed"]

        dev = dict(device)
        dev["memory_peak_bytes"] = peak
        result = {"correct": correct, "attempted": len(sent),
                  "failed": counts["failed"]}
        units = {m["name"]: m["unit"]
                 for m in bench["end_to_end"] + bench["per_layer"]}
        metrics, breakdown = end_to_end, None
        if args.trace:
            xplane = trace_reduce.find_xplane(trace_dir)
            if args.keep_trace and xplane:
                shutil.copyfile(xplane, args.keep_trace)
            metrics, breakdown = layer_metrics(
                bench, cell, spans, sent, requests, row_stats, xplane, dev,
                len(in_window))
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in metrics.items() if k in units}
        result["device"] = dev
        if breakdown:
            result["breakdown"] = breakdown
        result["diagnostics"] = diagnostics
        result["compared"] = compared
        return result
    finally:
        if load is not None:
            load.close()
        if srv is not None:
            try:
                srv.close()
            # a failed run's close is best effort: the failure is
            # already on its way up
            except Exception as e:
                print(f"close after failure: {e!r}", file=sys.stderr)
        shutil.rmtree(tmp, ignore_errors=True)


def layer_metrics(bench, cell, spans, sent, requests, row_stats, xplane,
                  dev, built_in_window):
    """The cell's per-layer metrics, each by the reader its file under
    ``layer_metrics/`` names, and the trace's ``breakdown``."""
    span = spans["trace"]
    done = [r for r in sent if span["t0"] <= r[1] <= span["t1"]]
    span["n"] = len(done)
    row_bytes = {f: s[1] for f, s in row_stats.items()}
    span["least_bytes"] = sum(requests.least_bytes(r[3], row_bytes)
                              for r in done)
    spans["window"]["n"] = len(sent)
    window_s = span["t1"] - span["t0"]
    trace = None
    if xplane is not None:
        trace = trace_reduce.reduce(trace_reduce.read_events(xplane),
                                    window_s)
    ctx = {"spans": spans, "trace": trace, "device": dev,
           "executables_built_in_window": built_in_window}
    metrics = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        spec = datagen.load_json("layer_metrics", m["name"])
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(spec, ctx)
        if value is not None:
            metrics[m["name"]] = value
    breakdown = None
    if trace is not None:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = window_s
        breakdown = {"device_ops": trace["device_ops"],
                     "idle_gaps": trace["idle_gaps"],
                     "device_ms_per_query":
                         1000.0 * trace["busy_s"] / max(span["n"], 1),
                     "requests_in_span": span["n"]}
    return metrics, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also judge the window's answers against the "
                         "control (a stale read) and report how many it "
                         "fails")
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced span's .xplane.pb to this file")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on whatever jax finds (the CPU): prints no "
                         "result line and is never a pass")
    ap.add_argument("--shards", type=int, default=0,
                    help="rehearsal only: shards to load")
    args = ap.parse_args(argv)
    if args.shards and not args.rehearsal:
        ap.error("--shards is for --rehearsal: a cell runs at its "
                 "configuration's size")

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find(bench["workloads"], args.workload, "workload")

    device = serving.device_info()
    log(f"platform {device['platform']} kind {device['kind']} "
        f"count {device['count']}")
    on_chip = device["platform"] == "tpu" and device["count"] >= cell["chips"]
    if not on_chip and not args.rehearsal:
        print(f"no TPU with {cell['chips']} chip(s): jax found "
              f"{device['count']} x {device['platform']}; nothing was run",
              file=sys.stderr)
        return EXIT_NO_TPU

    try:
        result = run_cell(args, cell, bench, device)
    except Exception:
        import traceback
        traceback.print_exc()
        return EXIT_FAILED
    for name, c in result["compared"].items():
        print(f"compared {name}: {json.dumps(c)}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    if args.rehearsal:
        print("REHEARSAL (no result line; says nothing about the chip): "
              + json.dumps(result), file=sys.stderr, flush=True)
        return EXIT_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
