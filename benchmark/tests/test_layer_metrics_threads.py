"""The five per-layer metrics of the request thread's partition and of
the host threads' CPU time: each ``layer_metrics/*.json`` is pure data
for the ``vars_ratio`` reader, gives the hand-computed value on a
hand-made before/after snapshot, reads nothing on a program without
``query.wait`` / ``hostThreads``, and has its ``BENCHMARK.json`` entry."""

import json
import os

import pytest

from readers import vars_ratio

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
N = 250     # requests completed in the span


def _roles(request, dispatcher, python, native):
    return {r: {"threads": 1, "cpuSeconds": c, "runqueueSeconds": q}
            for r, (c, q) in zip(("request", "dispatcher", "python",
                                  "native"),
                                 (request, dispatcher, python, native))}


BEFORE = {
    "timings": {"http.query": {"count": 100, "sum": 5.0},
                "query.wait": {"count": 120, "sum": 2.0},
                "query.exec": {"count": 100, "sum": 0.3}},
    "hostThreads": {"wallSeconds": 1000.0, "roles": _roles(
        (10.0, 0.5), (4.0, 0.1), (1.0, 0.0), (20.0, 0.2))},
}
AFTER = {
    "timings": {"http.query": {"count": 300, "sum": 25.0},
                "query.wait": {"count": 360, "sum": 8.0},
                "query.exec": {"count": 300, "sum": 0.7}},
    "hostThreads": {"wallSeconds": 1003.0, "roles": _roles(
        (11.5, 0.6), (4.9, 0.15), (1.1, 0.0), (26.0, 0.25))},
}

# name -> (unit, better, layer, moves, value on the snapshots above)
METRICS = {
    "query_wait_ms_mean": ("ms", "lower", "dispatch batcher", "p95_ms",
                           1000 * 6.0 / 200),
    "query_exec_ms_mean": ("ms", "lower", "whole-query program", "p95_ms",
                           1000 * 0.4 / 200),
    "request_cpu_ms_per_query": ("ms/query", "lower", "HTTP front end",
                                 "qps", 1000 * 1.5 / N),
    "dispatcher_cpu_ms_per_query": ("ms/query", "lower", "dispatch batcher",
                                    "qps", 1000 * 0.9 / N),
    "python_cpu_share": ("%", "higher", "host threads (one GIL)", "qps",
                         100 * (1.5 + 0.9 + 0.1) / 3.0),
}


def _spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _ctx(before, after, n=N):
    return {"spans": {"trace": {"before": before, "after": after, "n": n}}}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_spec_is_data_for_vars_ratio(name):
    spec = _spec(name)
    assert spec["reader"] == "vars_ratio"
    assert set(spec) == {"reader", "num", "den", "scale", "span", "what"}
    assert spec["span"] == "trace"
    assert vars_ratio.read(spec, _ctx(BEFORE, AFTER)) == \
        pytest.approx(METRICS[name][4])


@pytest.mark.parametrize("name", sorted(METRICS))
def test_nothing_to_read_returns_nothing(name):
    """On a program without the span or the section (the parent commit)
    the reader finds no path and the line leaves the metric out."""
    bare = {"timings": {"http.query": {"count": 1, "sum": 1.0}}}
    assert vars_ratio.read(_spec(name), _ctx(bare, bare)) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_benchmark_json_entry(name):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, better, layer, moves, _ = METRICS[name]
    cells = [w["name"] for w in bench["workloads"]]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter", "layer": layer,
                     "moves": moves, "workloads": cells}
    assert moves in {m["name"] for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(METRICS):] == list(METRICS)
