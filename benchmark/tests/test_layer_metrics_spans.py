"""The eight per-layer metrics that read the program's layer spans
(ISSUE 26): each ``layer_metrics/*.json`` is pure data for the
``vars_ratio`` reader, gives the hand-computed value on a hand-made
before/after snapshot, and has its ``BENCHMARK.json`` entry."""

import json
import os

import pytest

from readers import vars_ratio

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELLS = ["taxi.topn-year-pcount", "ssb.q1-flight", "taxi.count-year-pcount"]

BEFORE = {
    "timings": {
        "http.query": {"count": 100, "sum": 5.0},
        "http.query.self": {"count": 100, "sum": 0.5},
        "query.plan": {"count": 100, "sum": 0.1},
        "query.fetch": {"count": 100, "sum": 1.0},
        "dispatch.ticket_wait": {"count": 100, "sum": 2.0},
        "dispatch.round": {"count": 40, "sum": 0.8},
        "dispatch.scatter": {"count": 10, "sum": 0.2},
    },
    "device": {"launches": {"launches": 40, "placeSecondsTotal": 0.04,
                            "dispatchSecondsTotal": 0.4}},
}
AFTER = {
    "timings": {
        "http.query": {"count": 300, "sum": 25.0},
        "http.query.self": {"count": 300, "sum": 0.9},
        "query.plan": {"count": 300, "sum": 0.2},
        "query.fetch": {"count": 300, "sum": 1.6},
        "dispatch.ticket_wait": {"count": 310, "sum": 6.2},
        "dispatch.round": {"count": 90, "sum": 1.3},
        "dispatch.scatter": {"count": 30, "sum": 0.45},
    },
    "device": {"launches": {"launches": 90, "placeSecondsTotal": 0.09,
                            "dispatchSecondsTotal": 0.7}},
}
N = 250     # requests completed in the span

# name -> (unit, layer, moves, value on the snapshots above)
METRICS = {
    "handler_self_ms_mean": ("ms", "HTTP front end", "p95_ms",
                             1000 * 0.4 / 200),
    "plan_ms_mean": ("ms", "parse / prepared plan", "p95_ms",
                     1000 * 0.1 / 200),
    "ticket_wait_ms_mean": ("ms", "dispatch batcher", "p95_ms",
                            1000 * 4.2 / 210),
    "dispatcher_ms_per_query": ("ms/query", "dispatch batcher", "qps",
                                1000 * 0.5 / N),
    "place_ms_per_query": ("ms/query", "stack and place", "qps",
                           1000 * 0.05 / N),
    "enqueue_ms_per_query": ("ms/query", "whole-query program", "qps",
                             1000 * 0.3 / N),
    "scatter_ms_per_query": ("ms/query", "dispatch batcher", "p95_ms",
                             1000 * 0.25 / N),
    "fetch_ms_mean": ("ms", "result fetch", "p95_ms", 1000 * 0.6 / 200),
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
    assert spec["span"] == "trace" and spec["scale"] == 1000
    assert vars_ratio.read(spec, _ctx(BEFORE, AFTER)) == \
        pytest.approx(METRICS[name][3])


@pytest.mark.parametrize("name", sorted(METRICS))
def test_nothing_to_read_returns_nothing(name):
    """On a program without the spans (the parent commit) the reader
    finds no path and the line leaves the metric out."""
    bare = {"timings": {"http.query": {"count": 1, "sum": 1.0}},
            "device": {"launches": {"launches": 3}}}
    assert vars_ratio.read(_spec(name), _ctx(bare, bare)) is None
    # and a span in which nothing ticked has no mean
    assert vars_ratio.read(_spec(name), _ctx(AFTER, AFTER, n=0)) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_benchmark_json_entry(name):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, layer, moves, _ = METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": moves, "workloads": CELLS}
    assert moves in {m["name"] for m in bench["end_to_end"]}
    assert set(CELLS) <= {w["name"] for w in bench["workloads"]}


def test_additions_come_last():
    """New entries go at the end of ``per_layer``; the eight accepted
    ones keep their places."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert names[:8] == ["handler_ms_mean", "prepared_hit_share",
                         "wq_fallback_share", "launches_per_query",
                         "compiles_in_window", "upload_bytes_per_query",
                         "kernels_roofline", "device_idle_share"]
    assert sorted(names[8:]) == sorted(METRICS)
