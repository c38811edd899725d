"""The rest of a run, driven past the harness's look for a chip, with the
timed path broken underneath: ``correct`` has to come out false.  The
fault a served read path can have is an answer altered where it is
produced (or never produced); it is switched on when the window opens, so
set-up's own checks pass and the window's comparison has to catch it."""

import argparse
import json
import os

import pytest

import run
import serving

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def drive(monkeypatch, fault):
    """One short run of the Count cell on whatever jax finds, 2 shards."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = run.find(bench["workloads"], "taxi.count-year-pcount", "workload")
    state = {"phases": 0}
    real_open, real_start = serving.open_server, run.Load.start

    def open_server(data_dir, device):
        srv, client = real_open(data_dir, device)
        real_query = srv.api.query

        def query(index, q, *a, **kw):
            out = real_query(index, q, *a, **kw)
            # phase 1 is the warm loop, phase 2 the window
            return fault(out) if state["phases"] >= 2 else out
        srv.api.query = query
        return srv, client

    def start(self, start, end):
        state["phases"] += 1
        return real_start(self, start, end)

    monkeypatch.setattr(serving, "open_server", open_server)
    monkeypatch.setattr(run.Load, "start", start)
    args = argparse.Namespace(workload=cell["name"], seed=2**31 + 99,
                              seconds=1.0, trace=0, control=True,
                              keep_trace="", rehearsal=True, shards=2)
    return run.run_cell(args, cell, bench, serving.device_info())


def test_a_sound_run_is_correct_and_its_control_is_not(monkeypatch):
    res = drive(monkeypatch, lambda out: out)
    assert res["correct"] is True
    c = res["compared"]
    assert c["wrong_answers"]["value"] == 0
    assert c["control_stale_read_wrong_answers"]["value"] > 0
    assert list(res)[-1] == "compared"


def test_an_altered_answer_is_not_correct(monkeypatch):
    n = {"calls": 0}

    def off_by_one(out):
        n["calls"] += 1
        return [out[0] + 1] if n["calls"] % 7 == 0 else out
    res = drive(monkeypatch, off_by_one)
    assert res["correct"] is False
    assert res["compared"]["wrong_answers"]["value"] > 0


def test_an_answer_that_fails_is_not_correct(monkeypatch):
    def boom(out):
        raise RuntimeError("injected")
    res = drive(monkeypatch, boom)
    assert res["correct"] is False
    assert res["failed"] > 0
    assert res["metrics"]["qps"]["value"] == 0
