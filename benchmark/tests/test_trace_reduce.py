"""The trace's arithmetic on hand-made intervals, and its reading on one
short trace recorded on the chip."""

import os

import pytest

import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_short.xplane.pb")


def test_merge_overlaps_nesting_and_touching():
    assert tr.merge([(5, 9), (0, 4), (1, 2), (3, 6), (20, 21)]) == \
        [(0, 9), (20, 21)]
    assert tr.merge([(0, 1), (1, 2)]) == [(0, 2)]
    assert tr.merge([]) == []


def test_busy_union_is_exact():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 6, 2), ("d", 30, 5)]
    # [0, 15) and [30, 35)
    assert tr.busy_ns(ops) == 20


def test_op_totals_sum_durations_not_the_union():
    ops = [("a", 0, 10), ("b", 5, 10), ("a", 30, 5)]
    assert tr.op_totals(ops) == [("a", 15.0), ("b", 10.0)]


def test_gaps_skip_nested_ops_and_name_both_sides():
    ops = [("a", 0, 10), ("inner", 2, 3), ("b", 14, 1), ("c", 15, 5),
           ("d", 26, 1)]
    assert tr.gaps(ops) == [("a -> b", 10, 4), ("c -> d", 20, 6)]


def test_reduce_idle_share_and_breakdown():
    events = [
        ("/device:TPU:0", "XLA Ops", "k1", 0.0, 0.25e9),
        ("/device:TPU:0", "XLA Ops", "k2", 0.5e9, 0.25e9),
        # other lines of a device plane repeat the ops: not counted
        ("/device:TPU:0", "XLA Modules", "jit_f", 0.0, 0.75e9),
        ("/host:CPU", "python3", "work", 0.0, 2e9),
    ]
    out = tr.reduce(events, window_s=1.0)
    assert out["busy_s"] == 0.5
    assert out["idle_share"] == 0.5
    assert out["device_ops"] == [["k1", 0.25], ["k2", 0.25]]
    assert out["idle_gaps"] == [["k1 -> k2", 0.25]]


def test_reduce_averages_over_devices():
    events = [("/device:TPU:0", "XLA Ops", "k", 0.0, 1e9),
              ("/device:TPU:1", "XLA Ops", "k", 0.0, 0.5e9)]
    out = tr.reduce(events, window_s=2.0)
    assert out["devices"] == 2 and out["busy_s"] == 0.75
    assert out["device_ops"] == [["k", 0.75]]


def test_reduce_without_a_device_plane_reads_nothing():
    assert tr.reduce([("/host:CPU", "python3", "work", 0.0, 1e9)], 1.0) \
        is None


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded chip trace checked in")
def test_reading_a_recorded_chip_trace():
    events = tr.read_events(FIXTURE)
    planes = tr.device_ops(events)
    assert list(planes) == ["/device:TPU:0"]
    out = tr.reduce(events, window_s=1.0)
    assert 0 < out["busy_s"] < 1.0
    assert out["device_ops"] and out["op_count"] > 0


def test_short_name_keeps_the_op_and_its_shape():
    hlo = ("%convert_reduce_fusion = s32[64]{0:T(128)} fusion(u32[256,64,"
           "32768]{2,1,0:T(8,128)} %flat_0_.1), kind=kLoop")
    assert tr.short_name(hlo) == "%convert_reduce_fusion s32[64]{0:T(128)}"
    assert tr.short_name("jit_traced") == "jit_traced"
