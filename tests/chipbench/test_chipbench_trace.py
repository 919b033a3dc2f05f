"""The reduction from a trace to busy and idle time, time per operation, exposed
collectives and idle gaps by host span: on hand-made events, and on one small trace
recorded on a v5e chip (``data/v5e_small.xplane.pb``)."""

import os

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_small.xplane.pb")


def test_op_family_strips_instance_numbers():
    assert trace.op_family("%fusion.123") == "fusion"
    assert trace.op_family("paged_decode_attention.2") == "paged_decode_attention"
    assert trace.op_family("bitcast_dynamic-update-slice_fusion.7.1") == "bitcast_dynamic-update-slice_fusion"
    assert trace.is_collective("all-gather-start") and trace.is_collective("all-reduce") and not trace.is_collective("fusion")


def test_self_time_takes_children_out_of_control_flow():
    events = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 3.0), ("copy.2", 5.0, 4.0), ("fusion.2", 12.0, 1.0)]
    got = {name: self for name, _, self in trace.self_times(events)}
    assert got == {"while.1": 3.0, "fusion.1": 3.0, "copy.2": 4.0, "fusion.2": 1.0}


def test_union_clips_and_merges():
    assert trace.union([(0, 2), (1, 3), (5, 6), (9, 12)], 0.5, 10) == [[0.5, 3], [5, 6], [9, 10]]


def raw_two_devices():
    ops0 = [("fusion.1", 1.0, 2.0), ("all-reduce.1", 3.0, 1.0), ("fusion.2", 6.0, 2.0)]
    ops1 = [("fusion.1", 1.0, 3.0), ("all-reduce.1", 4.0, 0.5), ("fusion.2", 6.0, 2.0)]
    spans = [("window", 0.0, 10.0), ("step", 0.0, 4.5), ("feed", 4.5, 1.4), ("step", 5.9, 4.1)]
    return {"devices": {0: ops0, 1: ops1}, "spans": sorted(spans, key=lambda s: s[1])}


def test_reduce_busy_idle_collectives_and_gaps():
    r = trace.reduce(raw_two_devices())
    assert r["window_s"] == pytest.approx(10.0) and r["devices"] == 2
    assert r["busy_s"] == pytest.approx((5.0 + 5.5) / 2)
    assert r["collective_s"] == pytest.approx(0.75)
    assert r["op_seconds"]["fusion"] == pytest.approx((4.0 + 5.0) / 2) and r["op_calls"]["fusion"] == 2
    # device 0 idles 0-1 (step), 4-6 (midpoint 5.0: feed), 8-10 (step)
    assert r["idle_by_span"] == pytest.approx({"step": 3.0, "feed": 2.0})
    assert r["span_seconds"]["step"] == pytest.approx(8.6)
    b = trace.breakdown(r)
    assert b["device_ops"][0][0] == "fusion" and b["idle_gaps"][0] == ["step", pytest.approx(3.0)]


def test_reduce_without_a_device_operation_raises():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "spans": []})


def test_recorded_v5e_trace_reduces():
    """Recorded by ``record_small_trace.py`` on one chip: 6 fenced matmul steps under a
    ``window`` span, each in a ``step`` span, with a sleep in a ``feed`` span between them."""
    if not os.path.isfile(RECORDED):
        pytest.skip("no recorded trace in this checkout")
    raw = trace.load(RECORDED, ("step", "feed"))
    assert list(raw["devices"]) == [0] and len(raw["devices"][0]) >= 6
    r = trace.reduce(raw)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_by_span"].get("feed", 0.0) > 0.05, "the sleeps show as idle gaps owned by the feed span"
    assert any("fusion" in k or "dot" in k or "convolution" in k for k in r["op_seconds"])
