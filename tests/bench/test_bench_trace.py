"""Trace reduction: busy union, time per module and per custom call, self
time of nested ops, and idle gaps named by the host span over them."""

import pathlib

import pytest

from bench import trace

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


def test_union_counts_overlap_once():
    spans = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert trace.union_s(spans) == pytest.approx(25e-9)
    assert trace.busy_segments(spans) == [(0, 15), (20, 30)]


def test_self_time_takes_nested_ops_out():
    spans = [("while.1", 0, 100), ("fusion.2", 10, 30), ("fusion.3", 40, 90),
             ("copy.4", 50, 60), ("add.5", 120, 130)]
    got = trace.self_times(spans)
    assert got["while.1"] == pytest.approx(30e-9)
    assert got["fusion.3"] == pytest.approx(40e-9)
    assert got["copy.4"] == pytest.approx(10e-9)
    assert got["add.5"] == pytest.approx(10e-9)


def test_names_from_hlo_text():
    text = '%fused_adamw4.4 = (f32[8,2048,8192]{2,1,0}) custom-call(f32[7]{0} %c), ' \
           'custom_call_target="tpu_custom_call"'
    assert trace.op_name(text) == "fused_adamw4.4"
    assert trace.base_name("fused_adamw4.4") == "fused_adamw4"
    assert trace.base_name("jit_train_step(4989876370014728418)") == "jit_train_step"
    assert trace.is_custom_call(text)
    assert not trace.is_custom_call('%c.1 = f32[2] custom-call(), custom_call_target="ConcatBitcast"')


def test_gap_named_by_innermost_host_span():
    us = 1000
    ops = [[("%f.1 = f32[] add()", 0, 10 * us), ("%f.2 = f32[] add()", 50 * us, 60 * us),
            ("%f.3 = f32[] add()", 60 * us + 10, 70 * us)]]
    host = [("bench.window", -5 * us, 100 * us), ("$feed.py:1 batch_at", 15 * us, 45 * us)]
    s = trace.reduce(ops, [[]], host, window_s=1e-4)
    # the 10 ns pause between f.2 and f.3 is no idle gap
    assert s.gaps == [("$feed.py:1 batch_at", pytest.approx(40e-6))]
    assert s.busy_s == pytest.approx(30e-6 - 10e-9)
    assert s.breakdown()["idle_gaps"] == [["$feed.py:1 batch_at", pytest.approx(40e-6)]]


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a v5e: two calls of the fused kernel on a
    (2, 256, 512) leaf and a small matmul, under one host annotation."""
    ops, modules, host = trace.read_planes(str(SMALL))
    assert len(ops) == 1 and len(ops[0]) == 116
    assert any(name == "bench.small" for name, _, _ in host)
    s = trace.reduce(ops, modules, host, window_s=0.0124)
    assert s.busy_s == pytest.approx(124.374e-6, rel=1e-6)
    assert s.custom_calls == {"fused_adamw4": [2.0, pytest.approx(62.036e-6, rel=1e-6)]}
    assert s.modules["jit__lambda"] == [4.0, pytest.approx(117.494e-6, rel=1e-6)]
    assert s.modules["jit_squeeze"][0] == 2.0
    assert s.busy_s < s.window_s
    assert len(s.gaps) == 7
    assert sum(g for _, g in s.gaps) == pytest.approx(11.013041e-3, rel=1e-6)
    assert s.gaps[0][0] == "$time sleep"
    top = s.breakdown()["device_ops"]
    assert top[0][0] == "fused_adamw4.1" and len(top) == 10
    assert sum(s.ops.values()) == pytest.approx(s.busy_s, rel=1e-6)


def test_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([[]], [[]], [], window_s=1.0)
