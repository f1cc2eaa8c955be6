"""The trace reduction: busy union, idle share, op totals, gap labels."""
import benchpath  # noqa: F401

import pytest

from benchlib import trace as T


def _trace():
    ms = 1e6
    dev = [T.Event("fusion.1", 0 * ms, 10 * ms),
           T.Event("fusion.1", 5 * ms, 20 * ms),       # overlaps the first
           T.Event("top_k", 40 * ms, 50 * ms),
           T.Event("copy", 95 * ms, 120 * ms)]         # runs past the window
    host = [T.Event("bench.window", 0 * ms, 100 * ms),
            T.Event("bench.wait", 0 * ms, 100 * ms),
            T.Event("bench.dsm", 22 * ms, 30 * ms),
            T.Event("PjitFunction(_gather_topk)", 52 * ms, 90 * ms)]
    return T.Trace(device_ops={"/device:TPU:0": dev}, host=host)


def test_union_and_gaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3),
                                                                 (5, 8)]
    assert T.gaps([(1, 2), (4, 6)], 0, 10) == [(0, 1), (2, 4), (6, 10)]
    assert T.clip([(-5, 1), (2, 3), (9, 20)], 0, 10) == [(0, 1), (2, 3),
                                                         (9, 10)]


def test_reduce_busy_idle_ops_and_gaps():
    r = T.reduce(_trace())
    assert r.window_s == pytest.approx(0.100)
    # union of [0,20) [40,50) [95,100) inside the window = 35 ms
    assert r.busy_s == pytest.approx(0.035)
    assert r.idle_share == pytest.approx(0.65)
    assert r.op_seconds["fusion.1"] == pytest.approx(0.025)   # summed, not union
    assert r.op_seconds["copy"] == pytest.approx(0.005)       # clipped
    labels = dict((round(s, 4), lab) for lab, s in r.idle_gaps)
    assert labels[0.045] == "wait:PjitFunction(_gather_topk)"  # [50, 95)
    assert labels[0.02] == "dsm"                              # [20, 40)
    assert [s for _, s in r.idle_gaps] == sorted(
        (s for _, s in r.idle_gaps), reverse=True)


def test_reduce_refuses_a_trace_without_window_or_device():
    t = _trace()
    with pytest.raises(ValueError):
        T.reduce(T.Trace(device_ops={}, host=t.host))
    with pytest.raises(ValueError):
        T.reduce(T.Trace(device_ops=t.device_ops, host=t.host[1:]))


def test_ops_are_named_by_program_and_instruction():
    mods = [T.Event("jit__multi_scan_topk(123)", 0, 50),
            T.Event("jit__gather_topk(9)", 60, 90)]
    ops = [T.Event("%fusion.3 = f32[32,690000]{1,0:T(8,128)} fusion(...)",
                   10, 20),
           T.Event("%top_k = (f32[8,10]{1,0}) custom-call(...)", 70, 80),
           T.Event("%copy = f32[8]{0} copy(...)", 95, 99)]
    names = [e.name for e in T.name_ops(ops, mods)]
    assert names == ["jit__multi_scan_topk:%fusion.3 = f32[32,690000]",
                     "jit__gather_topk:%top_k = (f32[8,10]",
                     "?:%copy = f32[8]"]


def test_busy_is_averaged_over_devices():
    t = _trace()
    t.device_ops["/device:TPU:1"] = [T.Event("x", 0, 100e6)]
    assert T.reduce(t).busy_s == pytest.approx((0.035 + 0.100) / 2)
