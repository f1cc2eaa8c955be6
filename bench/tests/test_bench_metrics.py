"""End-to-end readers take their percentiles over every request, so a
stall inside the window moves them."""
import benchpath  # noqa: F401

import math

import pytest

from benchlib import load
from benchlib.cell import RunData, load_reader


def _run(lat_s, ops=()):
    qs = []
    for i, lat in enumerate(lat_s):
        q = load.Query(i, "/", True, t_sched=0.1 * i)
        if lat is not None:
            q.t_recv = q.t_sched + lat
            q.ids = object()
        qs.append(q)
    win = load.Window(seconds=10.0, queries=qs, ops=list(ops),
                      groups=[len(ops)] if ops else [], apply_s=[0.01])
    return RunData(cell={}, config={}, traffic={}, window=win, setup_s=1.0,
                   peak_bytes=0, corpus_bytes=1, compiles_in_window=0)


def test_percentile_nearest_rank():
    assert load.percentile([3, 1, 2, 4], 50) == 2
    assert load.percentile(list(range(1, 101)), 95) == 95
    assert math.isnan(load.percentile([], 95))


def test_a_stall_moves_p95_but_not_the_median():
    p95 = load_reader("dsq_p95_ms")
    steady = _run([0.010] * 100)
    assert p95(steady) == pytest.approx(10.0)
    stall = _run([0.010] * 90 + [2.0] * 10)     # 10 requests caught in a stall
    assert p95(stall) == pytest.approx(2000.0)
    failed = _run([0.010] * 94 + [None] * 6)     # failures count as worst
    assert p95(failed) == pytest.approx(1e3 * (10.0 + 60.0))


def test_qps_counts_only_answers_inside_the_window():
    qps = load_reader("dsq_qps")
    # 100 answers, the last at 9.91 s, over the 10 s window
    assert qps(_run([0.010] * 100)) == pytest.approx(100 / 10.0)
    late = _run([0.010] * 50 + [20.0] * 50)      # answered after the close
    assert qps(late) == pytest.approx(50 / 10.0)
    assert qps(_run([None] * 3)) is None


def test_qps_counts_a_stall_at_the_end_of_the_window():
    qps = load_reader("dsq_qps")
    # the first 50 answered by 4.91 s, the rest stalled past the close
    stall = _run([0.010] * 50 + [6.0] * 50)
    assert qps(stall) == pytest.approx(50 / 10.0)


def test_dsm_p90_counts_rejected_ops_as_worst():
    ops = [load.DsmOp("move", "/a/", "/b/", 0.1 * i, t_done=0.1 * i + 0.5)
           for i in range(20)]
    ops[0].error = "ValueError()"
    ops[1].t_done = float("nan")
    run = _run([0.01], ops)
    assert load_reader("dsm_p90_ms")(run) == pytest.approx(500.0)
    ops[2].error = ops[3].error = "x"
    assert load_reader("dsm_p90_ms")(run) == pytest.approx(70_000.0)
