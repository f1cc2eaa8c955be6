"""The readers of the program's spans and counters: they keep the spans
inside the window, average per batch, and read nothing from a ring that
dropped records of the window or from a program without spans."""
import benchpath  # noqa: F401

import sys

import pytest

from benchlib import load
from benchlib.cell import RunData, load_reader
from repro import tracing

T0 = 1000.0                     # window start, seconds on the span clock
SECONDS = 10.0
NS = 1_000_000_000


class _Acct:
    def __init__(self, seq, h2d_bytes=0):
        self.seq = seq
        self.h2d_bytes = h2d_bytes


def _run(batches=(), ops=(), groups=(), apply_s=()):
    qs = []
    for i, acct in enumerate(batches):
        q = load.Query(i, "/", True, t_sched=0.1 * i)
        q.t_recv, q.ids, q.batch = q.t_sched + 0.01, object(), acct
        qs.append(q)
    win = load.Window(seconds=SECONDS, queries=qs, ops=list(ops),
                      groups=list(groups), apply_s=list(apply_s), t0=T0)
    return RunData(cell={}, config={}, traffic={}, window=win, setup_s=1.0,
                   peak_bytes=0, corpus_bytes=1, compiles_in_window=0)


@pytest.fixture
def ring(monkeypatch):
    r = tracing.Ring(size=64)
    monkeypatch.setattr(tracing, "RING", r)
    return r


def _at(s):
    """Seconds into the window -> ns on the span clock."""
    return int((T0 + s) * NS)


def _add(ring, name, a, b, batch=tracing.NO_BATCH):
    ring.append((name, "cb-executor", _at(a), _at(b), batch))


@pytest.mark.parametrize("metric,name", [("gather_host_ms", "dsq.gather.rows"),
                                         ("h2d_ms", "dsq.h2d"),
                                         ("device_wait_ms", "dsq.fetch")])
def test_executor_span_means_per_batch_inside_the_window(ring, metric, name):
    read = load_reader(metric)
    # batch 1 and 2 inside, batch 3 straddles the end, batch 0 before
    _add(ring, name, -2.0, -1.0, 0)
    _add(ring, name, 1.0, 1.010, 1)
    _add(ring, name, 1.020, 1.030, 1)
    _add(ring, "dsq.plan", 2.0, 2.001, 2)          # batch 2: none of `name`
    _add(ring, name, 9.995, 10.010, 3)
    _add(ring, "resolve.traverse", 5.0, 6.0, 4)    # not an executor span
    got = read(_run())
    # 20 ms + 5 ms clipped at the close, over batches 1, 2 and 3
    assert got == pytest.approx((20.0 + 5.0) / 3, rel=1e-6)


@pytest.mark.parametrize("metric", ["gather_host_ms", "h2d_ms",
                                    "device_wait_ms", "maint_pct",
                                    "dsm_patch_ms"])
def test_span_readers_read_nothing_after_a_drop(monkeypatch, metric):
    r = tracing.Ring(size=2)
    monkeypatch.setattr(tracing, "RING", r)
    for i in range(3):
        r.append(("dsq.fetch", "x", _at(1.0 + i), _at(1.5 + i), i))
    op = load.DsmOp("move", "/a/", "/b/", 0.5, t_done=2.0)
    assert load_reader(metric)(_run(ops=[op], groups=[1],
                                    apply_s=[1.0])) is None


@pytest.mark.parametrize("metric", ["gather_host_ms", "h2d_ms",
                                    "device_wait_ms", "maint_pct",
                                    "dsm_patch_ms", "h2d_mb"])
def test_readers_read_nothing_from_a_program_without_spans(monkeypatch,
                                                           metric):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(sys.modules["repro"], "tracing")
    op = load.DsmOp("move", "/a/", "/b/", 0.5, t_done=2.0)
    batches = [object(), object()]                 # no h2d_bytes field
    assert load_reader(metric)(_run(batches, ops=[op], groups=[1],
                                    apply_s=[1.0])) is None


def test_h2d_mb_is_the_mean_counter_per_batch():
    read = load_reader("h2d_mb")
    a, b = _Acct(1, 3_000_000), _Acct(2, 1_000_000)
    assert read(_run([a, a, b])) == pytest.approx(2.0)   # once per batch
    assert read(_run()) is None


def test_maint_pct_clips_the_stall_to_the_window(ring):
    read = load_reader("maint_pct")
    _add(ring, "sched.maint", -0.5, 0.5)           # 0.5 s inside
    _add(ring, "sched.maint", 4.0, 5.0)
    _add(ring, "sched.maint", 9.5, 11.0)           # 0.5 s inside
    _add(ring, "dsq.fetch", 6.0, 7.0, 5)
    assert read(_run()) == pytest.approx(100.0 * 2.0 / SECONDS)


def test_dsm_patch_ms_counts_whole_groups_inside_the_window(ring):
    read = load_reader("dsm_patch_ms")
    ops = [load.DsmOp("move", "/a/", "/b/", 0.0) for _ in range(7)]
    # group 0 straddles the start, group 1 lies inside (3 ops, one
    # rejected), group 2 straddles the end
    for o in ops[:2]:
        o.t_done = 0.2
    for o in ops[2:5]:
        o.t_done = 3.0
    ops[4].error = "KeyError()"
    for o in ops[5:]:
        o.t_done = 10.4
    _add(ring, "dsm.cache_patch", -0.1, 0.1)
    _add(ring, "dsm.cache_patch", 2.2, 2.204)
    _add(ring, "dsm.cache_patch", 2.5, 2.502)
    _add(ring, "dsm.cache_patch", 9.9, 9.95)
    _add(ring, "dsm.cache_patch", 4.0, 4.5)        # outside every group
    run = _run(ops=ops, groups=[2, 5, 7], apply_s=[0.5, 1.0, 0.6])
    assert read(run) == pytest.approx(6.0 / 2, rel=1e-6)
    assert read(_run(ops=ops[:2], groups=[2], apply_s=[0.5])) is None
