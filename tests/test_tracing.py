"""Program spans and counters (``repro.tracing``): the ring, the batch id
every span of a batch carries, ``into=`` stage timers, the spans the served
path writes, and their place in a profiler trace."""
import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import DSM, DSMStats, ResolveStats, make_scope_index
from repro.serving.scheduler import ScheduledDSQ, SchedulerConfig
from repro.vectordb import DirectoryVectorDB
from repro.vectordb.flat import bucket

DIM = 16
K = 4


@pytest.fixture
def ring(monkeypatch):
    """A fresh ring in place of the process-wide one."""
    r = tracing.Ring()
    monkeypatch.setattr(tracing, "RING", r)
    return r


def _all(ring):
    return tracing.window(0, 1 << 62).spans


@pytest.fixture
def db():
    """400 rows: 4 under /a/x/ (a gather scope), the rest spread over /b/."""
    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((400, DIM)).astype(np.float32)
    paths = ["/a/x/"] * 4 + [f"/b/{i % 7}/" for i in range(396)]
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
    db.ingest(vecs, paths)
    db.build_ann("flat")
    return db


def test_spans_nest_and_carry_the_batch_id(ring):
    with tracing.batch() as outer:
        with tracing.span("dsq.plan"):
            with tracing.span("resolve.traverse"):
                pass
        with tracing.batch(outer.seq + 1000):
            with tracing.span("dsq.fetch"):
                pass
        with tracing.span("dsq.launch", batch=7):
            pass
    with tracing.span("sched.maint"):
        pass
    got = {s.name: s for s in _all(ring)}
    plan, trav = got["dsq.plan"], got["resolve.traverse"]
    assert plan.batch == trav.batch == outer.seq
    assert plan.start_ns <= trav.start_ns <= trav.end_ns <= plan.end_ns
    assert got["dsq.fetch"].batch == outer.seq + 1000
    assert got["dsq.launch"].batch == 7
    assert got["sched.maint"].batch == tracing.NO_BATCH
    assert {s.thread for s in got.values()} == {
        threading.current_thread().name}
    assert tracing.current_batch() == tracing.NO_BATCH


def test_batch_inherits_the_thread_batch_and_counts_h2d_bytes():
    tracing.count_h2d(99)                       # outside a batch: dropped
    with tracing.batch() as b:
        with tracing.batch() as inner:
            assert inner is b
            tracing.count_h2d(10)
        tracing.count_h2d(5)
    assert b.h2d_bytes == 15
    assert tracing.new_batch() > b.seq


def test_ring_is_bounded_and_counts_drops():
    r = tracing.Ring(size=4)
    for i in range(10):
        r.append(("s", "t", 100 * i, 100 * i + 50, i))
    w = r.window(0, 10_000)
    assert [s.batch for s in w.spans] == [6, 7, 8, 9]
    assert r.dropped == 6 and w.dropped == 6
    # the dropped records all ended by 550: a later window lost nothing
    assert r.window(600, 10_000).dropped == 0
    assert r.window(500, 10_000).dropped == 6


def test_window_keeps_only_overlapping_spans():
    r = tracing.Ring(size=16)
    for name, a, b in [("before", 0, 100), ("edge_in", 50, 150),
                       ("inside", 200, 300), ("edge_out", 900, 1100),
                       ("after", 1000, 1200), ("touching", 100, 100)]:
        r.append((name, "t", a, b, 1))
    w = r.window(100, 1000)
    assert [s.name for s in w.spans] == ["edge_in", "inside", "edge_out"]
    assert w.dropped == 0
    assert w.clipped_ns("edge_in") == 50
    assert w.clipped_ns("edge_out") == 100
    assert w.clipped_ns("inside") == 100
    assert w.clipped_ns("missing") == 0


def test_into_accumulates_like_the_stage_timers(ring):
    st = {"apply": 5}
    for _ in range(3):
        with tracing.span("dsm.apply", into=st):
            time.sleep(0.001)
    with tracing.span("resolve.bitmap_fetch", into=st):
        pass
    spans = _all(ring)
    assert st["apply"] == 5 + sum(s.ns for s in spans
                                  if s.name == "dsm.apply")
    assert st["bitmap_fetch"] == sum(s.ns for s in spans
                                     if s.name == "resolve.bitmap_fetch")
    assert set(st) == {"apply", "bitmap_fetch"}


@pytest.mark.parametrize("strategy", ["triehi", "pe_online", "pe_offline"])
def test_resolve_stage_ns_are_the_resolve_spans(ring, strategy):
    idx = make_scope_index(strategy)
    for eid, path in enumerate(["/a/x/", "/a/y/", "/b/"]):
        idx.insert(eid, path)
    stats = ResolveStats()
    assert idx.resolve("/a/", recursive=True, stats=stats).to_array(
        ).tolist() == [0, 1]
    assert idx.resolve("/a/", recursive=False, stats=stats).to_array(
        ).tolist() == []
    per_key = {}
    for s in _all(ring):
        assert s.name.startswith("resolve.")
        key = s.name.split(".", 1)[1]
        per_key[key] = per_key.get(key, 0) + s.ns
    assert stats.stage_ns == per_key


def test_dsm_stage_ns_are_the_dsm_spans(ring, db):
    db.planner("fs")                 # subscribes the mask cache to deltas
    stats = DSMStats()
    res = db.dsm_batch([("move", "/b/1/", "/a/")], stats=stats)
    assert res.applied == 1
    spans = _all(ring)
    names = [s.name for s in spans]
    assert names.count("dsm.journal") == 2
    assert names.count("dsm.apply") == 1
    assert names.count("dsm.cache_patch") == 1
    for key in ("journal", "apply"):
        assert stats.stage_ns[key] == sum(
            s.ns for s in spans if s.name == f"dsm.{key}")
    single = DSMStats()
    db._dsm["fs"].apply(DSM("move", "/b/2/", "/a/"), stats=single)
    assert set(single.stage_ns) == {"lock_wait", "journal", "apply"}


def test_served_batch_spans_tile_and_share_the_accounting_seq(ring, db):
    sdsq = ScheduledDSQ(db, k=K, cfg=SchedulerConfig(max_batch=4,
                                                     max_wait_ms=1e4))
    q = np.random.default_rng(0).standard_normal((3, DIM)).astype(
        np.float32)
    tickets = [sdsq.submit(q[0], "/a/x/"), sdsq.submit(q[1], "/"),
               sdsq.submit(q[2], "/a/x/")]
    assert sdsq.pump() == 3
    res = [t.result(5.0) for t in tickets]
    assert [r.plan for r in res] == ["gather", "scan", "gather"]
    acct = res[0].batch
    spans = [s for s in _all(ring) if s.batch == acct.seq]
    names = [s.name for s in spans]
    for name in ("sched.stage", "dsq.plan", "dsq.gather.rows", "dsq.h2d",
                 "dsq.launch", "dsq.fetch"):
        assert name in names, name
    # the executor's spans tile the batch: none overlaps another
    ex = sorted((s for s in spans if s.name.startswith("dsq.")),
                key=lambda s: s.start_ns)
    assert all(a.end_ns <= b.start_ns for a, b in zip(ex, ex[1:]))
    assert acct.directory_ns == next(s.ns for s in spans
                                     if s.name == "dsq.plan")
    stage = next(s.ns for s in spans if s.name == "sched.stage")
    assert abs(acct.sched_stage_ns - stage) <= 1     # via float seconds
    # h2d: the gather group (2 queries, its 4 row ids padded, their count:
    # the rows are taken on the device) and the scan (1 query, the packed
    # words of 400 rows, the scope ids)
    gather = bucket(2) * DIM * 4 + bucket(4) * 4 + 4
    scan = bucket(1) * DIM * 4 + bucket(1) * ((400 + 31) // 32) * 4 \
        + bucket(1) * 4
    assert acct.h2d_bytes == gather + scan


def test_maintenance_slot_is_one_span(ring, db):
    calls = []
    sdsq = ScheduledDSQ(db, k=K, maintenance=lambda: calls.append(1) or {})
    assert sdsq.pump() == 0              # nothing queued: the slot runs
    assert calls == [1]
    maint = [s for s in _all(ring) if s.name == "sched.maint"]
    assert len(maint) == 1 and maint[0].batch == tracing.NO_BATCH


def test_no_span_name_is_the_benchmarks(ring, db):
    sdsq = ScheduledDSQ(db, k=K, maintenance=lambda: None)
    q = np.ones(DIM, np.float32)
    t = sdsq.submit(q, "/a/x/")
    sdsq.pump()
    t.result(5.0)
    sdsq.pump()
    db.planner("fs")
    db.dsm_batch([("merge", "/b/3/", "/b/4/")])
    names = {s.name for s in _all(ring)}
    assert names and not any(n.startswith("bench.") for n in names)


def test_profiler_trace_holds_the_spans_on_a_host_plane(ring, tmp_path):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    from benchlib import trace as trace_mod
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.batch():
            with tracing.span("dsq.gather.rows"):
                time.sleep(0.02)
            with tracing.span("dsq.fetch"):
                time.sleep(0.03)
        with tracing.span("sched.maint"):
            time.sleep(0.025)
    finally:
        jax.profiler.stop_trace()
    tr = trace_mod.load(trace_mod.find_xplane(str(tmp_path)))
    host = {e.name: e for e in tr.host}
    for s in _all(ring):
        assert s.name in host, s.name
        e = host[s.name]
        assert abs((e.end - e.start) - s.ns) <= 0.05 * s.ns
