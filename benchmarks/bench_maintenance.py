"""Online maintenance under streaming churn: recall, reclamation, SLO.

Four sections, gated with ``--smoke``:

* **Recall + growth under drifted churn**: rounds of delete + drifted
  re-ingest, one twin with online maintenance (repair/compact/
  repartition between rounds) and one without. PG's beam keeps
  tombstones traversable as routers (mask-aware post-collection), so the
  no-maintenance twin degrades in *cost*, not raw recall: its store and
  graph grow without bound and every query pays for the dead rows.
  Gated: maintained recall@10 >= 0.95 against the exact scan, recall
  parity with the unmaintained twin (>= degraded - 0.02), maintained
  store stays bounded while the degraded twin grows by the full churn
  volume.
* **Reclamation**: tombstone + pad-waste bytes before/after maintenance —
  compaction must reclaim every tombstoned row and repartition must not
  increase CSR pad waste (gated).
* **Serving p99 during maintenance**: the threaded scheduler serves an
  open-loop arrival stream twice over identically-sized twins — quiescent
  (no hook) vs with maintenance slots active over a tombstone-heavy store
  (compaction + repair land mid-stream). Gated: p99 with maintenance
  <= 1.5x quiescent p99 (+5 ms clock-noise floor). A warmup twin of the
  same sizes runs first so measured runs see warm XLA caches for both the
  pre- and post-compaction shapes.
* **Crash kill-points**: for every maintenance op kind, a crash between
  journal BEGIN and the mutation must recover() to the bit-identical
  state of a twin that never crashed (gated).

    PYTHONPATH=src python -m benchmarks.bench_maintenance [--scale S] \
        [--smoke] [--json out.json]
"""
from __future__ import annotations

import json
import time
from typing import Dict, List

import numpy as np

from repro.serving.scheduler import (ScheduledDSQ, SchedulerConfig,
                                     open_loop_arrivals)
from repro.vectordb import DirectoryVectorDB, MaintenancePolicy

from .common import DIM

K = 10
SMOKE_SCALE = 0.01
CHURN_N = 1536          # base corpus for the recall/reclamation sections
CHURN_ROUNDS = 8
CHURN_BATCH = 192       # deletes + drifted re-ingests per round
EF_SEARCH = 128
N_REQUESTS = 160        # p99 section arrival stream
RECALL_GATE = 0.95
PARITY_BAND = 0.02      # maintained recall vs unmaintained twin
P99_X = 1.5
P99_FLOOR_MS = 5.0


def _policy() -> MaintenancePolicy:
    return MaintenancePolicy(tombstone_min=64, tombstone_fraction=0.10,
                             pad_waste_min=128, pad_waste_fraction=0.25,
                             repair_deletes=64, repair_budget=0,
                             n_iters=4, sample=1024)


def _serving_policy() -> MaintenancePolicy:
    """The p99 section's policy: tiny repair slices so no single
    maintenance slot stalls a serving batch past the SLO envelope."""
    pol = _policy()
    pol.repair_budget = 1      # ~1.4 ms/relink beam: keep a slice well
    return pol                 # under half the quiescent p99


def _unit(x: np.ndarray) -> np.ndarray:
    return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(np.float32)


def _churn_db(seed: int, n: int, tmp_journal: str = None
              ) -> DirectoryVectorDB:
    rng = np.random.default_rng(seed)
    db = DirectoryVectorDB(dim=DIM, journal_path=tmp_journal)
    db.mkdir("/a/")
    db.mkdir("/b/")
    db.ingest(_unit(rng.normal(size=(n, DIM))),
              ["/a/" if i % 2 else "/b/" for i in range(n)])
    db.build_ann("flat")
    db.build_ann("ivf", n_lists=16)
    db.build_ann("pg", max_degree=16, ef_construction=64)
    return db


def _churn_rounds(db, rng, rounds: int, batch: int, mgr=None) -> None:
    """Steady-state churn: each round deletes a batch and re-ingests a
    drifted batch (unit-norm, round-specific cluster direction — the
    workload of §streaming maintenance). ``mgr`` runs the maintenance
    loop between rounds; None is the degraded baseline."""
    for rnd in range(rounds):
        alive_b = db.store.alive_bool()
        alive = (np.nonzero(alive_b)[0] if alive_b is not None
                 else np.arange(len(db.store)))
        kill = rng.choice(alive, size=min(batch, len(alive) - K),
                          replace=False)
        for i in kill:
            db.delete(int(i))
        mu = rng.normal(size=DIM)
        db.ingest(_unit(rng.normal(size=(batch, DIM)) + 0.5 * mu),
                  ["/a/" if i % 2 else "/b/" for i in range(batch)])
        if mgr is not None:
            mgr.run_all()


def _recall_at_k(db, qs, executor: str, **kw) -> float:
    hits = total = 0
    for q in qs:
        exact = db.dsq(q, "/", k=K, executor="flat")
        got = db.dsq(q, "/", k=K, executor=executor, **kw)
        want = {int(i) for i in exact.ids[0] if int(i) >= 0}
        ids = {int(i) for i in got.ids[0] if int(i) >= 0}
        hits += len(want & ids)
        total += len(want)
    return hits / max(total, 1)


def _pg_us_per_query(db, qs) -> float:
    t0 = time.perf_counter_ns()
    for q in qs:
        db.dsq(q, "/", k=K, executor="pg", ef_search=EF_SEARCH)
    return (time.perf_counter_ns() - t0) / 1e3 / len(qs)


def _section_recall(scale: float, smoke: bool) -> List[Dict]:
    n = max(512, int(CHURN_N * scale / SMOKE_SCALE))
    n = min(n, 4096)
    rng_m = np.random.default_rng(1)
    rng_b = np.random.default_rng(1)     # identical churn on both twins
    maintained = _churn_db(0, n)
    degraded = _churn_db(0, n)
    mgr = maintained.maintenance(policy=_policy())
    t0 = time.perf_counter()
    _churn_rounds(maintained, rng_m, CHURN_ROUNDS, CHURN_BATCH, mgr=mgr)
    t_maint = time.perf_counter() - t0
    _churn_rounds(degraded, rng_b, CHURN_ROUNDS, CHURN_BATCH, mgr=None)
    qs = _unit(np.random.default_rng(9).normal(size=(32, DIM)))
    r_maint = _recall_at_k(maintained, qs, "pg", ef_search=EF_SEARCH)
    r_degr = _recall_at_k(degraded, qs, "pg", ef_search=EF_SEARCH)
    us_maint = _pg_us_per_query(maintained, qs)
    us_degr = _pg_us_per_query(degraded, qs)
    rows_m, rows_d = len(maintained.store), len(degraded.store)
    stats = mgr.stats()
    if smoke:
        assert r_maint >= RECALL_GATE, (
            f"maintained recall@10 {r_maint:.3f} < {RECALL_GATE} after "
            f"{CHURN_ROUNDS} drifted churn rounds ({stats['ops_run']})")
        assert r_maint >= r_degr - PARITY_BAND, (r_maint, r_degr)
        assert stats["journal_pending"] == 0
        # the unbounded-growth contrast: the degraded twin carries every
        # tombstoned row; the maintained twin stays near the live size
        assert rows_d == n + CHURN_ROUNDS * CHURN_BATCH, rows_d
        assert rows_m <= n + 2 * CHURN_BATCH, rows_m
        assert maintained.store.n_deleted <= degraded.store.n_deleted
    return [{
        "name": "maintenance/recall/pg_maintained",
        "us_per_call": us_maint,
        "derived": (f"recall={r_maint:.3f};rounds={CHURN_ROUNDS};"
                    f"rows={rows_m};"
                    f"maint_ms_per_round={1e3 * t_maint / CHURN_ROUNDS:.1f};"
                    f"ops={stats['ops_run']}".replace(",", ";")),
    }, {
        "name": "maintenance/recall/pg_degraded_baseline",
        "us_per_call": us_degr,
        "derived": (f"recall={r_degr:.3f};rounds={CHURN_ROUNDS};"
                    f"rows={rows_d};"
                    f"dead={degraded.store.n_deleted}"),
    }]


def _section_reclaim(scale: float, smoke: bool) -> List[Dict]:
    n = max(512, int(CHURN_N * scale / SMOKE_SCALE))
    n = min(n, 4096)
    rng = np.random.default_rng(2)
    db = _churn_db(3, n)
    _churn_rounds(db, rng, CHURN_ROUNDS // 2, CHURN_BATCH, mgr=None)
    ivf = db.executors["ivf"]
    rows_before = len(db.store)
    dead_before = db.store.n_deleted
    waste_before = ivf.pad_waste()
    mgr = db.maintenance(policy=_policy())
    t0 = time.perf_counter()
    ran = mgr.run_all()
    dt = time.perf_counter() - t0
    waste_after = ivf.pad_waste()
    if smoke:
        assert db.store.n_deleted == 0, "compaction must reclaim tombstones"
        assert len(db.store) == rows_before - dead_before
        assert waste_after <= waste_before, (waste_after, waste_before)
        assert len(db.store.deleted_log) == 0
    return [{
        "name": "maintenance/reclaim/run_all",
        "us_per_call": 1e6 * dt / max(len(ran), 1),
        "derived": (f"ops={len(ran)};reclaimed_rows={dead_before};"
                    f"pad_waste={waste_before}->{waste_after}"),
    }]


def _p99_run(db, queries, paths, offsets, maintenance) -> Dict[str, float]:
    n = len(paths)
    sdsq = ScheduledDSQ(db, k=K, maintenance=maintenance,
                        maintenance_every=4,
                        cfg=SchedulerConfig(max_batch=32, max_wait_ms=4.0,
                                            queue_capacity=4 * n))
    tickets = []
    with sdsq:
        t0 = time.perf_counter()
        for i in range(n):
            now = time.perf_counter() - t0
            if offsets[i] > now:
                time.sleep(offsets[i] - now)
            tickets.append(sdsq.submit(queries[i], paths[i],
                                       t_arrival=t0 + offsets[i]))
        for t in tickets:
            t.result(timeout=600.0)
    if maintenance is not None:
        assert sdsq.scheduler.maintenance_error is None, \
            sdsq.scheduler.maintenance_error
    lat = np.asarray(sorted(t.latency_s for t in tickets)) * 1e3
    return {"p50": float(np.percentile(lat, 50)),
            "p99": float(np.percentile(lat, 99)),
            "steps": getattr(sdsq.scheduler, "maintenance_steps", 0)}


def _seeded_serving_db(seed: int, n: int) -> DirectoryVectorDB:
    """A serving twin with a tombstone-heavy store (maintenance due)."""
    db = _churn_db(seed, n)
    rng = np.random.default_rng(seed + 100)
    alive = np.arange(len(db.store))
    for i in rng.choice(alive, size=n // 3, replace=False):
        db.delete(int(i))
    return db


def _section_p99(scale: float, smoke: bool) -> List[Dict]:
    n = max(512, int(CHURN_N * scale / SMOKE_SCALE))
    n = min(n, 4096)
    rng = np.random.default_rng(4)
    queries = rng.normal(size=(N_REQUESTS, DIM)).astype(np.float32)
    paths = [("/a/", "/b/", "/")[i % 3] for i in range(N_REQUESTS)]

    # capacity probe on a throwaway twin sizes the offered load
    probe = _seeded_serving_db(5, n)
    t0 = time.perf_counter()
    for i in range(16):
        probe.dsq_batch(queries[i: i + 1], [paths[i]], k=K)
    cap_qps = 16 / (time.perf_counter() - t0)
    offered = 0.5 * cap_qps              # headroom: idle slots exist
    offsets = open_loop_arrivals(offered, N_REQUESTS, seed=13)

    # warmup twin compiles every launch shape; draining its manager to
    # quiescence also covers the post-compaction / repartition shapes so
    # no XLA compile lands inside the measured maintained run
    warm = _seeded_serving_db(5, n)
    warm_mgr = warm.maintenance(policy=_serving_policy())
    _p99_run(warm, queries, paths, offsets, warm_mgr)
    while warm_mgr.run_all():
        pass

    measured = _seeded_serving_db(5, n)
    quiet = _p99_run(measured, queries, paths, offsets, None)
    mgr = measured.maintenance(policy=_serving_policy())
    withm = _p99_run(measured, queries, paths, offsets, mgr)
    ops = mgr.stats()["ops_run"]
    if smoke:
        assert sum(ops.values()) >= 1, f"no maintenance ran: {ops}"
        limit = max(P99_X * quiet["p99"], quiet["p99"] + P99_FLOOR_MS)
        assert withm["p99"] <= limit, (
            f"p99 with maintenance {withm['p99']:.2f} ms exceeds "
            f"{P99_X}x quiescent {quiet['p99']:.2f} ms")
    return [{
        "name": "maintenance/p99/quiescent",
        "us_per_call": 1e3 * quiet["p99"],
        "derived": f"p50_ms={quiet['p50']:.2f};p99_ms={quiet['p99']:.2f}",
    }, {
        "name": "maintenance/p99/with_maintenance",
        "us_per_call": 1e3 * withm["p99"],
        "derived": (f"p50_ms={withm['p50']:.2f};p99_ms={withm['p99']:.2f};"
                    f"x_quiescent={withm['p99'] / max(quiet['p99'], 1e-9):.2f};"
                    f"slots={withm['steps']};"
                    f"ops={ops}".replace(",", ";")),
    }]


def _section_crash(smoke: bool) -> List[Dict]:
    import tempfile
    rows: List[Dict] = []
    for kind in ("maint_pg_repair", "maint_compact", "maint_repartition"):
        with tempfile.TemporaryDirectory() as tmp:
            a = _churn_db(7, 512, tmp_journal=f"{tmp}/a.journal")
            b = _churn_db(7, 512, tmp_journal=f"{tmp}/b.journal")
            for i in range(0, 200, 2):
                a.delete(i)
                b.delete(i)
            mgr_a = a.maintenance(policy=_policy())
            mgr_b = b.maintenance(policy=_policy())
            t0 = time.perf_counter()
            mgr_a._run(kind)
            dt = time.perf_counter() - t0
            # twin B: BEGIN journaled, then crash before the mutation
            b._dsm["fs"].journal.begin(mgr_b._intent(kind))
            replayed = b.recover()
            ok = ([op.kind for op in replayed["fs"]] == [kind]
                  and np.array_equal(a.store.vectors, b.store.vectors)
                  and a.store.compact_gen == b.store.compact_gen
                  and a.executors["pg"].repair_gen
                  == b.executors["pg"].repair_gen
                  and a.executors["ivf"].repartition_gen
                  == b.executors["ivf"].repartition_gen)
            q = np.random.default_rng(8).normal(size=DIM).astype(np.float32)
            ra = a.dsq(q, "/", k=K, executor="flat")
            rb = b.dsq(q, "/", k=K, executor="flat")
            ok = ok and np.array_equal(ra.ids, rb.ids) \
                and np.array_equal(ra.scores, rb.scores)
            if smoke:
                assert ok, f"kill-point recovery diverged for {kind}"
            rows.append({
                "name": f"maintenance/crash/{kind}",
                "us_per_call": 1e6 * dt,
                "derived": f"bit_identical={ok}",
            })
    return rows


def run(scale: float = SMOKE_SCALE, smoke: bool = False) -> List[Dict]:
    if smoke:
        scale = max(scale, SMOKE_SCALE)
    rows: List[Dict] = []
    rows.extend(_section_recall(scale, smoke))
    rows.extend(_section_reclaim(scale, smoke))
    rows.extend(_section_p99(scale, smoke))
    rows.extend(_section_crash(smoke))
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=SMOKE_SCALE)
    ap.add_argument("--smoke", action="store_true",
                    help="enforce the recall/p99/crash-recovery gates")
    ap.add_argument("--json", default="",
                    help="also write the result rows to this JSON file")
    args = ap.parse_args()
    from .common import emit
    rows = run(scale=args.scale, smoke=args.smoke)
    emit(rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
